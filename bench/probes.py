"""What the benchmark attaches to the program from outside.

- A **span** wraps a function of the program, named ``"module:attr"``:
  each call is timed on the host clock after ``block_until_ready`` on its
  result, and shows in a profiler trace as a host annotation
  ``bench:<span>``.  Spans are recorded while ``recording`` is set.
- A **capture** wraps a function of the program and keeps its last result,
  for the reference's diagnostics.
- The **compile counter** counts XLA backend compilations (a persistent
  cache hit counts too: JAX reports it as the same event) while
  ``recording`` is set.

The program's code is not edited: the wrappers replace module attributes
for the life of a :class:`Probes` and are taken off by :meth:`close`.
"""

from __future__ import annotations

import importlib
import time

import jax

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _resolve(target: str):
    mod_name, attr = target.split(":")
    return importlib.import_module(mod_name), attr


class Probes:
    def __init__(self):
        self.spans: dict[str, list[tuple[float, float]]] = {}
        self.captured: dict[str, object] = {}
        self.compiles = 0
        self.recording = False
        self._restore: list[tuple[object, str, object]] = []
        self._listener = None

    def _patch(self, target: str, make):
        try:
            mod, attr = _resolve(target)
            orig = getattr(mod, attr)
        except (ImportError, AttributeError):
            return  # the program no longer has it: nothing to read
        self._restore.append((mod, attr, orig))
        setattr(mod, attr, make(orig))

    def span(self, name: str, target: str):
        def make(orig):
            def timed(*args, **kwargs):
                if not self.recording:
                    return orig(*args, **kwargs)
                t0 = time.perf_counter()
                with jax.profiler.TraceAnnotation(f"bench:{name}"):
                    out = jax.block_until_ready(orig(*args, **kwargs))
                self.spans.setdefault(name, []).append(
                    (t0, time.perf_counter()))
                return out
            return timed
        self._patch(target, make)

    def capture(self, name: str, target: str):
        def make(orig):
            def kept(*args, **kwargs):
                out = orig(*args, **kwargs)
                self.captured[name] = out
                return out
            return kept
        self._patch(target, make)

    def count_compiles(self):
        def listener(event, duration, **kwargs):
            if self.recording and event == COMPILE_EVENT:
                self.compiles += 1
        self._listener = listener
        jax.monitoring.register_event_duration_secs_listener(listener)

    def close(self):
        for mod, attr, orig in reversed(self._restore):
            setattr(mod, attr, orig)
        self._restore.clear()
        if self._listener is not None:
            jax.monitoring.unregister_event_duration_listener(self._listener)
            self._listener = None
