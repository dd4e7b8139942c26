"""Spans and counters: the program's one tracing mechanism.

``span(name)`` marks a phase.  It always opens a
``jax.profiler.TraceAnnotation``, so a profiler trace shows the phase on
the same clock as the device's events; with no profiler running that
costs next to nothing.  While recording is on (:func:`enable`), a span
also records ``(name, t0, t1, id, parent, root)`` on
``time.perf_counter_ns`` into a bounded buffer, and waits for the
outputs handed to :meth:`Span.wait_for` before it stamps its end, so its
duration covers the device work and not only the enqueue.  All spans
under one outermost span share that span's id as their ``root``; each
thread keeps its own stack, so a span opened on a thread with no open
span is a root of its own.

``count(name, n)`` adds to a process-wide total, and, while recording is
on, to the counts of the root span open on the calling thread (a count
made on a thread with no open span goes to the total alone).

``to_host(x)`` is a device-to-host pull: a ``host.pull`` span around
``np.asarray(x)``, counting its bytes as ``host.pull_bytes``.

Recording is off by default: a span is then the annotation and one flag
check; it records nothing and never waits for a device.
"""

from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import NamedTuple

import jax
import numpy as np

__all__ = ["Record", "count", "counters", "disable", "enable", "records",
           "reset", "self_ns", "span", "to_host"]

MAX_RECORDS = 4096
MAX_ROOTS = 256

_on = False
_records: collections.deque = collections.deque(maxlen=MAX_RECORDS)
_root_counts: collections.deque = collections.deque(maxlen=MAX_ROOTS)
_totals: dict[str, int] = {}
_lock = threading.Lock()
_ids = itertools.count(1)
_local = threading.local()


class Record(NamedTuple):
    name: str
    t0: int  # perf_counter_ns
    t1: int
    id: int
    parent: int | None
    root: int


def enable() -> None:
    global _on
    _on = True


def disable() -> None:
    global _on
    _on = False


def records() -> list[Record]:
    """The recorded spans, oldest first (at most ``MAX_RECORDS``)."""
    return list(_records)


def self_ns(rec: Record, recs) -> int:
    """A span's duration less the time its direct children cover (a
    span's children run one after another, on its thread)."""
    return (rec.t1 - rec.t0) - sum(r.t1 - r.t0 for r in recs
                                   if r.parent == rec.id)


def counters(root: int | None = None) -> dict[str, int]:
    """The process-wide totals, or the counts recorded under one root
    (empty when that root is unknown or has fallen out of the buffer)."""
    if root is None:
        with _lock:
            return dict(_totals)
    for rid, counts in reversed(_root_counts):
        if rid == root:
            return dict(counts)
    return {}


def reset(name: str | None = None) -> None:
    """Zero one total, or, with no name, forget every record and count."""
    with _lock:
        if name is not None:
            _totals.pop(name, None)
            return
        _totals.clear()
        _records.clear()
        _root_counts.clear()


def _stack() -> list:
    st = getattr(_local, "stack", None)
    if st is None:
        st = _local.stack = []
    return st


def count(name: str, n: int = 1) -> None:
    with _lock:
        _totals[name] = _totals.get(name, 0) + n
        if _on:
            st = _stack()
            if st:
                counts = st[0].counts
                counts[name] = counts.get(name, 0) + n


class Span:
    __slots__ = ("name", "counts", "_ann", "id", "_parent", "_root", "_t0",
                 "_outs")

    def __init__(self, name: str):
        self.name = name
        self._ann = jax.profiler.TraceAnnotation(name)
        self._t0 = None

    def __enter__(self) -> Span:
        self._ann.__enter__()
        if _on:
            st = _stack()
            self.id = next(_ids)
            self._parent = st[-1].id if st else None
            self._root = st[0].id if st else self.id
            self.counts = {} if not st else None
            self._outs = None
            st.append(self)
            self._t0 = time.perf_counter_ns()
        return self

    def wait_for(self, outs):
        """Hand over the phase's outputs: a recording span waits for them
        before its end.  Returns ``outs``."""
        if self._t0 is not None:
            self._outs = outs
        return outs

    def __exit__(self, *exc) -> None:
        try:
            if self._t0 is not None:
                if self._outs is not None and exc[0] is None:
                    jax.block_until_ready(self._outs)
                t1 = time.perf_counter_ns()
                st = _stack()
                st.pop()
                _records.append(Record(self.name, self._t0, t1, self.id,
                                       self._parent, self._root))
                if self.counts is not None:
                    _root_counts.append((self.id, self.counts))
                self._outs = None
        finally:
            self._ann.__exit__(*exc)


span = Span


def to_host(x) -> np.ndarray:
    """``np.asarray(x)``; for a device array, as a ``host.pull`` span that
    counts the bytes pulled."""
    if not isinstance(x, jax.Array):
        return np.asarray(x)
    with Span("host.pull"):
        out = np.asarray(x)
    count("host.pull_bytes", out.nbytes)
    return out
