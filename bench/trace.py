"""Reduction of a profiler trace to device busy time, op time and gaps.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
Device planes are named ``/device:TPU:<n>``; their ``XLA Ops`` line holds
one event per operation that ran on the device.  The benchmark marks its
traced window with a host annotation ``bench:window`` and each probed
program call with ``bench:<span>``; host and device events share one
clock in the file.

- busy time: the union of the intervals in which a device ran an op or
  a program (an XLA module execution, line ``XLA Modules``) inside the
  window, averaged over the devices that ran anything.  A program still
  running when the profile stops keeps its module event, cut at the stop,
  but loses its enclosing ``while`` op, so programs count as a whole.  An
  op that no recorded program execution encloses belongs to a program
  whose execution the profile lost: the device's time around it is
  unknown, and the window then closes at the end of the last program
  execution that finished before the first such op;
- op time: the summed device durations of the ops, by program and op;
- idle gaps: the stretches inside the window where the first busy device
  ran nothing, each named by the innermost host event that spans it.
"""

from __future__ import annotations

import bisect
import glob
import os
from dataclasses import dataclass, field

WINDOW = "bench:window"
OP_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)


@dataclass
class Trace:
    # per device: [(name, start_ns, end_ns, program)], sorted by start;
    # ``program`` is the XLA module the op ran in
    device_ops: dict[str, list[tuple[str, float, float, str]]] = field(
        default_factory=dict)
    # per device: [(program, start_ns, end_ns)], one per program execution
    programs: dict[str, list[tuple[str, float, float]]] = field(
        default_factory=dict)
    host_events: list[tuple[str, float, float]] = field(default_factory=list)
    window: tuple[float, float] | None = None
    # the ``bench:window`` annotation's end, before any closing
    annotated_end: float | None = None
    closed_before_orphan_ops: bool = False

    @property
    def window_s(self) -> float:
        return 0.0 if self.window is None else (
            self.window[1] - self.window[0]) * 1e-9

    def ops_in_window(self):
        """{device: [(name, start, end, program)]} clipped to the window."""
        lo, hi = self.window
        out = {}
        for dev, ops in self.device_ops.items():
            kept = [(n, max(s, lo), min(e, hi), m) for n, s, e, m in ops
                    if e > lo and s < hi]
            if kept:
                out[dev] = kept
        return out


def find_xplane(logdir: str) -> str | None:
    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    return paths[-1] if paths else None


def short(name: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``%fusion.3``;
    ``jit_f(123)`` -> ``jit_f``."""
    return name.split(" = ", 1)[0].split("(", 1)[0]


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` file (or a gzipped one, ``.xplane.pb.gz``)."""
    import gzip

    import jax

    if str(path).endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = jax.profiler.ProfileData.from_serialized_xspace(f.read())
    else:
        data = jax.profiler.ProfileData.from_file(str(path))
    tr = Trace()
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            ops, mods = [], []
            for line in plane.lines:
                evs = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                       for ev in line.events]
                if line.name in OP_LINES:
                    ops.extend(evs)
                elif line.name in MODULE_LINES:
                    mods.extend(evs)
            if ops:
                tr.device_ops[plane.name] = _with_program(ops, mods)
                tr.programs[plane.name] = mods
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW:
                        tr.window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                        tr.annotated_end = tr.window[1]
                    tr.host_events.append(
                        (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
    if tr.window is not None:
        close_before_orphans(tr)
    return tr


def close_before_orphans(tr: Trace) -> None:
    """Close the window at the end of the last program execution that
    finished before the first op no recorded execution encloses."""
    lo, hi = tr.window
    first = min((s for ops in tr.device_ops.values() for _, s, e, prog in ops
                 if not prog and e > lo and s < hi), default=None)
    if first is None:
        return
    ends = [e for progs in tr.programs.values() for _, _, e in progs
            if lo < e <= first]
    tr.window = (lo, max(ends, default=max(first, lo)))
    tr.closed_before_orphan_ops = True


def summary(tr: Trace) -> dict:
    """What the reduction saw: program executions in the window and those
    still running when the window's annotation ended."""
    lo, hi = tr.window
    stop = hi if tr.annotated_end is None else tr.annotated_end
    progs = [(s, e) for ps in tr.programs.values() for _, s, e in ps
             if e > lo and s < hi]
    return {"programs": len(progs),
            "in_flight_at_stop": sum(e > stop for _, e in progs),
            "closed_before_orphan_ops": tr.closed_before_orphan_ops}


def _with_program(ops, mods):
    """Tag each op with the module whose execution encloses it."""
    ops.sort(key=lambda o: o[1])
    mods.sort(key=lambda m: m[1])
    starts = [m[1] for m in mods]
    out = []
    for name, s, e in ops:
        i = bisect.bisect_right(starts, s) - 1
        prog = mods[i][0] if i >= 0 and mods[i][2] >= s else ""
        out.append((name, s, e, prog))
    return out


def merge(intervals):
    """Union of (start, end) intervals, as a sorted disjoint list."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _busy(tr: Trace, dev: str):
    """Merged intervals in which the device ran an op or a program, clipped
    to the window.  A program counts as a whole: the device is busy
    running it between its ops too (a ``while`` loop's control), and a
    program still in flight when the profile stopped has its inner ops
    recorded but not its enclosing ``while`` op."""
    lo, hi = tr.window
    spans = [(s, e) for _, s, e, _ in tr.device_ops.get(dev, ())]
    spans += [(s, e) for _, s, e in tr.programs.get(dev, ())]
    return merge((max(s, lo), min(e, hi)) for s, e in spans
                 if e > lo and s < hi)


def busy_s(tr: Trace) -> float:
    """Seconds in which the device ran something, averaged over the
    devices that ran anything."""
    per_dev = [sum(e - s for s, e in _busy(tr, d)) for d in tr.device_ops]
    per_dev = [b for b in per_dev if b > 0]
    return 1e-9 * sum(per_dev) / len(per_dev) if per_dev else 0.0


def program_seconds(tr: Trace) -> dict[str, float]:
    """Device seconds inside the window, summed over devices, by the XLA
    program the ops ran in."""
    out: dict[str, float] = {}
    for ops in tr.ops_in_window().values():
        for _, s, e, prog in ops:
            out[prog] = out.get(prog, 0.0) + (e - s) * 1e-9
    return out


def idle_gaps(tr: Trace, top: int = 10):
    """The longest gaps on the first busy device, named by host activity."""
    ops = tr.ops_in_window()
    if not ops:
        return []
    lo, hi = tr.window
    busy = _busy(tr, sorted(ops)[0])
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    named = []
    for s, e in gaps[:top]:
        mid = 0.5 * (s + e)
        covering = [(he - hs, n) for n, hs, he in tr.host_events
                    if hs <= mid <= he and n != WINDOW]
        name = min(covering)[1] if covering else "no host event"
        named.append([name, (e - s) * 1e-9])
    return named


def breakdown(tr: Trace, top: int = 10) -> dict:
    """The ops that took most device time, as ``program/op``, and the
    longest idle gaps."""
    secs: dict[str, float] = {}
    for ops in tr.ops_in_window().values():
        for name, s, e, prog in ops:
            key = f"{short(prog)}/{short(name)}"
            secs[key] = secs.get(key, 0.0) + (e - s) * 1e-9
    ops = sorted(secs.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": idle_gaps(tr, top)}
