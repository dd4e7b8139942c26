"""One run of one benchmark cell: set-up, the measured window, the check.

Everything that belongs to one configuration, traffic mix, reference or
per-layer metric lives in a file of its own, found by the name
``BENCHMARK.json`` gives it (see ``bench/README.md``):

- ``bench/configs/<config>.json``: the graph, the partitioner and its k;
- ``bench/graphs/<generator>.py``: ``generate(params, seed)``;
- ``bench/traffic/<mix>.json``: read by ``traffic_gen``;
- ``bench/reference/<partitioner>.py``: ``partition(...)`` and ``LIMITS``;
- ``bench/metrics/<metric>.py``: ``read(run)`` and optional ``SPANS``.

A run: make the graph from the seed; run one whole job through the
partitioner's library entry (``repro.core.baselines.PARTITIONERS``) to
warm every shape (set-up ends here); run jobs back to back until the job
that ends at or after ``seconds``; read the device's memory peak; compute
RF and balance of the last job; then run the reference and compare every
edge's partition in every job of the window with it.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import shutil
import sys
import threading
import time
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

from . import quality, traffic_gen
from . import trace as tracemod
from .probes import Probes

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / "build" / "bench" / "jax_cache"
FILLED_DIR = ROOT / "build" / "bench" / "cache_filled"
TRACE_DIR = ROOT / "build" / "bench" / "traces"
# a compile time no program reaches: the cache keeps nothing new
NEVER_WRITE_S = 1e9

# A warm-up job longer than this runs a per-edge loop whose device trace
# would be too large to keep (S5P's Alg. 1 on its lax.scan rung: about 35
# device events per edge); the traced run then profiles only the head of
# the window.  Stopping the profiler costs about 145 s per profiled second
# there (TPU v5e), so the head is short enough to keep the run in 360 s.
LONG_JOB_S = 15.0
TRACE_HEAD_S = 0.5


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list


def load(kind: str, name: str):
    """The module ``bench/<kind>/<name>.py``."""
    path = BENCH / kind / f"{name}.py"
    mod_name = "bench_" + kind + "_" + "".join(
        c if c.isalnum() else "_" for c in name)
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload: str, spec_path: Path = ROOT / "BENCHMARK.json"):
    spec = json.loads(Path(spec_path).read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"one of {sorted(cells)}")
    w = cells[workload]
    cfg = next(c for c in spec["configs"] if c["name"] == w["config"])
    config = json.loads((ROOT / cfg["file"]).read_text())
    traffic = json.loads((BENCH / "traffic" / f"{w['traffic']}.json")
                         .read_text())

    def here(m):
        return workload in m.get("workloads", [workload])

    e2e = [m for m in spec["end_to_end"] if here(m)]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m
                     else m["moves"] in moved)]
    return Cell(workload, int(w["chips"]), config, traffic, e2e, per_layer)


def make_graph(cell: Cell, seed: int):
    g = cell.config["graph"]
    src, dst, n = load("graphs", g["generator"]).generate(g, seed)
    src, dst = traffic_gen.apply(cell.traffic, src, dst)
    return np.ascontiguousarray(src), np.ascontiguousarray(dst), n


def job_seed(seed: int, part: dict) -> int:
    """The partitioner's own seed: the configuration's ``seed`` where it
    states one, else the run's seed folded into int32."""
    return int(part["seed"]) if "seed" in part else int(seed) % (2**31)


class Tracer:
    """The profiler over the window, or over its first ``cap_s`` seconds.

    A helper thread holds the ``bench:window`` host annotation open until
    the window ends or the cap passes, then stops the profiler."""

    def __init__(self, logdir: Path, cap_s: float | None):
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        self.logdir = str(logdir)
        jax.profiler.start_trace(self.logdir, profiler_options=opts)
        self.capped = False
        self._done = threading.Event()
        self._started = threading.Event()
        self._thread = threading.Thread(target=self._hold, args=(cap_s,))
        self._thread.start()
        self._started.wait()

    def _hold(self, cap_s):
        import jax

        with jax.profiler.TraceAnnotation(tracemod.WINDOW):
            self._started.set()
            self.capped = not self._done.wait(cap_s)
        jax.profiler.stop_trace()

    def stop(self):
        self._done.set()
        self._thread.join()


@dataclass
class RunView:
    """What a per-layer metric's ``read(run)`` may look at."""

    spans: dict
    compiles: int
    jobs_in_window: int
    edges_in_window: int
    k: int
    peaks: dict
    trace: tracemod.Trace | None = None
    busy_s: float = 0.0
    edges_traced: int | None = None
    _program_s: dict = field(default_factory=dict)

    def program_seconds(self) -> dict:
        if self.trace is not None and not self._program_s:
            self._program_s = tracemod.program_seconds(self.trace)
        return self._program_s

    @staticmethod
    def load(kind: str, name: str):
        return load(kind, name)


def _peaks(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def devices_for(chips: int, require_tpu: bool):
    import jax

    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"need {chips} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return devs[:chips]


def enable_cache(cell_name: str) -> Path | None:
    """JAX's persistent compilation cache, at a fixed path in the checkout.

    The first run of a cell there fills it with every program it compiles;
    later runs of the cell read from it and write nothing.  A program whose
    shapes follow the graph (S5P's game takes the cluster counts as static
    arguments) is then compiled by every later run, as it is for any new
    graph, and a run's set-up does not depend on which seeds ran before it
    in the checkout.  Returns the marker the first run writes once its
    set-up is done, or None when the cell's cache is already filled."""
    import jax

    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    FILLED_DIR.mkdir(parents=True, exist_ok=True)
    marker = FILLED_DIR / cell_name
    first = not marker.exists()
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      0.0 if first else NEVER_WRITE_S)
    return marker if first else None


def _peak_bytes(dev) -> int:
    return int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))


def _say(line: str):
    print(line, file=sys.stderr, flush=True)


def _window(job, seconds: float, tracer, dev):
    """Jobs back to back until the first that ends at or after ``seconds``;
    returns (the jobs' parts on the host, the window's host-clock seconds).

    Each job's parts come to the host as the job ends, so the device holds
    none of the benchmark's between jobs and the memory peak does not grow
    with the number of jobs in the window."""
    kept, ends, peaks = [], [], []
    t_w = time.perf_counter()
    while True:
        kept.append(np.asarray(job()))
        ends.append(time.perf_counter())
        peaks.append(_peak_bytes(dev))
        if ends[-1] - t_w >= seconds:
            break
    if tracer is not None:
        tracer.stop()
    _say(f"window jobs={len(kept)} window_s={ends[-1] - t_w:.3f} job_s="
         f"{[round(e - s, 3) for s, e in zip([t_w] + ends, ends)]} "
         f"peak_bytes_after_job={peaks}")
    return kept, ends[-1] - t_w


def _read_trace(tracer: Tracer, view: RunView, device: dict):
    """Fill the view and ``device`` from the profile; returns the
    breakdown, or None when the profile recorded nothing.  The raw profile
    stays in the cell's trace directory, with ``reduced.json`` beside it."""
    path = tracemod.find_xplane(tracer.logdir)
    tr = tracemod.load(path) if path else None
    if tr is None or tr.window is None:
        return None
    view.trace = tr
    view.busy_s = tracemod.busy_s(tr)
    view.edges_traced = None if tracer.capped else view.edges_in_window
    device["busy_s"] = view.busy_s
    device["window_s"] = tr.window_s
    bd = tracemod.breakdown(tr)
    summary = tracemod.summary(tr)
    _say(f"trace extent_s={tr.window_s:.3f} capped={tracer.capped} "
         f"busy_s={view.busy_s:.3f} programs={summary['programs']} "
         f"in_flight_at_stop={summary['in_flight_at_stop']} "
         f"closed_before_orphan_ops={summary['closed_before_orphan_ops']}")
    (Path(tracer.logdir) / "reduced.json").write_text(json.dumps(
        dict(summary, capped=tracer.capped, busy_s=view.busy_s,
             window_s=tr.window_s, breakdown=bd,
             program_seconds=view.program_seconds()), indent=1))
    return bd


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_tpu: bool = True):
    """One run; returns the result object the command prints."""
    import jax

    devs = devices_for(cell.chips, require_tpu)
    fill = enable_cache(cell.name)
    dev = devs[0]
    _say(f"device platform={dev.platform} kind={dev.device_kind} "
         f"count={len(devs)}")
    part = cell.config["partitioner"]
    k = int(part["k"])
    from repro.core.baselines import PARTITIONERS

    entry = PARTITIONERS[part["name"]]
    kwargs = dict(part.get("entry_kwargs", {}),
                  chunk_size=int(part["chunk_size"]))
    ref = load("reference", part["name"])

    src, dst, n = make_graph(cell, seed)
    E = int(src.size)
    pseed = job_seed(seed, part)
    _say(f"graph V={n} E={E} k={k} job_seed={pseed}")

    def job():
        return jax.block_until_ready(entry(src, dst, n, k, pseed, **kwargs))

    probes = Probes()
    probes.count_compiles()
    for name, target in getattr(ref, "CAPTURES", {}).items():
        probes.capture(name, target)
    if trace:
        for m in cell.per_layer:
            for name, target in getattr(load("metrics", m["name"]), "SPANS",
                                        {}).items():
                probes.span(name, target)
    try:
        t0 = time.perf_counter()
        job()
        warm_s = time.perf_counter() - t0
        setup_s = time.perf_counter() - t_start
        _say(f"setup setup_s={setup_s:.3f} warmup_job_s={warm_s:.3f} "
             f"cache={'filling' if fill else 'read-only'} "
             f"peak_bytes={_peak_bytes(dev)}")
        if fill is not None:
            fill.touch()

        tracer = None
        if trace:
            logdir = TRACE_DIR / cell.name  # the newest traced run's
            shutil.rmtree(logdir, ignore_errors=True)
            logdir.mkdir(parents=True)
            tracer = Tracer(logdir,
                            TRACE_HEAD_S if warm_s > LONG_JOB_S else None)
        probes.recording = True
        parts, window_s = _window(job, seconds, tracer, dev)
        probes.recording = False
        peak = _peak_bytes(dev)
        gc.collect()
        jobs = len(parts)
        rf = quality.replication_factor(src, dst, parts[-1], n_vertices=n,
                                        k=k)
        _say(f"quality rf={rf:.6f} "
             f"balance={quality.load_balance(parts[-1], k=k):.6f}")
        view = RunView(spans=probes.spans, compiles=probes.compiles,
                       jobs_in_window=jobs, edges_in_window=jobs * E, k=k,
                       peaks=_peaks(dev.device_kind) if require_tpu else {})
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(devs), "memory_peak_bytes": peak}
        breakdown = _read_trace(tracer, view, device) if trace else None

        t_ref = time.perf_counter()
        ref_parts, internals = ref.partition(src, dst, n, k, pseed,
                                             part.get("params", {}))
        _say(f"reference reference_s={time.perf_counter() - t_ref:.3f}")
        for key, val in getattr(ref, "diagnose", lambda c, i: {})(
                probes.captured, internals).items():
            _say(f"info {key}={val}")
    finally:
        probes.close()

    mism = [int(np.count_nonzero(p != ref_parts))
            if p.shape == ref_parts.shape else E for p in parts]
    limit = ref.LIMITS["parts_mismatch"]
    checks = {"parts_mismatch": {"value": max(mism), "limit": limit}}
    if trace:
        metrics = {}
        for m in cell.per_layer:
            v = load("metrics", m["name"]).read(view)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        values = {"edges_per_s": jobs * E / window_s, "rf": rf,
                  "hbm_peak_mib": peak / 2**20, "setup_s": setup_s}
        metrics = {m["name"]: {"value": float(values[m["name"]]),
                               "unit": m["unit"]} for m in cell.end_to_end}
    result = {"correct": all(c["value"] <= c["limit"]
                             for c in checks.values()),
              "attempted": jobs, "failed": sum(x > limit for x in mism),
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks  # last: the compared numbers and limits
    for name, c in checks.items():
        _say(f"check {name}={c['value']} limit={c['limit']} "
             f"(worst of {jobs} jobs in the window)")
    return result
