"""Partitioning driver: the paper's system as a CLI.

  python -m repro.launch.partition --graph rmat:16 --k 32 --partitioner s5p
  python -m repro.launch.partition --graph community:4000 --k 8 --compare

Out-of-core (mmap-paged edge shards; see ``repro.streaming.oocstream``):

  # convert any synthetic spec to a shard directory
  python -m repro.launch.partition --graph rmat:18 --write-shards /data/g18 \
      --shard-edges 1048576
  # partition straight from disk shards — edges page in chunk by chunk
  python -m repro.launch.partition --graph file:/data/g18/manifest.json \
      --k 32 --partitioner hdrf --ordering windowed

Parallel ingest (S sharded sub-streams per pass, carries merged every
--super-chunk chunks; see ``repro.streaming.parallel``):

  python -m repro.launch.partition --graph rmat:17 --k 8 \
      --partitioner hdrf --num-streams 8 --super-chunk 8
  # quality-neutral lanes: pin each hub's edges to one lane and let the
  # merge cadence adapt to carry contention
  python -m repro.launch.partition --graph rmat:17 --k 8 \
      --partitioner hdrf --num-streams 8 --shard-mode hub --super-chunk auto

Memory-budget hybrid (resident high-degree core + streamed tail; see
``repro.hybrid``).  ``--hybrid`` alone sizes the budget from available
host memory (``--budget-fraction`` of it); ``--host-budget`` pins it:

  python -m repro.launch.partition --graph rmat:18 --k 32 --hybrid
  python -m repro.launch.partition --graph rmat:18 --k 32 --host-budget 2G

Incremental re-partitioning (warm-start replay of only the new edges; see
``repro.incremental``):

  # cold run, persist the carry bundle
  python -m repro.launch.partition --graph community:4000 --k 8 \
      --partitioner s5p --save-carry /data/carry
  # absorb an insertion batch against the saved carry (drift-triggered
  # refinement past --drift-threshold)
  python -m repro.launch.partition --graph community:4000 --k 8 \
      --partitioner s5p --resume-carry /data/carry --delta rmat:10
  # delete edges against the saved carry: the oldest 10 %, a seeded
  # random 5 %, or the most recent 2000 (exact counted retraction for
  # greedy/hdrf/grid; tombstoned + drift-refined for s5p)
  python -m repro.launch.partition --graph community:4000 --k 8 \
      --partitioner s5p --resume-carry /data/carry --delete first:0.1
  python -m repro.launch.partition --graph community:4000 --k 8 \
      --partitioner hdrf --resume-carry /data/carry --delete frac:0.05

Sliding-window streaming (track the last W edges continuously; see
``repro.streaming.window`` + ``repro.incremental.s5p_sliding_window``):

  python -m repro.launch.partition --graph rmat:14 --k 8 \
      --partitioner s5p --window-edges 65536 --window-step 8192
  # out-of-core flavor: grow the shard directory in place, then resume —
  # the delta is everything past the carry's recorded stream position
  python -m repro.launch.partition --graph rmat:12 --write-shards /data/g \
      --shard-edges 65536
  python -m repro.launch.partition --graph file:/data/g/manifest.json \
      --k 8 --partitioner hdrf --save-carry /data/carry
  python -m repro.launch.partition --graph rmat:10 --write-shards /data/g \
      --append
  python -m repro.launch.partition --graph file:/data/g/manifest.json \
      --k 8 --partitioner hdrf --resume-carry /data/carry
"""

from __future__ import annotations

import argparse
import inspect
import time

import numpy as np

from ..core import replication_factor, load_balance, gas_comm_bytes
from ..core.baselines import PARTITIONERS
from ..graphs import rmat_graph, powerlaw_graph, toy_graph_fig3
from ..graphs.generators import community_graph
from ..runtime.compile_cache import enable_compile_cache


def load_graph(spec: str, seed: int = 0):
    kind, _, arg = spec.partition(":")
    if kind == "rmat":
        return rmat_graph(int(arg or 14), edge_factor=8, seed=seed)
    if kind == "powerlaw":
        return powerlaw_graph(int(arg or 10000), seed=seed)
    if kind == "community":
        return community_graph(int(arg or 4000), seed=seed)
    if kind == "toy":
        return toy_graph_fig3()
    if kind == "file":
        raise ValueError("file: specs are opened by run(); use the CLI or "
                         "open_sharded_stream() directly")
    raise ValueError(f"unknown graph spec {spec!r}")


def open_sharded_stream(manifest: str, *, chunk_size: int = 1 << 16,
                        ordering: str = "natural", seed: int = 0,
                        window: int = 4096):
    """Open a ``file:<manifest>`` spec as a mmap-paged ShardedEdgeStream."""
    from ..streaming import ShardedEdgeStream

    return ShardedEdgeStream(manifest, chunk_size=chunk_size,
                             ordering=ordering, seed=seed, window=window)


def write_shards_cli(graph: str, out_dir: str, shard_edges: int,
                     seed: int = 0, append: bool = False) -> str:
    """``--write-shards`` converter: synthetic spec → shard directory.

    With ``append=True`` the spec's edges grow an existing shard directory
    in place (same chunk layout as a one-shot write of the concatenation —
    see :func:`repro.streaming.append_shards`).
    """
    from ..streaming import append_shards, write_shards

    src, dst, n = load_graph(graph, seed)
    t0 = time.time()
    if append:
        # append keeps the manifest's own shard size; --shard-edges is
        # a write-time knob only
        mpath = append_shards(out_dir, src, dst)
        print(f"appended {len(src)} edges ({n} vertices) to {mpath}  "
              f"[{time.time() - t0:.1f}s]")
    else:
        mpath = write_shards(out_dir, src, dst, shard_edges=shard_edges,
                             n_vertices=n)
        print(f"wrote {len(src)} edges ({n} vertices) as shards of "
              f"{shard_edges} to {mpath}  [{time.time() - t0:.1f}s]")
    return str(mpath)


_BYTE_SUFFIXES = {"K": 1 << 10, "M": 1 << 20, "G": 1 << 30, "T": 1 << 40}


def parse_bytes(spec: str) -> int:
    """``--host-budget`` spec → bytes: plain int, or ``512M`` / ``2G`` /
    ``64KB`` (binary suffixes, case-insensitive, optional trailing B)."""
    s = str(spec).strip().upper()
    if s.endswith("B") and len(s) > 1 and not s[:-1].isdigit():
        s = s[:-1]
    mult = 1
    if s and s[-1] in _BYTE_SUFFIXES:
        mult = _BYTE_SUFFIXES[s[-1]]
        s = s[:-1]
    try:
        value = int(s)
    except ValueError:
        raise argparse.ArgumentTypeError(
            f"expected bytes like 1048576, 512M or 2G, got {spec!r}")
    if value < 0:
        raise argparse.ArgumentTypeError(f"byte budget must be >= 0, got {spec!r}")
    return value * mult


def _parse_meminfo_available(text: str) -> int | None:
    """``/proc/meminfo`` text → available bytes (``MemAvailable`` line,
    falling back to ``MemFree``), or None when neither parses."""
    free = None
    for line in text.splitlines():
        key, _, rest = line.partition(":")
        key = key.strip()
        if key not in ("MemAvailable", "MemFree"):
            continue
        fields = rest.split()
        if not fields or not fields[0].isdigit():
            continue
        value = int(fields[0])
        unit = fields[1].upper() if len(fields) > 1 else "KB"
        mult = {"B": 1, "KB": 1 << 10, "MB": 1 << 20, "GB": 1 << 30}.get(unit)
        if mult is None:
            continue
        if key == "MemAvailable":
            return value * mult
        free = value * mult
    return free


def detect_available_memory() -> int | None:
    """Available host memory in bytes, or None when undetectable.

    ``/proc/meminfo``'s MemAvailable first (counts reclaimable cache, the
    honest answer on Linux), then the portable
    ``os.sysconf(SC_AVPHYS_PAGES) * SC_PAGE_SIZE``.  No new deps.
    """
    import os

    try:
        with open("/proc/meminfo") as fh:
            avail = _parse_meminfo_available(fh.read())
        if avail is not None:
            return avail
    except OSError:
        pass
    try:
        pages = os.sysconf("SC_AVPHYS_PAGES")
        page_size = os.sysconf("SC_PAGE_SIZE")
    except (ValueError, OSError, AttributeError):
        return None
    if pages <= 0 or page_size <= 0:
        return None
    return int(pages) * int(page_size)


def auto_host_budget(fraction: float = 0.5) -> int:
    """Size ``--host-budget`` from available memory (``--hybrid`` with no
    explicit budget): ``fraction`` of what the host reports as available."""
    if not 0 < fraction <= 1:
        raise ValueError(
            f"budget_fraction must be in (0, 1], got {fraction}")
    avail = detect_available_memory()
    if avail is None:
        raise RuntimeError(
            "could not detect available host memory (/proc/meminfo and "
            "os.sysconf both unavailable); pass --host-budget explicitly")
    return int(avail * fraction)


def _parse_delete(spec: str, n_edges: int, seed: int) -> np.ndarray:
    """``--delete`` spec → arrival indices.

    ``first:X`` / ``last:X`` — the oldest / most recent X edges (a count,
    or a fraction when X < 1); ``frac:F`` — a seeded random fraction.
    """
    kind, _, arg = spec.partition(":")
    try:
        x = float(arg)
    except ValueError:
        raise ValueError(f"--delete {spec!r}: expected a number after ':'")
    if kind in ("first", "last"):
        count = int(round(x * n_edges)) if 0 < x < 1 else int(x)
        count = max(0, min(count, n_edges))
        return (np.arange(count, dtype=np.int64) if kind == "first"
                else np.arange(n_edges - count, n_edges, dtype=np.int64))
    if kind == "frac":
        if not 0 <= x <= 1:
            raise ValueError(f"--delete frac: needs a fraction in [0, 1]")
        rng = np.random.default_rng(seed + 0x5EED)
        count = int(round(x * n_edges))
        return np.sort(rng.choice(n_edges, size=count, replace=False)
                       ).astype(np.int64)
    raise ValueError(
        f"unknown --delete spec {spec!r}; one of first:X | last:X | frac:F")


def run(graph: str, k: int, partitioner: str = "s5p", seed: int = 0,
        compare: bool = False, *, chunk_size: int = 1 << 16,
        ordering: str = "natural", window: int = 4096,
        num_streams: int = 1, super_chunk: int | str = 8,
        shard: str = "range",
        save_carry: str | None = None, resume_carry: str | None = None,
        delta: str | None = None, delete: str | None = None,
        drift_threshold: float | None = None,
        refine_rounds: int | None = None,
        xi_refresh_threshold: float | None = None,
        window_edges: int | None = None, window_step: int | None = None,
        resize_k: int | None = None, host_budget: int | None = None,
        hybrid: bool = False, budget_fraction: float = 0.5):
    """Partition ``graph`` and print one quality row per partitioner.

    The plain flow returns ``[(name, RF, balance, GAS bytes/iter, wall s,
    parts), ...]``; the window/hybrid/resize/carry flows return their own
    result objects.
    """
    for pname, v in (("k", k), ("chunk_size", chunk_size), ("window", window),
                     ("num_streams", num_streams)):
        if v < 1:
            raise ValueError(f"{pname} must be >= 1, got {v}")
    if isinstance(super_chunk, str):
        if super_chunk != "auto":
            raise ValueError(
                f"super_chunk must be >= 1 or 'auto', got {super_chunk!r}")
    elif super_chunk < 1:
        raise ValueError(f"super_chunk must be >= 1, got {super_chunk}")
    if shard not in ("range", "rr", "round-robin", "hub"):
        raise ValueError(f"shard must be one of range | rr | round-robin | "
                         f"hub, got {shard!r}")
    if hybrid and host_budget is None:
        host_budget = auto_host_budget(budget_fraction)
        print(f"[hybrid] auto-sized --host-budget: {host_budget} bytes "
              f"({budget_fraction:.0%} of available host memory)")
    if host_budget is not None:
        if partitioner != "s5p":
            raise ValueError("--host-budget drives the s5p hybrid pipeline; "
                             "use --partitioner s5p")
        if (compare or window_edges is not None or resize_k is not None
                or resume_carry or delta or delete):
            raise ValueError("--host-budget runs a single hybrid partition; "
                             "drop --compare/--window-edges/--resize-k/"
                             "carry-resume flags (--save-carry combines)")
    if resize_k is not None:
        if compare or window_edges is not None or resume_carry or delta or delete:
            raise ValueError("--resize-k runs a single cold partition "
                             "followed by an elastic reshard; drop "
                             "--compare/--window-edges/carry flags")
    stream = None
    if graph.startswith("file:"):
        stream = open_sharded_stream(graph[5:], chunk_size=chunk_size,
                                     ordering=ordering, seed=seed,
                                     window=window)
        n = stream.n_vertices
        # metrics are per-edge aggregates — the one deliberate O(E)
        # materialization in this driver (the partition scans themselves
        # page from disk through the stream)
        src, dst = stream.arrival_arrays()
    else:
        src, dst, n = load_graph(graph, seed)
    if num_streams > 1:
        # a lane count or super-chunk longer than the stream used to
        # degenerate silently (clamped lanes / a single merge); reject it
        # like the other stream args instead
        n_chunks = max(-(-len(src) // chunk_size), 1)
        if num_streams > n_chunks:
            raise ValueError(
                f"num_streams must be <= the stream's chunk count "
                f"({n_chunks} chunks of {chunk_size}), got {num_streams}")
        rounds = -(-n_chunks // num_streams)
        if not isinstance(super_chunk, str) and super_chunk > rounds:
            raise ValueError(
                f"super_chunk must be <= the {rounds} chunks each of the "
                f"{num_streams} sub-streams ingests (else it degenerates "
                f"to a single merge), got {super_chunk}")
    if window_edges is not None:
        if compare:
            raise ValueError("--window-edges runs a single partitioner, "
                             "not --compare")
        if num_streams > 1:
            raise ValueError("--window-edges is sequential (the per-step "
                             "delta/retract batches are not sharded); drop "
                             "--num-streams")
        for flag, val in (("--save-carry", save_carry),
                          ("--resume-carry", resume_carry),
                          ("--delta", delta), ("--delete", delete)):
            if val:
                raise ValueError(
                    f"{flag} does not combine with --window-edges (the "
                    "window loop manages its own bundle in memory)")
        try:
            return _run_window_cli(
                src, dst, n, k, partitioner, seed, window_edges, window_step,
                stream=stream, chunk_size=chunk_size, ordering=ordering,
                drift_threshold=drift_threshold,
                refine_rounds=refine_rounds,
                xi_refresh_threshold=xi_refresh_threshold)
        finally:
            if stream is not None:
                stream.close()
    if host_budget is not None:
        try:
            return _run_hybrid_cli(
                src, dst, n, k, seed, host_budget, stream=stream,
                chunk_size=chunk_size, ordering=ordering,
                num_streams=num_streams, super_chunk=super_chunk,
                shard=shard, refine_rounds=refine_rounds,
                save_carry=save_carry)
        finally:
            if stream is not None:
                stream.close()
    if resize_k is not None:
        try:
            return _run_resize_cli(
                src, dst, n, k, resize_k, partitioner, seed,
                chunk_size=chunk_size, drift_threshold=drift_threshold,
                refine_rounds=refine_rounds,
                xi_refresh_threshold=xi_refresh_threshold)
        finally:
            if stream is not None:
                stream.close()
    if save_carry or resume_carry or delta or delete:
        try:
            return _run_incremental_cli(
                graph, src, dst, n, k, partitioner, seed, compare,
                stream=stream, chunk_size=chunk_size, ordering=ordering,
                num_streams=num_streams, super_chunk=super_chunk,
                shard=shard,
                save_carry=save_carry, resume_carry=resume_carry,
                delta=delta, delete=delete,
                drift_threshold=drift_threshold,
                refine_rounds=refine_rounds,
                xi_refresh_threshold=xi_refresh_threshold)
        finally:
            if stream is not None:
                stream.close()
    names = list(PARTITIONERS) if compare else [partitioner]
    rows = []
    for name in names:
        fn = PARTITIONERS[name]
        kw = {}
        params = inspect.signature(fn).parameters
        takes_stream = "stream" in params
        if stream is not None and takes_stream:
            kw["stream"] = stream
        elif "chunk_size" in params:
            kw["chunk_size"] = chunk_size
        if num_streams > 1 and "num_streams" in params:
            kw["num_streams"] = num_streams
            kw["super_chunk"] = super_chunk
            if "shard" in params:
                kw["shard"] = shard
        t0 = time.time()
        parts = fn(src, dst, n, k, seed, **kw)
        dt = time.time() - t0
        rf = replication_factor(src, dst, parts, n_vertices=n, k=k)
        bal = load_balance(parts, k=k)
        comm = gas_comm_bytes(src, dst, parts, n_vertices=n, k=k)
        rows.append((name, rf, bal, comm, dt, parts))
        # partitioners without a stream= parameter run on the materialized
        # arrays in natural arrival order — flag them so a file:-graph
        # comparison table is honest about which rows paged from disk (and
        # which saw the requested --ordering)
        note = "" if stream is None or takes_stream else "  [in-memory, natural]"
        print(f"{name:10s} RF={rf:7.3f} balance={bal:5.2f} "
              f"gas_comm={comm/1e6:8.2f} MB/iter  {dt:6.1f}s{note}")
    if stream is not None:
        peak = stream.budget.peak_bytes
        print(f"[oocstream] peak stream-host bytes (stream-backed rows): "
              f"{peak} ({peak / max(8 * len(src), 1):.1%} of the edge list)")
        stream.close()
    return rows


def _s5p_cfg(k, seed, chunk_size, ordering, num_streams, super_chunk,
             drift_threshold, refine_rounds, xi_refresh_threshold,
             shard="range"):
    import dataclasses

    from ..core import S5PConfig

    cfg = S5PConfig(k=k, seed=seed, chunk_size=chunk_size, ordering=ordering,
                    num_streams=num_streams, super_chunk=super_chunk,
                    shard=shard)
    overrides = {}
    if drift_threshold is not None:
        overrides["drift_rf_threshold"] = drift_threshold
    if refine_rounds is not None:
        overrides["refine_rounds"] = refine_rounds
    if xi_refresh_threshold is not None:
        overrides["xi_refresh_threshold"] = xi_refresh_threshold
    return dataclasses.replace(cfg, **overrides) if overrides else cfg


def _run_window_cli(src, dst, n, k, partitioner, seed, window_edges,
                    window_step, *, stream, chunk_size, ordering,
                    drift_threshold, refine_rounds, xi_refresh_threshold):
    """``--window-edges`` flow: continuous sliding-window partitioning."""
    from ..incremental import s5p_sliding_window

    if partitioner != "s5p":
        raise ValueError("--window-edges drives the s5p pipeline; use "
                         "--partitioner s5p (scan partitioners delete via "
                         "--resume-carry --delete)")
    if ordering != "natural":
        raise ValueError("sliding windows are defined over arrival order; "
                         "drop --ordering")
    cfg = _s5p_cfg(k, seed, chunk_size, ordering, 1, 8, drift_threshold,
                   refine_rounds, xi_refresh_threshold)
    t0 = time.time()
    history, _ = s5p_sliding_window(src, dst, n, cfg, window_edges,
                                    step_edges=window_step, stream=stream)
    dt = time.time() - t0
    for st_ in history:
        flags = "".join((
            "F" if st_.filling else "-",
            "R" if st_.refined else "-",
            "B" if st_.rolled_back else "-",
            "C" if st_.n_compacted else "-",
            "X" if st_.needs_cold_restart else "-",
        ))
        print(f"step {st_.step:4d} window=[{st_.lo},{st_.hi}) "
              f"RF={st_.rf:7.3f} balance={st_.balance:5.2f} "
              f"+{st_.n_inserted}/-{st_.n_retracted} churn={st_.churn:.2f} "
              f"xi_drift={st_.xi_drift:.2f} [{flags}]")
    print(f"[window] {len(history)} steps, {dt:.1f}s total "
          f"({dt / max(len(history), 1):.2f}s/step)")
    return history


def _run_hybrid_cli(src, dst, n, k, seed, host_budget, *, stream,
                    chunk_size, ordering, num_streams, super_chunk,
                    shard, refine_rounds, save_carry):
    """``--host-budget`` flow: memory-budget hybrid partition (s5p).

    Budget 0 degrades to the pure-streaming pipeline; a budget covering
    the edge list runs fully in-memory; anything between holds the
    high-degree core resident (``repro.hybrid``).  ``--save-carry``
    persists the hybrid warm bundle exactly like a cold run's.
    """
    import dataclasses

    from ..hybrid import run_hybrid

    cfg = _s5p_cfg(k, seed, chunk_size, ordering, num_streams, super_chunk,
                   None, refine_rounds, None, shard)
    cfg = dataclasses.replace(cfg, host_budget=int(host_budget))
    t0 = time.time()
    res = run_hybrid(stream if stream is not None else (src, dst, n), cfg)
    dt = time.time() - t0
    pct = res.peak_budget_bytes / max(host_budget, 1)
    print(f"{'hybrid':10s} RF={res.rf:7.3f} balance={res.balance:5.2f} "
          f"mode={res.mode} core={res.core_edges} "
          f"streamRF={res.rf_streaming:7.3f} "
          f"peak={res.peak_budget_bytes}B ({pct:.0%} of budget) "
          f"rounds={res.game_rounds}  {dt:6.1f}s")
    if save_carry:
        from ..incremental.driver import _prefix_crc
        from ..incremental import CarryStore, s5p_identity_config

        E = int(np.asarray(src).shape[0])
        store = CarryStore(save_carry)
        path = store.save(
            res.bundle, consumer="s5p", config=s5p_identity_config(cfg),
            stream_pos=E,
            extra_meta={"n_vertices": int(n),
                        "prefix_crc": _prefix_crc(src, dst, E)})
        print(f"[hybrid] carry→{path}")
    return res


def _run_resize_cli(src, dst, n, k, k_new, partitioner, seed, *,
                    chunk_size, drift_threshold, refine_rounds,
                    xi_refresh_threshold):
    """``--resize-k`` flow: cold partition at k, elastic reshard to k′.

    The operational shape this models: a cluster resize arrives while a
    partition is live, and instead of a cold re-partition at k′ (full
    stream replay + 100 % migration) the bundle is re-homed with bounded
    migration (``repro.elastic``).  Prints RF before/after and the
    migrated-edge fraction.
    """
    from ..elastic import reshard_bundle
    from ..incremental.pipeline import s5p_cold_bundle

    if partitioner != "s5p":
        raise ValueError("--resize-k reshards the s5p warm bundle; use "
                         "--partitioner s5p (scan carries reshard via "
                         "repro.elastic.reshard_scan_carry)")
    cfg = _s5p_cfg(k, seed, chunk_size, "natural", 1, 8, drift_threshold,
                   refine_rounds, xi_refresh_threshold)
    t0 = time.time()
    _, bundle = s5p_cold_bundle(src, dst, n, cfg)
    t_cold = time.time() - t0
    rf0 = float(bundle["rf_baseline"])
    t0 = time.time()
    _, _, res = reshard_bundle(bundle, cfg, k_new, src, dst)
    t_resize = time.time() - t0
    print(f"{partitioner:10s} k={k} RF={rf0:7.3f}  [{t_cold:.1f}s cold]")
    print(f"resize →k={k_new} RF={res.rf:7.3f} balance={res.balance:5.2f} "
          f"migrated={res.migrated_fraction:.1%} "
          f"({res.migrated_edges}/{res.n_live} edges, "
          f"{res.n_displaced} displaced, {res.moved_clusters} clusters "
          f"moved, {res.game_rounds} rounds)  [{t_resize:.1f}s]")
    return res


def _run_incremental_cli(graph, src, dst, n, k, partitioner, seed, compare,
                         *, stream, chunk_size, ordering, num_streams,
                         super_chunk, shard, save_carry, resume_carry, delta,
                         delete, drift_threshold, refine_rounds,
                         xi_refresh_threshold):
    """``--save-carry`` / ``--resume-carry`` / ``--delta`` / ``--delete``."""
    from ..incremental import cold_start, run_incremental

    if compare:
        raise ValueError("carry flows need a single --partitioner, "
                         "not --compare")
    if delta and not resume_carry:
        raise ValueError("--delta needs --resume-carry (an insertion batch "
                         "is replayed against a saved carry)")
    if delete and not resume_carry:
        raise ValueError("--delete needs --resume-carry (deletions retract "
                         "against a saved carry)")
    if ordering != "natural":
        raise ValueError(
            "incremental carries assume natural (insertion-order) streams; "
            f"a {ordering!r} reordering permutes the whole grown stream and "
            "has no stable prefix to resume from")
    if delta:
        dsrc, ddst, dn = load_graph(delta, seed + 1)
        src = np.concatenate([np.asarray(src, np.int32),
                              np.asarray(dsrc, np.int32)])
        dst = np.concatenate([np.asarray(dst, np.int32),
                              np.asarray(ddst, np.int32)])
        n = max(n, dn)
    cfg = _s5p_cfg(k, seed, chunk_size, ordering, num_streams, super_chunk,
                   drift_threshold, refine_rounds, xi_refresh_threshold,
                   shard)

    if resume_carry:
        delete_idx = _parse_delete(delete, len(src), seed) if delete else None
        t0 = time.time()
        res = run_incremental(
            resume_carry, partitioner, src, dst, n, k, seed=seed,
            chunk_size=chunk_size, s5p_config=cfg, delete=delete_idx,
            num_streams=num_streams, super_chunk=super_chunk, save=True,
            save_dir=save_carry)
        dt = time.time() - t0
        cold_note = (" NEEDS-COLD-RESTART"
                     if res.needs_cold_restart else "")
        print(f"{partitioner:10s} RF={res.rf:7.3f} balance={res.balance:5.2f} "
              f"delta={res.n_delta_edges} deleted={res.n_retracted} "
              f"replay={res.replay_fraction:.1%} "
              f"drift={res.rf_drift:+.3f} churn={res.churn:.2f} "
              f"refined={res.refined} rolled_back={res.rolled_back} "
              f"rounds={res.game_rounds}  {dt:6.1f}s{cold_note}")
        return res
    t0 = time.time()
    parts, path = cold_start(save_carry, partitioner, src, dst, n, k,
                             seed=seed, chunk_size=chunk_size,
                             s5p_config=cfg, stream=stream,
                             num_streams=num_streams,
                             super_chunk=super_chunk)
    dt = time.time() - t0
    rf = replication_factor(src, dst, parts, n_vertices=n, k=k)
    bal = load_balance(parts, k=k)
    print(f"{partitioner:10s} RF={rf:7.3f} balance={bal:5.2f} "
          f"carry→{path}  {dt:6.1f}s")
    return [(partitioner, rf, bal, None, dt)]


def _positive_int(value: str) -> int:
    """argparse type: reject non-positive sizes at the CLI boundary with a
    clear message instead of a numpy traceback from deep inside a stream."""
    try:
        iv = int(value)
    except ValueError:
        raise argparse.ArgumentTypeError(f"expected an integer, got {value!r}")
    if iv < 1:
        raise argparse.ArgumentTypeError(f"must be >= 1, got {iv}")
    return iv


def _super_chunk_arg(value: str):
    """argparse type for ``--super-chunk``: a positive chunk count, or
    ``auto`` for the adaptive cadence controller."""
    if value.strip().lower() == "auto":
        return "auto"
    try:
        return _positive_int(value)
    except argparse.ArgumentTypeError:
        raise argparse.ArgumentTypeError(
            f"expected a chunk count >= 1 or 'auto', got {value!r}")


def _fraction_arg(value: str) -> float:
    """argparse type for ``--budget-fraction``: a float in (0, 1]."""
    try:
        fv = float(value)
    except ValueError:
        raise argparse.ArgumentTypeError(f"expected a fraction, got {value!r}")
    if not 0 < fv <= 1:
        raise argparse.ArgumentTypeError(
            f"must be a fraction in (0, 1], got {value!r}")
    return fv


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--graph", default="community:4000",
                    help="rmat:S | powerlaw:N | community:N | toy | "
                         "file:<shard manifest.json>")
    ap.add_argument("--k", type=_positive_int, default=8)
    ap.add_argument("--partitioner", default="s5p", choices=list(PARTITIONERS))
    ap.add_argument("--compare", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chunk-size", type=_positive_int, default=1 << 16,
                    help="device-resident edges per chunk (also the "
                         "parallel-ingest sharding granularity)")
    ap.add_argument("--ordering", default="natural",
                    choices=("natural", "shuffled", "dst-sorted", "windowed"),
                    help="stream arrival order (file: graphs)")
    ap.add_argument("--window", type=_positive_int, default=4096,
                    help="windowed-ordering buffer (file: graphs)")
    ap.add_argument("--num-streams", type=_positive_int, default=1,
                    help="parallel-ingest sub-streams per pass (1 = "
                         "sequential, bit-identical)")
    ap.add_argument("--super-chunk", type=_super_chunk_arg, default=8,
                    help="chunks each sub-stream ingests between carry "
                         "merges, or 'auto' for the adaptive cadence "
                         "controller (merge every chunk while contested, "
                         "geometric backoff as the tables warm; state-only "
                         "passes fold isolated and merge once) — parallel "
                         "ingest only")
    ap.add_argument("--shard-mode", default="range",
                    choices=("range", "rr", "round-robin", "hub"),
                    help="how edges are dealt onto the --num-streams lanes: "
                         "contiguous chunk ranges (range), interleaved "
                         "chunks (rr), or hub-pinned edge routing (hub: an "
                         "online CMS degree sketch pins every hub's edges "
                         "to one rendezvous-hashed lane — the "
                         "quality-neutral mode on power-law graphs)")
    ap.add_argument("--write-shards", default=None, metavar="DIR",
                    help="convert --graph to edge shards in DIR and exit")
    ap.add_argument("--shard-edges", type=_positive_int, default=1 << 20,
                    help="edges per shard for --write-shards")
    ap.add_argument("--append", action="store_true",
                    help="with --write-shards: grow the existing shard "
                         "directory in place instead of writing fresh")
    ap.add_argument("--save-carry", default=None, metavar="DIR",
                    help="persist the partitioner's warm-start carry "
                         "bundle to DIR (greedy/hdrf/grid/s5p)")
    ap.add_argument("--resume-carry", default=None, metavar="DIR",
                    help="warm-start from the carry in DIR; the delta is "
                         "everything past its recorded stream position "
                         "(grow file: graphs via --write-shards --append) "
                         "plus any --delta batch")
    ap.add_argument("--delta", default=None, metavar="SPEC",
                    help="insertion batch (same specs as --graph) appended "
                         "to the stream before resuming")
    ap.add_argument("--delete", default=None, metavar="SPEC",
                    help="deletion batch against a resumed carry: first:X | "
                         "last:X (count, or fraction when X < 1) | frac:F "
                         "(seeded random fraction)")
    ap.add_argument("--window-edges", type=_positive_int, default=None,
                    help="sliding-window mode: continuously partition the "
                         "last W edges of the stream (s5p)")
    ap.add_argument("--window-step", type=_positive_int, default=None,
                    help="edges admitted per sliding-window step "
                         "(default: min(chunk-size, window-edges))")
    ap.add_argument("--drift-threshold", type=float, default=None,
                    help="relative RF drift that triggers game refinement "
                         "on resume (s5p; default from S5PConfig)")
    ap.add_argument("--refine-rounds", type=int, default=None,
                    help="refinement budget in Stackelberg rounds "
                         "(s5p; 0 disables)")
    ap.add_argument("--resize-k", type=_positive_int, default=None,
                    help="elastic resize: cold-partition at --k, then "
                         "reshard the warm bundle onto this partition "
                         "count with bounded migration (s5p)")
    ap.add_argument("--host-budget", type=parse_bytes, default=None,
                    metavar="BYTES",
                    help="memory-budget hybrid mode: host bytes spendable "
                         "on a resident high-degree core (accepts 512M / "
                         "2G suffixes; 0 = pure streaming; s5p only)")
    ap.add_argument("--hybrid", action="store_true",
                    help="memory-budget hybrid mode with the budget "
                         "auto-sized from available host memory "
                         "(--budget-fraction of /proc/meminfo "
                         "MemAvailable, falling back to os.sysconf); "
                         "--host-budget overrides")
    ap.add_argument("--budget-fraction", type=_fraction_arg, default=0.5,
                    help="fraction of detected available memory --hybrid "
                         "spends on the resident core (default 0.5)")
    ap.add_argument("--xi-refresh-threshold", type=float, default=None,
                    help="relative ξ/κ drift past which a warm chain "
                         "reports needs_cold_restart (s5p; default from "
                         "S5PConfig)")
    args = ap.parse_args()
    if args.append and not args.write_shards:
        ap.error("--append only makes sense with --write-shards DIR")
    enable_compile_cache()
    if args.write_shards:
        write_shards_cli(args.graph, args.write_shards, args.shard_edges,
                         args.seed, append=args.append)
        return
    run(args.graph, args.k, args.partitioner, args.seed, args.compare,
        chunk_size=args.chunk_size, ordering=args.ordering,
        window=args.window, num_streams=args.num_streams,
        super_chunk=args.super_chunk, shard=args.shard_mode,
        save_carry=args.save_carry,
        resume_carry=args.resume_carry, delta=args.delta,
        delete=args.delete, drift_threshold=args.drift_threshold,
        refine_rounds=args.refine_rounds,
        xi_refresh_threshold=args.xi_refresh_threshold,
        window_edges=args.window_edges, window_step=args.window_step,
        resize_k=args.resize_k, host_budget=args.host_budget,
        hybrid=args.hybrid, budget_fraction=args.budget_fraction)


if __name__ == "__main__":
    main()
