"""Smoke run of the S5P partition path on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the four-chip ingest path only

One chip: the three ``stream_scan`` megakernels (scoring greedy/HDRF
insert + retract, Alg. 1, Alg. 3) against their ``lax.scan`` oracles at a
width the fused rung admits, and Alg. 1 again at V = 65,536 on its tiled
rung; S5P and HDRF partitioning ``rmat:18`` (cut
from ``rmat:20``, see ``SCALE``) at k = 32 through
``repro.launch.partition.run`` (the automatic kernel path) against the
same partitioner with ``use_kernel=False``, bitwise; and a short
live-serving run that must observe at least two bundle swaps.

Four chips: ``run_parallel`` with four lanes for S5P's two passes and for
HDRF on the ``shard_map`` backend (one lane per chip, the backend a host
with four chips picks by itself) against the same plan on the ``threads``
backend on one chip: parts and carries must be bitwise equal, and the
four lanes' carries must sit on four different chips.

Each phase prints one line: sizes, the ladder rung each consumer took,
RF/balance, parity, and host-clock seconds taken after
``block_until_ready`` (a smoke timing, not a benchmark figure).  The last
line is one JSON object, printed only when every phase passed on a TPU;
anything else exits non-zero.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

from repro.core import S5PConfig, s5p_partition  # noqa: E402
from repro.core import s5p as s5p_mod  # noqa: E402
from repro.core.baselines import hdrf_partition  # noqa: E402
from repro.core.clustering import (ClusterCarry, compact_clusters,  # noqa: E402
                                   compute_degrees, init_state)
from repro.core.postprocess import (AssignCarry, _assign_chunk,  # noqa: E402
                                    _retract_load)
from repro.kernels import stream_scan as ss  # noqa: E402
from repro.launch.partition import load_graph, run  # noqa: E402
from repro.launch.serve import serve_graph  # noqa: E402
from repro.runtime.compile_cache import enable_compile_cache  # noqa: E402
from repro.streaming import (EdgeStream, last_ingest_stats,  # noqa: E402
                             run_parallel)
FULL_SCALE = 20  # rmat:20 with edge_factor 8: V = 1,048,576, E = 8,042,892
# Alg. 1 runs its lax.scan rung at these V (past the tiled rung's 254,976
# vertices at the default budget), twice per smoke, at about 40 µs per edge
# on one v5e: rmat:20 would take about 1,000 s of the 1,200 s a smoke may
# run, rmat:19 about 800 s with a warm compile cache.  The default is cut
# to rmat:18 (V = 262,144, E = 1,969,463, about 330 s) so a cold start
# keeps a wide margin; pass --scale for the larger graphs.
SCALE = 18
K = 32
V_TILED = 1 << 16  # the S5P benchmark cell's V: Alg. 1's tiled rung


class SmokeFailure(RuntimeError):
    pass


def _check(ok, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def _say(phase: str, **fields) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def _same(a, b) -> bool:
    la = [np.asarray(x) for x in jax.tree_util.tree_leaves(a)]
    lb = [np.asarray(x) for x in jax.tree_util.tree_leaves(b)]
    return len(la) == len(lb) and all(
        x.shape == y.shape and np.array_equal(x, y) for x, y in zip(la, lb))


_ready = jax.block_until_ready


def _rungs(taken=None) -> str:
    """The ladder rungs taken since the last ``reset_path_log``."""
    taken = ss.paths_taken() if taken is None else taken
    return ",".join(f"{c}[{m}]={p}" if m else f"{c}={p}"
                    for c, m, p in taken) or "none"


def _kernel_inputs(V: int, E: int, seed: int = 0):
    """Skewed random edges: 30 % of endpoints land on 64 hubs, so replica
    hits, self-loops and capacity overflow all occur."""
    rng = np.random.default_rng(seed)

    def ends():
        hub = rng.random(E) < 0.3
        return np.where(hub, rng.integers(0, 64, E), rng.integers(0, V, E))

    return jnp.asarray(ends(), jnp.int32), jnp.asarray(ends(), jnp.int32)


def phase_kernels(V: int = 16_000, E: int = 1 << 16) -> None:
    """Each megakernel compiled on the chip against its oracle on the chip,
    at a width the fused rung admits (V = 16,000 at k = 32 needs 7.8 MiB of
    the 8 MiB default VMEM budget); Alg. 1 also at ``V_TILED``, on its
    tiled rung."""
    src, dst = _kernel_inputs(V, E)
    n = jnp.int32(E)
    for mode in ("greedy", "hdrf"):
        kern = (ss.GreedyCarry(V, K, use_kernel=True) if mode == "greedy"
                else ss.HdrfCarry(V, K, use_kernel=True))
        orac = (ss.GreedyCarry(V, K, use_kernel=False) if mode == "greedy"
                else ss.HdrfCarry(V, K, use_kernel=False))
        ss.reset_path_log()
        carry0 = kern.init()
        _ready(kern.step_chunk(carry0, src, dst, n))  # compile
        t0 = time.perf_counter()
        c_k, p_k = _ready(kern.step_chunk(carry0, src, dst, n))
        t_k = time.perf_counter() - t0
        _check(ss.paths_taken() == [("scoring", mode, "fused")],
               f"scoring[{mode}] took {ss.paths_taken()}, expected fused")
        _ready(orac.step_chunk(carry0, src, dst, n))
        t0 = time.perf_counter()
        c_o, p_o = _ready(orac.step_chunk(carry0, src, dst, n))
        t_o = time.perf_counter() - t0
        ins_ok = _same((p_k, c_k), (p_o, c_o))
        back_k = _ready(kern.retract_chunk(c_k, src, dst, n, p_k))
        back_o = orac.retract_chunk(c_o, src, dst, n, p_o)
        ret_ok = _same(back_k, back_o) and _same(back_k, carry0)
        _say(f"kernel:scoring[{mode}]", V=V, E=E, k=K, rung=_rungs(),
             insert_parity=ins_ok, retract_parity=ret_ok,
             kernel_s=f"{t_k:.4f}", oracle_s=f"{t_o:.4f}")
        _check(ins_ok, f"scoring[{mode}] insert differs from its oracle")
        _check(ret_ok, f"scoring[{mode}] retract is not the exact inverse")

    for v_alg1, rung in ((V, "fused"), (V_TILED, "tiled")):
        phase_alg1(v_alg1, E, rung)

    rng = np.random.default_rng(1)
    n_cl = 512
    c2p = jnp.asarray(rng.integers(0, K, n_cl), jnp.int32)
    cu = jnp.asarray(rng.integers(0, n_cl, E), jnp.int32)
    cv = jnp.asarray(rng.integers(0, n_cl, E), jnp.int32)
    head = jnp.asarray(rng.integers(0, 2, E), jnp.int32)
    cap = E // K  # tight: both overflow branches fire
    kern = AssignCarry(K, cap, c2p, use_kernel=True)
    ss.reset_path_log()
    load0 = kern.init()
    _ready(kern.step_chunk(load0, src, dst, n, head, cu, cv))
    t0 = time.perf_counter()
    l_k, p_k = _ready(kern.step_chunk(load0, src, dst, n, head, cu, cv))
    t_k = time.perf_counter() - t0
    _check(ss.paths_taken() == [("assign", "", "fused")],
           f"assign took {ss.paths_taken()}, expected fused")
    l_o, p_o = _assign_chunk(load0, jnp.int32(cap), src, dst, head, cu, cv,
                             c2p, k=K)
    nv = jnp.int32(E - 1000)  # partial retraction exercises the limit
    back_k = kern.retract_chunk(l_k, src, dst, nv, p_k)
    ok = _same((p_k, l_k), (p_o, l_o))
    ret_ok = _same(back_k, _retract_load(l_o, src, dst, nv, p_o))
    _say("kernel:assign", E=E, k=K, cap=cap, rung=_rungs(),
         insert_parity=ok, retract_parity=ret_ok, kernel_s=f"{t_k:.4f}")
    _check(ok, "assign_scan differs from its oracle")
    _check(ret_ok, "assign_scan retract differs from its oracle")


def phase_alg1(V: int, E: int, rung: str) -> None:
    """Alg. 1 through ``ClusterCarry`` on the rung the ladder picks at V,
    against its oracle on the chip, bitwise, for S5P and S5P-B tails."""
    src, dst = _kernel_inputs(V, E)
    n = jnp.int32(E)
    deg = compute_degrees(src, dst, V)
    xi = max(int(np.asarray(deg).mean()), 1)
    kappa = max(2 * E // K, 2)
    s0 = init_state(V)
    for global_tail in (False, True):
        kw = dict(xi=xi, kappa=kappa, global_tail=global_tail)
        kern = ClusterCarry(deg, V, use_kernel=True, **kw)
        orac = ClusterCarry(deg, V, use_kernel=False, **kw)
        ss.reset_path_log()
        _ready(kern.step_chunk(s0, src, dst, n))
        t0 = time.perf_counter()
        s_k, _ = _ready(kern.step_chunk(s0, src, dst, n))
        t_k = time.perf_counter() - t0
        _check(ss.paths_taken() == [("cluster", "", rung)],
               f"cluster took {ss.paths_taken()}, expected {rung}")
        s_o, _ = _ready(orac.step_chunk(s0, src, dst, n))
        ok = _same(s_k, s_o)
        _say("kernel:cluster", V=V, E=E, xi=xi, kappa=kappa,
             global_tail=global_tail, rung=_rungs(), parity=ok,
             kernel_s=f"{t_k:.4f}")
        _check(ok, f"cluster_scan ({rung}) differs from its oracle")


def phase_partition(name: str, scale: int, graph) -> None:
    """``run`` on the automatic kernel path, then the plain XLA-scan
    reference (``use_kernel=False``) on the same chip: parts bitwise."""
    src, dst, n = graph
    ss.reset_path_log()
    t0 = time.perf_counter()
    rows = run(f"rmat:{scale}", K, name)
    _, rf, bal, _, _, parts = rows[0]
    _ready(parts)
    t_run = time.perf_counter() - t0
    rungs = ss.paths_taken()
    _check(any(p != "oracle" for _, _, p in rungs),
           f"{name}: no kernel rung ran ({rungs})")
    t0 = time.perf_counter()
    if name == "s5p":
        ref = s5p_partition(src, dst, n, S5PConfig(k=K, seed=0,
                                                   use_kernel=False)).parts
    else:
        ref = hdrf_partition(src, dst, n, K, 0, chunk_size=1 << 16,
                             use_kernel=False)
    _ready(ref)
    t_ref = time.perf_counter() - t0
    ok = _same(parts, ref)
    _say(f"partition:{name}", graph=f"rmat:{scale}", V=n, E=len(src), k=K,
         rung=_rungs(rungs), RF=f"{rf:.4f}", balance=f"{bal:.4f}",
         parity=ok, kernel_path_s=f"{t_run:.1f}", reference_s=f"{t_ref:.1f}")
    _check(ok, f"{name}: kernel-path parts differ from use_kernel=False")
    if name == "s5p":
        _check(bal <= 1.05, f"s5p balance {bal:.4f} > 1.05")


def phase_serve() -> None:
    """A short live-serving run: sliding-window S5P publishes bundle swaps
    while GAS PageRank super-steps run on the device."""
    ss.reset_path_log()
    t0 = time.perf_counter()
    server, controller = serve_graph(window_edges=2048, step_edges=512,
                                     verbose=False)
    _ready(server.values)
    dt = time.perf_counter() - t0
    s = server.metrics.summary()
    _say("serve", graph="block-rmat", window=2048, step=512,
         versions=controller.version,
         swaps=s["swaps_observed"], supersteps=s["supersteps"],
         rf=f"{s['rf_final']:.4f}", rung=_rungs(), wall_s=f"{dt:.1f}")
    _check(s["swaps_observed"] >= 2,
           f"serving observed {s['swaps_observed']} swaps, expected >= 2")


def phase_four_chips(scale: int, chunk_size: int = 1 << 14) -> None:
    """Four ingest lanes: shard_map (one lane per chip) against threads (all
    lanes on one chip), bitwise, for HDRF and S5P's Alg. 1 / Alg. 3.  At
    the default rmat:14 every lane runs a kernel: HDRF on the tiled rung
    (V = 16,384 is just past the fused rung), Alg. 1 and Alg. 3 fused."""
    S = 4
    _check(len(jax.devices()) >= S, f"{len(jax.devices())} devices, need {S}")
    spec = f"rmat:{scale}"
    for name in ("s5p", "hdrf"):
        ss.reset_path_log()
        t0 = time.perf_counter()
        rows = run(spec, K, name, num_streams=S, super_chunk=1,
                   chunk_size=chunk_size)
        _ready(rows[0][5])
        dt = time.perf_counter() - t0
        st = last_ingest_stats()
        devs = [l.device for l in st.lanes]
        _say(f"4chip:run:{name}", graph=spec, S=S, backend=st.backend,
             lane_devices=devs, rung=_rungs(), RF=f"{rows[0][1]:.4f}",
             balance=f"{rows[0][2]:.4f}", wall_s=f"{dt:.1f}")
        _check(st.backend == "shard_map",
               f"{name}: --num-streams {S} on {S} chips ran {st.backend}")
        _check(len(set(devs)) == S, f"{name}: lanes on devices {devs}")

    src, dst, n = load_graph(spec)
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    E = int(src.shape[0])
    stream = EdgeStream(src, dst, n, chunk_size=chunk_size)
    degrees = compute_degrees(src, dst, n)
    xi = int(2.0 * E / n)
    kappa = max(-(-2 * E // K), 2)

    def both(pc, *extras):
        out = {}
        for backend in ("shard_map", "threads"):
            ss.reset_path_log()
            t0 = time.perf_counter()
            res = _ready(run_parallel(stream, pc, *extras, num_streams=S,
                                      super_chunk=1, backend=backend))
            st = last_ingest_stats()
            out[backend] = (res, [l.device for l in st.lanes],
                            time.perf_counter() - t0, _rungs())
        return out

    cases = [("hdrf", ss.HdrfCarry(n, K), ())]
    cl = both(ClusterCarry(degrees, n, xi=xi, kappa=kappa))
    cases.append(("s5p:alg1", None, cl))
    res = compact_clusters(cl["threads"][0][1], degrees, xi)
    cu, cv, is_head = s5p_mod._edge_clusters(src, dst, res, degrees, xi)
    c2p = jnp.arange(max(res.n_clusters, 1), dtype=jnp.int32) % K
    cases.append(("s5p:alg3", AssignCarry(K, -(-E // K), c2p),
                  (is_head, jnp.maximum(cu, 0), jnp.maximum(cv, 0))))
    for label, pc, extras in cases:
        out = extras if pc is None else both(pc, *extras)
        (r_sm, d_sm, t_sm, rg), (r_th, d_th, t_th, _) = (
            out["shard_map"], out["threads"])
        ok = _same(r_sm, r_th)
        _say(f"4chip:{label}", graph=spec, S=S, rung=rg,
             shard_map_devices=d_sm, threads_devices=d_th,
             parity=ok, shard_map_s=f"{t_sm:.1f}", threads_s=f"{t_th:.1f}")
        _check(ok, f"{label}: shard_map and threads results differ")
        _check(len(set(d_sm)) == S, f"{label}: shard_map lanes on {d_sm}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--scale", type=int, default=None,
                    help=f"R-MAT scale of the partition graph (default "
                         f"{SCALE} on one chip, 14 on four)")
    args = ap.parse_args(argv)
    try:
        devices = jax.devices()
        if devices[0].platform != "tpu":
            print(f"[smoke] no TPU: JAX found {devices[0].platform}",
                  file=sys.stderr)
            return 2
        cache = enable_compile_cache()
        _say("smoke", device=devices[0].device_kind, count=len(devices),
             compile_cache=cache)
        t_all = time.perf_counter()
        if args.chips == 4:
            phase_four_chips(args.scale or 14)
        else:
            scale = args.scale or SCALE
            if scale != FULL_SCALE:
                _say("smoke", note=f"graph cut to rmat:{scale} from "
                                   f"rmat:{FULL_SCALE}")
            phase_kernels()
            graph = load_graph(f"rmat:{scale}")
            for name in ("s5p", "hdrf"):
                phase_partition(name, scale, graph)
            phase_serve()
        _say("smoke", all_phases="passed",
             wall_s=f"{time.perf_counter() - t_all:.1f}")
    except Exception:  # noqa: BLE001 — any phase failing fails the smoke
        traceback.print_exc()
        print("[smoke] FAILED", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
