"""S5P — Skewness-aware Streaming Vertex-cut Partitioner (the paper's system).

Pipeline (paper Fig. 2):

  edge stream ──Alg.1──▶ head/tail clusters ──Alg.2──▶ cluster→partition
              ──Alg.3──▶ edge→partition  (+ RF / balance metrics)

Variants exposed here:
- ``S5P``            — the full system (CMS-backed Θ counts by default);
- ``S5P (exact Θ)``  — red-black-tree-equivalent exact counts (Fig. 9 ablation);
- ``S5P-B``          — bounded variant of §5.3 (global degrees everywhere,
                       no κ cap, no maxLoad) with the Theorem-2 RF bound;
- ``one_stage=True`` — single-stage simultaneous game (Fig. 7d ablation).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from . import clustering as _cl
from . import game as _game
from . import postprocess as _post
from .cms import CMSketch, SketchCarry, cms_query, pair_key, suggest_params
from .. import streaming as _stream
from ..runtime import spans

__all__ = ["S5PConfig", "S5POutput", "s5p_partition", "cluster_statistics"]

_INT32_MAX = 2**31 - 1


@dataclasses.dataclass(frozen=True)
class S5PConfig:
    k: int
    tau: float = 1.0  # balance threshold (paper uses 1.0)
    beta: float = 1.0  # ξ = β · avg_degree (paper recommends β = 1)
    use_cms: bool = True
    cms_epsilon: float = 0.1
    cms_nu: float = 0.01
    game_batch_size: int = 256
    game_max_rounds: int = 96
    # 0.9 damping converges to measurably better equilibria than the seed's
    # 0.7 (multi-seed mean RF beats HDRF on community graphs — Table 3)
    game_accept_prob: float = 0.9
    chunk_size: int = 1 << 16
    ordering: str = "natural"  # EdgeStream arrival order (§6.5 robustness)
    bounded: bool = False  # S5P-B (§5.3)
    one_stage: bool = False  # Fig. 7d ablation: no leader/follower split
    seed: int = 0
    # parallel ingest (HEP/CuSP regime): S sharded sub-streams per pass,
    # carry all-reduced every super_chunk chunks; 1 = sequential (exact).
    # super_chunk may be "auto" (adaptive merge cadence) and shard picks
    # the lane layout ("range" / "round-robin" / "hub" — hub-pinned edge
    # routing, the quality-neutral mode); see streaming.parallel.
    num_streams: int = 1
    super_chunk: int | str = 8
    shard: str = "range"
    # post-ingest touch-up (S > 1 only): one bounded masked-game pass over
    # clusters whose membership was written by ≥ 2 lanes, re-placing only
    # the moved clusters' edges (budget = refine_rounds)
    touch_up: bool = True
    # incremental re-partitioning (repro.incremental): relative RF /
    # absolute balance drift past which a delta triggers game refinement,
    # and the refinement budget in Stackelberg rounds (0 disables)
    drift_rf_threshold: float = 0.05
    drift_balance_threshold: float = 0.10
    refine_rounds: int = 16
    # decremental churn: fraction of live edges retracted (deleted or
    # window-expired) since the last baseline that triggers refinement
    # even when RF has not visibly drifted — retraction leaves the
    # approximate cluster volumes behind regardless of the RF signal
    drift_churn_threshold: float = 0.25
    # full-refresh policy: relative drift of the frozen ξ (or κ) from the
    # values a cold run over the live graph would choose, past which the
    # warm chain raises needs_cold_restart (advisory — see drift.py)
    xi_refresh_threshold: float = 0.5
    # megakernel dispatch: None = auto (fused Pallas path on TPU, oracle
    # scan elsewhere); vmem_budget overrides the fused/tiled/oracle ladder
    # gate (falls back to REPRO_VMEM_BUDGET env, then 8 MiB)
    use_kernel: bool | None = None
    vmem_budget: int | None = None
    # hybrid memory-budget mode (repro.hybrid): host bytes the partitioner
    # may spend on a resident high-degree core (HEP regime).  None/0 keeps
    # the pure-streaming pipeline; run_hybrid's host_budget= overrides.
    host_budget: int | None = None


@dataclasses.dataclass
class S5POutput:
    parts: jax.Array  # (E,) int32 edge → partition
    k: int
    n_clusters: int
    n_head_clusters: int
    game_rounds: int
    game_converged: bool
    xi: int
    kappa: int
    max_load: int
    cluster_assignment: np.ndarray  # (C,) cluster → partition
    aux: dict[str, Any]


def _edge_clusters(src, dst, res: _cl.ClusterResult, degrees, xi):
    """Per-edge (cu, cv, is_head_edge) from the compacted tables."""
    is_head = (degrees[src] > xi) & (degrees[dst] > xi)
    cu = jnp.where(is_head, res.v2c_h[src], res.v2c_t[src])
    cv = jnp.where(is_head, res.v2c_h[dst], res.v2c_t[dst])
    return cu, cv, is_head


def cluster_statistics(
    src,
    dst,
    res: _cl.ClusterResult,
    degrees,
    xi: int,
    *,
    use_cms: bool,
    cms_epsilon: float,
    cms_nu: float,
    seed: int,
    chunk_size: int = 1 << 18,
    num_streams: int = 1,
    super_chunk: int = 8,
):
    """Stream pass 2: cluster sizes + inter-cluster adjacency Θ.

    Sizes: an internal edge (cu == cv) contributes 1 to its cluster; a
    boundary edge contributes ½ to each side (postprocess will place it at
    one of the two — ½ is its expectation, keeping Σ|c| = |E|).

    Θ counts: streamed into a count-min sketch (paper §4.4) or kept exact.
    The *structural* pair list (which clusters are adjacent) is deduped
    host-side; CMS replaces only the count storage — the paper's claim (and
    our Fig. 9 benchmark) is about count-map memory, which dominates.

    Cross-type adjacency: a head vertex belongs to *both* a head cluster and
    (if it ever appears in a tail edge) a tail cluster.  An edge spans every
    pair of endpoint memberships (paper §4.3's Θ over C_H ∪ C_T) — this is
    the channel through which leader (head-cluster) moves steer followers;
    without it the two stages of the Stackelberg game would decouple.
    """
    C = res.n_clusters
    cu, cv, is_head = _edge_clusters(src, dst, res, degrees, xi)
    valid = src != dst
    internal = (cu == cv) & valid
    boundary = (cu != cv) & valid

    sizes = jax.ops.segment_sum(
        jnp.where(internal, 1.0, 0.0), jnp.maximum(cu, 0), num_segments=C
    )
    sizes = sizes + jax.ops.segment_sum(
        jnp.where(boundary, 0.5, 0.0), jnp.maximum(cu, 0), num_segments=C
    )
    sizes = sizes + jax.ops.segment_sum(
        jnp.where(boundary, 0.5, 0.0), jnp.maximum(cv, 0), num_segments=C
    )

    # membership cross-product pairs: primary (cu, cv) + the other-type
    # memberships of each endpoint (−1 ⇒ absent)
    hu, hv = res.v2c_h[src], res.v2c_h[dst]
    tu, tv = res.v2c_t[src], res.v2c_t[dst]
    alt_u = jnp.where(is_head, tu, hu)  # u's membership in the *other* table
    alt_v = jnp.where(is_head, tv, hv)
    pair_sets = [
        (cu, cv, valid),
        (alt_u, cv, valid & (alt_u >= 0)),
        (cu, alt_v, valid & (alt_v >= 0)),
    ]
    a_parts, b_parts = [], []
    for a, b, ok in pair_sets:
        ok = ok & (a != b) & (a >= 0) & (b >= 0)
        a_parts.append(spans.to_host(jnp.where(ok, jnp.minimum(a, b), C)))
        b_parts.append(spans.to_host(jnp.where(ok, jnp.maximum(a, b), C)))
    a_np = np.concatenate(a_parts)
    b_np = np.concatenate(b_parts)
    keys = a_np.astype(np.int64) * (C + 1) + b_np
    uniq, counts = np.unique(keys[a_np < C], return_counts=True)
    pa = (uniq // (C + 1)).astype(np.int32)
    pb = (uniq % (C + 1)).astype(np.int32)

    sketch_mem = 0
    if use_cms:
        w, d = suggest_params(cms_epsilon, cms_nu)
        # the Θ pass is itself an EdgeStream (over cluster-pair ids) driven
        # by a SketchCarry; the sketch is linear, so parallel ingest of the
        # pair stream merges exactly (table SUM)
        pair_stream = _stream.EdgeStream(
            a_np[a_np < C], b_np[a_np < C], C + 1, chunk_size=chunk_size
        )
        theta = SketchCarry(w * max(1, int(math.sqrt(C))), d, seed=seed)
        # the pair stream always shards by range: the sketch is linear, so
        # lane merges are exact regardless of routing — hub pinning buys
        # nothing here and would re-sketch degrees of cluster-pair ids
        _, sketch = _stream.run_parallel(
            pair_stream, theta, num_streams=num_streams,
            super_chunk=super_chunk)
        pw = cms_query(sketch, pair_key(jnp.asarray(pa), jnp.asarray(pb))).astype(jnp.float32)
        sketch_mem = sketch.memory_bytes()
    else:
        sketch = None
        pw = jnp.asarray(counts, jnp.float32)

    exact_mem = int(uniq.size) * (8 + 4)  # RBT-equivalent: key + count per pair
    return sizes, jnp.asarray(pa), jnp.asarray(pb), pw, {
        "n_pairs": int(uniq.size),
        "sketch_bytes": sketch_mem,
        "exact_count_bytes": exact_mem,
        "counts_exact": counts,
        "sketch": sketch,
    }


def s5p_partition(src, dst, n_vertices: int, config: S5PConfig,
                  stream: "_stream.EdgeStream | None" = None) -> S5POutput:
    """One whole S5P job, as the span ``s5p.job``; its phases are the
    child spans ``s5p.alg1``, ``s5p.compact``, ``s5p.theta``,
    ``s5p.game``, ``s5p.alg3`` and ``s5p.touch_up``, and every
    device-to-host pull on the path is a ``host.pull`` span."""
    with spans.span("s5p.job") as job:
        out = _s5p_job(src, dst, n_vertices, config, stream)
        job.wait_for(out.parts)
    return out


def _s5p_job(src, dst, n_vertices, config, stream):
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    E = int(src.shape[0])
    k = config.k

    # one EdgeStream, replayed by every pass (Fig. 2's single-stream pipeline)
    if stream is None:
        stream = _stream.EdgeStream(
            src, dst, n_vertices, chunk_size=config.chunk_size,
            ordering=config.ordering, seed=config.seed,
        )

    degrees = _cl.compute_degrees(src, dst, n_vertices)
    avg_deg = 2.0 * E / max(n_vertices, 1)
    xi = min(int(config.beta * avg_deg), _INT32_MAX - 1)
    kappa = _INT32_MAX if config.bounded else max(int(math.ceil(2.0 * E / k)), 2)

    # ---- Phase 1: skewness-aware streaming clustering (Alg. 1) ----
    with spans.span("s5p.alg1") as sp:
        state = sp.wait_for(_cl.cluster_stream(
            src, dst, n_vertices, xi=xi, kappa=kappa,
            global_tail=config.bounded, stream=stream,
            num_streams=config.num_streams, super_chunk=config.super_chunk,
            shard=config.shard,
            use_kernel=config.use_kernel, vmem_budget=config.vmem_budget,
        ))
    with spans.span("s5p.compact") as sp:
        res = sp.wait_for(_cl.compact_clusters(state, degrees, xi))

    if res.n_clusters == 0:  # degenerate: no valid edges
        return S5POutput(
            parts=jnp.full((E,), -1, jnp.int32), k=k, n_clusters=0,
            n_head_clusters=0, game_rounds=0, game_converged=True, xi=xi,
            kappa=kappa, max_load=0, cluster_assignment=np.zeros(0, np.int32),
            aux={},
        )

    # ---- Phase 2: Stackelberg game (Alg. 2) ----
    with spans.span("s5p.theta") as sp:
        sizes, pa, pb, pw, stats = cluster_statistics(
            src, dst, res, degrees, xi,
            use_cms=config.use_cms, cms_epsilon=config.cms_epsilon,
            cms_nu=config.cms_nu, seed=config.seed,
            num_streams=config.num_streams, super_chunk=config.super_chunk,
        )
        sp.wait_for((sizes, pa, pb, pw))
    n_head = res.n_clusters if config.one_stage else res.n_head
    inputs = _game.GameInputs(
        sizes=sizes.astype(jnp.float32), pair_a=pa, pair_b=pb,
        pair_w=pw.astype(jnp.float32), n_head=n_head, k=k,
    )
    bs = _game.default_batch_size(config.game_batch_size, res.n_clusters)
    with spans.span("s5p.game") as sp:
        game = _game.run_game(
            inputs, res.n_clusters,
            batch_size=bs, max_rounds=config.game_max_rounds,
            accept_prob=config.game_accept_prob, seed=config.seed,
        )
        sp.wait_for(game.assignment)

    # ---- Phase 3: postprocess (Alg. 3) ----
    max_load = _INT32_MAX if config.bounded else int(math.ceil(config.tau * E / k))
    with spans.span("s5p.alg3") as sp:
        cu, cv, is_head = _edge_clusters(src, dst, res, degrees, xi)
        # the placement's lane plan, kept for the touch-up's provenance
        plan = (_stream.ParallelEdgeStream(stream, config.num_streams,
                                           shard=config.shard)
                if config.num_streams > 1 else None)
        parts, load = sp.wait_for(_post.assign_edges_stream(
            src, dst, is_head, jnp.maximum(cu, 0), jnp.maximum(cv, 0),
            game.assignment, k, max_load, stream=stream,
            num_streams=config.num_streams, super_chunk=config.super_chunk,
            shard=config.shard, plan=plan,
            use_kernel=config.use_kernel, vmem_budget=config.vmem_budget,
        ))
    ingest = _stream.last_ingest_stats()  # the placement pass's drive
    if ingest is not None:
        stats["parallel_ingest"] = ingest.as_dict()

    # ---- post-ingest touch-up (parallel quality recovery) ----
    c2p = spans.to_host(game.assignment)
    if (config.num_streams > 1 and config.touch_up
            and config.refine_rounds > 0 and res.n_clusters > 1):
        with spans.span("s5p.touch_up") as sp:
            parts, load, c2p, tu_stats = sp.wait_for(_touch_up(
                src, dst, n_vertices, config, plan, res, inputs, bs,
                cu, cv, is_head, sizes, parts, load, c2p, k, max_load))
        stats["touch_up"] = tu_stats

    # pipeline internals for warm starts (repro.incremental builds its
    # carry bundle from these instead of re-deriving them): O(|V| + C + P
    # + k) state, no per-edge arrays beyond what parts already is
    stats["incremental"] = {
        "cluster_state": state,
        "degrees": degrees,
        "compact": res,
        "sizes": sizes,
        "pair_a": pa,
        "pair_b": pb,
        "pair_w": pw,
        "load": load,
    }

    return S5POutput(
        parts=parts,
        k=k,
        n_clusters=res.n_clusters,
        n_head_clusters=res.n_head,
        game_rounds=int(game.rounds),
        game_converged=bool(game.converged),
        xi=xi,
        kappa=kappa,
        max_load=max_load,
        cluster_assignment=c2p,
        aux=stats,
    )


def _touch_up(src, dst, n_vertices, config, plan, res, inputs, bs,
              cu, cv, is_head, sizes, parts, load, c2p, k, max_load):
    """One bounded masked-game pass over the clusters whose membership was
    written by ≥ 2 ingest lanes — the only clusters whose carry state could
    have gone stale across lanes — then re-place exactly those clusters'
    edges (the ``_refine_pass`` recipe of ``repro.incremental``): lift the
    moved edges out of the load vector and replay them in arrival order
    against the refined cluster→partition table.  Counts
    ``s5p.touch_up.contested``, ``.moved`` (clusters) and
    ``.replayed_edges``."""
    C = res.n_clusters
    # provenance: which lane folded each edge, from the placement pass's
    # own plan (every pass of the job shards the stream the same way)
    lanes = plan.edge_lanes()
    cu_np = spans.to_host(cu)
    cv_np = spans.to_host(cv)
    valid = spans.to_host(src != dst)
    c_all = np.concatenate([cu_np[valid], cv_np[valid]])
    l_all = np.concatenate([lanes[valid], lanes[valid]])
    ok = c_all >= 0
    mn = np.full(C, np.iinfo(np.int32).max, np.int64)
    mx = np.full(C, -1, np.int64)
    np.minimum.at(mn, c_all[ok], l_all[ok])
    np.maximum.at(mx, c_all[ok], l_all[ok])
    contested = (mx > mn)  # touched by ≥ 2 lanes
    move_mask = contested & (spans.to_host(sizes) > 0)
    stats = {"contested_clusters": int(contested.sum()), "moved_clusters": 0,
             "replayed_edges": 0, "rounds": 0}
    spans.count("s5p.touch_up.contested", stats["contested_clusters"])
    if not move_mask.any():
        return parts, load, c2p, stats
    refined = _game.run_game(
        inputs, C, batch_size=bs, max_rounds=config.refine_rounds,
        accept_prob=config.game_accept_prob, assign0=jnp.asarray(c2p),
        seed=config.seed + 1,
        leader_mask=np.arange(C) < inputs.n_head,
        move_mask=move_mask,
    )
    stats["rounds"] = int(refined.rounds)
    c2p_new = spans.to_host(refined.assignment)
    moved = np.flatnonzero(c2p_new != c2p)
    stats["moved_clusters"] = int(moved.size)
    spans.count("s5p.touch_up.moved", stats["moved_clusters"])
    if not moved.size:
        return parts, load, c2p, stats
    moved_mask = np.zeros(C, bool)
    moved_mask[moved] = True
    aff = valid & (moved_mask[np.maximum(cu_np, 0)]
                   | moved_mask[np.maximum(cv_np, 0)])
    aidx = np.flatnonzero(aff)
    stats["replayed_edges"] = int(aidx.size)
    spans.count("s5p.touch_up.replayed_edges", stats["replayed_edges"])
    parts_np = spans.to_host(parts).copy()
    load64 = spans.to_host(load).astype(np.int64)
    np.subtract.at(load64, parts_np[aidx], 1)
    re_stream = _stream.EdgeStream(
        spans.to_host(src)[aidx], spans.to_host(dst)[aidx], n_vertices,
        chunk_size=config.chunk_size)
    ac = _post.AssignCarry(k, max_load, jnp.asarray(c2p_new),
                           use_kernel=config.use_kernel,
                           vmem_budget=config.vmem_budget)
    re_parts, load = _stream.run_carry(
        re_stream, ac,
        jnp.asarray(spans.to_host(is_head)[aidx]),
        jnp.asarray(np.maximum(cu_np[aidx], 0)),
        jnp.asarray(np.maximum(cv_np[aidx], 0)),
        carry=jnp.asarray(load64.astype(np.int32)))
    parts_np[aidx] = spans.to_host(re_parts)
    return jnp.asarray(parts_np), load, c2p_new, stats
