"""Streaming vertex-cut baselines the paper compares against (§6.2).

All are single-pass streaming partitioners over the same edge-stream
contract as S5P.  Scoring/sequential ones (Greedy, HDRF, Grid) run as
jitted ``lax.scan`` with O(k|V|) carry (the counted replica table — the
same asymptotics as their reference C++ implementations' bitmaps; the
int32 counters OR-project for scoring, identically, and additionally
support exact edge deletion via ``retract_chunk`` — see
``repro.kernels.stream_scan`` and ``repro.incremental``).  Hash/DBH are
one-shot vectorized.

- Hash:   p = h(eid) mod k                                    [random]
- DBH:    hash the lower-(global-)degree endpoint             [Xie et al. 2014]
- Grid:   candidate cells = row∪col of each endpoint's hashed
          cell; pick least-loaded intersection cell           [GraphBuilder 2013]
- Greedy: PowerGraph's 4-case replica-aware heuristic         [Gonzalez 2012]
- HDRF:   degree-weighted replica score + balance term        [Petroni 2015]
- 2PS-L-style: Holl-ish global-degree clustering + linear
          cluster placement + streaming refinement            [Mayer 2022]
- CLUGP-style: local-degree clustering + ONE-stage
          simultaneous cluster game + postprocess             [Kong 2022]

The 2PS-L / CLUGP entries are faithful *reimplementations of the published
algorithmsʼ structure* (clustering-refinement), not the authors' binaries;
they double as the paper's Fig. 7 ablations (CLUGP-style == S5P with
``one_stage`` game and local-degree-only clustering).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from . import clustering as _cl
from . import postprocess as _post
from .s5p import S5PConfig, s5p_partition
from ..kernels import stream_scan as _scan
from ..streaming import as_stream, run_parallel, run_scan_batched

__all__ = [
    "hash_partition",
    "dbh_partition",
    "grid_partition",
    "greedy_partition",
    "hdrf_partition",
    "hdrf_partition_batched",
    "grid_partition_multi_seed",
    "two_ps_partition",
    "clugp_partition",
    "PARTITIONERS",
]

_GOLD = np.uint32(0x9E3779B1)


def _hash32(x: jax.Array, seed: int = 0) -> jax.Array:
    h = x.astype(jnp.uint32) * jnp.uint32(_GOLD) ^ jnp.uint32(
        (seed * 0x85EBCA6B + 1) % (2**32))
    h = h ^ (h >> 15)
    h = h * jnp.uint32(0x2C1B3C6D)
    h = h ^ (h >> 12)
    return h


def hash_partition(src, dst, n_vertices, k, seed=0):
    eid = jnp.arange(src.shape[0], dtype=jnp.int32)
    return (_hash32(eid, seed) % jnp.uint32(k)).astype(jnp.int32)


def dbh_partition(src, dst, n_vertices, k, seed=0):
    """Degree-Based Hashing: cut the lower-degree endpoint."""
    deg = _cl.compute_degrees(src, dst, n_vertices)
    pick_src = deg[src] <= deg[dst]
    v = jnp.where(pick_src, src, dst)
    return (_hash32(v, seed) % jnp.uint32(k)).astype(jnp.int32)


def _grid_dims(k: int) -> tuple[int, int]:
    r = int(math.isqrt(k))
    while k % r:
        r -= 1
    return r, k // r


def _grid_rowcol(n_vertices, k, c, seed):
    cell = (_hash32(jnp.arange(n_vertices, dtype=jnp.int32), seed) % jnp.uint32(k)).astype(
        jnp.int32
    )
    return cell // c, cell % c


def grid_partition(src, dst, n_vertices, k, seed=0, *, stream=None,
                   chunk_size=None, num_streams=1, super_chunk=8,
                   shard="range"):
    """Grid/constrained candidate partitioning, sequential least-loaded pick.

    Candidate set: grid intersection of u's row/col with v's — cells
    (row_u, col_v) and (row_v, col_u); degenerate → own cell.
    """
    _, c = _grid_dims(k)
    row, col = _grid_rowcol(n_vertices, k, c, seed)
    st = as_stream(src, dst, n_vertices, stream=stream, chunk_size=chunk_size)
    parts, _ = run_parallel(st, _scan.GridCarry(k, row, col, c),
                            num_streams=num_streams, super_chunk=super_chunk,
                            shard=shard)
    return parts


def grid_partition_multi_seed(src, dst, n_vertices, k, seeds, *, stream=None,
                              chunk_size=None):
    """Vmapped multi-seed grid: one compiled engine, |seeds| scenarios.

    Returns (len(seeds), E) parts — each row identical to
    ``grid_partition(..., seed=s)``.
    """
    _, c = _grid_dims(k)
    carries = [_scan.grid_init(k, *_grid_rowcol(n_vertices, k, c, s), c) for s in seeds]
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *carries)
    st = as_stream(src, dst, n_vertices, stream=stream, chunk_size=chunk_size)
    parts, _ = run_scan_batched(st, stacked, _scan.grid_chunk)
    return parts


def greedy_partition(src, dst, n_vertices, k, seed=0, *, stream=None,
                     chunk_size=None, use_kernel=None, vmem_budget=None,
                     num_streams=1, super_chunk=8, shard="range"):
    """PowerGraph Greedy: 4-case replica-aware assignment."""
    st = as_stream(src, dst, n_vertices, stream=stream, chunk_size=chunk_size)
    pc = _scan.GreedyCarry(n_vertices, k, use_kernel=use_kernel,
                           vmem_budget=vmem_budget)
    parts, _ = run_parallel(st, pc, num_streams=num_streams,
                            super_chunk=super_chunk, shard=shard)
    return parts


def hdrf_partition(src, dst, n_vertices, k, seed=0, lam: float = 1.1, *,
                   stream=None, chunk_size=None, use_kernel=None,
                   vmem_budget=None, num_streams=1, super_chunk=8,
                   shard="range"):
    """High-Degree Replicated First (partial-degree variant, as published)."""
    st = as_stream(src, dst, n_vertices, stream=stream, chunk_size=chunk_size)
    pc = _scan.HdrfCarry(n_vertices, k, lam, use_kernel=use_kernel,
                         vmem_budget=vmem_budget)
    parts, _ = run_parallel(st, pc, num_streams=num_streams,
                            super_chunk=super_chunk, shard=shard)
    return parts


def hdrf_partition_batched(src, dst, n_vertices, ks, lams=None, *,
                           stream=None, chunk_size=None):
    """Vmapped multi-scenario HDRF: a batch over partition counts (padded
    to max(ks), inactive lanes masked out of the argmax) and optionally λ
    values (``lams[i]`` per scenario; default 1.1 — sweep λ at fixed k by
    passing ``ks=[k]*len(lams)``).

    Returns (B, E) parts where B = len(ks); scenario i uses ``ks[i]``
    partitions and ``lams[i]``.  One compiled engine serves the whole
    batch — the multi-k / multi-λ sweep of the paper's Fig. 12 in a
    single stream pass.
    """
    if not ks:
        raise ValueError("ks must name at least one partition count")
    if lams is None:
        lams = [1.1] * len(ks)
    if len(ks) != len(lams):
        raise ValueError("ks and lams length mismatch")
    kmax = max(ks)
    carries = [
        _scan.hdrf_init(n_vertices, kmax, lam, k_active=k)
        for k, lam in zip(ks, lams)
    ]
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *carries)
    st = as_stream(src, dst, n_vertices, stream=stream, chunk_size=chunk_size)
    parts, _ = run_scan_batched(st, stacked, _scan.hdrf_chunk)
    return parts


def two_ps_partition(src, dst, n_vertices, k, seed=0):
    """2PS-L-style: global-degree streaming clustering, then linear
    cluster placement (first-fit decreasing) + streaming second pass."""
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    E = int(src.shape[0])
    deg = _cl.compute_degrees(src, dst, n_vertices)
    kappa = max(int(math.ceil(2.0 * E / k)), 2)
    # xi = -1 ⇒ every edge is a 'head' edge ⇒ single global-degree table
    state = _cl.cluster_stream(src, dst, n_vertices, xi=-1, kappa=kappa)
    res = _cl.compact_clusters(state, deg, -1)
    c_of = res.v2c  # every vertex has a head cluster here
    # cluster sizes in edges (by source attribution)
    cu = c_of[src]
    cv = c_of[dst]
    csize = np.asarray(
        jax.ops.segment_sum(jnp.ones((E,), jnp.float32), jnp.maximum(cu, 0),
                            num_segments=max(res.n_clusters, 1))
    )
    # first-fit decreasing placement under capacity τ|E|/k
    cap = math.ceil(1.05 * E / k)
    order = np.argsort(-csize, kind="stable")
    c2p = np.zeros(max(res.n_clusters, 1), np.int32)
    loads = np.zeros(k, np.int64)
    for c in order:
        fits = loads + csize[c] <= cap
        p = int(np.argmax(fits)) if fits.any() else int(np.argmin(loads))
        c2p[c] = p
        loads[p] += csize[c]
    # streaming second pass: place each edge at the less-loaded endpoint
    # partition under the hard cap (reuses the Alg. 3 scan machinery)
    max_load = int(math.ceil(1.0 * E / k))
    parts, _ = _post.assign_edges_stream(
        src, dst, jnp.zeros((E,), jnp.bool_), jnp.maximum(cu, 0),
        jnp.maximum(cv, 0), jnp.asarray(c2p), k, max_load,
    )
    return parts


def clugp_partition(src, dst, n_vertices, k, seed=0):
    """CLUGP-style: local-degree clustering + one-stage simultaneous game.

    Realized as S5P with ``one_stage=True`` and ξ = ∞ (all edges take the
    local-degree tail path) — the clustering-refinement skeleton CLUGP
    shares, minus the Stackelberg (leader/follower) structure.
    """
    cfg = S5PConfig(k=k, beta=float(2**30), one_stage=True, use_cms=False, seed=seed)
    return s5p_partition(src, dst, n_vertices, cfg).parts


def _s5p(src, dst, n_vertices, k, seed=0, *, stream=None, chunk_size=None,
         num_streams=1, super_chunk=8, shard="range"):
    cfg = S5PConfig(k=k, seed=seed, chunk_size=chunk_size or 1 << 16,
                    num_streams=num_streams, super_chunk=super_chunk,
                    shard=shard)
    return s5p_partition(src, dst, n_vertices, cfg, stream=stream).parts


def _s5p_exact(src, dst, n_vertices, k, seed=0, *, stream=None,
               chunk_size=None, num_streams=1, super_chunk=8, shard="range"):
    cfg = S5PConfig(k=k, use_cms=False, seed=seed,
                    chunk_size=chunk_size or 1 << 16,
                    num_streams=num_streams, super_chunk=super_chunk,
                    shard=shard)
    return s5p_partition(src, dst, n_vertices, cfg, stream=stream).parts


def _s5p_lanes(src, dst, n_vertices, k, seed=0, *, stream=None,
               chunk_size=None, num_streams=4, super_chunk="auto",
               shard="range"):
    """S5P with S-way parallel ingest (``streaming.parallel``): S loaders
    each fold their share of the stream, one per device where the host
    has S, their carries merged by the declared merge laws; then the game
    once and the touch-up of the clusters two loaders wrote.  The
    defaults are split-file ingest: 4 loaders, contiguous ranges, the
    consumer-aware merge cadence."""
    if num_streams < 2:
        raise ValueError(f"s5p-lanes needs num_streams >= 2, got "
                         f"{num_streams}; 's5p' is the one-stream entry")
    return _s5p(src, dst, n_vertices, k, seed, stream=stream,
                chunk_size=chunk_size, num_streams=num_streams,
                super_chunk=super_chunk, shard=shard)


PARTITIONERS = {
    "hash": hash_partition,
    "dbh": dbh_partition,
    "grid": grid_partition,
    "greedy": greedy_partition,
    "hdrf": hdrf_partition,
    "2ps-l": two_ps_partition,
    "clugp": clugp_partition,
    "s5p": _s5p,
    "s5p-exact": _s5p_exact,
    "s5p-lanes": _s5p_lanes,
}
