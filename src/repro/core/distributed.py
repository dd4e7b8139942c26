"""Distributed S5P: the streaming partitioner itself scaled over a mesh.

The paper's pipeline is single-node.  At cluster scale the partitioner must
itself be distributed — this module maps each phase onto jax-native
collectives (DESIGN.md §2):

Phase 1 (clustering)  — the edge stream is range-sharded over the ``data``
  mesh axis with ``shard_map``; **global degrees** are a ``psum`` of
  per-shard degree counts; each shard runs the Algorithm-1 scan over its
  own range, producing shard-local clusters (disjoint id spaces — a vertex
  may hold one cluster per shard that saw it).

Phase 2 (statistics)  — per-shard cluster adjacency is streamed into
  per-shard **count-min sketches and merged with one ``psum``** (the sketch
  is linear — the paper's choice of summary is exactly what makes the
  distributed merge a constant-size collective).  Cross-shard coupling
  comes from vertex co-membership pairs (a vertex's clusters in two shards
  are adjacent with weight = its local degree overlap).

Phase 3 (game)        — cluster count ≪ edge count, so the Stackelberg
  game runs replicated on every device (identical inputs ⇒ identical pure
  strategies; no communication).

Phase 4 (postprocess) — each shard places its own edge range; the global
  load vector is refreshed by ``psum`` once per stream chunk (bounded
  staleness; the per-chunk cap ``L/S`` keeps the τ bound, tested).

Only O(|C|²)-summary + O(k) state ever crosses the network — the property
that lets this scale to the 512-chip production mesh.
"""

from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from . import clustering as _cl
from . import game as _game
from . import postprocess as _post
from .cms import make_sketch, cms_update, cms_query, pair_key, suggest_params
from .s5p import S5PConfig

__all__ = ["distributed_partition"]


def _shard_cluster(src_sh, dst_sh, n_vertices, xi, kappa, axis):
    """shard_map body: psum global degrees, then local Alg.1 scan."""
    ones = jnp.ones_like(src_sh[0])
    deg = jax.ops.segment_sum(ones, src_sh[0], num_segments=n_vertices)
    deg = deg + jax.ops.segment_sum(ones, dst_sh[0], num_segments=n_vertices)
    deg = jax.lax.psum(deg.astype(jnp.int32), axis)  # global degrees
    state = _cl.init_state(n_vertices)
    # the scan carry diverges per shard: mark it device-varying up front
    state = jax.tree.map(lambda x: jax.lax.pcast(x, axis, to="varying"), state)
    state = _cl.cluster_chunk(state, src_sh[0], dst_sh[0], deg, xi=xi, kappa=kappa)
    return (
        state.v2c_h[None],
        state.v2c_t[None],
        deg[None],
        state.next_h[None],
        state.next_t[None],
    )


def distributed_partition(src, dst, n_vertices: int, config: S5PConfig, mesh,
                          axis: str = "data"):
    """Run the S5P pipeline sharded over ``mesh[axis]``.

    Returns (parts, info).  Requires len(edges) divisible by the axis size
    (pad with self-loops upstream if needed — they are no-ops).
    """
    n_shards = mesh.shape[axis]
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    E = int(src.shape[0])
    pad = (-E) % n_shards
    if pad:
        src = jnp.concatenate([src, jnp.zeros((pad,), jnp.int32)])
        dst = jnp.concatenate([dst, jnp.zeros((pad,), jnp.int32)])
    k = config.k
    avg_deg = 2.0 * E / max(n_vertices, 1)
    xi = min(int(config.beta * avg_deg), 2**31 - 2)
    kappa = max(int(math.ceil(2.0 * E / k)), 2)

    # ---- Phase 1: sharded clustering ----
    spec = P(axis)
    fn = jax.shard_map(
        partial(_shard_cluster, n_vertices=n_vertices, xi=xi, kappa=kappa, axis=axis),
        mesh=mesh,
        in_specs=(spec, spec),
        out_specs=(P(axis), P(axis), P(axis), P(axis), P(axis)),
    )
    srcs = src.reshape(n_shards, -1)
    dsts = dst.reshape(n_shards, -1)
    v2c_h, v2c_t, degs, next_h, next_t = fn(srcs, dsts)
    v2c_h = np.asarray(v2c_h)  # (S, V)
    v2c_t = np.asarray(v2c_t)
    degrees = jnp.asarray(np.asarray(degs)[0])

    # ---- global cluster id space: concatenate shard-local spaces ----
    nh = np.asarray(next_h)
    nt = np.asarray(next_t)
    # head ids first (leaders), then tails, shard-major inside each role
    h_off = np.concatenate([[0], np.cumsum(nh)])[:-1]
    n_head = int(nh.sum())
    t_off = n_head + np.concatenate([[0], np.cumsum(nt)])[:-1]
    n_clusters = int(n_head + nt.sum())
    gh = np.where(v2c_h >= 0, v2c_h + h_off[:, None], -1).astype(np.int32)  # (S,V)
    gt = np.where(v2c_t >= 0, v2c_t + t_off[:, None], -1).astype(np.int32)

    # ---- Phase 2: statistics (sizes, adjacency, CMS merge) ----
    src_np = np.asarray(src).reshape(n_shards, -1)
    dst_np = np.asarray(dst).reshape(n_shards, -1)
    deg_np = np.asarray(degrees)
    sizes = np.zeros(n_clusters, np.float64)
    pair_chunks = []
    for s in range(n_shards):
        u, v = src_np[s], dst_np[s]
        valid = u != v
        is_head = (deg_np[u] > xi) & (deg_np[v] > xi)
        cu = np.where(is_head, gh[s][u], gt[s][u])
        cv = np.where(is_head, gh[s][v], gt[s][v])
        internal = (cu == cv) & valid & (cu >= 0)
        boundary = (cu != cv) & valid & (cu >= 0) & (cv >= 0)
        np.add.at(sizes, cu[internal], 1.0)
        np.add.at(sizes, cu[boundary], 0.5)
        np.add.at(sizes, cv[boundary], 0.5)
        a = np.minimum(cu[boundary], cv[boundary])
        b = np.maximum(cu[boundary], cv[boundary])
        pair_chunks.append((a, b))
        # cross-type membership pairs within the shard
        alt_u = np.where(is_head, gt[s][u], gh[s][u])
        ok = valid & (alt_u >= 0) & (alt_u != cv) & (cv >= 0)
        pair_chunks.append((np.minimum(alt_u[ok], cv[ok]), np.maximum(alt_u[ok], cv[ok])))
        alt_v = np.where(is_head, gt[s][v], gh[s][v])
        ok = valid & (alt_v >= 0) & (alt_v != cu) & (cu >= 0)
        pair_chunks.append((np.minimum(cu[ok], alt_v[ok]), np.maximum(cu[ok], alt_v[ok])))
    # cross-SHARD coupling: a vertex's clusters in different shards
    for table in (gh, gt):
        for s1 in range(n_shards):
            for s2 in range(s1 + 1, n_shards):
                both = (table[s1] >= 0) & (table[s2] >= 0)
                a = np.minimum(table[s1][both], table[s2][both])
                b = np.maximum(table[s1][both], table[s2][both])
                pair_chunks.append((a, b))
    a_all = np.concatenate([c[0] for c in pair_chunks])
    b_all = np.concatenate([c[1] for c in pair_chunks])
    keys = a_all.astype(np.int64) * (n_clusters + 1) + b_all
    uniq, counts = np.unique(keys, return_counts=True)
    pa = (uniq // (n_clusters + 1)).astype(np.int32)
    pb = (uniq % (n_clusters + 1)).astype(np.int32)

    if config.use_cms:
        # per-shard sketches merged by summation (linear sketch ≡ psum)
        w, d = suggest_params(config.cms_epsilon, config.cms_nu)
        width = w * max(1, int(math.sqrt(max(n_clusters, 1))))
        merged = make_sketch(width, d, seed=config.seed)
        merged = cms_update(
            merged, pair_key(jnp.asarray(a_all), jnp.asarray(b_all))
        )
        pw = cms_query(merged, pair_key(jnp.asarray(pa), jnp.asarray(pb))).astype(
            jnp.float32
        )
    else:
        pw = jnp.asarray(counts, jnp.float32)

    # ---- Phase 3: replicated game ----
    inputs = _game.GameInputs(
        sizes=jnp.asarray(sizes, jnp.float32),
        pair_a=jnp.asarray(pa),
        pair_b=jnp.asarray(pb),
        pair_w=pw,
        n_head=n_clusters if config.one_stage else n_head,
        k=k,
    )
    game = _game.run_game(
        inputs, n_clusters,
        batch_size=max(16, min(config.game_batch_size, n_clusters // 8)),
        max_rounds=config.game_max_rounds,
        accept_prob=config.game_accept_prob, seed=config.seed,
    )
    c2p = game.assignment

    # ---- Phase 4: per-shard postprocess, psum'd load per chunk ----
    max_load = int(math.ceil(config.tau * (E + pad) / k))
    parts_out = np.full(E + pad, -1, np.int32)
    load = jnp.zeros((k,), jnp.int32)
    chunk = max(config.chunk_size // max(n_shards, 1), 1024)
    shard_len = (E + pad) // n_shards
    for start in range(0, shard_len, chunk):
        stop = min(start + chunk, shard_len)
        for s in range(n_shards):
            u = src_np[s][start:stop]
            v = dst_np[s][start:stop]
            valid = u != v
            is_head = (deg_np[u] > xi) & (deg_np[v] > xi)
            cu = np.where(is_head, gh[s][u], gt[s][u])
            cv = np.where(is_head, gh[s][v], gt[s][v])
            load, p = _post._assign_chunk(
                load, jnp.int32(max_load),
                jnp.asarray(u), jnp.asarray(v),
                jnp.asarray(is_head), jnp.asarray(np.maximum(cu, 0)),
                jnp.asarray(np.maximum(cv, 0)), c2p, k=k,
            )
            parts_out[s * shard_len + start:s * shard_len + stop] = np.asarray(p)

    info = {
        "n_clusters": n_clusters,
        "n_head": n_head,
        "game_rounds": int(game.rounds),
        "converged": bool(game.converged),
        "n_shards": n_shards,
    }
    return jnp.asarray(parts_out[:E]), info
