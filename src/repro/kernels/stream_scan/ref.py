"""Oracle chunk functions: the seed ``lax.scan`` scoring scans, extracted.

Each function processes one EdgeStream chunk and threads the partitioner
carry; the per-edge state transitions are the seed implementations of
``core.baselines`` moved here verbatim, so the refactored partitioners are
bit-identical to the originals (pinned by the golden hashes in
``tests/test_streaming.py``).

Carries are plain tuples of arrays so they vmap cleanly: scenario
parameters that vary across a batch (HDRF λ, the active-partition mask for
padded multi-k runs) live *inside* the carry, not in the closure — one
compiled chunk function serves every scenario in a batch.

Decremental representation: the replica "bitmaps" are **counted** — int32
per-(vertex, partition) occupancy counters that OR-project (``> 0``) for
scoring.  The projection is bit-identical to the old boolean bitmap on
insert-only streams (every score reads the projection, never the raw
count), and the counters form an abelian group, so the ``*_retract_chunk``
functions below subtract an edge's accounting exactly: when the last edge
that replicated v on partition p is deleted the counter hits 0 and the
replica vanishes.  Retraction is order-independent (pure scatter-
subtract), so it is vectorized — no scan.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "greedy_init",
    "greedy_chunk",
    "greedy_retract_chunk",
    "hdrf_init",
    "hdrf_chunk",
    "hdrf_retract_chunk",
    "grid_init",
    "grid_chunk",
    "grid_retract_chunk",
    "cluster_chunk_oracle",
    "assign_chunk_oracle",
]

_INF_I32 = jnp.int32(2**30)
_HDRF_EPS = 1e-3


def _retract_masks(src, dst, n_valid, parts):
    """(weights, safe parts) for a deletion chunk: only real (index <
    n_valid), non-self-loop, actually-placed (parts >= 0) edges count."""
    w = ((jnp.arange(src.shape[0]) < n_valid) & (src != dst)
         & (parts >= 0)).astype(jnp.int32)
    return w, jnp.maximum(parts, 0)


# ---------------------------------------------------------------- greedy
def greedy_init(n_vertices: int, k: int):
    """(load (k,), rep (V, k) counted replica table)."""
    return (
        jnp.zeros((k,), jnp.int32),
        jnp.zeros((n_vertices, k), jnp.int32),
    )


@jax.jit
def greedy_chunk(carry, src, dst):
    """PowerGraph Greedy: 4-case replica-aware assignment (one chunk)."""

    def step(carry, e):
        load, rep = carry
        u, v = e
        au = rep[u] > 0
        av = rep[v] > 0
        both = au & av
        either = au | av
        case1 = jnp.any(both)
        case2 = jnp.any(au) & jnp.any(av)
        case3 = jnp.any(either)
        mask = jnp.where(
            case1, both, jnp.where(case2, either, jnp.where(case3, either, True))
        )
        score = jnp.where(mask, load, _INF_I32)
        pick = jnp.argmin(score).astype(jnp.int32)
        valid = u != v
        w = jnp.where(valid, 1, 0)
        load = load.at[pick].add(w)
        rep = rep.at[u, pick].add(w)
        rep = rep.at[v, pick].add(w)
        return (load, rep), jnp.where(valid, pick, -1)

    return jax.lax.scan(step, carry, (src, dst))


@jax.jit
def greedy_retract_chunk(carry, src, dst, n_valid, parts):
    """Exact inverse of :func:`greedy_chunk`'s accounting for these edges."""
    load, rep = carry
    w, p = _retract_masks(src, dst, n_valid, parts)
    load = load - jax.ops.segment_sum(w, p, num_segments=load.shape[0])
    rep = rep.at[src, p].add(-w)
    rep = rep.at[dst, p].add(-w)
    return (load, rep)


# ----------------------------------------------------------------- hdrf
def hdrf_init(n_vertices: int, k: int, lam: float = 1.1, k_active: int | None = None):
    """(load, rep counted replica table, pd partial degrees, λ,
    active-partition mask).

    ``k_active < k`` pads the carry for multi-k batched runs: inactive
    lanes never win the argmax, so a batch of different partition counts
    shares one compiled engine at ``k = max(ks)``.
    """
    if k_active is None:
        k_active = k
    return (
        jnp.zeros((k,), jnp.int32),
        jnp.zeros((n_vertices, k), jnp.int32),
        jnp.zeros((n_vertices,), jnp.int32),
        jnp.float32(lam),
        jnp.arange(k) < k_active,
    )


@jax.jit
def hdrf_chunk(carry, src, dst):
    """HDRF (partial-degree variant, as published) over one chunk."""

    def step(carry, e):
        load, rep, pd, lam, kmask = carry
        u, v = e
        pd = pd.at[u].add(1)
        pd = pd.at[v].add(1)
        du = pd[u].astype(jnp.float32)
        dv = pd[v].astype(jnp.float32)
        theta_u = du / (du + dv)
        theta_v = 1.0 - theta_u
        g_u = jnp.where(rep[u] > 0, 1.0 + (1.0 - theta_u), 0.0)
        g_v = jnp.where(rep[v] > 0, 1.0 + (1.0 - theta_v), 0.0)
        loadf = load.astype(jnp.float32)
        maxl = jnp.max(jnp.where(kmask, loadf, -jnp.inf))
        minl = jnp.min(jnp.where(kmask, loadf, jnp.inf))
        # f32 absorbs ε once loads pass 2**15, so a full tie would divide
        # 0 by 0; its balance term is 0, as ε makes it in exact arithmetic
        den = _HDRF_EPS + maxl - minl
        bal = (maxl - loadf) / jnp.where(den > 0, den, 1.0)
        score = jnp.where(kmask, g_u + g_v + lam * bal, -jnp.inf)
        pick = jnp.argmax(score).astype(jnp.int32)
        valid = u != v
        w = jnp.where(valid, 1, 0)
        load = load.at[pick].add(w)
        rep = rep.at[u, pick].add(w)
        rep = rep.at[v, pick].add(w)
        return (load, rep, pd, lam, kmask), jnp.where(valid, pick, -1)

    return jax.lax.scan(step, carry, (src, dst))


@jax.jit
def hdrf_retract_chunk(carry, src, dst, n_valid, parts):
    """Exact inverse of :func:`hdrf_chunk`'s accounting for these edges.

    Partial degrees subtract for every real entry (including self-loops),
    mirroring the forward scan's unconditional ``pd`` update; load and
    replica counters only for placed edges.  The forward scan's *padding*
    contribution to ``pd`` (a documented chunk-seam approximation) is
    never retracted — deletion batches are chunked independently of how
    the edges originally arrived.
    """
    load, rep, pd, lam, kmask = carry
    real = (jnp.arange(src.shape[0]) < n_valid).astype(jnp.int32)
    n = pd.shape[0]
    pd = pd - jax.ops.segment_sum(real, src, num_segments=n)
    pd = pd - jax.ops.segment_sum(real, dst, num_segments=n)
    w, p = _retract_masks(src, dst, n_valid, parts)
    load = load - jax.ops.segment_sum(w, p, num_segments=load.shape[0])
    rep = rep.at[src, p].add(-w)
    rep = rep.at[dst, p].add(-w)
    return (load, rep, pd, lam, kmask)


# ----------------------------------------------------------------- grid
def grid_init(load_k: int, row: jax.Array, col: jax.Array, n_cols: int):
    """(load, per-vertex hashed row/col, #grid-columns)."""
    return (
        jnp.zeros((load_k,), jnp.int32),
        jnp.asarray(row, jnp.int32),
        jnp.asarray(col, jnp.int32),
        jnp.int32(n_cols),
    )


@jax.jit
def grid_chunk(carry, src, dst):
    """Grid/constrained candidate partitioning, least-loaded pick."""

    def step(carry, e):
        load, row, col, c = carry
        u, v = e
        cand1 = row[u] * c + col[v]
        cand2 = row[v] * c + col[u]
        pick = jnp.where(load[cand1] <= load[cand2], cand1, cand2)
        valid = u != v
        load = load.at[pick].add(jnp.where(valid, 1, 0))
        return (load, row, col, c), jnp.where(valid, pick, -1)

    return jax.lax.scan(step, carry, (src, dst))


@jax.jit
def grid_retract_chunk(carry, src, dst, n_valid, parts):
    """Exact inverse of :func:`grid_chunk`'s accounting for these edges."""
    load, row, col, c = carry
    w, p = _retract_masks(src, dst, n_valid, parts)
    load = load - jax.ops.segment_sum(w, p, num_segments=load.shape[0])
    return (load, row, col, c)


# --------------------------------------------------- cluster / assign oracles
# The bit-parity references for the Algorithm-1 / Algorithm-3 megakernels
# are the core scans themselves; these thin wrappers re-export them behind
# lazy imports (``core.baselines`` imports this package at module level,
# so the kernels package must never import ``core`` at module level).


def cluster_chunk_oracle(state, src, dst, degrees, *, xi, kappa,
                         global_tail=False):
    """``core.clustering.cluster_chunk`` on a 10-leaf state tuple.

    Takes/returns plain leaf tuples (same contract as
    :func:`..kernel.cluster_scan`) so parity tests compare like for like.
    """
    from ...core.clustering import ClusterState, cluster_chunk

    out = cluster_chunk(ClusterState(*state), src, dst, degrees,
                        xi=xi, kappa=kappa, global_tail=global_tail)
    return tuple(out)


def assign_chunk_oracle(load, max_load, src, dst, is_head_edge, cu, cv, c2p,
                        *, k):
    """``core.postprocess._assign_chunk`` — the Algorithm-3 scan oracle."""
    from ...core.postprocess import _assign_chunk

    return _assign_chunk(load, max_load, src, dst, is_head_edge, cu, cv,
                         c2p, k=k)
