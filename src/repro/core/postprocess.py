"""Edge-placement postprocessing (paper Algorithm 3).

After the game fixes the cluster→partition map ``C2P``, a final streaming
pass assigns every edge to a concrete partition under the hard capacity
``L = ⌈τ|E|/k⌉``:

- both endpoint partitions over capacity → skew-aware overflow: **head**
  edges take the *first* partition with room, **tail** edges the *last*
  (minimizing the spread of head vertices across partitions, per §4.3);
- otherwise the *less-loaded* of the two endpoint partitions (Alg. 3
  lines 9-10; the prose says "larger size" but the listing places into
  the smaller — we follow the listing, which is the balance-preserving
  reading; recorded in DESIGN.md).

Under S ingest lanes (``streaming.parallel``) every lane places against
the merge base's loads plus its own placements since, so the capacity is
shared out before each super-step (:meth:`AssignCarry.lane_shares`): the
room each partition has left, ``L − load``, is split between the lanes in
proportion to the edges each places before the next merge (rounded
down); a lane that rounding leaves with fewer slots than edges takes the
shortfall, lanes in order, from the room left over, partitions in
ascending order.  A lane treats a partition as full once its own view of
the load reaches the base load plus its share, and places an edge with
one full endpoint partition on the other (what the one-capacity rule does
too, since there the full partition is the more loaded).  So the merged
loads stay under ``L`` whenever ``k·L`` covers the edges (τ ≥ 1).

Implemented as a jitted ``lax.scan`` with an O(k) carry (the load vector),
streamed in chunks like Algorithm 1.
"""

from __future__ import annotations

import copy
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..runtime import spans
from ..streaming.carry import SUM, PartitionerCarry

__all__ = ["AssignCarry", "assign_edges", "assign_edges_stream"]


@partial(jax.jit, static_argnames=("k",))
def _assign_chunk(load, max_load, src, dst, is_head_edge, cu, cv, c2p, *, k: int):
    """One streamed chunk of Algorithm 3.  Returns (load, parts)."""
    arange = jnp.arange(k, dtype=jnp.int32)
    L = jnp.broadcast_to(max_load, (k,))  # one capacity, or one per part

    def step(load, edge):
        head, pcu, pcv, valid = edge
        over_u = load[pcu] >= L[pcu]
        over_v = load[pcv] >= L[pcv]
        room = load < L
        any_room = jnp.any(room)
        first_room = jnp.argmax(room).astype(jnp.int32)
        last_room = (k - 1 - jnp.argmax(room[::-1])).astype(jnp.int32)
        fallback = jnp.argmin(load).astype(jnp.int32)
        overflow_choice = jnp.where(
            any_room, jnp.where(head, first_room, last_room), fallback
        )
        # lines 9-10: more-loaded endpoint loses; tie → P_u (line 10 'else');
        # a full endpoint always loses (with one capacity for every part
        # the full one is the more loaded, so this is the same rule)
        endpoint_choice = jnp.where(
            over_u != over_v, jnp.where(over_u, pcv, pcu),
            jnp.where(load[pcu] > load[pcv], pcv, pcu))
        part = jnp.where(over_u & over_v, overflow_choice, endpoint_choice)
        load = load + jnp.where(valid, (arange == part).astype(load.dtype), 0)
        return load, jnp.where(valid, part, -1)

    pcu = c2p[cu]
    pcv = c2p[cv]
    valid = src != dst
    load, parts = jax.lax.scan(step, load, (is_head_edge, pcu, pcv, valid))
    return load, parts


class AssignCarry(PartitionerCarry):
    """Algorithm 3 as a carry: the O(k) load vector (SUM merge).

    Per-edge extras (head flag, endpoint clusters) ride the chunk; the
    cluster→partition map and capacity are replicated closure constants.
    Under parallel ingest each sub-stream places its edges against a load
    vector that is ``super_chunk`` chunks stale at worst — the bounded-
    staleness regime of ``core.distributed`` Phase 4 — and under its own
    share of the capacity (module doc), so no merged load passes ``L``.
    """

    merge_ops = (SUM,)
    supports_retract = True
    retract_exact = True

    def __init__(self, k: int, max_load: int, c2p: jax.Array, *,
                 use_kernel: bool | None = None,
                 vmem_budget: int | None = None):
        self.k = int(k)
        self.max_load = jnp.int32(max_load)
        self.c2p = c2p
        if use_kernel is None:
            use_kernel = jax.default_backend() == "tpu"
        self._use_kernel = bool(use_kernel)
        self._vmem_budget = vmem_budget

    def init(self) -> jax.Array:
        return jnp.zeros((self.k,), jnp.int32)

    def lane_shares(self, base, demand):
        """Each lane's per-partition load limits until the next merge, (S,
        k) int32: the base load plus the lane's share of the room left
        (module doc)."""
        load = spans.to_host(base).astype(np.int64)
        n = np.asarray(demand, np.int64)
        room = np.maximum(int(self.max_load) - load, 0)
        share = room[None, :] * n[:, None] // max(int(n.sum()), 1)
        left = room - share.sum(axis=0)
        for s in range(n.size):
            short = n[s] - share[s].sum()
            for p in np.flatnonzero(left):
                if short <= 0:
                    break
                take = min(short, left[p])
                share[s, p] += take
                left[p] -= take
                short -= take
        return (load[None, :] + share).astype(np.int32)

    def for_lane(self, share):
        lane = copy.copy(self)
        lane.max_load = jnp.asarray(share, jnp.int32)
        return lane

    def _kernel_path(self, chunk_size):
        # lazy import (core.baselines ↔ kernels layering, see clustering)
        from ..kernels import stream_scan as _scan

        return self._use_kernel and _scan.select_path(
            0, self.k, chunk_size, consumer="assign",
            budget=self._vmem_budget) == "fused"

    def step_chunk(self, carry, src, dst, n_valid, *extras):
        h, a, b = extras
        if self._kernel_path(src.shape[0]):
            from ..kernels import stream_scan as _scan

            parts, load = _scan.assign_scan(
                carry, src, dst, h, self.c2p[a], self.c2p[b],
                max_load=self.max_load)
            return load, parts
        load, parts = _assign_chunk(carry, self.max_load, src, dst, h, a, b,
                                    self.c2p, k=self.k)
        return load, parts

    def retract_chunk(self, carry, src, dst, n_valid, parts, *extras):
        if self._kernel_path(src.shape[0]):
            from ..kernels import stream_scan as _scan

            zeros = jnp.zeros_like(src)
            _, load = _scan.assign_scan(
                carry, src, dst, zeros, zeros, zeros,
                max_load=self.max_load, sign=-1, parts=parts,
                n_valid=n_valid)
            return load
        return _retract_load(carry, src, dst, n_valid, parts)


@jax.jit
def _retract_load(load, src, dst, n_valid, parts):
    """Exact inverse of a chunk's load accounting (one unit per placed edge)."""
    w = ((jnp.arange(src.shape[0]) < n_valid) & (src != dst)
         & (parts >= 0)).astype(jnp.int32)
    return load - jax.ops.segment_sum(w, jnp.maximum(parts, 0),
                                      num_segments=load.shape[0])


def assign_edges_stream(
    src: jax.Array,
    dst: jax.Array,
    is_head_edge: jax.Array,
    cu: jax.Array,
    cv: jax.Array,
    c2p: jax.Array,
    k: int,
    max_load: int,
    *,
    chunk_size: int = 1 << 16,
    stream=None,
    num_streams: int = 1,
    super_chunk: int | str = 8,
    shard: str = "range",
    plan=None,
    use_kernel: bool | None = None,
    vmem_budget: int | None = None,
):
    """Algorithm 3 over the full stream.  Returns (parts (E,), load (k,)).

    The per-edge attributes (head flag, endpoint clusters) ride along the
    EdgeStream as extras, so a reordered stream keeps them aligned; parts
    come back in arrival order either way.  ``num_streams > 1`` places S
    sharded sub-streams in parallel with load-vector all-reduces every
    ``super_chunk`` chunks (``num_streams=1`` is bit-identical sequential),
    each lane under its share of the capacity; ``plan`` is the caller's
    :class:`~repro.streaming.parallel.ParallelEdgeStream` of the stream.
    """
    from ..streaming import as_stream, run_parallel

    stream = as_stream(src, dst, stream=stream, chunk_size=chunk_size)
    pc = AssignCarry(k, max_load, c2p, use_kernel=use_kernel,
                     vmem_budget=vmem_budget)
    parts, load = run_parallel(
        stream, pc, is_head_edge, cu, cv,
        num_streams=num_streams, super_chunk=super_chunk, shard=shard,
        plan=plan)
    return parts, load


def assign_edges(
    src,
    dst,
    is_head_edge,
    cu,
    cv,
    c2p,
    k: int,
    max_load: int,
):
    """Single-shot convenience wrapper (no chunking)."""
    return assign_edges_stream(
        src, dst, is_head_edge, cu, cv, c2p, k, max_load,
        chunk_size=max(int(src.shape[0]), 1),
    )
