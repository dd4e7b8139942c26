"""Skewness-aware streaming graph clustering (paper Algorithm 1).

Edges arrive as a stream.  Each edge is classified *head* (both endpoints
have global degree > ξ) or *tail* (otherwise) and drives an
allocate/migrate update on one of two vertex→cluster tables:

- ``V2C_H`` (head): cluster volumes tracked in **global-degree** units;
- ``V2C_T`` (tail): volumes in **local-degree** units (1 per edge arrival).

Migration merges the lighter endpoint's cluster into the heavier one when
the receiving cluster stays under the volume cap κ = 2|E|/k.

TPU adaptation (recorded in DESIGN.md §2): the paper's per-edge loop with
early-exit branches becomes a ``jax.lax.scan`` with branchless
``jnp.where`` state transitions.  The carry is strictly O(|V|):
two V2C tables, two volume arrays (≤ |V| + 1 slots each; the trailing slot
is a write sink for masked updates), one local-degree array, two id
counters — plus, since the decremental refactor, two **membership
counters** (head/tail edge incidences per vertex; a vertex's assignment
projects to "unassigned" when its counter returns to 0 — counted
tombstones) and the head **allocation contribution** (the global degree
added to ``vol_h`` when the vertex was allocated, so orphaning a head
vertex can subtract exactly what its allocation added).  The insert-path
state transitions are bit-identical to the sequential algorithm —
``tests/test_clustering.py`` checks the scan against a pure-Python
transcription of Algorithm 1 on randomized streams.

Deletion (:meth:`ClusterCarry.retract_chunk`) is the documented
*approximate* retraction: membership counters and local degrees subtract
exactly, tail volumes subtract at the vertex's **current** cluster, and a
head vertex whose counter hits 0 hands back its allocation contribution —
but migrations are history-dependent, so volumes drift boundedly under
churn.  The drift monitor + masked-game refinement of
``repro.incremental`` are the quality backstop, exactly as for warm-start
insertion replay.

Global degrees come from a one-pass precompute (same contract as 2PS-L;
the paper's head-cluster volume updates explicitly use global degrees).
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..runtime import spans
from ..streaming.carry import COUNTED, SUM, PartitionerCarry

__all__ = [
    "ClusterState",
    "ClusterResult",
    "ClusterCarry",
    "DegreeCarry",
    "init_state",
    "cluster_chunk",
    "cluster_retract_chunk",
    "cluster_stream",
    "compact_clusters",
    "reference_cluster_python",
]


class ClusterState(NamedTuple):
    """Carry of the clustering scan.  All arrays are O(|V|)."""

    v2c_h: jax.Array  # (V,) int32, -1 = unassigned
    v2c_t: jax.Array  # (V,) int32, -1 = unassigned
    vol_h: jax.Array  # (V + 1,) int32 head-cluster volumes (global-degree units)
    vol_t: jax.Array  # (V + 1,) int32 tail-cluster volumes (local-degree units)
    ld: jax.Array  # (V,) int32 streaming local degree
    next_h: jax.Array  # () int32 next head cluster id
    next_t: jax.Array  # () int32 next tail cluster id
    cnt_h: jax.Array  # (V,) int32 counted head-edge incidences (membership)
    cnt_t: jax.Array  # (V,) int32 counted tail-edge incidences (membership)
    alloc_h: jax.Array  # (V,) int32 vol_h contribution added at allocation

    def effective(self) -> tuple[jax.Array, jax.Array]:
        """(v2c_h, v2c_t) with dead entries projected to ``-1``.

        Dead = membership counter ≤ 0 (every incident edge deleted) or an
        out-of-range id (the clamped resolution of a cross-worker merge
        conflict).  On insert-only sequential streams the projection is
        the identity on assigned entries — an assignment always arrives
        with its first incidence — which is what keeps the golden hashes
        unchanged.
        """
        ok_h = (self.cnt_h > 0) & (self.v2c_h >= 0) & (self.v2c_h < self.next_h)
        ok_t = (self.cnt_t > 0) & (self.v2c_t >= 0) & (self.v2c_t < self.next_t)
        return (jnp.where(ok_h, self.v2c_h, -1),
                jnp.where(ok_t, self.v2c_t, -1))


class ClusterResult(NamedTuple):
    """Compacted output of clustering (input to the Stackelberg game)."""

    v2c: jax.Array  # (V,) combined cluster id per vertex's *primary* table
    v2c_h: jax.Array  # (V,) head cluster id in combined id space (-1 if none)
    v2c_t: jax.Array  # (V,) tail cluster id in combined id space (-1 if none)
    n_head: int  # number of head clusters (ids [0, n_head))
    n_clusters: int  # total clusters; tail ids in [n_head, n_clusters)
    is_head_vertex: jax.Array  # (V,) bool


def init_state(n_vertices: int) -> ClusterState:
    v = n_vertices
    return ClusterState(
        v2c_h=jnp.full((v,), -1, jnp.int32),
        v2c_t=jnp.full((v,), -1, jnp.int32),
        vol_h=jnp.zeros((v + 1,), jnp.int32),
        vol_t=jnp.zeros((v + 1,), jnp.int32),
        ld=jnp.zeros((v,), jnp.int32),
        next_h=jnp.int32(0),
        next_t=jnp.int32(0),
        cnt_h=jnp.zeros((v,), jnp.int32),
        cnt_t=jnp.zeros((v,), jnp.int32),
        alloc_h=jnp.zeros((v,), jnp.int32),
    )


def _edge_step(state: ClusterState, edge, *, degrees, xi, kappa, global_tail=False):
    """One Algorithm-1 step.  ``edge`` = (u, v); branchless.

    ``global_tail=True`` is the S5P-B variant (§5.3): tail clusters also use
    allocation-time *global* degrees for volumes and migration amounts.
    """
    u, v = edge
    sink = state.vol_h.shape[0] - 1  # masked-write sink slot
    du = degrees[u]
    dv = degrees[v]
    is_head = (du > xi) & (dv > xi)
    valid = u != v  # self loops are no-ops (paper graphs are simple)

    # ---------------- head branch (global-degree volumes) ----------------
    cu = state.v2c_h[u]
    cv = state.v2c_h[v]
    new_u = cu < 0
    new_v = cv < 0
    h_on = is_head & valid
    # allocation: new ids, volume += global degree of the joining vertex
    cu2 = jnp.where(new_u, state.next_h, cu)
    next_h = state.next_h + jnp.where(h_on & new_u, 1, 0).astype(jnp.int32)
    cv2 = jnp.where(new_v, next_h, cv)
    next_h = next_h + jnp.where(h_on & new_v, 1, 0).astype(jnp.int32)
    vol_h = state.vol_h
    vol_h = vol_h.at[jnp.where(h_on & new_u, cu2, sink)].add(
        jnp.where(h_on & new_u, du, 0)
    )
    vol_h = vol_h.at[jnp.where(h_on & new_v, cv2, sink)].add(
        jnp.where(h_on & new_v, dv, 0)
    )
    # counted membership + the allocation contribution deletions hand back
    cnt_h = state.cnt_h
    cnt_h = cnt_h.at[u].add(jnp.where(h_on, 1, 0))
    cnt_h = cnt_h.at[v].add(jnp.where(h_on, 1, 0))
    alloc_h = state.alloc_h
    alloc_h = alloc_h.at[u].add(jnp.where(h_on & new_u, du, 0))
    alloc_h = alloc_h.at[v].add(jnp.where(h_on & new_v, dv, 0))
    v2c_h = state.v2c_h
    v2c_h = v2c_h.at[u].set(jnp.where(h_on, cu2, v2c_h[u]))
    v2c_h = v2c_h.at[v].set(jnp.where(h_on, cv2, v2c_h[v]))
    # migration (lines 5-11): only when both volumes < κ
    vu = vol_h[cu2]
    vv = vol_h[cv2]
    both_small = (vu < kappa) & (vv < kappa) & (cu2 != cv2)
    # i = argmin_z vol(C[z]) - d(z); j = other
    score_u = vu - du
    score_v = vv - dv
    u_is_i = score_u <= score_v  # tie → u (deterministic; matches reference)
    ci = jnp.where(u_is_i, cu2, cv2)
    cj = jnp.where(u_is_i, cv2, cu2)
    i_vtx = jnp.where(u_is_i, u, v)
    di = jnp.where(u_is_i, du, dv)
    can_migrate = h_on & both_small & (vol_h[cj] + di < kappa)
    vol_h = vol_h.at[jnp.where(can_migrate, cj, sink)].add(jnp.where(can_migrate, di, 0))
    vol_h = vol_h.at[jnp.where(can_migrate, ci, sink)].add(jnp.where(can_migrate, -di, 0))
    v2c_h = v2c_h.at[i_vtx].set(jnp.where(can_migrate, cj, v2c_h[i_vtx]))

    # ---------------- tail branch (local-degree volumes) ----------------
    t_on = (~is_head) & valid
    tu = state.v2c_t[u]
    tv = state.v2c_t[v]
    tnew_u = tu < 0
    tnew_v = tv < 0
    tu2 = jnp.where(tnew_u, state.next_t, tu)
    next_t = state.next_t + jnp.where(t_on & tnew_u, 1, 0).astype(jnp.int32)
    tv2 = jnp.where(tnew_v, next_t, tv)
    next_t = next_t + jnp.where(t_on & tnew_v, 1, 0).astype(jnp.int32)
    vol_t = state.vol_t
    ld = state.ld
    if global_tail:
        # S5P-B: allocation-time global-degree volumes (mirrors head branch)
        vol_t = vol_t.at[jnp.where(t_on & tnew_u, tu2, sink)].add(
            jnp.where(t_on & tnew_u, du, 0)
        )
        vol_t = vol_t.at[jnp.where(t_on & tnew_v, tv2, sink)].add(
            jnp.where(t_on & tnew_v, dv, 0)
        )
    else:
        # Update vol(·) by 1 and ld(·) by 1 for both endpoints (lines 14-15).
        vol_t = vol_t.at[jnp.where(t_on, tu2, sink)].add(jnp.where(t_on, 1, 0))
        vol_t = vol_t.at[jnp.where(t_on, tv2, sink)].add(jnp.where(t_on, 1, 0))
        ld = ld.at[u].add(jnp.where(t_on, 1, 0))
        ld = ld.at[v].add(jnp.where(t_on, 1, 0))
    v2c_t = state.v2c_t.at[u].set(jnp.where(t_on, tu2, state.v2c_t[u]))
    v2c_t = v2c_t.at[v].set(jnp.where(t_on, tv2, v2c_t[v]))
    cnt_t = state.cnt_t
    cnt_t = cnt_t.at[u].add(jnp.where(t_on, 1, 0))
    cnt_t = cnt_t.at[v].add(jnp.where(t_on, 1, 0))
    # migration (lines 16-21): i = argmin vol; move ld(i) units
    tvu = vol_t[tu2]
    tvv = vol_t[tv2]
    t_small = (tvu < kappa) & (tvv < kappa) & (tu2 != tv2)
    tu_is_i = tvu <= tvv
    tci = jnp.where(tu_is_i, tu2, tv2)
    tcj = jnp.where(tu_is_i, tv2, tu2)
    ti_vtx = jnp.where(tu_is_i, u, v)
    ldi = degrees[ti_vtx] if global_tail else ld[ti_vtx]
    t_mig = t_on & t_small
    if global_tail:
        t_mig = t_mig & (vol_t[tcj] + ldi < kappa)
    vol_t = vol_t.at[jnp.where(t_mig, tcj, sink)].add(jnp.where(t_mig, ldi, 0))
    vol_t = vol_t.at[jnp.where(t_mig, tci, sink)].add(jnp.where(t_mig, -ldi, 0))
    v2c_t = v2c_t.at[ti_vtx].set(jnp.where(t_mig, tcj, v2c_t[ti_vtx]))

    return ClusterState(
        v2c_h=v2c_h,
        v2c_t=v2c_t,
        vol_h=vol_h,
        vol_t=vol_t,
        ld=ld,
        next_h=next_h,
        next_t=next_t,
        cnt_h=cnt_h,
        cnt_t=cnt_t,
        alloc_h=alloc_h,
    )


@partial(jax.jit, static_argnames=("xi", "kappa", "global_tail"))
def cluster_chunk(
    state: ClusterState,
    src: jax.Array,
    dst: jax.Array,
    degrees: jax.Array,
    *,
    xi: int,
    kappa: int,
    global_tail: bool = False,
) -> ClusterState:
    """Process one chunk of the edge stream through Algorithm 1."""

    def body(s, e):
        return (
            _edge_step(s, e, degrees=degrees, xi=xi, kappa=kappa, global_tail=global_tail),
            (),
        )

    state, _ = jax.lax.scan(body, state, (src, dst))
    return state


def cluster_retract_chunk(
    state: ClusterState,
    src: jax.Array,
    dst: jax.Array,
    n_valid,
    degrees: jax.Array | None = None,
    *,
    xi: int | None = None,
    is_head: jax.Array | None = None,
) -> ClusterState:
    """Retract one chunk of **deleted** edges from the clustering carry.

    Order-independent decremental accounting (no scan): membership
    counters and streaming local degrees subtract exactly; tail volumes
    subtract one unit per endpoint at the vertex's *current* tail cluster
    (bounded staleness when the vertex migrated since insertion); a head
    vertex orphaned by this chunk (counter reaches 0) hands its recorded
    allocation contribution back to its current head cluster and resets
    to unassigned, so a re-inserted head edge re-allocates it cleanly.

    Head/tail classification: pass the per-edge ``is_head`` flags recorded
    at insertion time when available (the S5P bundle stores them — the
    retraction then mirrors exactly what insertion accounted), else the
    frozen-ξ classification against ``degrees`` (which should be the
    pre-deletion table so both sides see the same degrees).
    """
    if is_head is None:
        if degrees is None or xi is None:
            raise ValueError("need either is_head flags or (degrees, xi)")
        is_head = (degrees[src] > xi) & (degrees[dst] > xi)
    return _cluster_retract(state, src, dst, jnp.int32(n_valid),
                            jnp.asarray(is_head))


@jax.jit
def _cluster_retract(state, src, dst, n_valid, is_head):
    V = state.ld.shape[0]
    sink = state.vol_h.shape[0] - 1
    real = jnp.arange(src.shape[0]) < n_valid
    valid = real & (src != dst)
    h = (valid & is_head).astype(jnp.int32)
    t = (valid & ~is_head).astype(jnp.int32)

    cnt_h = state.cnt_h
    cnt_h = cnt_h - jax.ops.segment_sum(h, src, num_segments=V)
    cnt_h = cnt_h - jax.ops.segment_sum(h, dst, num_segments=V)
    cnt_t = state.cnt_t
    cnt_t = cnt_t - jax.ops.segment_sum(t, src, num_segments=V)
    cnt_t = cnt_t - jax.ops.segment_sum(t, dst, num_segments=V)
    ld = state.ld
    ld = ld - jax.ops.segment_sum(t, src, num_segments=V)
    ld = ld - jax.ops.segment_sum(t, dst, num_segments=V)

    # tail volumes: one unit per endpoint at the current tail cluster
    vol_t = state.vol_t
    for vtx, w in ((src, t), (dst, t)):
        c = state.v2c_t[vtx]
        on = (w > 0) & (c >= 0)
        vol_t = vol_t.at[jnp.where(on, c, sink)].add(-on.astype(jnp.int32))

    # head orphans: hand back the allocation contribution, reset the id
    orphan = (cnt_h <= 0) & (state.cnt_h > 0) & (state.v2c_h >= 0)
    vol_h = state.vol_h.at[jnp.where(orphan, state.v2c_h, sink)].add(
        jnp.where(orphan, -state.alloc_h, 0))
    alloc_h = jnp.where(orphan, 0, state.alloc_h)
    v2c_h = jnp.where(orphan, -1, state.v2c_h)
    # tail orphans: volumes already subtracted per incidence — reset the id
    orphan_t = (cnt_t <= 0) & (state.cnt_t > 0) & (state.v2c_t >= 0)
    v2c_t = jnp.where(orphan_t, -1, state.v2c_t)

    return ClusterState(
        v2c_h=v2c_h, v2c_t=v2c_t, vol_h=vol_h, vol_t=vol_t, ld=ld,
        next_h=state.next_h, next_t=state.next_t,
        cnt_h=cnt_h, cnt_t=cnt_t, alloc_h=alloc_h,
    )


class ClusterCarry(PartitionerCarry):
    """Algorithm 1 as a :class:`~repro.streaming.carry.PartitionerCarry`.

    Carry = :class:`ClusterState`.  Merge semantics for parallel ingest
    are pure group ops: volumes, local degrees and the id counters are
    additive (SUM of per-worker deltas against the shared merge base);
    the vertex→cluster tables merge as SUM-of-transitions — when a single
    worker reassigned a vertex the telescoped sum *is* that worker's
    value (the overwhelmingly common case under chunk-range sharding);
    membership counters are COUNTED.  When two workers concurrently
    reassign the *same* vertex within one super-chunk the telescoped sum
    would be a fabricated id (out-of-range sums project to unassigned,
    in-range ones alias an unrelated cluster), so the two v2c leaves are
    flagged :attr:`~repro.streaming.carry.PartitionerCarry.pick_first`:
    concurrent reassignments resolve to the lowest-lane writer's id — a
    *real* cluster some lane chose — instead of the telescoped sum.
    Parallel cluster ingest is still approximate by design (the loser
    lane's volume deltas were accrued against its own id), but membership
    is never garbage; hub-sharded lanes (``shard="hub"``) additionally
    make every hub single-writer, shrinking the conflict set to
    cross-lane tail vertices.  The slow-lane 8-device band test pins the
    quality envelope, and the group structure is what buys exact
    deletions everywhere else.  State-only — no per-edge parts.
    """

    emits_parts = False
    supports_retract = True
    retract_exact = False  # migrations are history-dependent (see module doc)
    # ClusterState leaf order: v2c_h, v2c_t, vol_h, vol_t, ld, next_h,
    # next_t, cnt_h, cnt_t, alloc_h
    merge_ops = (SUM, SUM, SUM, SUM, SUM, SUM, SUM, COUNTED, COUNTED, SUM)
    pick_first = (0, 1)  # v2c_h, v2c_t: keep a real id under contention

    def __init__(self, degrees: jax.Array, n_vertices: int, *, xi: int,
                 kappa: int, global_tail: bool = False,
                 use_kernel: bool | None = None,
                 vmem_budget: int | None = None):
        self.degrees = degrees
        self.n_vertices = int(n_vertices)
        self.xi = int(xi)
        self.kappa = int(kappa)
        self.global_tail = bool(global_tail)
        if use_kernel is None:
            use_kernel = jax.default_backend() == "tpu"
        self._use_kernel = bool(use_kernel)
        self._vmem_budget = vmem_budget

    def init(self) -> ClusterState:
        return init_state(self.n_vertices)

    def step_chunk(self, carry, src, dst, n_valid, *extras):
        path = "oracle"
        if self._use_kernel:
            # lazy import: core.baselines imports the kernels package at
            # module level, so the reverse edge must stay function-local
            from ..kernels import stream_scan as _scan

            budget = _scan.vmem_budget(self._vmem_budget)
            path = _scan.select_path(
                self.n_vertices, 1, src.shape[0], consumer="cluster",
                budget=budget)
        spans.count(f"stream_scan.cluster.{path}")
        if path == "oracle":
            return cluster_chunk(
                carry, src, dst, self.degrees, xi=self.xi, kappa=self.kappa,
                global_tail=self.global_tail,
            ), None
        leaves = _scan.cluster_scan(
            tuple(carry), src, dst, self.degrees, xi=self.xi,
            kappa=self.kappa, global_tail=self.global_tail,
            vmem=_scan.cluster_vmem_arrays(self.n_vertices, src.shape[0]),
            vmem_limit=budget)
        return ClusterState(*leaves), None

    def retract_chunk(self, carry, src, dst, n_valid, parts, *extras):
        return cluster_retract_chunk(carry, src, dst, n_valid, self.degrees,
                                     xi=self.xi)

    def occupancy_contest(self, before, after) -> float:
        """Membership churn between consecutive merge bases.

        The COUNTED occupancy default saturates almost immediately here
        (membership *counters* go nonzero on first touch and stay), which
        would let auto cadence back off while vertices are still hopping
        between clusters — exactly the window where concurrent
        reassignments degrade quality.  Measure reassignment instead:
        the fraction of assigned vertices whose cluster id moved
        (assigned→assigned with a different id) across the two v2c
        tables.  Fresh assignments (unassigned→id) are growth, not
        contention, and don't count."""
        changed = active = 0
        for b, a in ((before.v2c_h, after.v2c_h),
                     (before.v2c_t, after.v2c_t)):
            changed += int(jnp.sum((b >= 0) & (a >= 0) & (a != b)))
            active += int(jnp.sum(a >= 0))
        return changed / max(active, 1)


class DegreeCarry(PartitionerCarry):
    """One-pass global degree precompute as a carry (deg SUM; state-only).

    Padding is masked via ``n_valid`` (real (0, 0) self-loops *do* count
    toward vertex 0's degree, exactly as :func:`compute_degrees` counts
    them — padding entries must not)."""

    emits_parts = False
    supports_retract = True
    retract_exact = True
    merge_ops = (SUM,)

    def __init__(self, n_vertices: int):
        self.n_vertices = int(n_vertices)

    def init(self) -> jax.Array:
        return jnp.zeros((self.n_vertices,), jnp.int32)

    def step_chunk(self, carry, src, dst, n_valid, *extras):
        return _degree_chunk(carry, src, dst, n_valid), None

    def retract_chunk(self, carry, src, dst, n_valid, parts, *extras):
        return carry - _degree_chunk(jnp.zeros_like(carry), src, dst, n_valid)

    def finalize(self, carry):
        return carry.astype(jnp.int32)


@jax.jit
def _degree_chunk(deg, src, dst, n_valid):
    w = (jnp.arange(src.shape[0]) < n_valid).astype(jnp.int32)
    n = deg.shape[0]
    deg = deg + jax.ops.segment_sum(w, src, num_segments=n)
    deg = deg + jax.ops.segment_sum(w, dst, num_segments=n)
    return deg


def cluster_stream(
    src: jax.Array,
    dst: jax.Array,
    n_vertices: int,
    *,
    xi: int,
    kappa: int,
    chunk_size: int = 1 << 16,
    global_tail: bool = False,
    stream=None,
    num_streams: int = 1,
    super_chunk: int | str = 8,
    shard: str = "range",
    use_kernel: bool | None = None,
    vmem_budget: int | None = None,
) -> ClusterState:
    """Run Algorithm 1 over the whole stream in fixed-size device chunks.

    Only the O(|V|) carry persists between chunks — the streaming memory
    contract.  Degrees are the one-pass global precompute.  An existing
    :class:`repro.streaming.EdgeStream` (e.g. with a non-natural ordering)
    may be passed instead of raw arrays.  ``num_streams > 1`` ingests S
    sharded sub-streams in parallel with :class:`ClusterCarry` merges every
    ``super_chunk`` chunks (``num_streams=1`` is bit-identical sequential).
    """
    from ..streaming import as_stream, run_parallel

    stream = as_stream(src, dst, n_vertices, stream=stream,
                       chunk_size=chunk_size)
    # host-resident streams get the one-call vectorized precompute; streams
    # without full arrays (out-of-core) take the chunked pass — the two are
    # bit-identical (integer segment sums commute)
    src_full = getattr(stream, "src", None)
    if src_full is not None:
        degrees = compute_degrees(jnp.asarray(src_full, jnp.int32),
                                  jnp.asarray(stream.dst, jnp.int32),
                                  stream.n_vertices)
    else:
        degrees = compute_degrees_stream(stream)
    pc = ClusterCarry(degrees, stream.n_vertices, xi=xi, kappa=kappa,
                      global_tail=global_tail, use_kernel=use_kernel,
                      vmem_budget=vmem_budget)
    _, state = run_parallel(stream, pc, num_streams=num_streams,
                            super_chunk=super_chunk, shard=shard)
    return state


def compute_degrees(src: jax.Array, dst: jax.Array, n_vertices: int) -> jax.Array:
    ones = jnp.ones_like(src)
    deg = jax.ops.segment_sum(ones, src, num_segments=n_vertices)
    deg = deg + jax.ops.segment_sum(ones, dst, num_segments=n_vertices)
    return deg.astype(jnp.int32)


def compute_degrees_stream(stream, num_streams: int = 1,
                           super_chunk: int = 8) -> jax.Array:
    """The one-pass global degree precompute, chunk by chunk — O(|V|) carry,
    so it runs on out-of-core streams too.  Integer segment sums commute,
    so the result is bit-identical to :func:`compute_degrees` on the full
    arrays (padding entries are masked out, not counted as self-loops) —
    and, for the same reason, to any ``num_streams``/``super_chunk``."""
    from ..streaming import run_parallel

    _, deg = run_parallel(stream, DegreeCarry(stream.n_vertices),
                          num_streams=num_streams, super_chunk=super_chunk)
    return deg


def compact_clusters(state: ClusterState, degrees: jax.Array, xi: int) -> ClusterResult:
    """Renumber head/tail clusters into one dense combined id space.

    Head clusters keep ids [0, n_head); tail clusters are shifted to
    [n_head, n_head + n_tail).  A vertex's *primary* cluster is its head
    cluster if it has one (head vertices lead), else its tail cluster.
    Works on the counted projection, so vertices orphaned by deletions
    (membership counter 0) drop out of the id space here.
    """
    eff_h, eff_t = state.effective()
    v2c_h = spans.to_host(eff_h)
    v2c_t = spans.to_host(eff_t)
    deg = spans.to_host(degrees)

    used_h = np.unique(v2c_h[v2c_h >= 0])
    used_t = np.unique(v2c_t[v2c_t >= 0])
    remap_h = np.full(int(state.next_h) + 1, -1, np.int32)
    remap_h[used_h] = np.arange(used_h.size, dtype=np.int32)
    remap_t = np.full(int(state.next_t) + 1, -1, np.int32)
    remap_t[used_t] = np.arange(used_t.size, dtype=np.int32) + used_h.size

    out_h = np.where(v2c_h >= 0, remap_h[np.maximum(v2c_h, 0)], -1).astype(np.int32)
    out_t = np.where(v2c_t >= 0, remap_t[np.maximum(v2c_t, 0)], -1).astype(np.int32)
    primary = np.where(out_h >= 0, out_h, out_t).astype(np.int32)
    is_head_vertex = deg > xi

    return ClusterResult(
        v2c=jnp.asarray(primary),
        v2c_h=jnp.asarray(out_h),
        v2c_t=jnp.asarray(out_t),
        n_head=int(used_h.size),
        n_clusters=int(used_h.size + used_t.size),
        is_head_vertex=jnp.asarray(is_head_vertex),
    )


# ---------------------------------------------------------------------------
# Pure-Python transcription of Algorithm 1 — the oracle for property tests.
# ---------------------------------------------------------------------------


def reference_cluster_python(edges, n_vertices, xi, kappa):
    """Direct sequential transcription of paper Algorithm 1 (line numbers in
    comments refer to the paper listing).  Returns plain numpy state."""
    v2c_h = np.full(n_vertices, -1, np.int64)
    v2c_t = np.full(n_vertices, -1, np.int64)
    vol_h = np.zeros(n_vertices + 1, np.int64)
    vol_t = np.zeros(n_vertices + 1, np.int64)
    ld = np.zeros(n_vertices, np.int64)
    deg = np.zeros(n_vertices, np.int64)
    for u, v in edges:
        deg[u] += 1
        deg[v] += 1
    next_h = 0
    next_t = 0
    for u, v in edges:
        if u == v:
            continue
        if deg[u] > xi and deg[v] > xi:  # head edge
            if v2c_h[u] < 0:  # line 3: assign new id
                v2c_h[u] = next_h
                next_h += 1
                vol_h[v2c_h[u]] += deg[u]  # line 4: update vol by d(u)
            if v2c_h[v] < 0:
                v2c_h[v] = next_h
                next_h += 1
                vol_h[v2c_h[v]] += deg[v]
            cu, cv = v2c_h[u], v2c_h[v]
            if vol_h[cu] < kappa and vol_h[cv] < kappa and cu != cv:  # line 5
                # line 6: i = argmin vol(C[z]) - d(z); tie → u
                if vol_h[cu] - deg[u] <= vol_h[cv] - deg[v]:
                    i_vtx, ci, cj, di = u, cu, cv, deg[u]
                else:
                    i_vtx, ci, cj, di = v, cv, cu, deg[v]
                if vol_h[cj] + di < kappa:  # line 8
                    vol_h[cj] += di
                    vol_h[ci] -= di
                    v2c_h[i_vtx] = cj
        else:  # tail edge
            if v2c_t[u] < 0:  # line 13
                v2c_t[u] = next_t
                next_t += 1
            if v2c_t[v] < 0:
                v2c_t[v] = next_t
                next_t += 1
            vol_t[v2c_t[u]] += 1  # line 14: update vol by 1
            vol_t[v2c_t[v]] += 1
            ld[u] += 1  # line 15: update ld by 1
            ld[v] += 1
            tu, tv = v2c_t[u], v2c_t[v]
            if vol_t[tu] < kappa and vol_t[tv] < kappa and tu != tv:  # line 16
                if vol_t[tu] <= vol_t[tv]:  # line 17: i = argmin vol; tie → u
                    i_vtx, ci, cj = u, tu, tv
                else:
                    i_vtx, ci, cj = v, tv, tu
                ldi = ld[i_vtx]
                vol_t[cj] += ldi  # lines 19-21 (unconditional in listing)
                vol_t[ci] -= ldi
                v2c_t[i_vtx] = cj
    return dict(
        v2c_h=v2c_h, v2c_t=v2c_t, vol_h=vol_h, vol_t=vol_t, ld=ld,
        next_h=next_h, next_t=next_t, deg=deg,
    )
