"""Parallel ingest: one logical EdgeStream sharded into S sub-streams.

The HEP/CuSP-style regime (ROADMAP "Distributed streams"): S workers each
ingest a disjoint share of the stream, folding their own
:class:`~repro.streaming.carry.PartitionerCarry` replica, and the carries
are reconciled by the protocol's declared merge semantics (replica bitmaps
OR, loads/volumes/degree estimates/Θ tables SUM, assignment tables MAX)
once per *super-chunk* — the only cross-worker communication, O(|V|·k)
state per merge, never edges.

:class:`ParallelEdgeStream` is the sharding plan: it slices any
:class:`~repro.streaming.stream.EdgeStream` (in-memory or the mmap-paged
``ShardedEdgeStream``) into S logical sub-streams and serves lockstep
*rounds* — the r-th chunk of every sub-stream, staged on the host as
lane-major (S, R, B) blocks of R rounds (lanes that ran out of chunks
serve all-padding (0, 0) self-loop chunks, the masked no-op every
consumer already skips).  Three shard modes:

- ``"range"``       — chunk-granular: lane s scans the contiguous chunk
  range ``[s·⌈C/S⌉, (s+1)·⌈C/S⌉)`` (the HEP file-split layout);
- ``"round-robin"`` — chunk-granular: chunk i goes to lane ``i mod S``
  (arrival-interleaved; ``"rr"`` is an accepted alias);
- ``"hub"``         — **edge-granular, hub-pinned**: an online CMS degree
  sketch (the same ``core.cms`` machinery the Θ pass and the hybrid
  budget planner use) classifies each edge's min-degree endpoint as
  hub/tail at plan time; every edge of a given hub routes to one pinned
  lane (rendezvous hash on the vertex id), so a hub's replica set is
  built by exactly one lane and **never diverges across lanes**, while
  tail edges keep round-robin load balance.  This is what makes S-way
  ingest quality-neutral on power-law graphs: carry staleness collapses
  to the (cheap, exactly-mergeable) tail.

``super_chunk`` may be a fixed chunk count or ``"auto"`` — an adaptive
cadence controller that merges after every chunk while placements are
contested (measured by the per-merge delta in replica-table occupancy —
see :meth:`~repro.streaming.carry.PartitionerCarry.occupancy_contest`)
and backs off geometrically as the tables warm; state-only carries
(clustering, the sketches) instead fold in full lane isolation and merge
once at the end (see :class:`_CadenceController`).  The chosen schedule is
logged once per run (``reset_cadence_log`` re-arms, mirroring the kernel
ladder's ``reset_path_log``) and exposed — with per-lane
``(chunks, edges, merge_count, wall_s)`` stats — via
:func:`last_ingest_stats`.

:func:`run_parallel` drives a carry over that plan with three backends
that produce **bit-identical results on the same plan** (merges are
integer/bool exact, so reduction order cannot matter):

- ``"threads"``   — S host workers, each folding its sub-stream through
  the shared compiled chunk step (jax releases the GIL during execution,
  so workers genuinely overlap on multicore hosts; wall-clock gain is
  bounded by ``min(S, cores)``).  The default on single-device hosts.
- ``"shard_map"`` — one lane per device of a mesh axis (built over the
  first S local devices by default, or any provided mesh); the super-chunk
  merge becomes one ``psum``/``pmax`` collective per carry field — the
  same collective plumbing ``core.distributed`` uses.  The default when
  the platform reports ≥ S devices.  A super-step (``lanes_super_step``)
  takes the consumer's per-job arrays as arguments, so it compiles once
  per shape and serves every later job.  (Note: *forced* host-platform
  CPU devices execute serially — real parallelism needs real devices or
  the threads backend.)
- ``"vmap"``      — one compiled step processes all S lanes per round as
  a batch.  Semantically the reference backend; on XLA:CPU the batched
  per-edge scatters lower poorly, so use it for testing, not speed.

A consumer that bounds a resource (Alg. 3's partition capacity) shares
it out between the lanes at every merge base
(:meth:`~repro.streaming.carry.PartitionerCarry.lane_shares`), so a bound
the sequential scan keeps holds for the merged carry too.

A drive with S > 1 lanes is the span ``lanes.drive``; the vmap and
shard_map backends stage each super-step's blocks, and the lanes' shares
read from the merge base, under ``lanes.stage``.
Every merge counts ``lanes.merges``, ``lanes.rounds`` (chunks per lane
since the last merge) and ``lanes.merge_bytes`` (the carry bytes each
lane hands to it), on the host.

``num_streams=1`` (or a single-chunk stream) bypasses all of this and runs
the sequential :func:`~repro.streaming.engine.run_carry` driver — the
parallel path is additive, so every sequential result (and the pinned
golden hashes) is reproduced bit-identically by construction, in every
shard mode.
"""

from __future__ import annotations

import copy
import dataclasses
import logging
import math
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

from ..runtime import spans
from .carry import PartitionerCarry
from .engine import run_carry
from .stream import Chunk, EdgeStream

__all__ = ["ParallelEdgeStream", "run_parallel", "IngestStats", "LaneStats",
           "last_ingest_stats", "reset_cadence_log"]

log = logging.getLogger(__name__)

SHARD_MODES = ("range", "round-robin", "hub")
_SHARD_ALIASES = {"rr": "round-robin"}
LANE_FAILURE_MODES = ("raise", "replay")

#: adaptive-cadence knobs: merge every chunk while the per-merge replica-
#: occupancy delta exceeds WARM (the contested regime), then back off
#: geometrically (1 → 2 → 4 → …) up to CAP chunks between merges
AUTO_CADENCE_WARM = 0.05
AUTO_CADENCE_CAP = 32

#: the "merge once at the end" cadence auto mode resolves to for
#: state-only carries (every backend clamps a super-chunk to the rounds
#: actually remaining, so any value ≥ the stream length means isolation)
ISOLATE_CADENCE = 1 << 30


# ---------------------------------------------------------------------------
# observability: per-lane ingest stats + once-per-run cadence-schedule log
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class LaneStats:
    """One lane's share of a ``run_parallel`` drive.  ``device`` is the id
    of the device that held the lane's carry (None when not observed)."""

    chunks: int
    edges: int
    merge_count: int
    wall_s: float
    device: int | None = None


@dataclasses.dataclass(frozen=True)
class IngestStats:
    """What one ``run_parallel`` drive actually did — consumed by the
    benches and the StragglerMonitor instead of re-deriving it.

    ``schedule`` is the realized merge cadence (chunks per lane between
    consecutive merges); for ``super_chunk="auto"`` it is the controller's
    trace, for a fixed cadence it repeats that value.  ``wall_s`` is
    per-lane fold time on the threads backend and the shared loop time on
    the vmap/shard_map backends (lanes there execute as one program).
    """

    num_streams: int
    shard: str
    backend: str
    super_chunk: int | str
    schedule: tuple[int, ...]
    lanes: tuple[LaneStats, ...]

    def as_dict(self) -> dict:
        return {
            "num_streams": self.num_streams,
            "shard": self.shard,
            "backend": self.backend,
            "super_chunk": self.super_chunk,
            "schedule": list(self.schedule),
            "lanes": [dataclasses.asdict(l) for l in self.lanes],
        }


_last_stats: IngestStats | None = None
_logged_schedules: set[tuple] = set()


def last_ingest_stats() -> IngestStats | None:
    """Stats of the most recent :func:`run_parallel` drive (any backend,
    including the sequential ``num_streams=1`` delegation)."""
    return _last_stats


def reset_cadence_log() -> None:
    """Re-arm the once-per-run cadence-schedule logging (used by tests —
    the same contract as the kernel ladder's ``reset_path_log``)."""
    _logged_schedules.clear()


def _compress_schedule(schedule) -> str:
    """``[1,1,1,2,4,8,8]`` → ``"1×3,2,4,8×2"`` for one-line logging
    (:data:`ISOLATE_CADENCE` renders as ``"all"``)."""
    out, i = [], 0
    schedule = ["all" if c == ISOLATE_CADENCE else c for c in schedule]
    while i < len(schedule):
        j = i
        while j < len(schedule) and schedule[j] == schedule[i]:
            j += 1
        out.append(str(schedule[i]) if j - i == 1 else f"{schedule[i]}×{j - i}")
        i = j
    return ",".join(out)


def _log_schedule(consumer: str, stats: IngestStats) -> None:
    key = (consumer, stats.shard, stats.super_chunk, stats.schedule)
    if key in _logged_schedules:
        return
    _logged_schedules.add(key)
    log.info("ingest %s: S=%d shard=%s super_chunk=%s → cadence [%s] "
             "(%d merges)", consumer, stats.num_streams, stats.shard,
             stats.super_chunk, _compress_schedule(stats.schedule),
             len(stats.schedule))


class _CadenceController:
    """Merge-cadence policy shared by all three backends.

    Fixed ``super_chunk`` replays that value.  ``"auto"`` is
    consumer-aware:

    - carries that **emit per-edge parts** (the placement scans: HDRF,
      greedy, grid, Alg. 3 assignment) start at 1 — merge after every
      chunk while placements are contested, because every un-merged chunk
      is edges placed against stale replica tables — and double whenever
      a merge's occupancy delta falls below :data:`AUTO_CADENCE_WARM`,
      re-arming to 1 when contest re-spikes (a burst of new vertices).
      The geometric ladder keeps the shard_map backend's per-round-count
      compile cache to O(log CAP) distinct entries.
    - **state-only** carries (``emits_parts=False``: Alg. 1 clustering,
      the degree/Θ sketches) resolve to :data:`ISOLATE_CADENCE` — lanes
      fold in full isolation and merge exactly once at the end.  No
      per-edge decision is emitted mid-stream, so mid-stream merges buy
      no placement consistency; what they *do* is couple the lanes'
      assignment tables (measured on the block R-MAT bench: isolated
      hub-sharded clustering lands *under* the sequential RF, while a
      1 → 2 → 4 ramp is the worst of both regimes).  Linear sketches are
      cadence-invariant, so isolation is also the cheapest exact choice.
    """

    def __init__(self, pc: PartitionerCarry, super_chunk: int | str):
        self.pc = pc
        self.auto = super_chunk == "auto"
        self.isolate = self.auto and not pc.emits_parts
        if self.isolate:
            self.cadence = ISOLATE_CADENCE
        else:
            self.cadence = 1 if self.auto else int(super_chunk)
        self.schedule: list[int] = []

    def next(self) -> int:
        self.schedule.append(self.cadence)
        return self.cadence

    def observe(self, prev_base, new_base) -> None:
        if not self.auto or self.isolate:
            return
        contest = self.pc.occupancy_contest(prev_base, new_base)
        if contest > AUTO_CADENCE_WARM:
            self.cadence = 1
        else:
            self.cadence = min(self.cadence * 2, AUTO_CADENCE_CAP)


# ---------------------------------------------------------------------------
# sharding plan
# ---------------------------------------------------------------------------


def _rendezvous_lanes(v: np.ndarray, S: int) -> np.ndarray:
    """Highest-random-weight (rendezvous) lane per vertex id.

    ``argmax_s h(v, s)`` over an avalanche mix — stable under lane-count
    changes in the HRW sense and, more importantly here, a pure function
    of the vertex id, so every edge of a hub lands on the same lane no
    matter which chunk it arrives in."""
    with np.errstate(over="ignore"):
        h = (v.astype(np.uint32)[:, None] * np.uint32(0x9E3779B1)) ^ (
            np.arange(S, dtype=np.uint32)[None, :] * np.uint32(0x85EBCA6B))
        h ^= h >> np.uint32(16)
        h *= np.uint32(0x85EBCA6B)
        h ^= h >> np.uint32(13)
        h *= np.uint32(0xC2B2AE35)
        h ^= h >> np.uint32(16)
    return np.argmax(h, axis=1).astype(np.int32)


class ParallelEdgeStream:
    """Shard a stream into S logical sub-streams (see module docstring).

    ``"range"``/``"round-robin"`` shard the chunk index space; ``"hub"``
    shards the *edge* space: a plan-time pass classifies each edge by its
    min-endpoint's online CMS degree estimate (hub iff estimate >
    ``hub_threshold``, default the stream's average degree), pins hub
    edges to ``rendezvous(vertex)`` lanes and deals tail edges round-robin,
    then packs each lane's edges (stream order preserved) into synthetic
    fixed-size chunks.  Either way every edge belongs to exactly one
    sub-stream and sub-stream-local order preserves stream order.
    """

    def __init__(self, stream: EdgeStream, num_streams: int, *,
                 shard: str = "range", hub_threshold: int | None = None):
        shard = _SHARD_ALIASES.get(shard, shard)
        if num_streams < 1:
            raise ValueError("num_streams must be >= 1")
        if shard not in SHARD_MODES:
            raise ValueError(f"unknown shard mode {shard!r}; one of {SHARD_MODES}")
        self.stream = stream
        self.shard = shard
        # more lanes than chunks would only add all-padding lanes
        self.num_streams = max(1, min(int(num_streams), stream.n_chunks))
        C, S = stream.n_chunks, self.num_streams
        self._chunk_pos: list[np.ndarray] | None = None  # hub registry
        self._lane_of_pos: np.ndarray | None = None
        self._pin_vertex: np.ndarray | None = None
        self.pin_map: dict[int, int] = {}
        self.hub_threshold: int | None = None
        if shard == "range":
            q = -(-C // S)
            self.lanes = [list(range(s * q, min((s + 1) * q, C)))
                          for s in range(S)]
        elif shard == "round-robin":
            self.lanes = [list(range(s, C, S)) for s in range(S)]
        else:
            self._build_hub_plan(hub_threshold)

    # ---------------------------------------------------------- hub plan
    def _build_hub_plan(self, hub_threshold: int | None) -> None:
        # lazy import: core.cms imports streaming.carry, so a module-level
        # import here would cycle through the package __init__s
        from ..core.cms import cms_query, cms_update, make_sketch, \
            suggest_params, vertex_key

        st, S = self.stream, self.num_streams
        if (type(st)._edges_at is not EdgeStream._edges_at
                and st.order is not None):
            raise ValueError(
                "shard='hub' needs per-edge gathers; reordered out-of-core "
                "streams serve edges by stream-order ranges only — use "
                "ordering='natural' or an in-memory stream")
        E, V, B = st.n_edges, st.n_vertices, st.chunk_size
        if hub_threshold is None:
            # the ξ-style default: a vertex is a hub past the average degree
            hub_threshold = max(2, int(2.0 * E / max(V, 1)))
        self.hub_threshold = int(hub_threshold)
        w, d = suggest_params()
        width = w * max(1, int(math.sqrt(max(V, 1))))
        sketch = make_sketch(width, d, seed=st.seed)
        lane_of_pos = np.empty(E, np.int32)
        pin_vertex = np.full(E, -1, np.int32)
        tail_lane = np.full(V, -1, np.int32)  # tail vertex → dealt lane
        rr = 0  # round-robin cursor for newly seen tail vertices
        for i in range(st.n_chunks):
            ch = st.chunk_at(i)
            nv = ch.n_valid
            s = np.asarray(ch.src)[:nv]
            t = np.asarray(ch.dst)[:nv]
            # query *before* update: the estimate is online (edges seen in
            # prior chunks only), the HDRF-style partial-degree regime —
            # early edges of a not-yet-recognized hub go tail-routed, which
            # is exactly the HDRF partial-degree tradeoff and costs only
            # the warm-up prefix
            est_s = np.asarray(cms_query(sketch, vertex_key(jnp.asarray(s))))
            est_t = np.asarray(cms_query(sketch, vertex_key(jnp.asarray(t))))
            # the hub endpoint is the *higher-degree* one (ties break to
            # the smaller id, deterministically): its replica set is the
            # expensive one to let go stale, so its edges are what we pin
            s_wins = (est_s > est_t) | ((est_s == est_t) & (s <= t))
            hub_v = np.where(s_wins, s, t)
            is_hub = (np.maximum(est_s, est_t) > self.hub_threshold) & (s != t)
            lanes_c = np.empty(nv, np.int32)
            hub_idx = np.flatnonzero(is_hub)
            if hub_idx.size:
                lanes_c[hub_idx] = _rendezvous_lanes(hub_v[hub_idx], S)
            # tail edges route by their *lower-degree* endpoint (the DBH
            # rule: that's the vertex whose replica set must not scatter),
            # and the routing is vertex-granular round-robin — each newly
            # seen tail vertex is dealt the next lane cyclically, so lane
            # loads stay balanced while every tail vertex's edges stay on
            # one lane (per-edge round-robin would hand a degree-d vertex
            # ~d replicas purely from lane divergence)
            tail_idx = np.flatnonzero(~is_hub)
            if tail_idx.size:
                tv = np.where(s_wins, t, s)[tail_idx]
                newv = tv[tail_lane[tv] < 0]
                if newv.size:
                    _, first = np.unique(newv, return_index=True)
                    order_v = newv[np.sort(first)]  # first-appearance order
                    tail_lane[order_v] = (rr + np.arange(order_v.size)) % S
                    rr = (rr + order_v.size) % S
                lanes_c[tail_idx] = tail_lane[tv]
            pos0 = i * B
            lane_of_pos[pos0:pos0 + nv] = lanes_c
            pin_vertex[pos0 + hub_idx] = hub_v[hub_idx]
            counts = jnp.asarray((s != t).astype(np.uint32))
            sketch = cms_update(sketch, vertex_key(jnp.asarray(s)), counts)
            sketch = cms_update(sketch, vertex_key(jnp.asarray(t)), counts)
        self._lane_of_pos = lane_of_pos
        self._pin_vertex = pin_vertex
        for v in np.unique(pin_vertex[pin_vertex >= 0]):
            first = np.flatnonzero(pin_vertex == v)[0]
            self.pin_map[int(v)] = int(lane_of_pos[first])
        self._chunk_pos = []
        self.lanes = []
        for s in range(S):
            pos_s = np.flatnonzero(lane_of_pos == s).astype(np.int64)
            self.lanes.append(self._register_chunks(pos_s))

    def _register_chunks(self, positions: np.ndarray) -> list[int]:
        """Pack stream positions (ascending = stream order) into synthetic
        fixed-size chunks; returns the new chunk ids."""
        B = self.stream.chunk_size
        cids = []
        for i in range(0, len(positions), B):
            cids.append(len(self._chunk_pos))
            self._chunk_pos.append(positions[i:i + B])
        return cids

    @property
    def n_hubs(self) -> int:
        return len(self.pin_map)

    def edge_lanes(self) -> np.ndarray:
        """Per-edge lane id in **arrival order** — the provenance map the
        post-ingest touch-up uses to find clusters written by ≥ 2 lanes."""
        st = self.stream
        if self.shard == "hub":
            by_pos = self._lane_of_pos
        else:
            B = st.chunk_size
            lane_of_chunk = np.empty(st.n_chunks, np.int32)
            for s, lane in enumerate(self.lanes):
                lane_of_chunk[np.asarray(lane, np.int64)] = s
            by_pos = lane_of_chunk[
                np.minimum(np.arange(st.n_edges) // B, st.n_chunks - 1)]
        if st.order is None:
            return by_pos.astype(np.int32)
        out = np.empty(st.n_edges, np.int32)
        out[np.asarray(st.order)] = by_pos
        return out

    # ------------------------------------------------------------ serving
    @property
    def n_rounds(self) -> int:
        """Lockstep rounds = chunks of the longest sub-stream."""
        return max(len(lane) for lane in self.lanes)

    def chunk_n_valid(self, chunk_id: int) -> int:
        if self._chunk_pos is not None:
            return len(self._chunk_pos[chunk_id])
        cs, E = self.stream.chunk_size, self.stream.n_edges
        return min((chunk_id + 1) * cs, E) - chunk_id * cs

    def chunk_positions(self, chunk_id: int):
        """The stream positions a plan chunk's valid edges occupy."""
        if self._chunk_pos is not None:
            return self._chunk_pos[chunk_id]
        start = chunk_id * self.stream.chunk_size
        return slice(start, start + self.chunk_n_valid(chunk_id))

    def _chunk_edges(self, chunk_id: int, *extras):
        """``(src, dst, extras)`` of a plan chunk's valid edges, unpadded,
        as the stream and the extras hold them."""
        st = self.stream
        pos = self.chunk_positions(chunk_id)
        if isinstance(pos, slice):
            sl = pos if st.order is None else np.asarray(st.order[pos])
            s, d = st._edges_at(sl, pos.start, pos.stop)
        else:
            sl = pos if st.order is None else np.asarray(st.order)[pos]
            s, d = st._edges_at(np.asarray(sl), 0, len(pos))
        return s, d, [e[sl] for e in extras]

    def chunk_for(self, chunk_id: int, *extras) -> Chunk:
        """The chunk behind a plan chunk id: the stream's own chunk in the
        chunk-granular modes, a gathered synthetic chunk in hub mode."""
        if self._chunk_pos is None:
            return self.stream.chunk_at(chunk_id, *extras)
        B = self.stream.chunk_size
        ex = [np.asarray(e) for e in extras]
        s, d, exc = self._chunk_edges(chunk_id, *ex)
        s = np.asarray(s, np.int32)
        d = np.asarray(d, np.int32)
        nv = len(s)
        if nv < B:  # pad to the fixed chunk size ((0,0) self-loop no-ops)
            padn = B - nv
            s = np.concatenate([s, np.zeros(padn, np.int32)])
            d = np.concatenate([d, np.zeros(padn, np.int32)])
            exc = [np.concatenate(
                [e, np.zeros((padn,) + e.shape[1:], e.dtype)]) for e in exc]
        pos = self._chunk_pos[chunk_id]
        return Chunk(src=jnp.asarray(s), dst=jnp.asarray(d),
                     extras=tuple(jnp.asarray(e) for e in exc),
                     start=int(pos[0]) if nv else 0, n_valid=nv)

    def block(self, rounds, *extras):
        """Rounds ``rounds`` of every lane as host arrays, lane-major:
        ``((src (S, R, B), dst, n_valid (S, R), *extras (S, R, B, ...)),
        ids)``, where ``ids[ri][s]`` is the plan chunk lane s folds in round
        ``rounds[ri]``.  A chunk's tail past its valid edges, and the whole
        chunk of a lane that ran out of chunks (id ``None``, ``n_valid``
        0), are (0, 0) self-loops with zero extras: the padding every
        consumer skips.  ``extras`` are host arrays in arrival order."""
        S, R, B = self.num_streams, len(rounds), self.stream.chunk_size
        src = np.zeros((S, R, B), np.int32)
        dst = np.zeros((S, R, B), np.int32)
        nv = np.zeros((S, R), np.int32)
        exs = [np.zeros((S, R, B) + e.shape[1:], e.dtype) for e in extras]
        ids: list[list[int | None]] = [[None] * S for _ in range(R)]
        for s, lane in enumerate(self.lanes):
            for ri, r in enumerate(rounds):
                if r >= len(lane):
                    continue
                cid = ids[ri][s] = lane[r]
                cs, cd, cex = self._chunk_edges(cid, *extras)
                n = nv[s, ri] = len(cs)
                src[s, ri, :n] = cs
                dst[s, ri, :n] = cd
                for j, e in enumerate(cex):
                    exs[j][s, ri, :n] = e
        return (src, dst, nv, *exs), ids


def _mask_inactive_step(pc):
    """Wrap ``pc.step_chunk`` so an all-padding chunk (``n_valid == 0`` —
    only exhausted lanes serve these) is a true carry no-op.  Consumers
    only guarantee no-op behaviour for *embedded* (0, 0) self-loops where
    it matters for results (HDRF, e.g., counts partial degrees for them,
    exactly as the sequential tail-padding does); an exhausted lane must
    contribute an exact identity delta instead."""

    def step(carry, src, dst, n_valid, *extras):
        new, parts = pc.step_chunk(carry, src, dst, n_valid, *extras)
        active = n_valid > 0
        kept = jax.tree_util.tree_map(
            lambda o, n: jnp.where(active, n, o), carry, new)
        return kept, parts

    return step


def _device_of(tree) -> int:
    """Id of the (single) device holding a carry's first leaf."""
    leaf = jax.tree_util.tree_leaves(tree)[0]
    return min(d.id for d in leaf.devices())


def _resolve_backend(backend, S):
    if backend is not None:
        return backend
    return "shard_map" if len(jax.devices()) >= S else "threads"


def _streams_mesh(S):
    return jax.sharding.Mesh(np.asarray(jax.devices()[:S]), ("streams",))


def _publish_stats(pc, stats: IngestStats) -> IngestStats:
    global _last_stats
    _last_stats = stats
    _log_schedule(type(pc).__name__, stats)
    return stats


def run_parallel(
    stream: EdgeStream,
    pc: PartitionerCarry,
    *extras,
    num_streams: int = 1,
    super_chunk: int | str = 8,
    shard: str = "range",
    hub_threshold: int | None = None,
    backend: str | None = None,
    mesh=None,
    carry=None,
    on_lane_failure: str = "raise",
    lane_injector=None,
    straggler=None,
    carry_store=None,
    carry_consumer: str | None = None,
    carry_config=None,
    plan: ParallelEdgeStream | None = None,
):
    """Drive ``pc`` over ``stream`` with S-way parallel ingest.

    Same return contract as :func:`~repro.streaming.engine.run_carry`:
    ``(parts_in_arrival_order | None, pc.finalize(final_carry))``.
    ``super_chunk`` is the number of rounds (chunks per sub-stream)
    between carry merges — smaller means fresher cross-worker state,
    larger means less communication — or ``"auto"`` for the adaptive
    cadence controller (merge every chunk while contested, geometric
    backoff as the tables warm).  ``shard`` picks the lane layout
    (``"range"`` / ``"round-robin"`` / ``"hub"`` — see
    :class:`ParallelEdgeStream`); ``hub_threshold`` overrides hub mode's
    min-endpoint degree cut.  ``num_streams=1`` delegates to the
    sequential driver and is bit-identical to it in every mode.
    ``carry`` seeds the drive from a restored carry instead of
    ``pc.init()`` (the warm-start replay of ``repro.incremental``) — it
    becomes the first merge base, so SUM fields never double-count the
    restored state.  ``plan`` is a :class:`ParallelEdgeStream` of
    ``stream`` the caller built with these ``num_streams``, ``shard`` and
    ``hub_threshold``, used in place of a new one (so the caller knows
    which lane folded each edge).  A consumer that bounds a resource
    (:meth:`~repro.streaming.carry.PartitionerCarry.lane_shares`) gets
    each lane's share of it at every merge base.  Per-lane stats and the
    realized cadence schedule are published through
    :func:`last_ingest_stats`.

    Fault/straggler hardening (threads backend):

    - ``on_lane_failure="replay"`` — a lane whose fold raises mid-super-
      chunk is detected at the merge barrier and its chunk range replayed
      into a surviving worker, from the last committed merge base: lanes
      only ever publish state *at* merge points, so the replay is
      **bit-identical** to the unkilled drive (the plan — including hub
      mode's synthetic chunk registry — is deterministic, so the replayed
      chunks are the same chunks).  With a ``carry_store``
      (:class:`~repro.incremental.store.CarryStore`) the merge bases are
      additionally checkpointed and the replay restores from disk — the
      recovery path a real worker death (not just a raised exception)
      needs.  ``"raise"`` (default) propagates the failure.
    - ``lane_injector`` — duck-typed ``check(lane, chunk_id)`` called
      before each chunk fold
      (:class:`~repro.runtime.fault.LaneFaultInjector`).
    - ``straggler`` — a :class:`~repro.runtime.straggler.StragglerMonitor`:
      per-lane super-chunk times feed its EMAs, and its
      ``rebalance_plan`` drives **live lane-range handoff** — a tail cut
      of each straggler lane's remaining chunks moves to the fastest
      lane at the next merge boundary.  In hub mode the handoff is
      hub-granular: a hub's remaining edges move **wholesale** and its
      ``pin_map`` entry moves with them, so pinning (one lane owns a hub
      at any time, per-hub stream order intact) survives the handoff.
      Handoff regroups chunks between merge points — equivalent to
      having dealt a different (equally valid) lane assignment up front,
      so results drift within the same staleness envelope as changing
      ``num_streams``; quality bounds survive (the merge algebra is
      exact), bit-reproducibility of the no-handoff drive does not.
    """
    if num_streams < 1:
        raise ValueError("num_streams must be >= 1")
    if isinstance(super_chunk, str):
        if super_chunk != "auto":
            raise ValueError(
                f"super_chunk must be >= 1 or 'auto', got {super_chunk!r}")
    elif super_chunk < 1:
        raise ValueError("super_chunk must be >= 1")
    shard = _SHARD_ALIASES.get(shard, shard)
    if shard not in SHARD_MODES:
        raise ValueError(f"unknown shard mode {shard!r}; one of {SHARD_MODES}")
    if on_lane_failure not in LANE_FAILURE_MODES:
        raise ValueError(f"unknown on_lane_failure {on_lane_failure!r}; "
                         f"one of {LANE_FAILURE_MODES}")
    if num_streams == 1 or stream.n_chunks <= 1:
        t0 = time.perf_counter()
        out = run_carry(stream, pc, *extras, carry=carry)
        _publish_stats(pc, IngestStats(
            num_streams=1, shard=shard, backend="sequential",
            super_chunk=super_chunk, schedule=(),
            lanes=(LaneStats(chunks=stream.n_chunks, edges=stream.n_edges,
                             merge_count=0,
                             wall_s=time.perf_counter() - t0),)))
        return out

    with spans.span("lanes.drive") as drive:
        ps = plan if plan is not None else ParallelEdgeStream(
            stream, num_streams, shard=shard, hub_threshold=hub_threshold)
        S = ps.num_streams
        backend = _resolve_backend(backend, S)
        wants_fault_path = (lane_injector is not None or straggler is not None
                            or carry_store is not None
                            or on_lane_failure != "raise")
        if wants_fault_path and backend != "threads":
            raise ValueError(
                "lane fault handling / straggler handoff / carry checkpoints "
                "run on the threads backend (host workers die "
                f"independently); got backend={backend!r}")
        base = pc.init() if carry is None else carry
        # per plan chunk: its parts, valid edges only (on the host, or on the
        # device on the threads backend)
        parts_by_chunk: dict = {}
        ctl = _CadenceController(pc, super_chunk)
        t_run = time.perf_counter()
        lane_chunks = [0] * S
        lane_edges = [0] * S
        lane_wall = [0.0] * S
        lane_dev: list[int | None] = [None] * S

        if backend in ("vmap", "shard_map"):
            # the lanes' per-edge extras come to the host once; every
            # super-step's (S, R, B) blocks are staged there
            host_extras = [spans.to_host(e) for e in extras]
            vstep = None
            if backend == "shard_map":
                mesh = mesh if mesh is not None else _streams_mesh(S)
                axis = mesh.axis_names[0]
                if mesh.shape[axis] != S:
                    raise ValueError(
                        f"shard_map backend needs a {S}-wide mesh axis, got "
                        f"{mesh.shape[axis]} (use backend='threads' or 'vmap' "
                        f"on hosts with fewer devices)")
                P = jax.sharding.PartitionSpec
                by_lane = jax.sharding.NamedSharding(mesh, P(axis))
                replicated = jax.sharding.NamedSharding(mesh, P())
                arrays = jax.device_put(
                    tuple(_split_consumer(pc)[0].values()), replicated)
                base = jax.device_put(base, replicated)
                lane_dev = [d.id for d in mesh.devices.flat]
            r0 = 0
            while r0 < ps.n_rounds:
                sc = ctl.next()
                rounds = range(r0, min(r0 + sc, ps.n_rounds))
                R = len(rounds)
                with spans.span("lanes.stage") as sp:
                    host_blk, ids = ps.block(rounds, *host_extras)
                    shares = _shares(pc, base, host_blk[2].sum(axis=1))
                    blk = (*shares, *host_blk)
                    if backend == "shard_map":
                        blk = sp.wait_for(jax.device_put(blk, by_lane))
                prev = base
                if backend == "shard_map":
                    step = _super_step(pc, mesh, axis, R, base, len(extras),
                                       len(shares))
                    base, parts = step(arrays, prev, *blk)
                else:
                    if vstep is None:  # one executable for every round
                        vstep = jax.jit(jax.vmap(
                            _lane_step(pc, len(shares))))
                    local = jax.tree_util.tree_map(
                        lambda x: jnp.broadcast_to(
                            jnp.asarray(x), (S,) + jnp.shape(jnp.asarray(x))),
                        prev)
                    per_round = []
                    for ri in range(R):
                        local, p = vstep(local, *shares,
                                         *(x[:, ri] for x in host_blk))
                        per_round.append(p)
                    base = pc.merge_stacked(local, prev)
                    # one program, one device
                    lane_dev = [_device_of(local)] * S
                    parts = (jnp.stack(per_round, axis=1) if pc.emits_parts
                             else None)
                ctl.observe(prev, base)
                _count_merge(pc, base, R)
                if pc.emits_parts:
                    parts = spans.to_host(parts)
                    for ri, row in enumerate(ids):
                        for s, cid in enumerate(row):
                            if cid is not None:
                                parts_by_chunk[cid] = parts[
                                    s, ri, :host_blk[2][s, ri]]
                r0 += sc
            if backend == "shard_map":
                # the merged carry leaves the mesh: what the job does next
                # runs on one device, as after a sequential drive
                base = jax.device_put(base, jax.devices()[0])
        elif backend == "threads":
            # S host workers fold their sub-streams concurrently through the
            # shared compiled step (execution releases the GIL); chunk staging
            # is serialized under one lock — the out-of-core stream's budget
            # accounting and staging buffers are not thread-safe, and staging
            # is a small fraction of a chunk's scan cost.
            stage_lock = threading.Lock()
            # lanes are mutable here: straggler handoff re-deals remaining
            # chunks between merge boundaries (the sharding plan's own lists
            # stay pristine in the chunk-granular modes; hub mode re-registers
            # synthetic chunks, pin map updated in place)
            lanes = [list(lane) for lane in ps.lanes]
            pos = [0] * S  # per-lane cursor into its (possibly re-dealt) list
            edges_done = 0  # edges committed through merges (checkpoint key)
            consumer = (carry_consumer if carry_consumer is not None
                        else f"parallel:{type(pc).__name__}")
            store_cfg = dict(carry_config or {})
            store_cfg.setdefault("super_chunk", str(super_chunk))
            store_cfg.setdefault("shard", shard)

            def lane_fold(lane_id, chunks, start, inject, shares):
                lane_pc = pc.for_lane(shares[0][lane_id]) if shares else pc
                local = start
                t0 = time.perf_counter()
                for cid in chunks:
                    if inject is not None:
                        inject.check(lane_id, cid)
                    with stage_lock:
                        ch = ps.chunk_for(cid, *extras)
                    local, parts = lane_pc.step_chunk(
                        local, ch.src, ch.dst, jnp.int32(ch.n_valid),
                        *ch.extras)
                    if parts is not None:
                        parts_by_chunk[cid] = parts[: ch.n_valid]
                lane_dev[lane_id] = _device_of(local)
                return local, time.perf_counter() - t0

            def save_base(carry_val):
                if carry_store is not None:
                    carry_store.save(carry_val, consumer=consumer,
                                     config=store_cfg, stream_pos=edges_done)

            def restore_base():
                if carry_store is None:
                    return base  # in-memory merge base == last commit point
                restored, _ = carry_store.load(like=base, consumer=consumer,
                                               config=store_cfg,
                                               max_stream_pos=edges_done)
                return restored

            save_base(base)  # a lane can die before the first merge commits
            sc_index = 0
            with ThreadPoolExecutor(max_workers=S) as ex:
                while any(pos[s] < len(lanes[s]) for s in range(S)):
                    sc = ctl.next()
                    batches = [lanes[s][pos[s]:pos[s] + sc] for s in range(S)]
                    shares = _shares(pc, base, [
                        sum(ps.chunk_n_valid(c) for c in b) for b in batches])
                    futs = [ex.submit(lane_fold, s, batches[s], base,
                                      lane_injector, shares)
                            for s in range(S)]
                    locals_: list = [None] * S
                    times = [0.0] * S
                    failed: list[int] = []
                    for s, f in enumerate(futs):
                        try:
                            locals_[s], times[s] = f.result()
                        except Exception as e:  # noqa: BLE001 — lane death
                            if on_lane_failure != "replay":
                                raise
                            log.warning("ingest lane %d died mid-super-chunk "
                                        "(%s); replaying its range", s, e)
                            failed.append(s)
                    for s in failed:
                        # replay the dead lane's chunk range from the last
                        # committed base into a surviving worker — the merge
                        # below can't tell the difference (bit-identical)
                        locals_[s], times[s] = ex.submit(
                            lane_fold, s, batches[s], restore_base(),
                            None, shares).result()
                    prev = base
                    base = pc.merge(locals_, base=prev)
                    ctl.observe(prev, base)
                    _count_merge(pc, base, max(len(b) for b in batches))
                    edges_done += sum(ps.chunk_n_valid(cid)
                                      for b in batches for cid in b)
                    for s in range(S):
                        pos[s] += len(batches[s])
                        lane_chunks[s] += len(batches[s])
                        lane_edges[s] += sum(ps.chunk_n_valid(c)
                                             for c in batches[s])
                        lane_wall[s] += times[s]
                    save_base(base)
                    if straggler is not None:
                        for s in range(S):
                            if batches[s]:
                                # per-chunk time: lane *speed*, not workload
                                straggler.record(sc_index,
                                                 times[s] / len(batches[s]),
                                                 shard=s)
                        _handoff_lanes(ps, lanes, pos, straggler)
                    sc_index += 1
        else:
            raise ValueError(f"unknown backend {backend!r}")

        if backend != "threads":  # lanes execute as one program per round
            wall = time.perf_counter() - t_run
            for s in range(S):
                lane_chunks[s] = len(ps.lanes[s])
                lane_edges[s] = sum(ps.chunk_n_valid(c) for c in ps.lanes[s])
                lane_wall[s] = wall
        merges = len(ctl.schedule)
        _publish_stats(pc, IngestStats(
            num_streams=S, shard=shard, backend=backend,
            super_chunk=super_chunk,
            schedule=tuple(ctl.schedule),
            lanes=tuple(LaneStats(chunks=lane_chunks[s], edges=lane_edges[s],
                                  merge_count=merges, wall_s=lane_wall[s],
                                  device=lane_dev[s])
                        for s in range(S))))

        result = pc.finalize(base)
        parts = None
        if parts_by_chunk:
            first = next(iter(parts_by_chunk.values()))
            out = np.empty((stream.n_edges,), first.dtype)
            for cid, p in parts_by_chunk.items():
                out[ps.chunk_positions(cid)] = spans.to_host(p)
            parts = stream.scatter_back(jnp.asarray(out))
        return drive.wait_for((parts, result))


def _shares(pc, base, demand) -> tuple:
    """``(shares,)``, the consumer's per-lane shares for a super-step whose
    lanes fold ``demand`` edges each from ``base``, or ``()`` when it
    bounds nothing (:meth:`PartitionerCarry.lane_shares`)."""
    shares = pc.lane_shares(base, demand)
    return () if shares is None else (shares,)


def _lane_step(pc, n_shares):
    """:func:`_mask_inactive_step` for one lane, called as ``step(carry,
    *shares, src, dst, n_valid, *extras)``: with a share, the consumer as
    that lane folds it (:meth:`PartitionerCarry.for_lane`)."""

    def step(carry, *args):
        lane_pc = pc.for_lane(args[0]) if n_shares else pc
        return _mask_inactive_step(lane_pc)(carry, *args[n_shares:])

    return step


def _count_merge(pc, carry, rounds: int) -> None:
    """The lane counters of one merge: ``lanes.merges``, ``lanes.rounds``
    (chunks each lane folded since the last merge) and
    ``lanes.merge_bytes`` (what each lane hands to the merge)."""
    spans.count("lanes.merges")
    spans.count("lanes.rounds", rounds)
    spans.count("lanes.merge_bytes", pc.merge_bytes(carry))


def _handoff_lanes(ps, lanes, pos, straggler):
    """Live lane-range handoff at a merge boundary: ask the monitor's
    :meth:`rebalance_plan` what tail cut each straggler lane should give
    up, and physically move those chunk ids to the receiving lane's
    queue.  Chunks already folded (before ``pos``) never move.  In hub
    mode the cut is re-sliced at whole-hub boundaries — every remaining
    edge of a moved hub moves together and the plan's ``pin_map`` is
    updated — so hub pinning survives the handoff."""
    ranges = [(pos[s], len(lanes[s])) for s in range(len(lanes))]
    plan = straggler.rebalance_plan(ranges)
    if plan == ranges:
        return
    if ps.shard == "hub":
        _handoff_lanes_hub(ps, lanes, pos, ranges, plan)
        return
    moved: list[int] = []
    receiver = None
    for s, ((_, hi_old), (_, hi_new)) in enumerate(zip(ranges, plan)):
        if hi_new < hi_old:
            cut = hi_old - hi_new
            moved.extend(lanes[s][len(lanes[s]) - cut:])
            del lanes[s][len(lanes[s]) - cut:]
        elif hi_new > hi_old:
            receiver = s
    if receiver is not None and moved:
        # keep stream order within the receiving lane's tail
        lanes[receiver].extend(sorted(moved))
        log.info("straggler handoff: %d chunk(s) moved to lane %d",
                 len(moved), receiver)


def _handoff_lanes_hub(ps, lanes, pos, ranges, plan):
    """Hub-granular handoff: re-slice each straggler's remaining *edges*
    at a whole-hub boundary (a hub edge moves iff its hub's first
    remaining occurrence is past the boundary — so a hub's remaining
    edges either all stay or all move, in stream order either way),
    re-register both sides as fresh synthetic chunks, and move the moved
    hubs' ``pin_map`` entries to the receiver."""
    B = ps.stream.chunk_size
    receiver = None
    for s, ((_, hi_old), (_, hi_new)) in enumerate(zip(ranges, plan)):
        if hi_new > hi_old:
            receiver = s
    if receiver is None:
        return
    for s, ((_, hi_old), (_, hi_new)) in enumerate(zip(ranges, plan)):
        cut = hi_old - hi_new
        if cut <= 0 or s == receiver:
            continue
        rest = lanes[s][pos[s]:]
        if not rest:
            continue
        positions = np.concatenate([ps._chunk_pos[c] for c in rest])
        boundary = max(len(positions) - cut * B, 0)
        pv = ps._pin_vertex[positions]
        idx = np.arange(len(positions))
        move = (pv < 0) & (idx >= boundary)
        hub_ids, first = np.unique(pv[pv >= 0], return_index=True)
        # first occurrence per hub within the remaining edges: positions
        # are ascending, so np.unique's first index is the earliest
        hub_first = np.full(len(positions), -1, np.int64)
        if hub_ids.size:
            starts = np.flatnonzero(pv >= 0)
            # map each hub edge to its hub's first remaining index
            order = np.argsort(pv[starts], kind="stable")
            # simpler: dict lookup (hub counts are small by construction)
            first_of = {int(h): int(np.flatnonzero(pv == h)[0])
                        for h in hub_ids}
            for i in starts:
                move[i] = first_of[int(pv[i])] >= boundary
        keep_pos = positions[~move]
        move_pos = positions[move]
        if not move_pos.size:
            continue
        lanes[s] = lanes[s][:pos[s]] + ps._register_chunks(keep_pos)
        lanes[receiver].extend(ps._register_chunks(move_pos))
        moved_hubs = np.unique(pv[move & (pv >= 0)])
        for h in moved_hubs:
            ps.pin_map[int(h)] = receiver
        ps._lane_of_pos[move_pos] = receiver
        log.info("straggler handoff (hub): %d edge(s), %d hub pin(s) "
                 "moved lane %d → %d", len(move_pos), len(moved_hubs), s,
                 receiver)


#: compiled shard_map super-steps, shared by every drive whose consumer
#: differs only in its per-job arrays (see :func:`_super_step`)
_SUPER_STEPS: dict = {}
_SUPER_STEPS_MAX = 64


def _split_consumer(pc):
    """``(arrays, key)``: the consumer's per-job arrays (its attributes
    that are arrays: Alg. 1's degree table, Alg. 3's cluster→partition
    table and capacity) and a key of everything else it holds, its class
    and that class's step and merge.  ``key`` is None when something it
    holds cannot be hashed."""
    arrays, rest = {}, []
    for name, v in sorted(vars(pc).items()):
        if isinstance(v, (jax.Array, np.ndarray)):
            arrays[name] = v
        else:
            rest.append((name, v))
    cls = type(pc)
    key = (cls, cls.step_chunk, cls.merge_collective, tuple(rest))
    try:
        hash(key)
    except TypeError:
        return arrays, None
    return arrays, key


def _super_step(pc, mesh, axis, R, base, n_ex, n_shares=0):
    """The jitted shard_map super-step of R rounds for ``pc``, called as
    ``step(arrays, base, *shares, src, dst, nv, *extras)`` with ``arrays``
    the consumer's per-job arrays (:func:`_split_consumer`).  One step
    serves every later drive of a consumer that differs only in those
    arrays, so a job whose shapes an earlier job had compiles nothing."""
    arrays, key = _split_consumer(pc)
    if key is not None:
        key = (key, mesh, axis, R, n_ex, n_shares,
               jax.tree_util.tree_structure(base))
        step = _SUPER_STEPS.get(key)
        if step is not None:
            return step
    step = _make_super_step(pc, tuple(arrays), mesh, axis, R, n_ex,
                            n_shares)
    if key is not None:
        if len(_SUPER_STEPS) >= _SUPER_STEPS_MAX:
            _SUPER_STEPS.pop(next(iter(_SUPER_STEPS)))
        _SUPER_STEPS[key] = step
    return step


def _make_super_step(pc, names, mesh, axis, R, n_ex, n_shares=0):
    """Build the jitted shard_map super-step for R rounds: each device
    folds its lane's R chunks from the replicated base carry, then the
    carries are merged by one collective per field.  ``names`` are the
    consumer's array attributes, passed in as the step's first argument
    (replicated) rather than captured.  Returns a callable ``(arrays,
    base, *shares (S, ...), src (S,R,B), dst, nv (S,R), *extras) ->
    (merged carry (replicated), parts (S, R, B))``; the device trace
    names its program ``lanes_super_step``."""
    P = jax.sharding.PartitionSpec
    lane = P(axis)
    # the step keeps no job's arrays alive
    template = copy.copy(pc)
    for name in names:
        setattr(template, name, None)

    def lanes_super_step(arrays, base_carry, *args):
        job = copy.copy(template)
        for name, a in zip(names, arrays):
            setattr(job, name, a)
        shares = [x[0] for x in args[:n_shares]]
        src, dst, nv, *exs = args[n_shares:]
        step = _lane_step(job, n_shares)
        local = base_carry
        parts_rounds = []
        for r in range(R):
            local, parts = step(
                local, *shares, src[0, r], dst[0, r], nv[0, r],
                *[e[0, r] for e in exs])
            if job.emits_parts:
                parts_rounds.append(parts)
        merged = job.merge_collective(local, base_carry, axis)
        if parts_rounds:
            return merged, jnp.stack(parts_rounds)[None]
        return merged, jnp.zeros((1, 1, 1), jnp.int32)

    return jax.jit(jax.shard_map(
        lanes_super_step, mesh=mesh,
        in_specs=(P(), P()) + (lane,) * (n_shares + 3 + n_ex),
        # every lane holds the same merged carry after the collectives
        out_specs=(P(), lane),
        # the megakernels' interpret mode cannot carry varying-axis types
        # through its grid loop, so the body is not vma-checked
        check_vma=False,
    ))
