"""Plain S5P under S-way parallel ingest, the reference for ``s5p-lanes``
cells.

Written from the semantics the repository declares for parallel ingest
(the module docs of ``streaming/parallel.py`` and ``streaming/carry.py``)
and from the paper (arXiv:2402.18304, Alg. 1-3).  It imports nothing of
the program.  Alg. 1, the cluster statistics with their count-min sketch,
the float32 game and the placement rule of Alg. 3 are those of the
one-stream reference, ``reference/s5p.py``; what S loaders change is
written here:

- **Lane plan** (shard ``range``, the file-split layout): of the C chunks
  of the stream in arrival order, lane s folds chunks
  ``[s * q, (s + 1) * q)``, ``q = ceil(C / S)``, in order.  A lane that
  has no chunk left in a round folds nothing.
- **Merge laws.**  A merge takes the carries every lane folded from one
  base, the last merge's result (the empty state at first).  A SUM or
  COUNTED field becomes ``base + sum over lanes of (lane - base)``; on the
  vertex-to-cluster tables ``v2c_h`` and ``v2c_t`` a vertex takes the
  value of the lowest lane whose value there changed, and keeps the base
  where none did.  A vertex then counts as assigned where its membership
  count is positive and its id lies below the merged id counter.
- **Cadence** (``auto``): Alg. 1 and the Theta sketch carry no per-edge
  answer, so every lane folds all its chunks in isolation and they merge
  once, at the end.  Alg. 3 merges after its first round; after each
  merge it takes the occupancy contest, the share of partition loads
  whose zero/nonzero state flipped among those nonzero after the merge:
  above 0.05 the next super-step folds 1 round, else twice the last, at
  most 32, and never past the rounds left.  Each lane places its edges
  against the merged load plus its own placements since.
- **Capacity under lanes.**  No partition may end above
  ``L = ceil(tau * E / k)``, as in one stream.  Before each Alg. 3
  super-step the room of every partition, ``L - load`` (at least 0), is
  dealt to the lanes in proportion to the edges of the chunks each folds
  in it (self-loops included), rounded down; then, lane by lane in order,
  a lane left with fewer slots than those edges takes what it lacks from
  the room still undealt, partitions in ascending order.  A lane's limit
  on a partition is the merged load plus its slots there.  A lane places
  an edge as Alg. 3 does with those limits in the place of ``L``: both
  endpoint partitions at their limit, the first (head) or last (tail)
  partition under its limit; one at its limit, the other; else the less
  loaded, ``P_u`` on a tie.
- **Cluster ids.**  Every lane allocates head and tail ids from the merge
  base's counters, so clusters that different lanes grew can share an
  id after the merge (the merged counters are the sums, so every id is
  in range).  That is the defined result of the merge laws, not repaired
  here; compaction then numbers the ids in use.
- **Theta.**  The sketch is linear and its lanes merge by sum, so it
  equals the one-stream sketch over the same pairs.
- **Touch-up** (S > 1 and more than one cluster): a cluster is contested
  when the edges that touch it (as either endpoint's cluster) came
  through two or more lanes, and it may move when it is contested and of
  nonzero size.  From the game's assignment, a masked game of at most
  ``refine_rounds`` rounds runs with seed + 1: leaders (head clusters),
  then followers, each in the batch windows that hold a movable cluster,
  only movable clusters moving, the window's acceptance draws keyed by
  the window's index.  The edges of every cluster that moved are lifted
  out of the load and placed again, in arrival order, against the new
  assignment.

``dtype="bfloat16"`` computes both games in bfloat16, the step below the
float32 the configuration states: that is the control, which the
comparison has to reject.
"""

from __future__ import annotations

import importlib.util
import math
import sys
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np


def _one_stream():
    """``reference/s5p.py``, loaded once under the harness's module name."""
    name = "bench_reference_s5p"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            name, Path(__file__).with_name("s5p.py"))
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


s5p = _one_stream()

# The comparison: every edge's partition id, exactly.
LIMITS = {"parts_mismatch": 0}

# Program results kept for diagnostics only.
CAPTURES = {"clusters": "repro.core.clustering:compact_clusters"}

CONTEST_WARM = 0.05
CADENCE_CAP = 32


# ------------------------------------------------------------- lane plan
def lane_chunks(E, chunk, S):
    """Each lane's chunk ids, contiguous ranges of ceil(C / S)."""
    C = max(-(-E // chunk), 1)
    S = max(1, min(S, C))
    q = -(-C // S)
    return [list(range(s * q, min((s + 1) * q, C))) for s in range(S)]


def edge_lanes(E, chunk, lanes):
    """The lane that folds each edge, in arrival order."""
    of_chunk = np.empty(max(-(-E // chunk), 1), np.int64)
    for s, ids in enumerate(lanes):
        of_chunk[ids] = s
    return of_chunk[np.arange(E) // chunk]


# ------------------------------------------------------------ Alg. 1 lanes
def cluster_lanes(src, dst, deg, xi, kappa, chunk, lanes):
    """Alg. 1 folded by each lane from the empty state, merged once."""
    E = len(src)
    v2c_h = np.full(len(deg), -1, np.int64)
    v2c_t = np.full(len(deg), -1, np.int64)
    taken_h = np.zeros(len(deg), bool)
    taken_t = np.zeros(len(deg), bool)
    next_h = next_t = 0
    for ids in lanes:
        if not ids:
            continue
        lo, hi = ids[0] * chunk, min((ids[-1] + 1) * chunk, E)
        h, t = s5p.cluster(src[lo:hi], dst[lo:hi], deg, xi, kappa)
        # a lane's ids are its allocation count: one fresh id per vertex
        next_h += int(np.count_nonzero(h >= 0))
        next_t += int(np.count_nonzero(t >= 0))
        # the lowest lane whose value changed from the base (-1) wins
        win_h = (h >= 0) & ~taken_h
        win_t = (t >= 0) & ~taken_t
        v2c_h[win_h] = h[win_h]
        v2c_t[win_t] = t[win_t]
        taken_h |= h >= 0
        taken_t |= t >= 0
    # membership counts: head and tail incidences of the edges (SUM)
    s = np.asarray(src)
    d = np.asarray(dst)
    ok = s != d
    dg = np.asarray(deg)
    head = ok & (dg[s] > xi) & (dg[d] > xi)
    tail = ok & ~head
    cnt_h = (np.bincount(s[head], minlength=len(deg))
             + np.bincount(d[head], minlength=len(deg)))
    cnt_t = (np.bincount(s[tail], minlength=len(deg))
             + np.bincount(d[tail], minlength=len(deg)))
    v2c_h = np.where((cnt_h > 0) & (v2c_h < next_h), v2c_h, -1)
    v2c_t = np.where((cnt_t > 0) & (v2c_t < next_t), v2c_t, -1)
    return v2c_h, v2c_t


# ------------------------------------------------------------ Alg. 3 lanes
def _place(edges, load, pu, pv, head, k, limit, parts):
    """Alg. 3 for ``edges`` in order, from ``load`` (a list, updated),
    under the per-partition ``limit`` (a list)."""
    for e in edges:
        a, b = pu[e], pv[e]
        full_a, full_b = load[a] >= limit[a], load[b] >= limit[b]
        if full_a and full_b:
            room = [p for p in range(k) if load[p] < limit[p]]
            if room:
                p = room[0] if head[e] else room[-1]
            else:
                p = load.index(min(load))
        elif full_a or full_b:
            p = b if full_a else a
        else:
            p = b if load[a] > load[b] else a
        load[p] += 1
        parts[e] = p


def lane_limits(load, demand, max_load):
    """Each lane's limit on each partition for one super-step: the merged
    ``load`` plus the lane's slots of the room left under ``max_load``,
    dealt by ``demand``, the edges each lane folds in it."""
    k, S = len(load), len(demand)
    room = [max(max_load - x, 0) for x in load]
    total = max(sum(demand), 1)
    slots = [[room[p] * demand[s] // total for p in range(k)]
             for s in range(S)]
    left = [room[p] - sum(slots[s][p] for s in range(S)) for p in range(k)]
    for s in range(S):
        lack = demand[s] - sum(slots[s])
        for p in range(k):
            take = min(max(lack, 0), left[p])
            slots[s][p] += take
            left[p] -= take
            lack -= take
    return [[load[p] + slots[s][p] for p in range(k)] for s in range(S)]


def place_lanes(cu, cv, head, valid, c2p, k, max_load, chunk, lanes):
    """Alg. 3 by S lanes under the ``auto`` cadence; returns (parts, load,
    the rounds of each super-step)."""
    E = len(cu)
    pu = c2p[np.maximum(cu, 0)].tolist()
    pv = c2p[np.maximum(cv, 0)].tolist()
    head = head.tolist()
    parts = [-1] * E
    load = np.zeros(k, np.int64)
    n_rounds = max(len(ids) for ids in lanes)
    cadence, r0, schedule = 1, 0, []
    while r0 < n_rounds:
        R = min(cadence, n_rounds - r0)
        ranges = [[(c * chunk, min((c + 1) * chunk, E))
                   for c in ids[r0:r0 + R]] for ids in lanes]
        limits = lane_limits(load.tolist(),
                             [sum(hi - lo for lo, hi in sp) for sp in ranges],
                             max_load)
        deltas = np.zeros(k, np.int64)
        for sp, limit in zip(ranges, limits):
            local = load.tolist()
            for lo, hi in sp:
                edges = [e for e in range(lo, hi) if valid[e]]
                _place(edges, local, pu, pv, head, k, limit, parts)
            deltas += np.asarray(local, np.int64) - load
        merged = load + deltas
        flipped = np.count_nonzero((load != 0) != (merged != 0))
        contest = flipped / max(int(np.count_nonzero(merged)), 1)
        load = merged
        schedule.append(R)
        cadence = 1 if contest > CONTEST_WARM else min(2 * cadence,
                                                       CADENCE_CAP)
        r0 += R
    return np.asarray(parts, np.int32), load, schedule


# ------------------------------------------------------ touch-up game
@partial(jax.jit, static_argnames=("C", "k", "bs", "max_rounds"))
def _masked_game(sizes, pa, pb, pw, assign0, delta, accept, seed, leader,
                 movable, windows, *, C, k, bs, max_rounds):
    """Best response of the movable clusters only, leaders then
    followers, batch window by batch window; the others hold their
    partition and shape the costs (paper Eq. 6, as in ``s5p._game``)."""
    dt = sizes.dtype
    degs = s5p._degrees(pw, pa, pb, C)
    cid = jnp.arange(C, dtype=jnp.int32)
    inv_k = 1.0 / k
    dk = delta * inv_k
    a = jnp.minimum(pa, C)
    b = jnp.minimum(pb, C)
    key0 = jax.random.PRNGKey(seed)

    def respond(assign, active, key):
        ext = jnp.concatenate([assign, jnp.zeros((1,), jnp.int32)])
        w = jnp.zeros((C + 1, k), dt)
        w = w.at[a, ext[b]].add(pw)
        w = w.at[b, ext[a]].add(pw)
        w = w[:C]
        psize = jax.ops.segment_sum(sizes, assign, num_segments=k)
        onehot = jax.nn.one_hot(assign, k, dtype=dt)
        hyp = psize[None, :] + sizes[:, None] * (1.0 - onehot)
        cost = (dk * sizes[:, None] * hyp
                + (degs[:, None] - w + sizes[:, None]) * inv_k)
        cur = jnp.take_along_axis(cost, assign[:, None], axis=1)[:, 0]
        better = jnp.min(cost, axis=1) < cur
        best = jnp.where(better, jnp.argmin(cost, axis=1).astype(jnp.int32),
                         assign)
        improves = active & (best != assign) & better
        lucky = jax.random.uniform(key, (C,)) < accept
        return jnp.where(improves & lucky, best, assign), jnp.any(improves)

    def stage(assign, wanted, key, role):
        def body(i, carry):
            assign, wanted = carry
            win = windows[i]
            active = (cid >= win * bs) & (cid < win * bs + bs) & role
            assign, w = respond(assign, active, jax.random.fold_in(key, win))
            return assign, wanted | w
        return jax.lax.fori_loop(0, windows.shape[0], body, (assign, wanted))

    def one_round(state):
        assign, _, rounds = state
        k1, k2 = jax.random.split(jax.random.fold_in(key0, rounds))
        assign, wanted = stage(assign, jnp.bool_(False), k1,
                               leader & movable)
        assign, wanted = stage(assign, wanted, k2, ~leader & movable)
        return assign, wanted, rounds + 1

    state = one_round((assign0, jnp.bool_(True), jnp.int32(0)))
    assign, _, _ = jax.lax.while_loop(
        lambda s: s[1] & (s[2] < max_rounds), one_round, state)
    return assign


def touch_up(cu, cv, valid, sizes, pa, pb, pw_u32, n_head, C, k, seed,
             params, dtype, c2p, lane_of_edge, S):
    """The touch-up's assignment, or ``c2p`` when no cluster may move."""
    lanes_touching = np.zeros(C, np.int64)
    for s in range(S):
        mine = valid & (lane_of_edge == s)
        seen = (np.bincount(cu[mine & (cu >= 0)], minlength=C)
                + np.bincount(cv[mine & (cv >= 0)], minlength=C)) > 0
        lanes_touching += seen
    movable = (lanes_touching >= 2) & (sizes > 0)
    if not movable.any():
        return c2p
    dt = jnp.dtype(dtype)
    sizes_d = jnp.asarray(sizes.astype(np.float32)).astype(dt)
    pw = jnp.asarray(pw_u32).astype(jnp.float32).astype(dt)
    pa_d = jnp.asarray(pa.astype(np.int32))
    pb_d = jnp.asarray(pb.astype(np.int32))
    degs = s5p._degrees(pw, pa_d, pb_d, C)
    delta = k * jnp.sum(degs + sizes_d) / jnp.maximum(
        jnp.square(jnp.sum(sizes_d)), 1.0)
    bs = max(16, min(int(params["game_batch_size"]), C // 8))
    windows = np.unique(np.flatnonzero(movable) // bs).astype(np.int32)
    out = _masked_game(
        sizes_d, pa_d, pb_d, pw, jnp.asarray(c2p, jnp.int32), delta,
        jnp.float32(params["game_accept_prob"]), seed + 1,
        jnp.asarray(np.arange(C) < n_head), jnp.asarray(movable),
        jnp.asarray(windows), C=C, k=k, bs=bs,
        max_rounds=int(params["refine_rounds"]))
    return np.asarray(out)


# --------------------------------------------------------------- the whole
def partition(src, dst, n_vertices, k, seed, params, *, dtype="float32"):
    """(parts, internals) for one job on (src, dst) in arrival order."""
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    E = src.size
    chunk = int(params["chunk_size"])
    S = int(params["num_streams"])
    if params["shard"] != "range" or params["super_chunk"] != "auto":
        raise ValueError("this reference states shard 'range' with "
                         "super_chunk 'auto'")
    lanes = lane_chunks(E, chunk, S)
    deg = (np.bincount(src, minlength=n_vertices)
           + np.bincount(dst, minlength=n_vertices))
    xi = min(int(params["beta"] * (2.0 * E / max(n_vertices, 1))),
             s5p._INT32_MAX - 1)
    kappa = max(int(math.ceil(2.0 * E / k)), 2)
    v2c_h, v2c_t = cluster_lanes(src.tolist(), dst.tolist(), deg.tolist(),
                                 xi, kappa, chunk, lanes)
    out_h, out_t, n_head, C = s5p.compact(v2c_h, v2c_t)
    cu, cv, head, sizes, pa, pb, pw = s5p.statistics(
        src, dst, deg, xi, out_h, out_t, C, seed, params["cms_epsilon"],
        params["cms_nu"])
    c2p = s5p.game(sizes, pa, pb, pw, n_head, C, k, seed, params, dtype)
    max_load = int(math.ceil(params["tau"] * E / k))
    valid = src != dst
    parts, load, schedule = place_lanes(cu, cv, head, valid, c2p, k,
                                        max_load, chunk, lanes)
    c2p_final = c2p
    if (params["touch_up"] and params["refine_rounds"] > 0 and C > 1
            and len(lanes) > 1):
        c2p_final = touch_up(cu, cv, valid, sizes, pa, pb, pw, n_head, C,
                             k, seed, params, dtype, c2p,
                             edge_lanes(E, chunk, lanes), len(lanes))
        moved = c2p_final != c2p
        if moved.any():
            again = np.flatnonzero(valid & (moved[np.maximum(cu, 0)]
                                            | moved[np.maximum(cv, 0)]))
            load = load.copy()
            np.subtract.at(load, parts[again], 1)
            local = load.tolist()
            out = parts.tolist()
            _place(again.tolist(), local,
                   c2p_final[np.maximum(cu, 0)].tolist(),
                   c2p_final[np.maximum(cv, 0)].tolist(), head.tolist(), k,
                   [max_load] * k, out)
            parts = np.asarray(out, np.int32)
    return parts, {"v2c_h": out_h, "v2c_t": out_t, "n_clusters": C,
                   "n_head": n_head, "c2p": c2p_final,
                   "alg3_schedule": schedule}


def diagnose(captured: dict, internals: dict) -> dict:
    """Where the program's Alg. 1 parts from the reference (information
    only; ``correct`` is decided by the parts)."""
    res = captured.get("clusters")
    if res is None or not hasattr(res, "v2c_h"):
        return {}
    return {"alg1_vertices_differ": int(
        np.count_nonzero(np.asarray(res.v2c_h) != internals["v2c_h"])
        + np.count_nonzero(np.asarray(res.v2c_t) != internals["v2c_t"]))}
