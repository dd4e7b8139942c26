"""Plain S5P (arXiv:2402.18304, Alg. 1-3), the reference for S5P cells.

Written from the paper's listings and the semantics the repository fixes
for them (head/tail split at xi = beta * avg degree, volume cap
kappa = 2|E|/k, CMS-counted cluster adjacency, damped two-stage best
response in batches, capacity ceil(tau |E| / k)).  It imports nothing of
the program and takes nothing the program made.

- Degrees, Alg. 1 (clustering), the cluster statistics with their
  count-min sketch, and Alg. 3 (placement) are integer arithmetic: they
  run here in plain Python and numpy, sequentially, one edge at a time.
- The game is floating point.  Its payoffs are computed on the device in
  ``jax.numpy`` with the operations in the order the algorithm states
  them, because XLA:TPU's f32 division differs from IEEE division and a
  host reference would disagree with any correct program there.  Its
  acceptance draws are ``jax.random`` draws from the job's seed, as the
  algorithm specifies.

``dtype="bfloat16"`` computes the game in bfloat16, the step below the
float32 the configuration states: that is the control, which the
comparison has to reject.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

# The comparison: every edge's partition id, exactly.
LIMITS = {"parts_mismatch": 0}

# Program results kept for diagnostics only: the compacted clusters (Alg. 1)
# and the game's assignment.  They never enter the reference.
CAPTURES = {
    "clusters": "repro.core.clustering:compact_clusters",
    "game": "repro.core.game:run_game",
}

_INT32_MAX = 2**31 - 1
_GOLDEN = 0x9E3779B1
_MIX1 = 0x85EBCA6B
_MIX2 = 0xC2B2AE35


# ------------------------------------------------------------------ Alg. 1
def cluster(src, dst, deg, xi, kappa):
    """Alg. 1 over the stream: the raw (v2c_h, v2c_t) cluster ids."""
    n = len(deg)
    v2c_h = [-1] * n
    v2c_t = [-1] * n
    vol_h = [0] * (n + 1)
    vol_t = [0] * (n + 1)
    ld = [0] * n
    next_h = next_t = 0
    for u, v in zip(src, dst):
        if u == v:
            continue
        du, dv = deg[u], deg[v]
        if du > xi and dv > xi:  # head edge: global-degree volumes
            cu = v2c_h[u]
            if cu < 0:
                cu = next_h
                next_h += 1
                v2c_h[u] = cu
                vol_h[cu] += du
            cv = v2c_h[v]
            if cv < 0:
                cv = next_h
                next_h += 1
                v2c_h[v] = cv
                vol_h[cv] += dv
            if cu != cv and vol_h[cu] < kappa and vol_h[cv] < kappa:
                if vol_h[cu] - du <= vol_h[cv] - dv:  # tie: u moves
                    mover, ci, cj, di = u, cu, cv, du
                else:
                    mover, ci, cj, di = v, cv, cu, dv
                if vol_h[cj] + di < kappa:
                    vol_h[cj] += di
                    vol_h[ci] -= di
                    v2c_h[mover] = cj
        else:  # tail edge: local-degree volumes
            tu = v2c_t[u]
            if tu < 0:
                tu = next_t
                next_t += 1
                v2c_t[u] = tu
            tv = v2c_t[v]
            if tv < 0:
                tv = next_t
                next_t += 1
                v2c_t[v] = tv
            vol_t[tu] += 1
            vol_t[tv] += 1
            ld[u] += 1
            ld[v] += 1
            if tu != tv and vol_t[tu] < kappa and vol_t[tv] < kappa:
                if vol_t[tu] <= vol_t[tv]:  # tie: u moves
                    mover, ci, cj = u, tu, tv
                else:
                    mover, ci, cj = v, tv, tu
                moved = ld[mover]
                vol_t[cj] += moved
                vol_t[ci] -= moved
                v2c_t[mover] = cj
    return np.asarray(v2c_h, np.int64), np.asarray(v2c_t, np.int64)


def compact(v2c_h, v2c_t):
    """Dense ids: head clusters [0, n_head), tail clusters after them, each
    in the order of their raw ids."""
    used_h = np.unique(v2c_h[v2c_h >= 0])
    used_t = np.unique(v2c_t[v2c_t >= 0])
    out_h = np.full(v2c_h.shape, -1, np.int64)
    out_t = np.full(v2c_t.shape, -1, np.int64)
    out_h[v2c_h >= 0] = np.searchsorted(used_h, v2c_h[v2c_h >= 0])
    out_t[v2c_t >= 0] = (np.searchsorted(used_t, v2c_t[v2c_t >= 0])
                         + used_h.size)
    return out_h, out_t, int(used_h.size), int(used_h.size + used_t.size)


# ------------------------------------------------- cluster statistics + CMS
def _avalanche(h):
    h = h ^ (h >> np.uint32(16))
    h = h * np.uint32(_MIX1)
    h = h ^ (h >> np.uint32(13))
    h = h * np.uint32(_MIX2)
    return h ^ (h >> np.uint32(16))


def _pair_key(a, b):
    a = a.astype(np.uint32)
    b = b.astype(np.uint32)
    return _avalanche((np.minimum(a, b) * np.uint32(_GOLDEN))
                      ^ np.maximum(a, b))


def statistics(src, dst, deg, xi, out_h, out_t, C, seed, epsilon, nu):
    """Per-edge clusters, cluster sizes, and the adjacent cluster pairs
    with their count-min-sketch counts (paper §4.3-4.4)."""
    head = (deg[src] > xi) & (deg[dst] > xi)
    cu = np.where(head, out_h[src], out_t[src])
    cv = np.where(head, out_h[dst], out_t[dst])
    valid = src != dst
    internal = valid & (cu == cv)
    boundary = valid & (cu != cv)
    # sizes: an internal edge counts 1, a boundary edge 1/2 on each side
    sizes = (np.bincount(cu[internal], minlength=C)
             + 0.5 * np.bincount(cu[boundary], minlength=C)
             + 0.5 * np.bincount(cv[boundary], minlength=C))
    # adjacency: every pair of the two endpoints' memberships, head and tail
    alt_u = np.where(head, out_t[src], out_h[src])
    alt_v = np.where(head, out_t[dst], out_h[dst])
    lo, hi = [], []
    for a, b, ok in ((cu, cv, valid), (alt_u, cv, valid & (alt_u >= 0)),
                     (cu, alt_v, valid & (alt_v >= 0))):
        ok = ok & (a != b) & (a >= 0) & (b >= 0)
        lo.append(np.minimum(a, b)[ok])
        hi.append(np.maximum(a, b)[ok])
    lo = np.concatenate(lo)
    hi = np.concatenate(hi)
    pairs = np.unique(lo * (C + 1) + hi)
    pa = pairs // (C + 1)
    pb = pairs % (C + 1)
    # count-min sketch over every pair occurrence: w = ceil(e / eps) *
    # floor(sqrt(C)) columns, d = ceil(ln 1/nu) rows, row seeds from the
    # job's seed
    width = math.ceil(math.e / epsilon) * max(1, int(math.sqrt(C)))
    depth = math.ceil(math.log(1.0 / nu))
    row_seeds = np.asarray(jax.random.randint(
        jax.random.PRNGKey(seed), (depth,), 1, _INT32_MAX,
        dtype=jnp.int32)).astype(np.uint32)
    keys = _pair_key(lo, hi)
    qkeys = _pair_key(pa, pb)
    est = None
    for mix in row_seeds * np.uint32(_GOLDEN):  # one sketch row per seed
        row = np.bincount(_avalanche(keys ^ mix) % np.uint32(width),
                          minlength=width)
        got = row[_avalanche(qkeys ^ mix) % np.uint32(width)]
        est = got if est is None else np.minimum(est, got)
    return cu, cv, head, sizes, pa, pb, est.astype(np.uint32)


# -------------------------------------------------------------- Alg. 2 game
def snake_init(sizes, k):
    """Clusters by size, largest first, dealt 0..k-1, k-1..0, ..."""
    order = np.argsort(-sizes, kind="stable")
    lane = np.arange(order.size) % (2 * k)
    assign = np.empty(order.size, np.int32)
    assign[order] = np.where(lane < k, lane, 2 * k - 1 - lane)
    return assign


def _degrees(pw, pa, pb, C):
    """deg_i = sum_j Theta(i, j)."""
    d = jax.ops.segment_sum(pw, pa, num_segments=C + 1)
    d = d + jax.ops.segment_sum(pw, pb, num_segments=C + 1)
    return d[:C]


@partial(jax.jit, static_argnames=("C", "n_head", "k", "bs", "max_rounds"))
def _game(sizes, pa, pb, pw, assign0, delta, accept, seed, *, C, n_head, k,
          bs, max_rounds):
    """Best-response dynamics: each round the leaders (head clusters)
    move in batches of ``bs`` clusters, then the followers.  Within a
    batch every improving cluster moves to its cheapest partition (ties:
    stay, then the lowest id) with probability ``accept``.  Cost of
    cluster i on partition p (paper Eq. 6):
    delta/k * |c_i| * |p + c_i| + (deg_i - W[i, p] + |c_i|) / k."""
    dt = sizes.dtype
    degs = _degrees(pw, pa, pb, C)
    cid = jnp.arange(C, dtype=jnp.int32)
    leader = cid < n_head
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

    def stage(assign, wanted, key, role, n_batches, offset):
        def body(i, carry):
            assign, wanted = carry
            lo = offset + i * bs
            active = (cid >= lo) & (cid < lo + bs) & role
            assign, w = respond(assign, active, jax.random.fold_in(key, i))
            return assign, wanted | w
        return jax.lax.fori_loop(0, n_batches, body, (assign, wanted))

    nb_h = max(1, -(-n_head // bs))
    nb_t = max(1, -(-(C - n_head) // bs))

    def one_round(state):
        assign, _, rounds = state
        k1, k2 = jax.random.split(jax.random.fold_in(key0, rounds))
        assign, wanted = stage(assign, jnp.bool_(False), k1, leader, nb_h, 0)
        assign, wanted = stage(assign, wanted, k2, ~leader, nb_t, n_head)
        return assign, wanted, rounds + 1

    state = one_round((assign0, jnp.bool_(True), jnp.int32(0)))
    assign, _, _ = jax.lax.while_loop(
        lambda s: s[1] & (s[2] < max_rounds), one_round, state)
    return assign


def game(sizes, pa, pb, pw_u32, n_head, C, k, seed, params, dtype):
    dt = jnp.dtype(dtype)
    sizes_d = jnp.asarray(sizes.astype(np.float32)).astype(dt)
    pw = jnp.asarray(pw_u32).astype(jnp.float32).astype(dt)
    pa_d = jnp.asarray(pa.astype(np.int32))
    pb_d = jnp.asarray(pb.astype(np.int32))
    degs = _degrees(pw, pa_d, pb_d, C)
    num = k * jnp.sum(degs + sizes_d)
    den = jnp.square(jnp.sum(sizes_d))
    delta = num / jnp.maximum(den, 1.0)
    bs = max(16, min(int(params["game_batch_size"]), C // 8))
    assign0 = snake_init(sizes.astype(np.float32), k)
    out = _game(sizes_d, pa_d, pb_d, pw, jnp.asarray(assign0), delta,
                jnp.float32(params["game_accept_prob"]), seed, C=C,
                n_head=n_head, k=k, bs=bs,
                max_rounds=int(params["game_max_rounds"]))
    return np.asarray(out)


# ------------------------------------------------------------------ Alg. 3
def place(cu, cv, head, valid, c2p, k, max_load):
    """Each edge goes to the less loaded of its clusters' partitions (tie:
    u's); when both are full, a head edge takes the first partition with
    room, a tail edge the last, and with no room anywhere the least
    loaded."""
    load = [0] * k
    pu = c2p[np.maximum(cu, 0)].tolist()
    pv = c2p[np.maximum(cv, 0)].tolist()
    parts = [-1] * len(pu)
    for e, (a, b, h, ok) in enumerate(zip(pu, pv, head.tolist(),
                                          valid.tolist())):
        if not ok:
            continue
        la, lb = load[a], load[b]
        if la >= max_load and lb >= max_load:
            room = [p for p in range(k) if load[p] < max_load]
            if room:
                p = room[0] if h else room[-1]
            else:
                p = load.index(min(load))
        else:
            p = b if la > lb else a
        load[p] += 1
        parts[e] = p
    return np.asarray(parts, np.int32)


# --------------------------------------------------------------- the whole
def partition(src, dst, n_vertices, k, seed, params, *, dtype="float32"):
    """(parts, internals) for one job on (src, dst) in arrival order."""
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    E = src.size
    deg = (np.bincount(src, minlength=n_vertices)
           + np.bincount(dst, minlength=n_vertices))
    xi = min(int(params["beta"] * (2.0 * E / max(n_vertices, 1))),
             _INT32_MAX - 1)
    kappa = max(int(math.ceil(2.0 * E / k)), 2)
    v2c_h, v2c_t = cluster(src.tolist(), dst.tolist(), deg.tolist(), xi,
                           kappa)
    out_h, out_t, n_head, C = compact(v2c_h, v2c_t)
    cu, cv, head, sizes, pa, pb, pw = statistics(
        src, dst, deg, xi, out_h, out_t, C, seed, params["cms_epsilon"],
        params["cms_nu"])
    c2p = game(sizes, pa, pb, pw, n_head, C, k, seed, params, dtype)
    max_load = int(math.ceil(params["tau"] * E / k))
    parts = place(cu, cv, head, src != dst, c2p, k, max_load)
    return parts, {"v2c_h": out_h, "v2c_t": out_t, "n_clusters": C,
                   "n_head": n_head, "c2p": c2p}


def diagnose(captured: dict, internals: dict) -> dict:
    """Where the program's layers part from the reference (information
    only; ``correct`` is decided by the parts)."""
    out = {}
    res = captured.get("clusters")
    if res is not None and hasattr(res, "v2c_h"):
        out["alg1_vertices_differ"] = int(
            np.count_nonzero(np.asarray(res.v2c_h) != internals["v2c_h"])
            + np.count_nonzero(np.asarray(res.v2c_t) != internals["v2c_t"]))
    g = captured.get("game")
    if g is not None and hasattr(g, "assignment"):
        a = np.asarray(g.assignment)
        out["game_clusters_differ"] = (
            int(np.count_nonzero(a != internals["c2p"]))
            if a.shape == internals["c2p"].shape else -1)
    return out
