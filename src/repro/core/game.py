"""Two-stage Stackelberg game for cluster→partition assignment (Alg. 2).

Players are the head/tail clusters produced by Algorithm 1.  Each round has
two stages: **leaders** (head clusters) best-respond first, then
**followers** (tail clusters), per the two-stage Stackelberg structure.
Best-response dynamics run until a pure Nash equilibrium (no player moves)
or ``max_rounds``.

Cost of cluster i choosing partition p (paper Eq. 6):

    S_i(p) = (δ/k)·|c_i|·|p| + (F_i(p) + |c_i|)/k
    F_i(p) = Σ_j Θ(c_i, c_j)·1[p ≠ P(c_j)]  =  deg_i − W[i, p]
    W[i, p] = Σ_{j : P(c_j)=p} Θ(c_i, c_j)

TPU adaptation (DESIGN.md §2): the paper parallelizes best responses over
*batches of clusters* with a thread pool; we realize the identical batch
semantics as **vectorized argmin over the cluster axis** — one (batch × k)
cost matrix per batch, with ``W`` recomputed from the cluster-adjacency
edge list by scatter-add.  Within a batch all players move simultaneously
(as in the paper); across batches moves are sequential.  The whole game is
a single jitted ``lax.while_loop``.

Θ counts come either from the exact cluster-adjacency weights or from a
count-min sketch query (paper §4.4) — the caller chooses (see s5p.py).
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..runtime import spans

__all__ = [
    "GameInputs",
    "GameResult",
    "init_assignment",
    "compute_delta",
    "default_batch_size",
    "run_game",
    "best_response_gap",
]


def default_batch_size(requested: int, n_clusters: int) -> int:
    """Clamp a requested game batch to ≲ C/8 (floor 16): near-simultaneous
    sweeps over a small player set cycle — the potential argument needs
    mostly-sequential moves.  One policy shared by the cold pipeline and
    the incremental settle/refine games so warm dynamics match cold."""
    return max(16, min(int(requested), n_clusters // 8))


class GameInputs(NamedTuple):
    sizes: jax.Array  # (C,) float32 — edge-volume of each cluster
    pair_a: jax.Array  # (P,) int32 — cluster adjacency: endpoint a
    pair_b: jax.Array  # (P,) int32 — endpoint b (a < b; padded rows a=b=C_pad)
    pair_w: jax.Array  # (P,) float32 — Θ(a, b) (exact or CMS estimate)
    n_head: int  # leaders are cluster ids [0, n_head)
    k: int


class GameResult(NamedTuple):
    assignment: jax.Array  # (C,) int32 cluster → partition
    rounds: jax.Array  # () int32 rounds until convergence
    converged: jax.Array  # () bool


def init_assignment(sizes: np.ndarray, k: int) -> np.ndarray:
    """Deterministic size-balanced initialization: snake round-robin over
    clusters sorted by size descending (a 4/3-approx of makespan — a strong,
    cheap start consistent with the paper's 'initial partitioning')."""
    order = np.argsort(-np.asarray(sizes), kind="stable")
    assign = np.empty(order.size, np.int32)
    lane = np.arange(order.size) % (2 * k)
    snake = np.where(lane < k, lane, 2 * k - 1 - lane)
    assign[order] = snake.astype(np.int32)
    return assign


def compute_delta(sizes: jax.Array, degs: jax.Array, k: int) -> jax.Array:
    """δ_max of paper Eq. (12): k·Σ(F(c_i)+|c_i|) / (Σ|c_i|)² — the upper end
    of the admissible normalization range (the paper uses the maximum)."""
    num = k * jnp.sum(degs + sizes)
    den = jnp.square(jnp.sum(sizes))
    return num / jnp.maximum(den, 1.0)


def _cluster_degrees(inputs: GameInputs, n_clusters: int) -> jax.Array:
    """deg_i = Σ_j Θ(i, j): total inter-cluster edge weight per cluster."""
    deg = jax.ops.segment_sum(inputs.pair_w, inputs.pair_a, num_segments=n_clusters + 1)
    deg = deg + jax.ops.segment_sum(inputs.pair_w, inputs.pair_b, num_segments=n_clusters + 1)
    return deg[:n_clusters]


def _neighbor_partition_weight(inputs: GameInputs, assign: jax.Array, n_clusters: int) -> jax.Array:
    """W[i, p] = Σ_{j: P(j)=p} Θ(i, j), via two scatter-adds over the pair list."""
    k = inputs.k
    pad = n_clusters  # padded pairs point at the sink row
    a = jnp.minimum(inputs.pair_a, pad)
    b = jnp.minimum(inputs.pair_b, pad)
    assign_ext = jnp.concatenate([assign, jnp.zeros((1,), jnp.int32)])
    w = jnp.zeros((n_clusters + 1, k), jnp.float32)
    w = w.at[a, assign_ext[b]].add(inputs.pair_w)
    w = w.at[b, assign_ext[a]].add(inputs.pair_w)
    return w[:n_clusters]


def _batch_update(inputs, degs, assign, active, key, dk, inv_k, accept_prob,
                  n_clusters, move_pen=None):
    """Best response for ``active`` clusters (one simultaneous batch).

    Within a batch moves are simultaneous (the paper's batch parallelism).
    Simultaneous moves can cycle — S(Λ) is an *exact potential* only for
    unilateral deviations — so each improving move is accepted with
    probability ``accept_prob`` (ε-damped best response, a.s. convergent
    in potential games).  ``wanted`` tracks whether anyone had an
    improving move at all: the equilibrium test.

    ``move_pen`` (C, k), when given, is added to the cost matrix — the
    elastic-resharding migration penalty (zero on each cluster's home
    partition, so staying put is never taxed).  Adding a
    strategy-dependent constant keeps S an exact potential, so the
    convergence argument is unchanged.
    """
    sizes, k = inputs.sizes, inputs.k
    w_ip = _neighbor_partition_weight(inputs, assign, n_clusters)  # (C, k)
    part_sizes = jax.ops.segment_sum(sizes, assign, num_segments=k)  # (k,)
    onehot = jax.nn.one_hot(assign, k, dtype=jnp.float32)
    # hypothetical |p| if i moved to p: current size + s_i when p ≠ P_i
    hyp = part_sizes[None, :] + sizes[:, None] * (1.0 - onehot)
    cost = dk * sizes[:, None] * hyp + (degs[:, None] - w_ip + sizes[:, None]) * inv_k
    if move_pen is not None:
        cost = cost + move_pen
    # deterministic tie-breaking: the current partition wins cost ties
    # (no churn between equal-cost strategies), remaining ties go to the
    # lowest partition id — best responses are a pure function of state
    cur = jnp.take_along_axis(cost, assign[:, None], axis=1)[:, 0]
    strictly_better = jnp.min(cost, axis=1) < cur
    best = jnp.where(
        strictly_better, jnp.argmin(cost, axis=1).astype(jnp.int32), assign
    )
    improves = active & (best != assign) & strictly_better
    lucky = jax.random.uniform(key, (n_clusters,)) < accept_prob
    new_assign = jnp.where(improves & lucky, best, assign)
    wanted = jnp.any(improves)
    moved = jnp.any(new_assign != assign)
    return new_assign, moved, wanted


@partial(
    jax.jit,
    static_argnames=("n_clusters", "n_head", "k", "batch_size", "max_rounds"),
)
def _run_game_jit(
    sizes,
    pair_a,
    pair_b,
    pair_w,
    assign0,
    delta,
    accept_prob,
    seed,
    *,
    n_clusters: int,
    n_head: int,
    k: int,
    batch_size: int,
    max_rounds: int,
):
    inputs = GameInputs(sizes, pair_a, pair_b, pair_w, n_head, k)
    degs = _cluster_degrees(inputs, n_clusters)
    cid = jnp.arange(n_clusters, dtype=jnp.int32)
    is_leader = cid < n_head
    n_batches_h = max(1, -(-n_head // batch_size))
    n_tail = n_clusters - n_head
    n_batches_t = max(1, -(-n_tail // batch_size))
    inv_k = 1.0 / k
    dk = delta * inv_k
    key0 = jax.random.PRNGKey(seed)

    def batch_update(assign, active, key):
        return _batch_update(inputs, degs, assign, active, key, dk, inv_k,
                             accept_prob, n_clusters)

    def stage(assign, moved, wanted, key, role_mask, n_batches, offset):
        def body(b, carry):
            assign, moved, wanted = carry
            lo = offset + b * batch_size
            in_batch = (cid >= lo) & (cid < lo + batch_size) & role_mask
            assign, m, w = batch_update(assign, in_batch, jax.random.fold_in(key, b))
            return assign, moved | m, wanted | w

        return jax.lax.fori_loop(0, n_batches, body, (assign, moved, wanted))

    def round_body(state):
        assign, _, rounds = state
        moved = jnp.bool_(False)
        wanted = jnp.bool_(False)
        key = jax.random.fold_in(key0, rounds)
        k1, k2 = jax.random.split(key)
        # Stage 1: leaders (head clusters) move first.
        assign, moved, wanted = stage(assign, moved, wanted, k1, is_leader, n_batches_h, 0)
        # Stage 2: followers respond to the leaders' committed strategies.
        assign, moved, wanted = stage(assign, moved, wanted, k2, ~is_leader, n_batches_t, n_head)
        return assign, wanted, rounds + 1

    def cond(state):
        _, wanted, rounds = state
        return wanted & (rounds < max_rounds)

    # Always run at least one round; `wanted` of the *last* round decides
    # convergence (False ⇒ pure Nash equilibrium reached).
    assign, wanted, rounds = round_body((assign0, jnp.bool_(True), jnp.int32(0)))
    assign, wanted, rounds = jax.lax.while_loop(
        cond, lambda s: round_body(s), (assign, wanted, rounds)
    )
    return assign, rounds, ~wanted


@partial(
    jax.jit,
    static_argnames=("n_clusters", "k", "batch_size", "max_rounds",
                     "use_move_cost"),
)
def _run_game_masked_jit(
    sizes,
    pair_a,
    pair_b,
    pair_w,
    assign0,
    delta,
    accept_prob,
    seed,
    leader_mask,
    move_mask,
    batch_ids,
    move_cost,
    home,
    *,
    n_clusters: int,
    k: int,
    batch_size: int,
    max_rounds: int,
    use_move_cost: bool,
):
    """Masked best-response dynamics (incremental refinement path).

    Identical move semantics to :func:`_run_game_jit` with two
    generalizations the warm-start subsystem needs: leaders are named by
    an explicit boolean mask (stable combined cluster ids interleave new
    head/tail clusters, so the leader set is no longer a contiguous id
    prefix), and only ``move_mask`` clusters may deviate (every other
    player is frozen but still shapes costs) — the "refine only what the
    delta touched" game.  ``batch_ids`` names the batch windows that hold
    at least one movable cluster (precomputed on host): a refinement over
    a handful of touched clusters pays for those batches only, not a full
    sweep — frozen-only batches are provably no-ops.

    ``use_move_cost`` (static) selects the elastic-resharding payoff: each
    cluster pays ``move_cost[i]`` on every partition except ``home[i]``
    (``home = -1`` ⇒ no free square — a uniform penalty that cannot bias
    the argmin).  False leaves the trace identical to the pre-move-cost
    masked game, so the refinement goldens hold.
    """
    inputs = GameInputs(sizes, pair_a, pair_b, pair_w, 0, k)
    degs = _cluster_degrees(inputs, n_clusters)
    cid = jnp.arange(n_clusters, dtype=jnp.int32)
    n_batches = batch_ids.shape[0]
    inv_k = 1.0 / k
    dk = delta * inv_k
    key0 = jax.random.PRNGKey(seed)
    move_pen = None
    if use_move_cost:
        at_home = jax.nn.one_hot(home, k, dtype=jnp.float32)  # -1 ⇒ all-zero
        move_pen = move_cost[:, None] * (1.0 - at_home)

    def stage(assign, moved, wanted, key, role_mask):
        def body(b, carry):
            assign, moved, wanted = carry
            bid = batch_ids[b]
            lo = bid * batch_size
            in_batch = (cid >= lo) & (cid < lo + batch_size) & role_mask
            # fold in the window id (not the loop index) so a window's
            # acceptance draws don't depend on which other windows ran
            assign, m, w = _batch_update(
                inputs, degs, assign, in_batch, jax.random.fold_in(key, bid),
                dk, inv_k, accept_prob, n_clusters, move_pen)
            return assign, moved | m, wanted | w

        return jax.lax.fori_loop(0, n_batches, body, (assign, moved, wanted))

    def round_body(state):
        assign, _, rounds = state
        moved = jnp.bool_(False)
        wanted = jnp.bool_(False)
        key = jax.random.fold_in(key0, rounds)
        k1, k2 = jax.random.split(key)
        assign, moved, wanted = stage(assign, moved, wanted, k1,
                                      leader_mask & move_mask)
        assign, moved, wanted = stage(assign, moved, wanted, k2,
                                      (~leader_mask) & move_mask)
        return assign, wanted, rounds + 1

    def cond(state):
        _, wanted, rounds = state
        return wanted & (rounds < max_rounds)

    assign, wanted, rounds = round_body((assign0, jnp.bool_(True), jnp.int32(0)))
    assign, wanted, rounds = jax.lax.while_loop(
        cond, lambda s: round_body(s), (assign, wanted, rounds)
    )
    return assign, rounds, ~wanted


def run_game(
    inputs: GameInputs,
    n_clusters: int,
    *,
    batch_size: int = 256,
    max_rounds: int = 64,
    accept_prob: float = 0.7,
    assign0: np.ndarray | None = None,
    delta: float | None = None,
    seed: int = 0,
    leader_mask: np.ndarray | None = None,
    move_mask: np.ndarray | None = None,
    move_cost: np.ndarray | None = None,
    home: np.ndarray | None = None,
) -> GameResult:
    """Run (damped) best-response dynamics to a pure Nash equilibrium.

    ``leader_mask``/``move_mask`` select the masked refinement path: an
    explicit (C,) leader set replaces the contiguous ``[0, n_head)``
    convention, and only ``move_mask`` players may deviate (all others are
    frozen context).  With both ``None`` the original full game runs —
    bit-identical to before the masks existed.

    ``move_cost`` (C,) adds a migration penalty to the masked game's
    payoff: cluster i pays ``move_cost[i]`` on every partition other than
    ``home[i]`` (default: its ``assign0`` seat; pass ``home[i] = -1`` for
    clusters with no surviving home — displaced by a shrink — which makes
    the penalty uniform and therefore neutral).  This is the bounded-
    migration knob of elastic k→k′ resharding: a cluster relocates only
    when the equilibrium gain exceeds its migration cost.
    """
    if assign0 is None:
        assign0 = init_assignment(spans.to_host(inputs.sizes), inputs.k)
    degs = _cluster_degrees(inputs, n_clusters)
    if delta is None:
        delta = compute_delta(inputs.sizes, degs, inputs.k)
    if move_cost is not None and leader_mask is None and move_mask is None:
        # the migration-cost game is only defined on the masked path;
        # default every player movable with the contiguous leader prefix
        leader_mask = np.arange(n_clusters) < inputs.n_head
    if leader_mask is None and move_mask is None:
        assign, rounds, converged = _run_game_jit(
            inputs.sizes,
            inputs.pair_a,
            inputs.pair_b,
            inputs.pair_w,
            jnp.asarray(assign0, jnp.int32),
            jnp.asarray(delta, jnp.float32),
            jnp.float32(accept_prob),
            seed,
            n_clusters=n_clusters,
            n_head=inputs.n_head,
            k=inputs.k,
            batch_size=batch_size,
            max_rounds=max_rounds,
        )
        return GameResult(assignment=assign, rounds=rounds, converged=converged)
    if leader_mask is None:
        leader_mask = np.arange(n_clusters) < inputs.n_head
    if move_mask is None:
        move_mask = np.ones((n_clusters,), bool)
    # only batch windows holding a movable cluster are worth visiting
    batch_ids = np.unique(
        np.nonzero(np.asarray(move_mask))[0] // batch_size).astype(np.int32)
    if batch_ids.size == 0:  # every player frozen: a no-op equilibrium
        return GameResult(assignment=jnp.asarray(assign0, jnp.int32),
                          rounds=jnp.int32(0), converged=jnp.bool_(True))
    use_move_cost = move_cost is not None
    if use_move_cost:
        home = np.asarray(assign0, np.int32) if home is None else home
    else:  # dummy operands: unused under a use_move_cost=False trace
        move_cost = np.zeros((n_clusters,), np.float32)
        home = np.full((n_clusters,), -1, np.int32)
    assign, rounds, converged = _run_game_masked_jit(
        inputs.sizes,
        inputs.pair_a,
        inputs.pair_b,
        inputs.pair_w,
        jnp.asarray(assign0, jnp.int32),
        jnp.asarray(delta, jnp.float32),
        jnp.float32(accept_prob),
        seed,
        jnp.asarray(leader_mask, bool),
        jnp.asarray(move_mask, bool),
        jnp.asarray(batch_ids),
        jnp.asarray(move_cost, jnp.float32),
        jnp.asarray(home, jnp.int32),
        n_clusters=n_clusters,
        k=inputs.k,
        batch_size=batch_size,
        max_rounds=max_rounds,
        use_move_cost=use_move_cost,
    )
    return GameResult(assignment=assign, rounds=rounds, converged=converged)


def social_welfare(inputs: GameInputs, assign: jax.Array, delta: jax.Array) -> jax.Array:
    """S(Λ) of Eq. (5) = δ·Σ|p|²/k + Σ Θ(p, V)/k (Theorem 4 identity)."""
    k = inputs.k
    part_sizes = jax.ops.segment_sum(inputs.sizes, assign, num_segments=k)
    assign_ext = jnp.concatenate([assign, jnp.zeros((1,), jnp.int32)])
    cut = jnp.sum(
        inputs.pair_w
        * (assign_ext[inputs.pair_a] != assign_ext[inputs.pair_b]).astype(jnp.float32)
    )
    load = delta * jnp.sum(jnp.square(part_sizes)) / k
    # Θ(p_i, V) = Θ(p_i, V − p_i) + |p_i|; Σ_i Θ(p_i, V−p_i) counts each cut
    # pair from both sides ⇒ 2·cut.
    comm = (2.0 * cut + jnp.sum(part_sizes)) / k
    return load + comm


def best_response_gap(inputs: GameInputs, assign: jax.Array, n_clusters: int,
                      delta: jax.Array | None = None) -> jax.Array:
    """Max cost improvement any single player could get by deviating.

    0 ⇔ pure Nash equilibrium.  Used by the property tests (the converged
    flag of :func:`run_game` must imply gap == 0 *per batch semantics*, i.e.
    no player moves when all others are fixed)."""
    degs = _cluster_degrees(inputs, n_clusters)
    if delta is None:
        delta = compute_delta(inputs.sizes, degs, inputs.k)
    k = inputs.k
    sizes = inputs.sizes
    w_ip = _neighbor_partition_weight(inputs, assign, n_clusters)
    part_sizes = jax.ops.segment_sum(sizes, assign, num_segments=k)
    onehot = jax.nn.one_hot(assign, k, dtype=jnp.float32)
    hyp = part_sizes[None, :] + sizes[:, None] * (1.0 - onehot)
    cost = (delta / k) * sizes[:, None] * hyp + (degs[:, None] - w_ip + sizes[:, None]) / k
    cur = jnp.take_along_axis(cost, assign[:, None].astype(jnp.int32), axis=1)[:, 0]
    best = jnp.min(cost, axis=1)
    return jnp.max(cur - best)
