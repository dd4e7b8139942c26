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
cost matrix per batch window of ``bs`` consecutive cluster ids.  A game
call groups the directed cluster adjacency by batch window once; a batch
then scatter-adds only its window's entries into the window's rows of ``W``,
``ADJ_CHUNK`` entries per loop step, and the partition sizes are carried
and updated by each batch's own moves: no batch scatters every pair or
every cluster.  Within a batch all players move simultaneously (as in the
paper); across batches moves are sequential.  The whole game is a single
jitted ``lax.while_loop``.

While Θ weights are whole numbers below 2²⁴ in every cluster's sum (its
degree) and cluster sizes are multiples of ½ summing below 2²³, every
order of summing W and the partition sizes gives the same f32 values:
the game is then bitwise the one that rebuilds W for every cluster and
batch (``_neighbor_partition_weight``, which :func:`best_response_gap`
uses).

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

ADJ_CHUNK = 1024  # adjacency entries a batch update scatters per loop step

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


class _Adjacency(NamedTuple):
    """The directed cluster adjacency grouped by batch window: the entries
    of window b (their rows in it, in no set order) are
    ``[wptr[b], wptr[b + 1])``.  ``ADJ_CHUNK`` filler entries end the
    arrays, so a chunk read from any entry stays in bounds."""
    row: jax.Array  # (2P + ADJ_CHUNK,) int32
    nbr: jax.Array  # (2P + ADJ_CHUNK,) int32
    w: jax.Array  # (2P + ADJ_CHUNK,) float32
    wptr: jax.Array  # (n_windows + 2,) int32


def _window_adjacency(inputs: GameInputs, n_clusters: int, window_of,
                      n_windows: int) -> _Adjacency:
    """Both directions of every pair, grouped by ``window_of`` their row.
    Padded pairs (a = b ≥ C) go past the last window; a neighbour ≥ C
    reads the sink's partition 0, as in the full W of
    :func:`_neighbor_partition_weight`.  The grouping is a stable LSD
    radix sort on the window id, one binary partition per bit: the TPU
    compiler takes 14–53 s over a ``lax.sort`` of 10⁴–10⁵ elements, a
    cost every new graph would pay (the game compiles per cluster count)."""
    C = n_clusters
    a = inputs.pair_a.astype(jnp.int32)
    b = inputs.pair_b.astype(jnp.int32)
    row = jnp.minimum(jnp.concatenate([a, b]), C)
    nbr = jnp.minimum(jnp.concatenate([b, a]), C)
    w = jnp.concatenate([inputs.pair_w, inputs.pair_w])
    key = jnp.where(row < C, window_of(row), n_windows)
    n = row.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)

    def partition(bit, perm):
        one = (key[perm] >> bit) & 1
        ones_before = _prefix_sum(one) - one
        pos = jnp.where(one == 1, n - jnp.sum(one) + ones_before,
                        idx - ones_before)
        return jnp.zeros_like(perm).at[pos].set(perm, unique_indices=True)

    perm = jax.lax.fori_loop(0, n_windows.bit_length(), partition, idx)
    counts = jnp.zeros((n_windows + 1,), jnp.int32).at[key].add(1)
    wptr = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)])
    fill = (0, ADJ_CHUNK)
    return _Adjacency(jnp.pad(row[perm], fill, constant_values=C),
                      jnp.pad(nbr[perm], fill, constant_values=C),
                      jnp.pad(w[perm], fill), wptr)


def _prefix_sum(x):
    """Inclusive prefix sum of a long int32 vector, summed down the columns
    of a (1024, n / 1024) view and then across them: the TPU compiler
    takes seconds over one ``cumsum`` of 10⁵ elements, a fraction of a
    second over these."""
    block = 1024
    n = x.shape[0]
    m = -(-n // block)
    cols = jnp.pad(x, (0, m * block - n)).reshape(m, block).T
    within = jnp.cumsum(cols, axis=0)
    before = jnp.cumsum(within[-1]) - within[-1]
    return (within + before[None, :]).T.reshape(-1)[:n]


def _window_chunks(wptr, win):
    """First entry, end and ``ADJ_CHUNK`` count of window ``win``."""
    start, end = wptr[win], wptr[win + 1]
    return start, end, (end - start + ADJ_CHUNK - 1) // ADJ_CHUNK


class _Game(NamedTuple):
    """A game call's fixed operands.  Per-cluster arrays run ``bs`` rows
    past C (size 0, no move cost, no home) so that every batch window,
    the last partial one too, is sliced in bounds."""
    sizes: jax.Array  # (C + bs,) float32
    degs: jax.Array  # (C + bs,) float32
    adj: _Adjacency
    dk: jax.Array  # δ/k
    inv_k: float
    accept_prob: jax.Array
    n_clusters: int
    k: int
    bs: int
    move_cost: jax.Array | None = None  # (C + bs,) float32
    home: jax.Array | None = None  # (C + bs,) int32


def _setup(sizes, pair_a, pair_b, pair_w, delta, accept_prob, *, n_clusters,
           k, batch_size, window_of, n_windows, move_cost=None,
           home=None) -> _Game:
    inputs = GameInputs(sizes, pair_a, pair_b, pair_w, 0, k)
    pad = (0, batch_size)
    inv_k = 1.0 / k
    return _Game(
        sizes=jnp.pad(sizes, pad),
        degs=jnp.pad(_cluster_degrees(inputs, n_clusters), pad),
        adj=_window_adjacency(inputs, n_clusters, window_of, n_windows),
        dk=delta * inv_k, inv_k=inv_k, accept_prob=accept_prob,
        n_clusters=n_clusters, k=k, bs=batch_size,
        move_cost=None if move_cost is None else jnp.pad(move_cost, pad),
        home=None if home is None else jnp.pad(home, pad, constant_values=-1),
    )


def _window_weight(g: _Game, assign, lo, win):
    """W[lo : lo + bs] from window ``win``'s own entries (rows of the
    window that are not ``win``'s read 0), scattered ``ADJ_CHUNK`` at a
    time into a (bs + 1, k) table; a chunk's entries past the window go to
    its last, sink row."""
    start, end, n_chunks = _window_chunks(g.adj.wptr, win)
    lane = jnp.arange(ADJ_CHUNK, dtype=jnp.int32)

    def chunk(j, tbl):
        off = start + j * ADJ_CHUNK
        row, nbr, w = (jax.lax.dynamic_slice_in_dim(x, off, ADJ_CHUNK)
                       for x in (g.adj.row, g.adj.nbr, g.adj.w))
        local = jnp.where(off + lane < end, row - lo, g.bs)
        return tbl.at[local, assign[nbr]].add(w)

    tbl = jnp.zeros((g.bs + 1, g.k), jnp.float32)
    return jax.lax.fori_loop(0, n_chunks, chunk, tbl)[:g.bs]


def _batch_update(g: _Game, assign, part_sizes, lo, win, role, key):
    """Best response of the ``role`` players among rows ``[lo, lo + bs)``,
    window ``win`` (one simultaneous batch); only the window's rows and
    adjacency entries are read.

    Within a batch moves are simultaneous (the paper's batch parallelism).
    Simultaneous moves can cycle — S(Λ) is an *exact potential* only for
    unilateral deviations — so each improving move is accepted with
    probability ``accept_prob`` (ε-damped best response, a.s. convergent
    in potential games).  ``wanted`` tracks whether anyone had an
    improving move at all: the equilibrium test.  ``part_sizes`` is
    carried, updated by the batch's own moves.

    With ``g.move_cost``, each cluster pays ``move_cost[i]`` on every
    partition but ``home[i]`` — the elastic-resharding migration penalty
    (staying put is never taxed).  Adding a strategy-dependent constant
    keeps S an exact potential, so the convergence argument is unchanged.
    """
    bs, k = g.bs, g.k

    def rows(x):
        return jax.lax.dynamic_slice_in_dim(x, lo, bs)

    sizes, degs, cur_p, active = rows(g.sizes), rows(g.degs), rows(assign), rows(role)
    w_ip = _window_weight(g, assign, lo, win)  # (bs, k)
    onehot = jax.nn.one_hot(cur_p, k, dtype=jnp.float32)
    # hypothetical |p| if i moved to p: current size + s_i when p ≠ P_i
    hyp = part_sizes[None, :] + sizes[:, None] * (1.0 - onehot)
    cost = g.dk * sizes[:, None] * hyp + (degs[:, None] - w_ip + sizes[:, None]) * g.inv_k
    if g.move_cost is not None:
        at_home = jax.nn.one_hot(rows(g.home), k, dtype=jnp.float32)  # -1 ⇒ all-zero
        cost = cost + rows(g.move_cost)[:, None] * (1.0 - at_home)
    # deterministic tie-breaking: the current partition wins cost ties
    # (no churn between equal-cost strategies), remaining ties go to the
    # lowest partition id — best responses are a pure function of state
    cur = jnp.take_along_axis(cost, cur_p[:, None], axis=1)[:, 0]
    strictly_better = jnp.min(cost, axis=1) < cur
    best = jnp.where(
        strictly_better, jnp.argmin(cost, axis=1).astype(jnp.int32), cur_p
    )
    improves = active & (best != cur_p) & strictly_better
    # the draws of all C clusters, as the full-table game makes them
    draws = jax.random.uniform(key, (g.n_clusters,))
    lucky = rows(jnp.pad(draws, (0, bs))) < g.accept_prob
    new_p = jnp.where(improves & lucky, best, cur_p)
    part_sizes = part_sizes + jnp.sum(
        sizes[:, None] * (jax.nn.one_hot(new_p, k, dtype=jnp.float32) - onehot),
        axis=0)
    assign = jax.lax.dynamic_update_slice_in_dim(assign, new_p, lo, 0)
    return assign, part_sizes, jnp.any(improves)


def _play(g: _Game, assign0, seed, max_rounds, stages):
    """Best-response rounds until no player wants to move, or
    ``max_rounds``.  Each round plays the ``stages`` in order, leaders
    first; a stage is ``(role, los, wins, ids)``: the (C + bs,) mask of its
    players, and its batches' first rows, adjacency windows and key ids.
    Returns the assignment, rounds, converged flag and the adjacency
    entries one round reads, chunk padding included."""
    key0 = jax.random.PRNGKey(seed)

    def stage(assign, part_sizes, wanted, key, role, los, wins, ids):
        def body(b, carry):
            assign, part_sizes, wanted = carry
            assign, part_sizes, w = _batch_update(
                g, assign, part_sizes, los[b], wins[b], role,
                jax.random.fold_in(key, ids[b]))
            return assign, part_sizes, wanted | w

        return jax.lax.fori_loop(0, los.shape[0], body,
                                 (assign, part_sizes, wanted))

    def round_body(state):
        assign, part_sizes, _, rounds = state
        wanted = jnp.bool_(False)
        keys = jax.random.split(jax.random.fold_in(key0, rounds))
        # Stage 1: leaders move first; stage 2: followers respond to the
        # leaders' committed strategies.
        for key, st in zip(keys, stages):
            assign, part_sizes, wanted = stage(assign, part_sizes, wanted,
                                               key, *st)
        return assign, part_sizes, wanted, rounds + 1

    # Always run at least one round; `wanted` of the *last* round decides
    # convergence (False ⇒ pure Nash equilibrium reached).
    part_sizes = jax.ops.segment_sum(g.sizes[:g.n_clusters], assign0,
                                     num_segments=g.k)
    state = round_body((jnp.pad(assign0, (0, g.bs)), part_sizes,
                        jnp.bool_(True), jnp.int32(0)))
    assign, _, wanted, rounds = jax.lax.while_loop(
        lambda s: s[2] & (s[3] < max_rounds), round_body, state)
    entries = sum(jnp.sum(_window_chunks(g.adj.wptr, wins)[2])
                  for _, _, wins, _ in stages) * ADJ_CHUNK
    return assign[:g.n_clusters], rounds, ~wanted, entries


def _stage_batches(n_clusters: int, n_head: int, batch_size: int):
    """Leader and follower batch counts of one round of the full game."""
    return (max(1, -(-n_head // batch_size)),
            max(1, -(-(n_clusters - n_head) // batch_size)))


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
    """The full game: leaders are the id prefix ``[0, n_head)``, batch
    windows tile the leaders from 0 and the followers from ``n_head``."""
    nb_h, nb_t = _stage_batches(n_clusters, n_head, batch_size)

    def window_of(r):
        return jnp.where(r < n_head, r // batch_size,
                         nb_h + (r - n_head) // batch_size)

    g = _setup(sizes, pair_a, pair_b, pair_w, delta, accept_prob,
               n_clusters=n_clusters, k=k, batch_size=batch_size,
               window_of=window_of, n_windows=nb_h + nb_t)
    cid = jnp.arange(n_clusters + batch_size, dtype=jnp.int32)
    ids_h = jnp.arange(nb_h, dtype=jnp.int32)
    ids_t = jnp.arange(nb_t, dtype=jnp.int32)
    stages = (
        (cid < n_head, ids_h * batch_size, ids_h, ids_h),
        ((cid >= n_head) & (cid < n_clusters), n_head + ids_t * batch_size,
         nb_h + ids_t, ids_t),
    )
    return _play(g, assign0, seed, max_rounds, stages)


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
    sweep — frozen-only batches are provably no-ops.  Both stages visit
    the same windows and fold the window id (not the loop index) into
    their keys, so a window's acceptance draws don't depend on which
    other windows ran.

    ``use_move_cost`` (static) selects the elastic-resharding payoff: each
    cluster pays ``move_cost[i]`` on every partition except ``home[i]``
    (``home = -1`` ⇒ no free square — a uniform penalty that cannot bias
    the argmin).  False leaves the trace identical to the pre-move-cost
    masked game, so the refinement goldens hold.
    """
    g = _setup(sizes, pair_a, pair_b, pair_w, delta, accept_prob,
               n_clusters=n_clusters, k=k, batch_size=batch_size,
               window_of=lambda r: r // batch_size,
               n_windows=-(-n_clusters // batch_size),
               move_cost=move_cost if use_move_cost else None,
               home=home if use_move_cost else None)
    pad = (0, batch_size)
    los = batch_ids * batch_size
    stages = tuple(
        (jnp.pad(role & move_mask, pad), los, batch_ids, batch_ids)
        for role in (leader_mask, ~leader_mask))
    return _play(g, assign0, seed, max_rounds, stages)


def _counted(out, batches_per_round: int) -> GameResult:
    """The game's result, once its counters are added: ``game.batch_updates``
    and ``game.adj_entries`` (the adjacency entries its batch updates
    read, chunk padding included).  Waits for the game."""
    assign, rounds, converged, entries = out
    n_rounds = int(rounds)
    spans.count("game.batch_updates", n_rounds * batches_per_round)
    spans.count("game.adj_entries", n_rounds * int(entries))
    return GameResult(assignment=assign, rounds=rounds, converged=converged)


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
        out = _run_game_jit(
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
        return _counted(out, sum(_stage_batches(n_clusters, inputs.n_head,
                                                batch_size)))
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
    out = _run_game_masked_jit(
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
    return _counted(out, 2 * batch_ids.size)  # both stages visit every window


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
