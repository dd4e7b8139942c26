"""Stackelberg game: equilibrium, potential descent, PoA sanity, and the
window game against a game that rebuilds the whole W for every batch."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from proptest import cases
from repro.core import game as game_mod
from repro.core.game import (
    GameInputs, best_response_gap, compute_delta, init_assignment, run_game,
    social_welfare, _cluster_degrees,
)


def _random_inputs(seed, n_clusters=40, k=4, n_head=8):
    rng = np.random.default_rng(seed)
    sizes = rng.integers(1, 50, n_clusters).astype(np.float32)
    n_pairs = n_clusters * 3
    a = rng.integers(0, n_clusters, n_pairs)
    b = rng.integers(0, n_clusters, n_pairs)
    keep = a != b
    a, b = np.minimum(a, b)[keep], np.maximum(a, b)[keep]
    w = rng.integers(1, 10, a.size).astype(np.float32)
    return GameInputs(
        sizes=jnp.asarray(sizes), pair_a=jnp.asarray(a, jnp.int32),
        pair_b=jnp.asarray(b, jnp.int32), pair_w=jnp.asarray(w),
        n_head=n_head, k=k,
    ), n_clusters


@pytest.mark.parametrize("seed", list(cases(6)))
def test_converged_game_is_nash(seed):
    inputs, C = _random_inputs(seed)
    res = run_game(inputs, C, batch_size=1, max_rounds=200, accept_prob=1.0)
    assert bool(res.converged)
    gap = float(best_response_gap(inputs, res.assignment, C))
    assert gap <= 1e-4, f"equilibrium violated: gap={gap}"


@pytest.mark.parametrize("seed", list(cases(4, 50)))
def test_batched_game_converges_and_is_nash(seed):
    inputs, C = _random_inputs(seed, n_clusters=60)
    res = run_game(inputs, C, batch_size=16, max_rounds=300, accept_prob=0.7)
    assert bool(res.converged)
    gap = float(best_response_gap(inputs, res.assignment, C))
    assert gap <= 1e-4


def test_game_reduces_social_welfare():
    inputs, C = _random_inputs(0)
    degs = _cluster_degrees(inputs, C)
    delta = compute_delta(inputs.sizes, degs, inputs.k)
    init = jnp.asarray(init_assignment(np.asarray(inputs.sizes), inputs.k))
    s0 = float(social_welfare(inputs, init, delta))
    res = run_game(inputs, C, batch_size=1, max_rounds=200, accept_prob=1.0)
    s1 = float(social_welfare(inputs, res.assignment, delta))
    assert s1 <= s0 + 1e-5


def test_leaders_move_first():
    """With one round budget, only a full leader+follower sweep happens —
    sanity that the two-stage structure is wired (no crash, legal output)."""
    inputs, C = _random_inputs(1)
    res = run_game(inputs, C, batch_size=8, max_rounds=1)
    assign = np.asarray(res.assignment)
    assert assign.shape == (C,)
    assert assign.min() >= 0 and assign.max() < inputs.k


def test_delta_in_paper_range():
    """Eq. (11): 1/Σ|c| ≤ δ ≤ k·Σ(F+|c|)/(Σ|c|)²."""
    inputs, C = _random_inputs(2)
    degs = _cluster_degrees(inputs, C)
    delta = float(compute_delta(inputs.sizes, degs, inputs.k))
    total = float(jnp.sum(inputs.sizes))
    lo = 1.0 / total
    hi = inputs.k * float(jnp.sum(degs + inputs.sizes)) / total**2
    assert lo <= delta <= hi + 1e-9


# --------------------------------------------- window game against full W
def _full_w_batch(sizes, pa, pb, pw, degs, assign, active, key, dk, inv_k,
                  accept, C, k, move_pen):
    """The batch update that rebuilds the whole (C, k) W and cost table."""
    a, b = jnp.minimum(pa, C), jnp.minimum(pb, C)
    ext = jnp.concatenate([assign, jnp.zeros((1,), jnp.int32)])
    w = jnp.zeros((C + 1, k), jnp.float32)
    w = w.at[a, ext[b]].add(pw)
    w = w.at[b, ext[a]].add(pw)[:C]
    part_sizes = jax.ops.segment_sum(sizes, assign, num_segments=k)
    onehot = jax.nn.one_hot(assign, k, dtype=jnp.float32)
    hyp = part_sizes[None, :] + sizes[:, None] * (1.0 - onehot)
    cost = dk * sizes[:, None] * hyp + (degs[:, None] - w + sizes[:, None]) * inv_k
    if move_pen is not None:
        cost = cost + move_pen
    cur = jnp.take_along_axis(cost, assign[:, None], axis=1)[:, 0]
    better = jnp.min(cost, axis=1) < cur
    best = jnp.where(better, jnp.argmin(cost, axis=1).astype(jnp.int32), assign)
    improves = active & (best != assign) & better
    lucky = jax.random.uniform(key, (C,)) < accept
    return jnp.where(improves & lucky, best, assign), jnp.any(improves)


@partial(jax.jit, static_argnames=("C", "k", "bs", "max_rounds"))
def _full_w_game(sizes, pa, pb, pw, assign0, delta, accept, seed, roles,
                 los, ids, move_pen, *, C, k, bs, max_rounds):
    """Rounds of two stages over explicit windows (``los``/``ids`` per
    stage), every batch scored on the full W."""
    inputs = GameInputs(sizes, pa, pb, pw, 0, k)
    degs = _cluster_degrees(inputs, C)
    cid = jnp.arange(C, dtype=jnp.int32)
    inv_k = 1.0 / k
    dk = delta * inv_k

    def one_round(state):
        assign, _, rounds = state
        wanted = jnp.bool_(False)
        keys = jax.random.split(jax.random.fold_in(jax.random.PRNGKey(seed), rounds))
        for key, role, lo_s, id_s in zip(keys, roles, los, ids):
            def body(i, carry, key=key, role=role, lo_s=lo_s, id_s=id_s):
                assign, wanted = carry
                active = (cid >= lo_s[i]) & (cid < lo_s[i] + bs) & role
                assign, w = _full_w_batch(
                    sizes, pa, pb, pw, degs, assign, active,
                    jax.random.fold_in(key, id_s[i]), dk, inv_k, accept, C, k,
                    move_pen)
                return assign, wanted | w
            assign, wanted = jax.lax.fori_loop(0, lo_s.shape[0], body,
                                               (assign, wanted))
        return assign, wanted, rounds + 1

    state = one_round((assign0, jnp.bool_(True), jnp.int32(0)))
    assign, wanted, rounds = jax.lax.while_loop(
        lambda s: s[1] & (s[2] < max_rounds), one_round, state)
    return assign, rounds, ~wanted


def _window_inputs(seed, C=203, k=8, n_dense=64, n_empty=40, n_pad=9):
    """Integer Θ weights and half-integer sizes (so every W and partition
    size sum is exact); a dense block of the first ``n_dense`` clusters, so
    a 32-row window holds well over ``ADJ_CHUNK`` entries; no pairs on the
    last ``n_empty`` clusters, so their windows are empty; ``n_pad``
    padded pairs at the end (a = b = C + 7, nonzero weight)."""
    rng = np.random.default_rng(seed)
    sizes = rng.integers(0, 80, C) / 2.0
    da, db = np.triu_indices(n_dense, 1)
    m = C - n_empty
    ra = rng.integers(0, m, 3 * C)
    rb = rng.integers(0, m, 3 * C)
    keep = ra != rb
    a = np.concatenate([da, np.minimum(ra, rb)[keep], np.full(n_pad, C + 7)])
    b = np.concatenate([db, np.maximum(ra, rb)[keep], np.full(n_pad, C + 7)])
    w = rng.integers(1, 40, a.size)
    return (jnp.asarray(sizes, jnp.float32), jnp.asarray(a, jnp.int32),
            jnp.asarray(b, jnp.int32), jnp.asarray(w, jnp.float32), C, k)


@pytest.mark.parametrize("path", ["full", "masked", "move_cost"])
@pytest.mark.parametrize("seed,n_head,bs", [(0, 37, 16), (1, 0, 32),
                                            (2, 150, 32), (3, 203, 16)])
def test_window_game_is_bitwise_the_full_w_game(path, seed, n_head, bs):
    """``run_game`` scores a batch from its window's adjacency entries
    alone; its assignment, rounds and converged flag equal, bitwise, a
    game that rebuilds the whole W for every batch — on C = 203 (no
    multiple of the batch), leader windows across ``n_head``, empty
    windows, multi-chunk windows and padded pairs."""
    assert 64 * 63 // 2 > game_mod.ADJ_CHUNK  # a dense window spans chunks
    sizes, pa, pb, pw, C, k = _window_inputs(seed)
    inputs = GameInputs(sizes, pa, pb, pw, n_head, k)
    rng = np.random.default_rng(100 + seed)
    assign0 = rng.integers(0, k, C).astype(np.int32)
    delta = compute_delta(sizes, _cluster_degrees(inputs, C), k)
    kw = dict(batch_size=bs, max_rounds=6, accept_prob=0.7, assign0=assign0,
              seed=seed)
    leader = np.arange(C) < n_head
    move_pen = None
    if path == "full":
        res = run_game(inputs, C, **kw)
        nb_h = max(1, -(-n_head // bs))
        nb_t = max(1, -(-(C - n_head) // bs))
        roles = (jnp.asarray(leader), jnp.asarray(~leader))
        los = (np.arange(nb_h) * bs, n_head + np.arange(nb_t) * bs)
        ids = (np.arange(nb_h), np.arange(nb_t))
    else:
        move = rng.random(C) < 0.5
        move[bs:2 * bs] = False  # a window no stage visits
        if path == "move_cost":
            cost = rng.integers(0, 30, C).astype(np.float32) / 4
            home = np.where(rng.random(C) < 0.2, -1, assign0).astype(np.int32)
            kw.update(move_cost=cost, home=home)
            move_pen = (jnp.asarray(cost)[:, None]
                        * (1.0 - jax.nn.one_hot(home, k, dtype=jnp.float32)))
        res = run_game(inputs, C, **kw, leader_mask=leader, move_mask=move)
        bid = np.unique(np.nonzero(move)[0] // bs)
        roles = (jnp.asarray(leader & move), jnp.asarray(~leader & move))
        los, ids = (bid * bs,) * 2, (bid,) * 2
    ref = _full_w_game(
        sizes, pa, pb, pw, jnp.asarray(assign0), delta, jnp.float32(0.7),
        seed, roles, tuple(jnp.asarray(x, jnp.int32) for x in los),
        tuple(jnp.asarray(x, jnp.int32) for x in ids), move_pen,
        C=C, k=k, bs=bs, max_rounds=6)
    moved = int(np.sum(np.asarray(ref[0]) != assign0))
    assert moved > 0  # the dynamics are exercised
    np.testing.assert_array_equal(np.asarray(res.assignment), np.asarray(ref[0]))
    assert int(res.rounds) == int(ref[1])
    assert bool(res.converged) == bool(ref[2])
