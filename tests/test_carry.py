"""PartitionerCarry protocol: merge algebra, parallel ingest, validation.

Four layers:

1. *Merge algebra* — for every carry implementation in the repo, ``merge``
   is associative, commutative, and idempotent-safe w.r.t. the identity
   carry (``init()``), ``merge([c]) == c`` bitwise, and ``merge_stacked``
   agrees with ``merge``.  Property-based: hypothesis when installed, the
   seeded ``proptest`` harness otherwise.  All merged fields are int/bool,
   so every law is checked with exact equality — no tolerance.
2. *Parallel engine* — ``num_streams=1`` delegates bit-identically to the
   sequential driver; the threads and vmap backends agree bitwise for
   every carry; linear-merge carries (degrees, Θ sketch) are *exact*
   under any S; parts stay valid partitions.
3. *Sharding plan* — range/round-robin lanes partition the chunk id
   space; S is clamped to the chunk count.
4. *Validation* — non-positive chunk_size/window/num_streams/super_chunk
   raise ValueError at construction (not deep inside numpy), and the CLI
   rejects them at argparse level.

The 8-device shard_map quality-band test lives at the bottom (slow lane,
subprocess — same pattern as tests/test_distributed.py).
"""

import json
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from proptest import random_graph
from repro.core.clustering import ClusterCarry, DegreeCarry, compute_degrees
from repro.core.cms import SketchCarry
from repro.core.postprocess import AssignCarry
from repro.kernels.stream_scan import GreedyCarry, GridCarry, HdrfCarry
from repro.streaming import (
    EdgeStream,
    FnCarry,
    ParallelEdgeStream,
    run_carry,
    run_parallel,
)

try:  # optional — the container image has no hypothesis; gate, don't require
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st_

    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

K = 4


def _leaves(c):
    return jax.tree_util.tree_leaves(c)


def _tree_equal(a, b) -> bool:
    la, lb = _leaves(a), _leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb)
    )


def _make_carry_impls(n, extras_info=False):
    """Every PartitionerCarry implementation in the repo, ready to step
    random (src, dst) chunks of vertex ids < n."""
    deg = jnp.full((n,), 5, jnp.int32)  # fixed plausible degrees for Alg. 1
    c2p = jnp.arange(8, dtype=jnp.int32) % K
    impls = {
        "greedy": (GreedyCarry(n, K), 0),
        "hdrf": (HdrfCarry(n, K, 1.1), 0),
        "grid": (GridCarry(K, jnp.arange(n, dtype=jnp.int32) % 2,
                           jnp.arange(n, dtype=jnp.int32) % 2, 2), 0),
        "cluster": (ClusterCarry(deg, n, xi=3, kappa=17), 0),
        "assign": (AssignCarry(K, 50, c2p), 3),  # is_head, cu, cv extras
        "degree": (DegreeCarry(n), 0),
        "sketch": (SketchCarry(32, 3, seed=1), 0),
    }
    return impls


def _fold_random(pc, n_extras, n, rng, n_chunks=2, chunk=17):
    """Build a carry by folding random chunks from the identity."""
    carry = pc.init()
    for _ in range(n_chunks):
        src = jnp.asarray(rng.integers(0, n, chunk).astype(np.int32))
        dst = jnp.asarray(rng.integers(0, n, chunk).astype(np.int32))
        extras = []
        if n_extras:
            extras = [
                jnp.asarray(rng.integers(0, 2, chunk).astype(bool)),
                jnp.asarray(rng.integers(0, 8, chunk).astype(np.int32)),
                jnp.asarray(rng.integers(0, 8, chunk).astype(np.int32)),
            ]
        carry, _ = pc.step_chunk(carry, src, dst, jnp.int32(chunk), *extras)
    return carry


def _check_merge_algebra(name, pc, n_extras, n, seed):
    """The base-relative merge algebra every backend relies on.

    Since the decremental refactor all non-replicated fields are group
    elements merged as base + Σ(cᵢ − base) — so the laws are stated
    against a shared merge base (``run_parallel`` always supplies one;
    assignment tables init to −1, which is not the group identity)."""
    rng = np.random.default_rng(seed)
    base = pc.init()
    c1 = _fold_random(pc, n_extras, n, rng)
    c2 = _fold_random(pc, n_extras, n, rng)
    c3 = _fold_random(pc, n_extras, n, rng)
    m = pc.merge
    # singleton merge is the bitwise identity
    assert _tree_equal(m([c1]), c1), name
    # the base itself is the merge identity: base + (c1 − base) == c1
    assert _tree_equal(m([c1, base], base=base), c1), name
    assert _tree_equal(m([base, c1], base=base), c1), name
    # commutative on the group leaves.  pick_first leaves (assignment
    # tables: ClusterCarry's v2c) trade commutativity for sanity under
    # contention — they are deterministic by lane order instead: both
    # orders agree wherever at most one lane wrote, and the winner on a
    # contested cell is the first changed lane (a real id, never the
    # telescoped sum).  run_parallel always merges in lane order, so the
    # parallel result stays deterministic.
    ab = m([c1, c2], base=base)
    ba = m([c2, c1], base=base)
    pick = set(getattr(pc, "pick_first", ()))
    if not pick:
        assert _tree_equal(ab, ba), name
    else:
        la = jax.tree_util.tree_leaves(ab)
        lb = jax.tree_util.tree_leaves(ba)
        l1 = jax.tree_util.tree_leaves(c1)
        l2 = jax.tree_util.tree_leaves(c2)
        l0 = jax.tree_util.tree_leaves(base)
        for i, (x, y) in enumerate(zip(la, lb)):
            x, y = np.asarray(x), np.asarray(y)
            if i not in pick:
                np.testing.assert_array_equal(x, y, err_msg=name)
                continue
            v1, v2, b0 = (np.asarray(l1[i]), np.asarray(l2[i]),
                          np.asarray(l0[i]))
            ch1, ch2 = v1 != b0, v2 != b0
            both = ch1 & ch2
            np.testing.assert_array_equal(x[~both], y[~both], err_msg=name)
            np.testing.assert_array_equal(x, np.where(ch1, v1, v2),
                                          err_msg=name)
            np.testing.assert_array_equal(y, np.where(ch2, v2, v1),
                                          err_msg=name)
    # associative: merging a merged pair against the same base equals the
    # flat n-ary merge (the merged pair re-enters as one diverged carry)
    flat = m([c1, c2, c3], base=base)
    assert _tree_equal(m([m([c1, c2], base=base), c3], base=base), flat), name
    assert _tree_equal(m([c1, m([c2, c3], base=base)], base=base), flat), name
    # stacked reduction agrees with the list form
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), c1, c2, c3)
    assert _tree_equal(pc.merge_stacked(stacked, base=base),
                       m([c1, c2, c3], base=base)), name


def _check_group_laws(name, pc, n_extras, n, seed):
    """merge(c, δ) ∘ merge(·, −δ) is the identity for every carry type:
    signed deltas form a group, bitwise (integer / ℤ-2³² arithmetic)."""
    rng = np.random.default_rng(seed)
    c = _fold_random(pc, n_extras, n, rng)
    after = _fold_random(pc, n_extras, n, rng, n_chunks=3)
    delta = pc.signed_delta(after, c)
    # applying the delta reconstructs `after` exactly...
    assert _tree_equal(pc.apply_delta(c, delta), after), name
    # ...and applying its inverse is the identity, both ways round
    assert _tree_equal(pc.apply_delta(pc.apply_delta(c, delta),
                                      pc.negate(delta)), c), name
    assert _tree_equal(
        pc.apply_delta(pc.apply_delta(after, pc.negate(delta)), delta),
        after), name
    # double negation is the identity on the delta itself
    assert _tree_equal(pc.negate(pc.negate(delta)), delta), name


CARRY_NAMES = sorted(_make_carry_impls(8).keys())


# ====================================================== 1. merge algebra
@pytest.mark.parametrize("name", CARRY_NAMES)
@pytest.mark.parametrize("seed", [0, 1])
def test_merge_algebra(name, seed):
    n = 23
    pc, n_extras = _make_carry_impls(n)[name]
    _check_merge_algebra(name, pc, n_extras, n, seed)


@pytest.mark.parametrize("name", CARRY_NAMES)
@pytest.mark.parametrize("seed", [0, 1])
def test_group_laws(name, seed):
    n = 23
    pc, n_extras = _make_carry_impls(n)[name]
    _check_group_laws(name, pc, n_extras, n, seed)


if HAVE_HYPOTHESIS:

    @settings(max_examples=20, deadline=None)
    @given(name=st_.sampled_from(CARRY_NAMES), seed=st_.integers(0, 255),
           n=st_.integers(2, 64))
    def test_merge_algebra_fuzzed(name, seed, n):
        pc, n_extras = _make_carry_impls(n)[name]
        _check_merge_algebra(name, pc, n_extras, n, seed)

    @settings(max_examples=20, deadline=None)
    @given(name=st_.sampled_from(CARRY_NAMES), seed=st_.integers(0, 255),
           n=st_.integers(2, 64))
    def test_group_laws_fuzzed(name, seed, n):
        pc, n_extras = _make_carry_impls(n)[name]
        _check_group_laws(name, pc, n_extras, n, seed)


# =================================================== 1b. exact retraction
@pytest.mark.parametrize("name", CARRY_NAMES)
@pytest.mark.parametrize("seed", [0, 7])
def test_retract_is_exact_inverse_of_step(name, seed):
    """For the exact-retract carries, inserting a batch and then deleting
    it bitwise-restores the pre-batch carry — in any retraction order."""
    n = 23
    pc, n_extras = _make_carry_impls(n)[name]
    if not pc.supports_retract:
        pytest.skip(f"{name} does not retract")
    rng = np.random.default_rng(seed)
    before = _fold_random(pc, n_extras, n, rng)
    after, log = _fold_chunks_from(pc, n_extras, n, rng, before)
    if not pc.retract_exact:
        # cluster: retraction is the documented approximation — check the
        # exactly-counted fields (membership counters, local degrees)
        got = after
        for src, dst, parts, extras in reversed(log):
            got = pc.retract_chunk(got, src, dst, jnp.int32(src.shape[0]),
                                   parts, *extras)
        assert np.array_equal(np.asarray(got.cnt_h), np.asarray(before.cnt_h))
        assert np.array_equal(np.asarray(got.cnt_t), np.asarray(before.cnt_t))
        assert np.array_equal(np.asarray(got.ld), np.asarray(before.ld))
        return
    # exact carries restore bitwise — and retraction order cannot matter
    for order in (reversed(log), log):
        got = after
        for src, dst, parts, extras in order:
            got = pc.retract_chunk(got, src, dst, jnp.int32(src.shape[0]),
                                   parts, *extras)
        assert _tree_equal(got, before), name


def _fold_chunks_from(pc, n_extras, n, rng, carry, n_chunks=3, chunk=17):
    log = []
    for _ in range(n_chunks):
        src = jnp.asarray(rng.integers(0, n, chunk).astype(np.int32))
        dst = jnp.asarray(rng.integers(0, n, chunk).astype(np.int32))
        extras = []
        if n_extras:
            extras = [
                jnp.asarray(rng.integers(0, 2, chunk).astype(bool)),
                jnp.asarray(rng.integers(0, 8, chunk).astype(np.int32)),
                jnp.asarray(rng.integers(0, 8, chunk).astype(np.int32)),
            ]
        carry, parts = pc.step_chunk(carry, src, dst, jnp.int32(chunk), *extras)
        log.append((src, dst, parts, extras))
    return carry, log


def test_run_retract_driver_roundtrip():
    """run_carry over a deletion batch then run_retract with the recorded
    parts is the identity on the carry (greedy, chunked arbitrarily)."""
    from repro.streaming import run_retract

    src, dst, n, _ = random_graph(1)
    if len(src) < 64:
        pytest.skip("graph too small")
    cut = len(src) // 2
    pc = GreedyCarry(n, K)
    st_prefix = EdgeStream(src[:cut], dst[:cut], n, chunk_size=29)
    _, before = run_carry(st_prefix, pc)
    st_delta = EdgeStream(src[cut:], dst[cut:], n, chunk_size=29)
    delta_parts, after = run_carry(st_delta, pc, carry=before)
    # retract the delta through a *different* chunking than it arrived in
    st_back = EdgeStream(src[cut:], dst[cut:], n, chunk_size=13)
    got = run_retract(st_back, pc, np.asarray(delta_parts), carry=after)
    assert _tree_equal(got, before)


def test_merge_with_base_subtracts_deltas():
    """SUM fields merged against a common base count the base once:
    base + Σ(cᵢ − base).  DegreeCarry makes this exactly checkable."""
    n = 31
    rng = np.random.default_rng(7)
    pc = DegreeCarry(n)
    base = _fold_random(pc, 0, n, rng)
    all_src, all_dst = [], []

    def fold_from(base, n_chunks):
        carry = base
        for _ in range(n_chunks):
            src = jnp.asarray(rng.integers(0, n, 13).astype(np.int32))
            dst = jnp.asarray(rng.integers(0, n, 13).astype(np.int32))
            all_src.append(np.asarray(src))
            all_dst.append(np.asarray(dst))
            carry, _ = pc.step_chunk(carry, src, dst, jnp.int32(13))
        return carry

    merged = pc.merge([fold_from(base, 2), fold_from(base, 1),
                       fold_from(base, 3)], base=base)
    expect = np.asarray(base) + np.asarray(compute_degrees(
        jnp.asarray(np.concatenate(all_src)),
        jnp.asarray(np.concatenate(all_dst)), n))
    assert np.array_equal(np.asarray(merged), expect)


def test_merge_validates_op_declaration():
    pc = DegreeCarry(4)
    pc.merge_ops = ("sum", "sum")  # wrong arity
    with pytest.raises(ValueError, match="leaves"):
        pc.merge([pc.init(), pc.init()])
    pc.merge_ops = ("nope",)
    with pytest.raises(ValueError, match="unknown merge op"):
        pc.merge([pc.init(), pc.init()])
    with pytest.raises(ValueError, match="at least one"):
        DegreeCarry(4).merge([])


# ==================================================== 2. parallel engine
def test_run_parallel_s1_is_sequential_bitwise():
    src, dst, n, _ = random_graph(1)
    st = EdgeStream(src, dst, n, chunk_size=29)
    pc = HdrfCarry(n, K)
    seq_parts, seq_carry = run_carry(st, pc)
    par_parts, par_carry = run_parallel(st, pc, num_streams=1)
    assert np.array_equal(np.asarray(seq_parts), np.asarray(par_parts))
    assert _tree_equal(seq_carry, par_carry)


@pytest.mark.parametrize("graph_seed", [0, 1])
@pytest.mark.parametrize("S", [2, 4])
def test_backends_agree_bitwise(graph_seed, S):
    """threads and vmap realize the same plan + merge algebra, so they
    must agree bit-for-bit — for parts-emitting and state-only carries."""
    src, dst, n, _ = random_graph(graph_seed)
    if len(src) < 64:
        pytest.skip("graph too small for multiple chunks")
    st = EdgeStream(src, dst, n, chunk_size=31)
    for name, (pc, n_extras) in _make_carry_impls(n).items():
        extras = ()
        if n_extras:
            E = len(src)
            rng = np.random.default_rng(0)
            extras = (rng.integers(0, 2, E).astype(bool),
                      rng.integers(0, 8, E).astype(np.int32),
                      rng.integers(0, 8, E).astype(np.int32))
        pt, ct = run_parallel(st, pc, *extras, num_streams=S, super_chunk=3,
                              backend="threads")
        pv, cv = run_parallel(st, pc, *extras, num_streams=S, super_chunk=3,
                              backend="vmap")
        if pt is None:
            assert pv is None, name
        else:
            assert np.array_equal(np.asarray(pt), np.asarray(pv)), name
        assert _tree_equal(ct, cv), name


def test_parallel_parts_stay_valid_partitions():
    src, dst, n, _ = random_graph(1)
    st = EdgeStream(src, dst, n, chunk_size=23)
    for S in (2, 4):
        parts, _ = run_parallel(st, GreedyCarry(n, K), num_streams=S,
                                super_chunk=2, backend="threads")
        parts = np.asarray(parts)
        valid = src != dst
        assert parts.shape == src.shape
        assert np.all(parts[valid] >= 0) and np.all(parts[valid] < K)
        assert np.all(parts[~valid] == -1)


def test_parallel_linear_carries_are_exact():
    """SUM-only carries commute with sharding: parallel degree and Θ
    sketch ingest equal the sequential result exactly, any S."""
    src, dst, n, _ = random_graph(2)
    st = EdgeStream(src, dst, n, chunk_size=17)
    ref_deg = np.asarray(compute_degrees(jnp.asarray(src), jnp.asarray(dst), n))
    _, seq_sk = run_parallel(st, SketchCarry(64, 4, seed=3), num_streams=1)
    for S in (2, 4, 8):
        _, deg = run_parallel(st, DegreeCarry(n), num_streams=S,
                              super_chunk=2, backend="threads")
        assert np.array_equal(np.asarray(deg), ref_deg), S
        _, sk = run_parallel(st, SketchCarry(64, 4, seed=3), num_streams=S,
                             super_chunk=2, backend="threads")
        assert np.array_equal(np.asarray(sk.table), np.asarray(seq_sk.table)), S
        assert np.array_equal(np.asarray(sk.seeds), np.asarray(seq_sk.seeds)), S


def test_parallel_cli_paths_run():
    """The partitioner entry points accept num_streams/super_chunk and the
    parallel S5P pipeline produces a full assignment."""
    from repro.core import S5PConfig, s5p_partition
    from repro.core.baselines import hdrf_partition

    src, dst, n, _ = random_graph(1)
    p = np.asarray(hdrf_partition(src, dst, n, K, chunk_size=31,
                                  num_streams=2, super_chunk=2))
    valid = src != dst
    assert np.all(p[valid] >= 0) and np.all(p[valid] < K)
    out = s5p_partition(src, dst, n,
                        S5PConfig(k=K, use_cms=False, chunk_size=31,
                                  num_streams=2, super_chunk=2))
    p = np.asarray(out.parts)
    assert np.all(p[valid] >= 0) and np.all(p[valid] < K)


def test_fn_carry_has_no_merge_semantics():
    fc = FnCarry((jnp.zeros((2,)),), lambda c, s, d: (c, s))
    with pytest.raises(ValueError):
        fc.merge([fc.init(), fc.init()])


# ====================================================== 3. sharding plan
@pytest.mark.parametrize("shard", ["range", "round-robin"])
def test_parallel_stream_plan_partitions_chunks(shard):
    src, dst, n, _ = random_graph(0)
    st = EdgeStream(src, dst, n, chunk_size=7)
    ps = ParallelEdgeStream(st, 3, shard=shard)
    seen = sorted(cid for lane in ps.lanes for cid in lane)
    assert seen == list(range(st.n_chunks))
    for lane in ps.lanes:  # sub-stream-local order preserves stream order
        assert lane == sorted(lane)
    assert ps.n_rounds == max(len(lane) for lane in ps.lanes)
    # n_valid bookkeeping matches the underlying chunks
    for cid in range(st.n_chunks):
        assert ps.chunk_n_valid(cid) == st.chunk_at(cid).n_valid


def test_parallel_stream_clamps_num_streams():
    src, dst, n, _ = random_graph(0)
    st = EdgeStream(src, dst, n, chunk_size=1 << 16)  # single chunk
    assert ParallelEdgeStream(st, 8).num_streams == 1
    with pytest.raises(ValueError):
        ParallelEdgeStream(st, 0)
    with pytest.raises(ValueError):
        ParallelEdgeStream(st, 2, shard="nope")


# ======================================================== 4. validation
def test_stream_rejects_bad_sizes(tmp_path):
    from repro.streaming import ShardedEdgeStream, write_shards

    src, dst, n, _ = random_graph(3)
    with pytest.raises(ValueError, match="window"):
        EdgeStream(src, dst, n, window=0)
    with pytest.raises(ValueError, match="chunk_size"):
        EdgeStream(src, dst, n, chunk_size=0)
    with pytest.raises(ValueError, match="shard_edges"):
        write_shards(tmp_path, src, dst, shard_edges=-1)
    man = write_shards(tmp_path, src, dst, shard_edges=16, n_vertices=n)
    with pytest.raises(ValueError, match="window"):
        ShardedEdgeStream(man, window=-3)
    with pytest.raises(ValueError, match="chunk_size"):
        ShardedEdgeStream(man, chunk_size=0)


def test_run_parallel_rejects_bad_knobs():
    src, dst, n, _ = random_graph(0)
    st = EdgeStream(src, dst, n, chunk_size=16)
    with pytest.raises(ValueError, match="num_streams"):
        run_parallel(st, DegreeCarry(n), num_streams=0)
    with pytest.raises(ValueError, match="super_chunk"):
        run_parallel(st, DegreeCarry(n), num_streams=2, super_chunk=0)
    with pytest.raises(ValueError, match="backend"):
        run_parallel(st, DegreeCarry(n), num_streams=2, backend="nope")


def test_cli_rejects_nonpositive_sizes(monkeypatch, capsys):
    from repro.launch import partition as cli

    for flag, val in (("--chunk-size", "0"), ("--window", "-1"),
                      ("--num-streams", "0"), ("--super-chunk", "0"),
                      ("--shard-edges", "0"), ("--k", "0"),
                      ("--chunk-size", "abc")):
        monkeypatch.setattr(sys, "argv", ["partition", flag, val])
        with pytest.raises(SystemExit) as exc:
            cli.main()
        assert exc.value.code == 2  # argparse usage error, not a traceback
        err = capsys.readouterr().err
        assert ("must be >= 1" in err or "expected an integer" in err
                or "chunk count >= 1 or 'auto'" in err)
    # the library-level entry validates too (not just argparse)
    with pytest.raises(ValueError, match="num_streams"):
        cli.run("toy", 4, "hdrf", num_streams=0)


# ===================== 4b. S5P bundle round-trip + repr-version guard
def test_s5p_insert_then_delete_restores_carry_golden():
    """Inserting a 10 % delta then deleting it bitwise-restores the
    pre-delta S5P carry bundle, golden-anchored: the restored parts hash
    is the pinned sequential golden of tests/test_streaming.py."""
    import hashlib

    from repro.core import S5PConfig
    from repro.incremental import (
        JOURNAL_PREFIX,
        s5p_apply_delta,
        s5p_apply_deletion,
        s5p_cold_bundle,
    )

    def _h(a):
        return hashlib.sha256(
            np.ascontiguousarray(np.asarray(a)).tobytes()).hexdigest()[:16]

    src, dst, n, _ = random_graph(0)
    # the seed-era game parameters of the pinned goldens; refinement off so
    # the insertion keeps its rollback journal intact
    cfg = S5PConfig(k=4, use_cms=False, game_accept_prob=0.7,
                    game_max_rounds=64, seed=0,
                    drift_rf_threshold=float("inf"),
                    drift_balance_threshold=float("inf"),
                    drift_churn_threshold=float("inf"))
    _, before = s5p_cold_bundle(src, dst, n, cfg)
    assert _h(before["parts"]) == "5c2abcabc60d546d"  # GOLDEN[(0, "s5p")]
    E0 = len(src)
    rng = np.random.default_rng(9)
    m = max(E0 // 10, 4)
    full_src = np.concatenate([src, rng.integers(0, n, m).astype(np.int32)])
    full_dst = np.concatenate([dst, rng.integers(0, n, m).astype(np.int32)])
    mid, _ = s5p_apply_delta(before, cfg, full_src, full_dst, E0)
    assert bool(mid["journal_valid"])
    after, res = s5p_apply_deletion(mid, cfg, full_src, full_dst,
                                    np.arange(E0, E0 + m))
    assert res.rolled_back and res.n_retracted == m
    skip = ("journal_valid", "journal_pos")
    keys = {k_ for k_ in list(before) + list(after)
            if not k_.startswith(JOURNAL_PREFIX) and k_ not in skip}
    for key in sorted(keys):
        a = np.asarray(before[key])
        b = np.asarray(after[key])
        assert a.shape == b.shape and np.array_equal(a, b), key
    assert _h(after["parts"]) == "5c2abcabc60d546d"


def test_store_rejects_pre_refactor_monotone_checkpoint(tmp_path,
                                                        monkeypatch):
    """A carry persisted under the old monotone (OR/MAX) representation
    must raise CarryMismatchError, not silently mis-restore."""
    from repro.incremental import CarryMismatchError, CarryStore
    from repro.incremental import store as store_mod

    pc = DegreeCarry(8)
    st = CarryStore(tmp_path)
    with monkeypatch.context() as mp:
        mp.setattr(store_mod, "CARRY_REPR", 1)  # simulate a v1 writer
        st.save(pc.init(), consumer="degree", config={"n": 8}, stream_pos=0)
    with pytest.raises(CarryMismatchError, match="representation"):
        st.load(consumer="degree", config={"n": 8})


# ================================== 5. 8-device mesh quality (slow lane)
SRC_DIR = str(Path(__file__).resolve().parent.parent / "src")


def _run_subprocess(code: str) -> dict:
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=1500,
        env={"PYTHONPATH": SRC_DIR, "JAX_PLATFORMS": "cpu",
             "XLA_FLAGS":
             "--xla_force_host_platform_device_count=8",
             "PATH": "/usr/bin:/bin", "HOME": "/root"},
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_parallel_quality_band_on_8device_mesh():
    """num_streams ∈ {2,4,8} on the 8-device CPU mesh (shard_map backend):
    multi-seed mean RF for greedy/HDRF/S5P stays within the pinned band of
    the sequential run, and shard_map agrees bitwise with the vmap backend
    (same plan, same integer merge algebra)."""
    res = _run_subprocess("""
        import json
        import numpy as np
        from repro.core import S5PConfig, s5p_partition, replication_factor
        from repro.core.baselines import greedy_partition, hdrf_partition
        from repro.graphs.generators import community_graph

        CS = 512
        K = 8
        out = {"band": {}, "bitwise": None}
        algos = {
            "greedy": lambda s, d, n, **kw: greedy_partition(
                s, d, n, K, chunk_size=CS, **kw),
            "hdrf": lambda s, d, n, **kw: hdrf_partition(
                s, d, n, K, chunk_size=CS, **kw),
            "s5p": lambda s, d, n, **kw: s5p_partition(
                s, d, n, S5PConfig(k=K, use_cms=False, chunk_size=CS, **kw)
            ).parts,
        }
        graphs = [community_graph(1200, n_communities=24, avg_degree=8,
                                  seed=s) for s in (0, 1)]
        for name, fn in algos.items():
            seq = [replication_factor(s, d, fn(s, d, n), n_vertices=n, k=K)
                   for s, d, n in graphs]
            for S in (2, 8):
                # 8 devices >= S: run_parallel resolves to shard_map here
                kw = dict(num_streams=S, super_chunk=4)
                rfs = []
                for s, d, n in graphs:
                    parts = fn(s, d, n, **kw)
                    p = np.asarray(parts)
                    valid = np.asarray(s) != np.asarray(d)
                    assert (p[valid] >= 0).all() and (p[valid] < K).all()
                    rfs.append(replication_factor(s, d, parts,
                                                  n_vertices=n, k=K))
                out["band"][f"{name}/S{S}"] = [float(np.mean(rfs)),
                                               float(np.mean(seq))]
        # shard_map vs vmap bitwise agreement on the real 8-wide mesh
        from repro.streaming import EdgeStream, run_parallel
        from repro.kernels.stream_scan import HdrfCarry
        s, d, n = graphs[0]
        st = EdgeStream(s, d, n, chunk_size=CS)
        pc = HdrfCarry(n, K)
        pa, _ = run_parallel(st, pc, num_streams=8, super_chunk=4,
                             backend="shard_map")
        pb, _ = run_parallel(st, pc, num_streams=8, super_chunk=4,
                             backend="vmap")
        out["bitwise"] = bool(np.array_equal(np.asarray(pa), np.asarray(pb)))
        print(json.dumps(out))
    """)
    assert res["bitwise"], "shard_map and vmap backends diverged"
    for key, (rf_par, rf_seq) in res["band"].items():
        # the pinned tolerance band: S-way carry staleness may cost RF but
        # boundedly so (and may help S5P — more, smaller clusters)
        assert 0.6 * rf_seq <= rf_par <= 1.75 * rf_seq + 0.05, (key, res)
