"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.cms import cms_query, cms_update, make_sketch
from repro.kernels.cin import cin_layer_kernel, cin_layer_ref
from repro.kernels.cms_sketch import cms_query_kernel, cms_update_kernel
from repro.kernels.flash_attention import attention_ref, flash_attention_tpu
from repro.kernels.segment_agg import segment_agg_ref, segment_aggregate


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [
    (1, 128, 4, 2, 64),   # small GQA
    (2, 256, 8, 8, 64),   # MHA (G=1)
    (1, 200, 6, 2, 32),   # ragged (padding path)
])
@pytest.mark.parametrize("window", [None, 64])
def test_flash_kernel_sweep(shape, dtype, window):
    B, S, H, KV, hd = shape
    ks = jax.random.split(jax.random.PRNGKey(hash((shape, window)) % 2**31), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd)).astype(dtype)
    k = jax.random.normal(ks[1], (B, S, KV, hd)).astype(dtype)
    v = jax.random.normal(ks[2], (B, S, KV, hd)).astype(dtype)
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    out = flash_attention_tpu(q, k, v, pos, pos, causal=True, window=window,
                              block_q=64, block_k=64)
    G = H // KV
    qk = q.reshape(B, S, KV, G, hd).transpose(0, 2, 1, 3, 4).reshape(B * KV, S, G * hd)
    kk = k.transpose(0, 2, 1, 3).reshape(B * KV, S, hd)
    vk = v.transpose(0, 2, 1, 3).reshape(B * KV, S, hd)
    pp = jnp.repeat(pos, KV, axis=0)
    ref = attention_ref(qk, kk, vk, pp, pp, causal=True, window=window)
    ref = ref.reshape(B, KV, S, G, hd).transpose(0, 2, 1, 3, 4).reshape(B, S, H, hd)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=tol)


@pytest.mark.parametrize("width,depth,n", [(64, 4, 1000), (256, 5, 5000),
                                           (32, 3, 100)])
def test_cms_kernel_bit_exact(width, depth, n):
    sk = make_sketch(width, depth, seed=width)
    keys = jax.random.randint(jax.random.PRNGKey(n), (n,), 0, 2**31 - 1
                              ).astype(jnp.uint32)
    ref = cms_update(sk, keys)
    out = cms_update_kernel(sk, keys)
    assert jnp.all(ref.table == out.table)
    q = keys[: min(n, 500)]
    assert jnp.all(cms_query(ref, q) == cms_query_kernel(out, q))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("V,E,d", [(200, 1000, 32), (513, 4097, 64), (64, 100, 16)])
def test_segment_agg_sweep(V, E, d, dtype):
    ks = jax.random.split(jax.random.PRNGKey(V + E), 4)
    x = jax.random.normal(ks[0], (V, d)).astype(dtype)
    src = jax.random.randint(ks[1], (E,), 0, V, dtype=jnp.int32)
    dst = jax.random.randint(ks[2], (E,), 0, V, dtype=jnp.int32)
    w = jax.random.uniform(ks[3], (E,))
    out = segment_aggregate(x, src, dst, w, V)
    ref = segment_agg_ref(x, src, dst, w, V)
    tol = 1e-1 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,Hk,m,D,Hn", [(64, 10, 6, 8, 12), (300, 39, 39, 10, 200)])
def test_cin_kernel_sweep(B, Hk, m, D, Hn, dtype):
    ks = jax.random.split(jax.random.PRNGKey(B), 3)
    xk = jax.random.normal(ks[0], (B, Hk, D)).astype(dtype)
    x0 = jax.random.normal(ks[1], (B, m, D)).astype(dtype)
    w = (jax.random.normal(ks[2], (Hk * m, Hn)) * 0.1).astype(dtype)
    out = cin_layer_kernel(xk, x0, w, batch_block=64)
    ref = cin_layer_ref(xk, x0, w)
    tol = 1e-1 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=1e-2)


# ===========================================================================
# stream_scan megakernel: one dispatch per chunk, insert + retract (sign=±1)
# ===========================================================================

from proptest import cases, random_graph  # noqa: E402
from repro.kernels import stream_scan as ss  # noqa: E402

try:  # optional — the container image has no hypothesis; gate, don't require
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st_

    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

_ON_CPU = jax.default_backend() == "cpu"
K = 4


def _graph(seed, cap=400):
    src, dst, n, label = random_graph(seed)
    return (jnp.asarray(src[:cap], jnp.int32),
            jnp.asarray(dst[:cap], jnp.int32), n, label)


def _scoring_carry(mode, n):
    if mode == "greedy":
        return ss.greedy_init(n, K)
    return ss.hdrf_init(n, K, 1.1)


def _scoring_step_ref(mode, carry, src, dst):
    fn = ss.greedy_chunk if mode == "greedy" else ss.hdrf_chunk
    return fn(carry, src, dst)


def _scoring_step_kernel(mode, carry, src, dst, tiled, block=64):
    if mode == "greedy":
        parts, load, rep, _ = ss.scoring_scan(
            src, dst, carry[0], carry[1], mode=mode, tiled=tiled, block=block)
        return (load, rep), parts
    parts, load, rep, pd = ss.scoring_scan(
        src, dst, carry[0], carry[1], carry[2], carry[3], mode=mode,
        tiled=tiled, block=block)
    return (load, rep, pd, carry[3], carry[4]), parts


def _tree_bitwise(a, b, label=""):
    for i, (x, y) in enumerate(zip(a, b)):
        assert np.array_equal(np.asarray(x), np.asarray(y)), f"{label} leaf {i}"


@pytest.mark.parametrize("tiled", [False, True], ids=["fused", "tiled"])
@pytest.mark.parametrize("mode", ["greedy", "hdrf"])
@pytest.mark.parametrize("seed", list(cases(3)))
def test_scoring_scan_insert_parity(seed, mode, tiled):
    """Megakernel insert is bit-identical to the lax.scan oracle — exact
    counted replica table, not just the 0/1 scoring projection."""
    src, dst, n, label = _graph(seed)
    if src.shape[0] == 0:
        return
    carry = _scoring_carry(mode, n)
    ref_carry, ref_parts = _scoring_step_ref(mode, carry, src, dst)
    out_carry, parts = _scoring_step_kernel(mode, carry, src, dst, tiled)
    assert np.array_equal(np.asarray(parts), np.asarray(ref_parts)), label
    _tree_bitwise(out_carry, ref_carry, label)


@pytest.mark.parametrize("tiled", [False, True], ids=["fused", "tiled"])
@pytest.mark.parametrize("mode", ["greedy", "hdrf"])
@pytest.mark.parametrize("seed", list(cases(3)))
def test_scoring_retract_is_bitwise_inverse(seed, mode, tiled):
    """retract_chunk through the kernel (same kernel, sign=-1) undoes
    step_chunk exactly — the counted-table roundtrip property."""
    src, dst, n, label = _graph(seed)
    E = int(src.shape[0])
    if E == 0:
        return
    carry0 = _scoring_carry(mode, n)
    carry1, parts = _scoring_step_kernel(mode, carry0, src, dst, tiled)
    if mode == "greedy":
        _, load, rep, _ = ss.scoring_scan(
            src, dst, carry1[0], carry1[1], mode=mode, sign=-1, parts=parts,
            n_valid=E, tiled=tiled, block=64)
        back = (load, rep)
    else:
        _, load, rep, pd = ss.scoring_scan(
            src, dst, carry1[0], carry1[1], carry1[2], carry1[3], mode=mode,
            sign=-1, parts=parts, n_valid=E, tiled=tiled, block=64)
        back = (load, rep, pd, carry1[3], carry1[4])
    _tree_bitwise(back, carry0, label)


@pytest.mark.parametrize("tiled", [False, True], ids=["fused", "tiled"])
def test_hdrf_scan_parity_once_loads_absorb_eps(tiled):
    """Past 2**15 edges per partition f32 absorbs HDRF's ε, so a full load
    tie would score 0/0 = NaN on every lane (and XLA's argmax over NaNs
    differs between CPU and TPU).  Oracle and kernel both give a tie a zero
    balance term, as ε does in exact arithmetic: a tie picks the same
    partition at any load."""
    src, dst, n, label = _graph(0)
    carry0 = ss.hdrf_init(n, K, 1.1)
    carry = (jnp.full((K,), 40_000, jnp.int32),) + tuple(carry0[1:])
    ref_carry, ref_parts = ss.hdrf_chunk(carry, src, dst)
    _, low_parts = ss.hdrf_chunk(carry0, src, dst)
    assert int(ref_parts[0]) == int(low_parts[0])
    out_carry, parts = _scoring_step_kernel("hdrf", carry, src, dst, tiled)
    assert np.array_equal(np.asarray(parts), np.asarray(ref_parts)), label
    _tree_bitwise(out_carry, ref_carry, label)


@pytest.mark.parametrize("mode", ["greedy", "hdrf"])
def test_carry_retract_kernel_matches_oracle(mode):
    """GreedyCarry/HdrfCarry retract through the kernel == the vectorized
    oracle retraction, bitwise (deletion batches chunk arbitrarily)."""
    src, dst, n, _ = _graph(1)
    E = int(src.shape[0])
    pc_k = (ss.GreedyCarry(n, K, use_kernel=True) if mode == "greedy"
            else ss.HdrfCarry(n, K, use_kernel=True))
    pc_o = (ss.GreedyCarry(n, K, use_kernel=False) if mode == "greedy"
            else ss.HdrfCarry(n, K, use_kernel=False))
    carry, parts = pc_k.step_chunk(pc_k.init(), src, dst, jnp.int32(E))
    nv = jnp.int32(max(E - 37, 1))  # partial retraction exercises the limit
    a = pc_k.retract_chunk(carry, src, dst, nv, parts)
    b = pc_o.retract_chunk(carry, src, dst, nv, parts)
    _tree_bitwise(a, b, mode)


if HAVE_HYPOTHESIS:

    @settings(max_examples=25, deadline=None,
              suppress_health_check=[HealthCheck.too_slow])
    @given(data=st_.data())
    def test_scoring_roundtrip_property(data):
        n = data.draw(st_.integers(4, 40), label="n")
        E = data.draw(st_.integers(1, 120), label="E")
        mode = data.draw(st_.sampled_from(["greedy", "hdrf"]), label="mode")
        edges = st_.integers(0, n - 1)
        src = jnp.asarray(data.draw(st_.lists(edges, min_size=E, max_size=E)),
                          jnp.int32)
        dst = jnp.asarray(data.draw(st_.lists(edges, min_size=E, max_size=E)),
                          jnp.int32)
        carry0 = _scoring_carry(mode, n)
        carry1, parts = _scoring_step_kernel(mode, carry0, src, dst, False)
        if mode == "greedy":
            _, load, rep, _ = ss.scoring_scan(
                src, dst, carry1[0], carry1[1], mode=mode, sign=-1,
                parts=parts, n_valid=E, block=64)
            back = (load, rep)
        else:
            _, load, rep, pd = ss.scoring_scan(
                src, dst, carry1[0], carry1[1], carry1[2], carry1[3],
                mode=mode, sign=-1, parts=parts, n_valid=E, block=64)
            back = (load, rep, pd, carry1[3], carry1[4])
        _tree_bitwise(back, carry0, mode)


# --------------------------------------------------- Alg. 1 / Alg. 3 kernels


# V just past the SMEM gate: the tiled rung, one array in VMEM
V_TILED = 28_000
# each rung's (vertex count or None for the graph's own, arrays in VMEM)
_CLUSTER_RUNGS = {
    "fused": (None, lambda V, E: ()),
    "tiled": (V_TILED, ss.cluster_vmem_arrays),
    "all-vmem": (None, lambda V, E: ss.CLUSTER_ARRAYS),
}


@pytest.mark.parametrize("rung", list(_CLUSTER_RUNGS))
@pytest.mark.parametrize("global_tail", [False, True], ids=["s5p", "s5p-b"])
@pytest.mark.parametrize("seed", list(cases(3)))
def test_cluster_scan_parity(seed, global_tail, rung):
    """Every leaf bitwise equal to the lax.scan oracle over two chunks (the
    second starts from assigned vertices), each with a ragged last block."""
    from repro.core.clustering import compute_degrees, init_state

    src, dst, n, label = _graph(seed)
    if src.shape[0] == 0:
        return
    V, place = _CLUSTER_RUNGS[rung]
    V = V or n
    vmem = place(V, int(src.shape[0]))
    assert bool(vmem) == (rung != "fused")
    deg = compute_degrees(src, dst, V)
    xi = max(int(np.asarray(deg[:n]).mean()), 1)
    kappa = max(2 * int(src.shape[0]) // K, 2)
    kw = dict(xi=xi, kappa=kappa, global_tail=global_tail)
    ref = out = tuple(init_state(V))
    for s, d in ((src, dst), (dst, src)):
        ref = ss.cluster_chunk_oracle(ref, s, d, deg, **kw)
        out = ss.cluster_scan(out, s, d, deg, vmem=vmem, block=64, **kw)
        _tree_bitwise(out, ref, label)


@pytest.mark.parametrize("seed", list(cases(3)))
def test_assign_scan_parity(seed):
    src, dst, n, label = _graph(seed)
    E = int(src.shape[0])
    if E == 0:
        return
    rng = np.random.default_rng(seed)
    n_cl = 8
    c2p = jnp.asarray(rng.integers(0, K, n_cl), jnp.int32)
    cu = jnp.asarray(rng.integers(0, n_cl, E), jnp.int32)
    cv = jnp.asarray(rng.integers(0, n_cl, E), jnp.int32)
    head = jnp.asarray(rng.integers(0, 2, E), jnp.int32)
    load0 = jnp.zeros((K,), jnp.int32)
    L = max(E // (2 * K), 1)  # tight cap: exercise the overflow branches
    ref_load, ref_parts = ss.assign_chunk_oracle(
        load0, jnp.int32(L), src, dst, head, cu, cv, c2p, k=K)
    parts, load = ss.assign_scan(load0, src, dst, head, c2p[cu], c2p[cv],
                                 max_load=L, block=64)
    assert np.array_equal(np.asarray(parts), np.asarray(ref_parts)), label
    assert np.array_equal(np.asarray(load), np.asarray(ref_load))
    # retract through the same kernel == the vectorized oracle
    from repro.core.postprocess import _retract_load

    nv = jnp.int32(max(E - 19, 1))
    _, l2 = ss.assign_scan(load, src, dst, head, c2p[cu], c2p[cv],
                           max_load=L, sign=-1, parts=parts, n_valid=nv,
                           block=64)
    assert np.array_equal(
        np.asarray(l2), np.asarray(_retract_load(load, src, dst, nv, parts)))


@pytest.mark.parametrize("rung", ["fused", "tiled"])
def test_cluster_carry_kernel_via_engine(rung):
    """ClusterCarry(use_kernel=True) through run_carry == oracle, bitwise,
    and each chunk counted under the rung it took."""
    from repro.core.clustering import ClusterCarry, compute_degrees
    from repro.runtime import spans
    from repro.streaming import EdgeStream, run_carry

    src, dst, n, _ = _graph(2)
    if rung == "tiled":
        n = V_TILED
    deg = compute_degrees(src, dst, n)
    st = EdgeStream(src, dst, n, chunk_size=128)
    chunks = -(-int(src.shape[0]) // 128)
    kw = dict(xi=3, kappa=max(int(src.shape[0]) // 2, 2))
    names = [f"stream_scan.cluster.{r}" for r in ("fused", "tiled", "oracle")]
    for name in names:
        spans.reset(name)
    _, a = run_carry(st, ClusterCarry(deg, n, use_kernel=True, **kw))
    _, b = run_carry(st, ClusterCarry(deg, n, use_kernel=False, **kw))
    _tree_bitwise(tuple(a), tuple(b), "cluster engine")
    counts = spans.counters()
    assert [counts.get(name, 0) for name in names] == [
        chunks if rung == "fused" else 0, chunks if rung == "tiled" else 0,
        chunks]


def test_assign_carry_kernel_via_engine():
    """AssignCarry(use_kernel=True) through run_carry == oracle, bitwise."""
    from repro.core.postprocess import AssignCarry
    from repro.streaming import EdgeStream, run_carry

    src, dst, n, _ = _graph(3)
    E = int(src.shape[0])
    rng = np.random.default_rng(3)
    n_cl = 8
    c2p = jnp.asarray(rng.integers(0, K, n_cl), jnp.int32)
    cu = jnp.asarray(rng.integers(0, n_cl, E), jnp.int32)
    cv = jnp.asarray(rng.integers(0, n_cl, E), jnp.int32)
    head = jnp.asarray(rng.integers(0, 2, E), jnp.int32)
    L = max(E // K, 1)
    st = EdgeStream(src, dst, n, chunk_size=128)
    pa, la = run_carry(st, AssignCarry(K, L, c2p, use_kernel=True),
                       head, cu, cv)
    pb, lb = run_carry(st, AssignCarry(K, L, c2p, use_kernel=False),
                       head, cu, cv)
    assert np.array_equal(np.asarray(pa), np.asarray(pb))
    assert np.array_equal(np.asarray(la), np.asarray(lb))


# --------------------------------------------------- VMEM ladder + logging


def test_vmem_budget_resolution(monkeypatch):
    monkeypatch.delenv(ss.VMEM_BUDGET_ENV, raising=False)
    assert ss.vmem_budget() == ss.DEFAULT_VMEM_BUDGET
    monkeypatch.setenv(ss.VMEM_BUDGET_ENV, "123456")
    assert ss.vmem_budget() == 123456
    assert ss.vmem_budget(777) == 777  # explicit beats env


def test_select_path_gate_boundaries():
    V, k, chunk = 100, 4, 64
    state = ss.scoring_state_bytes(V, k, "hdrf")
    tiled = ss.scoring_state_bytes(V, k, "hdrf", tiled=True)
    assert ss.select_path(V, k, chunk, mode="hdrf", budget=state) == "fused"
    assert ss.select_path(V, k, chunk, mode="hdrf",
                          budget=state - 1) == "tiled"
    assert ss.select_path(V, k, chunk, mode="hdrf", budget=tiled) == "tiled"
    assert ss.select_path(V, k, chunk, mode="hdrf",
                          budget=tiled - 1) == "oracle"
    assert ss.kernel_fits(V, k, chunk, mode="hdrf", budget=state)
    assert not ss.kernel_fits(V, k, chunk, mode="hdrf", budget=state - 1)
    # greedy state is smaller (no λ block): same budget, wider gate
    assert ss.scoring_state_bytes(V, k, "greedy") < state
    # rows pad to 128 lanes and 8 sublanes: k = 4 costs what k = 127 does,
    # and HDRF's partial-degree lane spills k = 128 into a second tile
    assert (ss.scoring_state_bytes(V, 4, "greedy")
            == ss.scoring_state_bytes(V, 127, "greedy")
            < ss.scoring_state_bytes(V, 128, "hdrf"))
    assert (ss.scoring_state_bytes(97, k, "greedy")
            == ss.scoring_state_bytes(104, k, "greedy"))
    # the cluster ladder's fused rung is gated by SMEM (1-D arrays in
    # 1024-word tiles), whatever the VMEM budget; past it the tiled rung
    # moves the arrays SMEM cannot hold to VMEM, gated by the budget
    for budget in (None, 1):
        assert ss.select_path(27648, 1, 1 << 16, consumer="cluster",
                              budget=budget) == "fused"
    assert ss.cluster_state_bytes(27648) <= ss.SMEM_BYTES
    assert ss.cluster_state_bytes(27649) > ss.SMEM_BYTES
    assert ss.cluster_vmem_arrays(27648) == ()
    assert ss.cluster_vmem_arrays(27649) == ("alloc_h",)
    assert ss.cluster_vmem_arrays(1 << 16) == ss.CLUSTER_SMEM_ORDER[3:]
    need = ss.cluster_state_bytes(27649, tiled=True)
    assert ss.select_path(27649, 1, 1 << 16, consumer="cluster",
                          budget=need) == "tiled"
    assert ss.select_path(27649, 1, 1 << 16, consumer="cluster",
                          budget=need - 1) == "oracle"
    assert ss.select_path(27649, 1, 1 << 16, consumer="cluster") == "tiled"
    # VMEM rows pad to whole (8, 128) tiles: 1,024 vertices a step
    assert (ss.cluster_state_bytes(1 << 16, tiled=True)
            == ss.cluster_state_bytes((1 << 16) - 1023, tiled=True))
    # Alg. 3 holds one load row: fused unless the budget cannot hold it
    abytes = ss.assign_state_bytes(k)
    assert ss.select_path(0, k, 1 << 16, consumer="assign",
                          budget=abytes) == "fused"
    assert ss.select_path(0, k, 1 << 16, consumer="assign",
                          budget=abytes - 1) == "oracle"


def test_path_logged_once_per_run(caplog):
    ss.reset_path_log()
    with caplog.at_level("INFO", logger="repro.kernels.stream_scan.ops"):
        ss.select_path(100, 4, 64, mode="greedy", budget=1 << 20)
        ss.select_path(100, 4, 64, mode="greedy", budget=1 << 20)
    hits = [r for r in caplog.records if "greedy" in r.getMessage()]
    assert len(hits) == 1 and "fused" in hits[0].getMessage()
    ss.reset_path_log()
    with caplog.at_level("INFO", logger="repro.kernels.stream_scan.ops"):
        ss.select_path(100, 4, 64, mode="greedy", budget=1 << 20)
    assert len([r for r in caplog.records
                if "greedy" in r.getMessage()]) == 2  # re-armed


def test_ladder_tiled_path_bitwise_via_carry():
    """A budget too small for the fused table (but fine for edge ids)
    forces the tiled rung — results must stay bitwise-oracle."""
    src, dst, n, _ = _graph(0)
    E = int(src.shape[0])
    if E == 0:
        return
    tight = ss.scoring_state_bytes(n, K, "hdrf") - 1
    pc_t = ss.HdrfCarry(n, K, use_kernel=True, vmem_budget=tight)
    pc_o = ss.HdrfCarry(n, K, use_kernel=False)
    ca, pa = pc_t.step_chunk(pc_t.init(), src, dst, jnp.int32(E))
    cb, pb = pc_o.step_chunk(pc_o.init(), src, dst, jnp.int32(E))
    assert np.array_equal(np.asarray(pa), np.asarray(pb))
    _tree_bitwise(ca, cb, "tiled ladder")


def test_dispatch_count_one_per_chunk():
    """The acceptance contract on CPU: 1 pallas_call per chunk (the oracle
    re-materializes the carry per edge inside its scan)."""
    from repro.streaming import EdgeStream, run_carry

    src, dst, n, _ = _graph(1)
    E = int(src.shape[0])
    chunk = 100
    st = EdgeStream(src, dst, n, chunk_size=chunk)
    ss.reset_dispatch_count()
    run_carry(st, ss.GreedyCarry(n, K, use_kernel=True))
    assert ss.dispatch_count() == -(-E // chunk)


# --------------------------------------------------- compiled (accelerator)


@pytest.mark.skipif(_ON_CPU, reason="compiled Pallas needs a TPU/GPU backend")
@pytest.mark.parametrize("mode", ["greedy", "hdrf"])
def test_scoring_scan_compiled_matches_oracle(mode):
    """Accelerator lane: the compiled (non-interpret) megakernel against
    the XLA oracle.  Skips cleanly on CPU-only hosts."""
    src, dst, n, _ = _graph(0)
    if src.shape[0] == 0:
        return
    carry = _scoring_carry(mode, n)
    ref_carry, ref_parts = _scoring_step_ref(mode, carry, src, dst)
    if mode == "greedy":
        parts, load, rep, _ = ss.scoring_scan(
            src, dst, carry[0], carry[1], mode=mode, interpret=False)
        out_carry = (load, rep)
    else:
        parts, load, rep, pd = ss.scoring_scan(
            src, dst, carry[0], carry[1], carry[2], carry[3], mode=mode,
            interpret=False)
        out_carry = (load, rep, pd, carry[3], carry[4])
    assert np.array_equal(np.asarray(parts), np.asarray(ref_parts))
    _tree_bitwise(out_carry, ref_carry, mode)


@pytest.mark.skipif(_ON_CPU, reason="compiled Pallas needs a TPU/GPU backend")
def test_cluster_scan_compiled_matches_oracle():
    from repro.core.clustering import compute_degrees, init_state

    src, dst, n, _ = _graph(1)
    if src.shape[0] == 0:
        return
    deg = compute_degrees(src, dst, n)
    s0 = tuple(init_state(n))
    kw = dict(xi=3, kappa=max(int(src.shape[0]) // 2, 2))
    ref = ss.cluster_chunk_oracle(s0, src, dst, deg, **kw)
    out = ss.cluster_scan(s0, src, dst, deg, interpret=False, **kw)
    _tree_bitwise(out, ref, "cluster compiled")
