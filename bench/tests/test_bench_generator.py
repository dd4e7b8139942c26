"""The Kronecker generator: determinism, Graph500's parameters, and its
vertex permutation and edge shuffle."""

import json

import numpy as np

from bench import harness

KRON = harness.load("graphs", "kronecker")
G500 = {"scale": 12, "edgefactor": 16, "A": 0.57, "B": 0.19, "C": 0.19}


def test_same_seed_same_graph_other_seed_other_graph():
    a = KRON.generate(G500, 2**31 + 11)  # seeds past int32 are fine
    b = KRON.generate(G500, 2**31 + 11)
    c = KRON.generate(G500, 5)
    assert all(np.array_equal(x, y) for x, y in zip(a[:2], b[:2]))
    assert a[2] == 1 << 12
    assert not np.array_equal(a[0], c[0])


def test_configs_use_graph500_parameters():
    for name in ("s5p-g500-s16-k32", "hdrf-g500-s18-k32"):
        cfg = json.loads((harness.BENCH / "configs" / f"{name}.json")
                         .read_text())
        g = cfg["graph"]
        assert (g["generator"], g["edgefactor"], g["A"], g["B"], g["C"]) \
            == ("kronecker", 16, 0.57, 0.19, 0.19)


def test_quadrant_frequencies_are_the_initiator():
    src, dst = KRON.draw(np.random.default_rng(0), 12, 16, 0.57, 0.19, 0.19)
    top_s, top_d = src >> 11, dst >> 11
    freq = [np.mean((top_s == i) & (top_d == j)) for i in (0, 1)
            for j in (0, 1)]
    np.testing.assert_allclose(freq, [0.57, 0.19, 0.19, 0.05], atol=0.004)


def test_labels_permuted_and_order_shuffled():
    n = 1 << 12
    src, dst, _ = KRON.generate(G500, 3)
    rng = np.random.default_rng(3)
    raw = KRON.simple(*KRON.draw(rng, 12, 16, 0.57, 0.19, 0.19), n)
    deg = np.bincount(src, minlength=n) + np.bincount(dst, minlength=n)
    raw_deg = (np.bincount(raw[0], minlength=n)
               + np.bincount(raw[1], minlength=n))
    # the same graph up to labels: same edge count and degree sequence
    assert src.size == raw[0].size
    np.testing.assert_array_equal(np.sort(deg), np.sort(raw_deg))
    # R-MAT's hub is vertex 0 before relabelling, and not after it
    assert raw_deg.argmax() == 0 and deg.argmax() != 0
    # arrival order is shuffled: the first edges are not the raw first edges
    perm = rng.permutation(n)
    assert not np.array_equal(perm[raw[0][:100]], src[:100])
    # no self-loops, no repeated undirected edge
    assert not np.any(src == dst)
    key = np.minimum(src, dst).astype(np.int64) * n + np.maximum(src, dst)
    assert np.unique(key).size == key.size


def test_structure_seed_fixes_edges_and_order_seed_draws_labels():
    params = dict(G500, structure_seed=4)
    a_src, a_dst, n = KRON.generate(params, 2**31 + 7)
    b_src, b_dst, _ = KRON.generate(params, 9)
    assert not np.array_equal(a_src, b_src)
    # one relabelling maps one run's stream onto the other, edge by edge
    relabel = np.full(n, -1, np.int64)
    relabel[a_src] = b_src
    relabel[a_dst] = b_dst
    np.testing.assert_array_equal(relabel[a_src], b_src)
    np.testing.assert_array_equal(relabel[a_dst], b_dst)
    used = relabel[relabel >= 0]
    assert np.unique(used).size == used.size


def test_configured_partitioner_seed_is_the_job_seed():
    assert harness.job_seed(2**31 + 5, {}) == 5
    assert harness.job_seed(2**31 + 5, {"seed": 0}) == 0
