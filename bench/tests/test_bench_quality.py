"""The benchmark's copies of RF and balance agree with the program's."""

import numpy as np
import pytest

from bench import harness, quality
from repro.core import metrics


@pytest.mark.parametrize("seed", [0, 7])
def test_rf_and_balance_match_core_metrics(seed):
    g = {"scale": 10, "edgefactor": 16, "A": 0.57, "B": 0.19, "C": 0.19}
    src, dst, n = harness.load("graphs", "kronecker").generate(g, seed)
    k = 32
    parts = np.random.default_rng(seed).integers(0, k, src.size)
    parts[::97] = -1  # unplaced edges count nowhere
    parts = parts.astype(np.int32)
    assert quality.replication_factor(src, dst, parts, n_vertices=n, k=k) \
        == pytest.approx(metrics.replication_factor(
            src, dst, parts, n_vertices=n, k=k), rel=1e-6)
    assert quality.load_balance(parts, k=k) == pytest.approx(
        metrics.load_balance(parts, k=k), rel=1e-6)


def test_rf_counts_only_vertices_with_edges():
    src = np.array([0, 0, 1], np.int32)
    dst = np.array([1, 2, 2], np.int32)
    parts = np.array([0, 1, 1], np.int32)
    # vertex 0 on {0, 1}, 1 on {0, 1}, 2 on {1}; vertex 3 has no edge
    assert quality.replication_factor(src, dst, parts, n_vertices=4,
                                      k=2) == pytest.approx(5 / 3)
    assert quality.load_balance(parts, k=2) == pytest.approx(2 * 2 / 3)
