"""Partition quality, computed by the benchmark from the parts alone.

The arithmetic of ``repro.core.metrics`` (paper Eq. 1 and Eq. 2), copied
into the benchmark in numpy so that the yardstick cannot move with the
program: ``RF = sum_v |P(v)| / |V'|`` over the vertices ``V'`` that hold
at least one placed edge, and ``balance = k * max_p |p| / |E_placed|``.
"""

from __future__ import annotations

import numpy as np


def replication_factor(src, dst, parts, *, n_vertices: int, k: int) -> float:
    parts = np.asarray(parts)
    placed = parts >= 0
    rep = np.zeros((n_vertices, k), bool)
    rep[np.asarray(src)[placed], parts[placed]] = True
    rep[np.asarray(dst)[placed], parts[placed]] = True
    replicas = rep.sum(axis=1)
    present = int((replicas > 0).sum())
    return float(replicas.sum()) / max(present, 1)


def load_balance(parts, *, k: int) -> float:
    parts = np.asarray(parts)
    loads = np.bincount(parts[parts >= 0], minlength=k)
    return float(k * loads.max()) / max(int(loads.sum()), 1)
