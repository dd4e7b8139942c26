"""Graph500 Kronecker edge list, drawn from the run's seed.

The quadrant descent is the repository's R-MAT sampler
(``repro.graphs.generators.rmat_graph``), copied so that no later change
to the program can change the benchmark's graphs: every edge draws one
source bit and one destination bit per level with Graph500's initiator
(A, B, C, D = 1 - A - B - C).  Self-loops and duplicate undirected edges
are removed, keeping each edge's first occurrence (the repository's
convention, not Graph500's, which keeps both).  Then, as Graph500 does,
the vertex labels are permuted and the edge list is shuffled, so neither
the label nor the position of an edge says anything about its degree.
"""

from __future__ import annotations

import numpy as np


def draw(rng, scale: int, edgefactor: int, a: float, b: float, c: float):
    """Kronecker edges before clean-up: (src, dst) int64, labels unpermuted."""
    d = 1.0 - a - b - c
    if d < 0:
        raise ValueError("A + B + C must be <= 1")
    m = edgefactor << scale
    src = np.zeros(m, np.int64)
    dst = np.zeros(m, np.int64)
    for _ in range(scale):
        sbit = (rng.random(m) >= a + b).astype(np.int64)
        p_right = np.where(sbit == 0, b / (a + b), d / (c + d))
        dbit = (rng.random(m) < p_right).astype(np.int64)
        src = (src << 1) | sbit
        dst = (dst << 1) | dbit
    return src, dst


def simple(src, dst, n: int):
    """Self-loops and repeated undirected edges dropped, first kept."""
    keep = src != dst
    src, dst = src[keep], dst[keep]
    key = np.minimum(src, dst) * n + np.maximum(src, dst)
    _, first = np.unique(key, return_index=True)
    first.sort()
    return src[first], dst[first]


def generate(params: dict, seed: int):
    """(src, dst, n_vertices): int32 edge arrays in arrival order.

    With ``structure_seed`` in ``params``, the edges and their arrival
    order are drawn from it, the same for every run, and the run's seed
    draws only the vertex labels.  A partitioner whose work follows the
    stream's structure and not its labels (S5P: clusters by arrival, the
    sketch over cluster ids) then does the same work for every seed, on
    other vertex ids.  Without it the run's seed draws everything."""
    scale = int(params["scale"])
    n = 1 << scale
    fixed = "structure_seed" in params
    rng = np.random.default_rng(params["structure_seed"] if fixed else seed)
    src, dst = draw(rng, scale, int(params["edgefactor"]), float(params["A"]),
                    float(params["B"]), float(params["C"]))
    src, dst = simple(src, dst, n)
    if fixed:
        order = rng.permutation(src.size)
        perm = np.random.default_rng(seed).permutation(n)
    else:
        perm = rng.permutation(n)
        order = rng.permutation(src.size)
    return (perm[src][order].astype(np.int32),
            perm[dst][order].astype(np.int32), n)
