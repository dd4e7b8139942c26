"""The one traffic generator: a mix file's parameters applied to a graph.

A mix (``bench/traffic/<name>.json``) says in what order the edges of the
configuration's graph reach the partitioner:

- ``order``: ``"as_generated"`` keeps the generator's arrival order (for
  the Kronecker graphs, Graph500's shuffle);
- ``about``: one line for the reader.

Jobs run back to back in a closed loop; every job partitions the whole
stream with fresh state.  A mix that needs another order, or arguments
for the partitioner's entry, adds it here together with the cell that
uses it.
"""

from __future__ import annotations

import numpy as np

KEYS = ("order", "about")
ORDERS = ("as_generated",)


def apply(traffic: dict, src: np.ndarray, dst: np.ndarray):
    """(src, dst) in the mix's arrival order."""
    unknown = sorted(set(traffic) - set(KEYS))
    if unknown:
        raise ValueError(f"unknown traffic keys {unknown}; one of {KEYS}")
    order = traffic.get("order", "as_generated")
    if order not in ORDERS:
        raise ValueError(f"unknown order {order!r}; one of {ORDERS}")
    return src, dst
