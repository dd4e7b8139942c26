"""Dispatch layer: the fused → tiled → oracle degradation ladder.

``select_path`` picks, per (state size, chunk size), how a chunk runs:

- **fused** — the per-vertex state fits on chip; one blocked-grid
  megakernel dispatch per chunk with the state resident across grid steps
  (the packed scoring table in VMEM, Algorithm 1's scalars in SMEM);
- **tiled** — the state is too big for the fused layout; same single
  dispatch.  Scoring keeps the table in HBM and DMAs the two endpoint rows
  of each edge; Algorithm 1 keeps in SMEM the arrays of
  :data:`CLUSTER_SMEM_ORDER` that fit there and holds the rest in VMEM,
  one ``(ceil(V / 128), 128)`` scratch each;
- **oracle** — nothing fits (or the consumer has no kernel variant): the
  jitted ``lax.scan`` reference.

The gate counts bytes as the chip lays them out (measured against the
TPU compiler's own out-of-memory reports for v5e):

- VMEM scratch tiles int32 as (8, 128): a ``(V, W)`` table costs
  ``roundup(V, 8) · roundup(W, 128) · 4`` bytes;
- a pipelined ``(1, W)`` block costs ``roundup(W, 128) · 4`` bytes per
  buffer, two buffers each for the input and the output;
- SMEM holds 1-D int32 arrays in 1024-word tiles; the blocked per-edge
  operands take two buffers each, and v5e has 1 MiB of SMEM in all.

Byte counts of the cluster consumer at the default 65,536-edge chunk:
fused, everything in SMEM, up to V = 27,648; tiled, VMEM
``Σ roundup(ceil(words / 128), 8) · 512`` over the arrays SMEM cannot
hold, plus 1 KiB the compiler keeps (V = 65,536: six arrays, 1.5 MiB),
up to V = 254,976 at the 8 MiB budget, where the degree table too leaves
SMEM; oracle beyond.

The VMEM budget resolves explicit argument → ``REPRO_VMEM_BUDGET`` env var
→ 8 MiB default, and is also handed to the compiler as the kernel's VMEM
limit, so a state the gate admits is a state the compiler accepts.  The
chosen path is logged once per (consumer, mode, path) per process and
listed by ``paths_taken`` (``reset_path_log`` re-arms both, e.g. for
tests).

The scoring baselines' :class:`~repro.streaming.carry.PartitionerCarry`
implementations live here too (``GreedyCarry`` / ``HdrfCarry`` /
``GridCarry``): they wrap the ladder dispatch as ``step_chunk`` /
``retract_chunk`` and declare the parallel-ingest merge algebra — counted
replica tables COUNTED, loads/partial degrees SUM, scenario constants
(λ, k-mask, grid tables) replicated — so oracle and kernel stay in
lockstep behind one protocol surface.  Since the counted megakernel,
**retraction is the same kernel invoked with ``sign=-1``**: the replica
counters update in-kernel (the seed's separate ``_recount`` scatter-add
patch is gone), and deleting an edge subtracts exactly the load /
replica-count / partial-degree accounting its insertion added.
"""

from __future__ import annotations

import logging
import os

import jax

from ...streaming.carry import COUNTED, REPLICATED, SUM, PartitionerCarry
from .kernel import DEFAULT_BLOCK, LANES, scoring_scan, table_width
from . import ref as _ref

__all__ = [
    "CLUSTER_SMEM_ORDER",
    "DEFAULT_VMEM_BUDGET",
    "GreedyCarry",
    "GridCarry",
    "HdrfCarry",
    "SMEM_BYTES",
    "VMEM_BUDGET_ENV",
    "assign_state_bytes",
    "cluster_state_bytes",
    "cluster_vmem_arrays",
    "kernel_fits",
    "make_chunk_fn",
    "paths_taken",
    "reset_path_log",
    "scoring_state_bytes",
    "select_path",
    "vmem_budget",
]

DEFAULT_VMEM_BUDGET = 8 << 20
VMEM_BUDGET_ENV = "REPRO_VMEM_BUDGET"
SMEM_BYTES = 1 << 20  # v5e: the compiler reports "1.00M smem"

_log = logging.getLogger(__name__)
_logged_paths: set[tuple] = set()


def vmem_budget(explicit: int | None = None) -> int:
    """Resolve the VMEM budget: explicit arg → env var → 8 MiB default."""
    if explicit is not None:
        return int(explicit)
    env = os.environ.get(VMEM_BUDGET_ENV)
    if env:
        return int(env)
    return DEFAULT_VMEM_BUDGET


def _up(n: int, m: int) -> int:
    return -(-n // m) * m


def _vmem_table(rows: int, width: int) -> int:
    """VMEM scratch bytes of an int32 (rows, width) array: (8, 128) tiles."""
    return _up(rows, 8) * _up(width, LANES) * 4


def _vmem_block(width: int) -> int:
    """A pipelined (1, width) int32/f32 block: two buffers, in and out."""
    return 2 * 2 * _up(width, LANES) * 4


def _smem(words: int) -> int:
    """SMEM bytes of a 1-D int32 array: 1024-word tiles."""
    return _up(max(words, 1), 1024) * 4


def _edge_smem(chunk_size: int, operands: int) -> int:
    """Blocked per-edge SMEM operands (two buffers each) plus ``meta``."""
    block = min(DEFAULT_BLOCK, max(chunk_size, 1))
    return operands * 2 * _smem(block) + _smem(3)


def scoring_state_bytes(n_vertices: int, k: int, mode: str = "hdrf", *,
                        tiled: bool = False) -> int:
    """VMEM the scoring kernel holds: the packed table (fused) or two
    row buffers (tiled), plus the load row and HDRF's λ."""
    W = table_width(k, mode)
    small = _vmem_block(W) + (_vmem_block(1) // 2 if mode == "hdrf" else 0)
    if tiled:
        return small + 2 * _vmem_table(1, W)
    return small + _vmem_table(n_vertices, W)


# Algorithm 1's per-vertex arrays in the order they claim the kernel's
# SMEM: the degree table and the two volume arrays are read and written most
# per edge; the membership counters and the allocation record are only
# added to, which costs no scalar read in VMEM.
CLUSTER_SMEM_ORDER = ("deg", "vol_h", "vol_t", "v2c_h", "v2c_t", "ld",
                      "cnt_h", "cnt_t", "alloc_h")


def _cluster_words(name: str, n_vertices: int) -> int:
    return n_vertices + 1 if name.startswith("vol") else n_vertices


def cluster_vmem_arrays(n_vertices: int,
                        chunk_size: int = 1 << 16) -> tuple[str, ...]:
    """The Algorithm-1 arrays that do not fit in SMEM beside the ones
    before them in :data:`CLUSTER_SMEM_ORDER`, with the blocked endpoint
    ids and the two id counters: the kernel holds these in VMEM.  Empty
    when everything fits SMEM (the fused rung)."""
    free = SMEM_BYTES - _edge_smem(chunk_size, 2) - 2 * _smem(1)
    for i, name in enumerate(CLUSTER_SMEM_ORDER):
        free -= _smem(_cluster_words(name, n_vertices))
        if free < 0:
            return CLUSTER_SMEM_ORDER[i:]
    return ()


def cluster_state_bytes(n_vertices: int, chunk_size: int = 1 << 16, *,
                        tiled: bool = False) -> int:
    """Fused: the SMEM the Algorithm-1 kernel holds with every array there
    (the degree table, 6 (V,) leaves, 2 (V+1,) volume arrays with their
    sink slot, 2 id counters, and the blocked endpoint ids).  Tiled: the
    VMEM of the arrays :func:`cluster_vmem_arrays` moves there, each
    ``(ceil(words / 128), 128)``, and the 1 KiB the compiler keeps beside
    them (the least limit the v5e compiler accepts is exactly that sum)."""
    V = n_vertices
    if tiled:
        return 1024 + sum(
            _vmem_table(-(-_cluster_words(a, V) // LANES), LANES)
            for a in cluster_vmem_arrays(V, chunk_size))
    return (7 * _smem(V) + 2 * _smem(V + 1) + 2 * _smem(1)
            + _edge_smem(chunk_size, 2))


def assign_state_bytes(k: int) -> int:
    """VMEM the Algorithm-3 kernel holds: the load row."""
    return _vmem_block(table_width(k, "assign"))


def select_path(n_vertices: int, k: int, chunk_size: int, *,
                mode: str = "hdrf", budget: int | None = None,
                consumer: str = "scoring") -> str:
    """Pick ``"fused" | "tiled" | "oracle"`` for one chunk and log the
    choice once per run."""
    b = vmem_budget(budget)
    if consumer == "cluster":
        vmem = 0
        smem = cluster_state_bytes(n_vertices, chunk_size)
        path = "fused"
        if smem > SMEM_BYTES:
            moved = cluster_vmem_arrays(n_vertices, chunk_size)
            smem -= sum(_smem(_cluster_words(a, n_vertices)) for a in moved)
            vmem = cluster_state_bytes(n_vertices, chunk_size, tiled=True)
            path = "tiled" if vmem <= b else "oracle"
    elif consumer == "assign":
        vmem = assign_state_bytes(k)
        smem = _edge_smem(chunk_size, 7)
        path = "fused" if vmem <= b and smem <= SMEM_BYTES else "oracle"
    else:
        smem = _edge_smem(chunk_size, 4)
        vmem = scoring_state_bytes(n_vertices, k, mode)
        path = "fused"
        if vmem > b:
            vmem = scoring_state_bytes(n_vertices, k, mode, tiled=True)
            path = "tiled" if vmem <= b else "oracle"
    key = (consumer, mode if consumer == "scoring" else "", path)
    if key not in _logged_paths:
        _logged_paths.add(key)
        _log.info(
            "%s%s: %s path (VMEM %.1f KiB of %.1f MiB, SMEM %.1f KiB of "
            "%.1f MiB)", consumer, f"[{key[1]}]" if key[1] else "", path,
            vmem / 1024, b / (1 << 20), smem / 1024, SMEM_BYTES / (1 << 20))
    return path


def paths_taken() -> list[tuple[str, str, str]]:
    """(consumer, mode, path) of every rung chosen since the last reset
    (``mode`` is empty for the cluster and assign consumers)."""
    return sorted(_logged_paths)


def reset_path_log() -> None:
    """Re-arm the once-per-run path logging (used by tests)."""
    _logged_paths.clear()


def kernel_fits(n_vertices: int, k: int, chunk_size: int, *,
                mode: str = "hdrf", budget: int | None = None) -> bool:
    """Back-compat gate: does the *fused* scoring path fit the budget?"""
    return scoring_state_bytes(n_vertices, k, mode) <= vmem_budget(budget)


# ---------------------------------------------------------------------------
# ladder-dispatching chunk functions (engine contract)
# ---------------------------------------------------------------------------


def _greedy_kernel_chunk(carry, src, dst, *, budget=None):
    load, rep = carry
    b = vmem_budget(budget)
    path = select_path(rep.shape[0], rep.shape[1], src.shape[0],
                       mode="greedy", budget=b)
    if path == "oracle":
        return _ref.greedy_chunk(carry, src, dst)
    parts, load2, rep2, _ = scoring_scan(
        src, dst, load, rep, mode="greedy", tiled=(path == "tiled"),
        vmem_limit=b)
    return (load2, rep2), parts


def _greedy_kernel_retract(carry, src, dst, n_valid, parts, *, budget=None):
    load, rep = carry
    b = vmem_budget(budget)
    path = select_path(rep.shape[0], rep.shape[1], src.shape[0],
                       mode="greedy", budget=b)
    if path == "oracle":
        return _ref.greedy_retract_chunk(carry, src, dst, n_valid, parts)
    _, load2, rep2, _ = scoring_scan(
        src, dst, load, rep, mode="greedy", sign=-1, parts=parts,
        n_valid=n_valid, tiled=(path == "tiled"), vmem_limit=b)
    return (load2, rep2)


def _hdrf_kernel_chunk(carry, src, dst, *, budget=None):
    load, rep, pd, lam, kmask = carry
    b = vmem_budget(budget)
    path = select_path(rep.shape[0], rep.shape[1], src.shape[0],
                       mode="hdrf", budget=b)
    if path == "oracle":
        return _ref.hdrf_chunk(carry, src, dst)
    parts, load2, rep2, pd2 = scoring_scan(
        src, dst, load, rep, pd, lam, mode="hdrf", tiled=(path == "tiled"),
        vmem_limit=b)
    return (load2, rep2, pd2, lam, kmask), parts


def _hdrf_kernel_retract(carry, src, dst, n_valid, parts, *, budget=None):
    load, rep, pd, lam, kmask = carry
    b = vmem_budget(budget)
    path = select_path(rep.shape[0], rep.shape[1], src.shape[0],
                       mode="hdrf", budget=b)
    if path == "oracle":
        return _ref.hdrf_retract_chunk(carry, src, dst, n_valid, parts)
    _, load2, rep2, pd2 = scoring_scan(
        src, dst, load, rep, pd, lam, mode="hdrf", sign=-1, parts=parts,
        n_valid=n_valid, tiled=(path == "tiled"), vmem_limit=b)
    return (load2, rep2, pd2, lam, kmask)


def _auto_use_kernel(use_kernel: bool | None) -> bool:
    """None → the fused kernel on TPU, the oracle scan elsewhere
    (interpret-mode Pallas is orders slower than XLA's compiled scan)."""
    if use_kernel is None:
        return jax.default_backend() == "tpu"
    return bool(use_kernel)


def make_chunk_fn(mode: str, *, use_kernel: bool | None = None,
                  vmem_budget: int | None = None):
    """Chunk function for ``streaming.run_scan``.

    The kernel path does not implement the padded multi-k mask, so
    batched multi-k runs must use the oracle.
    """
    kern = _auto_use_kernel(use_kernel)
    if mode == "greedy":
        if kern:
            return lambda c, s, d: _greedy_kernel_chunk(c, s, d,
                                                        budget=vmem_budget)
        return _ref.greedy_chunk
    if mode == "hdrf":
        if kern:
            return lambda c, s, d: _hdrf_kernel_chunk(c, s, d,
                                                      budget=vmem_budget)
        return _ref.hdrf_chunk
    if mode == "grid":
        return _ref.grid_chunk  # O(k) carry — no replica table, nothing to fuse
    raise ValueError(f"unknown mode {mode!r}")


# ---------------------------------------------------------------------------
# PartitionerCarry implementations (oracle/kernel dispatch behind one surface)
# ---------------------------------------------------------------------------


class GreedyCarry(PartitionerCarry):
    """PowerGraph Greedy as a carry: (load SUM, replica counters COUNTED)."""

    merge_ops = (SUM, COUNTED)
    supports_retract = True
    retract_exact = True

    def __init__(self, n_vertices: int, k: int, *,
                 use_kernel: bool | None = None,
                 vmem_budget: int | None = None):
        self.n_vertices = int(n_vertices)
        self.k = int(k)
        self._use_kernel = _auto_use_kernel(use_kernel)
        self._budget = vmem_budget

    def init(self):
        return _ref.greedy_init(self.n_vertices, self.k)

    def step_chunk(self, carry, src, dst, n_valid, *extras):
        if self._use_kernel:
            return _greedy_kernel_chunk(carry, src, dst, budget=self._budget)
        return _ref.greedy_chunk(carry, src, dst)

    def retract_chunk(self, carry, src, dst, n_valid, parts, *extras):
        if self._use_kernel:
            return _greedy_kernel_retract(carry, src, dst, n_valid, parts,
                                          budget=self._budget)
        return _ref.greedy_retract_chunk(carry, src, dst, n_valid, parts)


class HdrfCarry(PartitionerCarry):
    """HDRF as a carry: (load SUM, replica counters COUNTED, partial
    degrees SUM, λ replicated, active-partition mask replicated).

    The kernel scores without the padded multi-k mask, so a carry with
    ``k_active < k`` always runs the oracle."""

    merge_ops = (SUM, COUNTED, SUM, REPLICATED, REPLICATED)
    supports_retract = True
    retract_exact = True

    def __init__(self, n_vertices: int, k: int, lam: float = 1.1, *,
                 k_active: int | None = None,
                 use_kernel: bool | None = None,
                 vmem_budget: int | None = None):
        self.n_vertices = int(n_vertices)
        self.k = int(k)
        self.lam = float(lam)
        self.k_active = k_active
        masked = k_active is not None and int(k_active) != int(k)
        self._use_kernel = _auto_use_kernel(use_kernel) and not masked
        self._budget = vmem_budget

    def init(self):
        return _ref.hdrf_init(self.n_vertices, self.k, self.lam,
                              k_active=self.k_active)

    def step_chunk(self, carry, src, dst, n_valid, *extras):
        if self._use_kernel:
            return _hdrf_kernel_chunk(carry, src, dst, budget=self._budget)
        return _ref.hdrf_chunk(carry, src, dst)

    def retract_chunk(self, carry, src, dst, n_valid, parts, *extras):
        if self._use_kernel:
            return _hdrf_kernel_retract(carry, src, dst, n_valid, parts,
                                        budget=self._budget)
        return _ref.hdrf_retract_chunk(carry, src, dst, n_valid, parts)


class GridCarry(PartitionerCarry):
    """Grid partitioning as a carry: (load SUM, row/col/#cols replicated)."""

    merge_ops = (SUM, REPLICATED, REPLICATED, REPLICATED)
    supports_retract = True
    retract_exact = True

    def __init__(self, k: int, row, col, n_cols: int):
        self.k = int(k)
        self.row = row
        self.col = col
        self.n_cols = int(n_cols)

    def init(self):
        return _ref.grid_init(self.k, self.row, self.col, self.n_cols)

    def step_chunk(self, carry, src, dst, n_valid, *extras):
        return _ref.grid_chunk(carry, src, dst)

    def retract_chunk(self, carry, src, dst, n_valid, parts, *extras):
        return _ref.grid_retract_chunk(carry, src, dst, n_valid, parts)
