"""EdgeStream — chunked, replayable edge streams with pluggable orderings.

The stream owns host-resident edge arrays; the device only ever sees one
fixed-size chunk (padded with self-loops, which every consumer already
masks as no-ops).  Replay is free: ``chunks()`` is a generator over the
same deterministic order every time it is called, so the multi-pass
structure of the paper's pipeline (clustering pass → Θ pass → placement
pass) is three replays of one stream object.

Orderings (``ordering=``):

- ``"natural"``   — arrival order as given (the paper's setting);
- ``"shuffled"``  — a seeded global permutation (stream-order robustness);
- ``"dst-sorted"``— stable sort by destination (CSR-ish locality; the
  order the segment_agg kernel's data pipeline emits);
- ``"windowed"``  — bounded-buffer reordering: a sliding window of
  ``window`` edges from which the lowest-destination edge is emitted
  first (Patwary et al. 2019-style window streaming — locality gains
  without breaking the bounded-memory contract).
"""

from __future__ import annotations

import heapq
from typing import Iterator, NamedTuple, Sequence

import jax.numpy as jnp
import numpy as np

from ..runtime import spans

__all__ = ["Chunk", "EdgeStream", "ORDERINGS"]

ORDERINGS = ("natural", "shuffled", "dst-sorted", "windowed")

DEFAULT_CHUNK = 1 << 16


class Chunk(NamedTuple):
    """One device-resident slice of the stream.

    Padding entries (tail chunk only) are (0, 0) self-loops with zeroed
    extras — the masked no-op every scan consumer already skips.
    """

    src: jnp.ndarray  # (B,) int32
    dst: jnp.ndarray  # (B,) int32
    extras: tuple  # per-edge arrays sliced in the same order
    start: int  # offset of this chunk in stream order
    n_valid: int  # true (unpadded) edge count, ≤ B


def _windowed_emit(dst_iter, window: int) -> Iterator[int]:
    """Sliding-buffer reorder: emit the buffered edge with the smallest
    destination first.  Deterministic; the buffer never holds more than
    ``window`` edges (bounded memory), so no edge is emitted more than
    ``window`` slots *before* its arrival position.  Departure can be
    late without bound — a high-destination edge sits until the drain.

    Shared by the in-memory and the out-of-core engines (the latter feeds
    ``dst`` shard-by-shard), so the two orders agree by construction.
    """
    heap: list[tuple[int, int]] = []
    for i, d in enumerate(dst_iter):
        heapq.heappush(heap, (int(d), i))
        if len(heap) > window:
            yield heapq.heappop(heap)[1]
    while heap:
        yield heapq.heappop(heap)[1]


def _windowed_order(dst: np.ndarray, window: int) -> np.ndarray:
    n = dst.shape[0]
    return np.fromiter(_windowed_emit(dst, window), np.int64, count=n)


class EdgeStream:
    """Chunked multi-pass view over an edge list (bounded device memory)."""

    def __init__(
        self,
        src,
        dst,
        n_vertices: int | None = None,
        *,
        chunk_size: int = DEFAULT_CHUNK,
        ordering: str = "natural",
        seed: int = 0,
        window: int = 4096,
    ):
        if ordering not in ORDERINGS:
            raise ValueError(f"unknown ordering {ordering!r}; one of {ORDERINGS}")
        if chunk_size < 1:
            raise ValueError("chunk_size must be >= 1")
        if window < 1:
            raise ValueError("window must be >= 1")
        self.src = np.asarray(spans.to_host(src), np.int32)
        self.dst = np.asarray(spans.to_host(dst), np.int32)
        if self.src.shape != self.dst.shape:
            raise ValueError("src/dst shape mismatch")
        if n_vertices is None:  # metadata only — infer when not supplied
            n_vertices = int(max(self.src.max(), self.dst.max())) + 1 if self.src.size else 0
        self.n_vertices = int(n_vertices)
        self.chunk_size = int(chunk_size)
        self.ordering = ordering
        self.seed = int(seed)
        self.window = int(window)
        self._order = self._make_order()

    # ------------------------------------------------------------------
    def _make_order(self) -> np.ndarray | None:
        if self.ordering == "natural":
            return None
        if self.ordering == "shuffled":
            return np.random.default_rng(self.seed).permutation(self.n_edges)
        if self.ordering == "dst-sorted":
            return np.argsort(self.dst, kind="stable")
        return _windowed_order(self.dst, self.window)

    @property
    def n_edges(self) -> int:
        return int(self.src.shape[0])

    def __len__(self) -> int:
        return self.n_edges

    @property
    def n_chunks(self) -> int:
        return max(-(-self.n_edges // self.chunk_size), 1)

    @property
    def order(self) -> np.ndarray | None:
        """Stream order as a permutation of arrival indices (None = identity)."""
        return self._order

    # ------------------------------------------------------------------
    def _edges_at(self, sl, start: int, stop: int):
        """Data-access hook: edges for stream positions [start, stop).

        ``sl`` is a ``slice`` (natural order) or an int array of arrival
        indices (permuted orders); out-of-core subclasses override this to
        page from disk — everything else in :meth:`chunk_at` (padding,
        extras, dtypes) is shared, which is what makes the engines
        bit-identical.
        """
        return self.src[sl], self.dst[sl]

    def chunk_at(self, i: int, *extras, pad: bool = True) -> Chunk:
        """Build chunk ``i`` on demand — O(chunk) host/device footprint.

        ``extras`` are per-edge arrays sliced/permuted alongside src/dst
        (padded with zeros).  With ``pad=True`` every chunk of a multi-chunk
        stream has exactly ``chunk_size`` entries so one compiled scan step
        serves all chunks; a single-chunk stream comes back unpadded.
        """
        if not 0 <= i < self.n_chunks:
            raise IndexError(f"chunk {i} out of range [0, {self.n_chunks})")
        # anything already exposing .shape/__getitem__ (ndarray, memmap, an
        # out-of-core field view) passes through unmaterialized
        ex = [e if hasattr(e, "shape") else np.asarray(e) for e in extras]
        for e in ex:
            if e.shape[0] != self.n_edges:
                raise ValueError("extra array length != n_edges")
        n, cs = self.n_edges, self.chunk_size
        start = i * cs
        stop = min(start + cs, n)
        if self._order is None:
            sl = slice(start, stop)
        else:
            sl = np.asarray(self._order[start:stop])
        s, d = self._edges_at(sl, start, stop)
        exc = [e[sl] for e in ex]
        if pad and s.shape[0] < cs and start > 0:
            padn = cs - s.shape[0]
            s = np.concatenate([s, np.zeros(padn, np.int32)])
            d = np.concatenate([d, np.zeros(padn, np.int32)])
            exc = [
                np.concatenate([spans.to_host(e),
                                np.zeros((padn,) + e.shape[1:], e.dtype)])
                for e in exc
            ]
        return Chunk(
            src=jnp.asarray(s),
            dst=jnp.asarray(d),
            extras=tuple(jnp.asarray(e) for e in exc),
            start=start,
            n_valid=stop - start,
        )

    def chunks(self, *extras, pad: bool = True) -> Iterator[Chunk]:
        """Yield the stream as fixed-size chunks (a fresh replay per call);
        only one chunk is device-resident at a time — see :meth:`chunk_at`.
        """
        for i in range(self.n_chunks):
            yield self.chunk_at(i, *extras, pad=pad)

    # ------------------------------------------------------------------
    def scatter_back(self, values):
        """Map per-edge results from stream order back to arrival order.

        Works on (E,) or batched (..., E) arrays (last axis = edges).
        """
        if self._order is None:
            return values
        order = np.asarray(self._order)  # mmap-backed orders view in cheaply
        inv = np.empty(order.size, order.dtype)
        inv[order] = np.arange(order.size)
        return jnp.take(values, jnp.asarray(inv), axis=-1)
