"""PartitionerCarry — the one carry protocol every streaming consumer speaks.

A streaming partitioner is an ``init / step_chunk / merge / finalize``
quadruple over an O(|V| + k) carry pytree:

- ``init()``        — the identity carry (empty replica bitmaps, zero loads);
- ``step_chunk``    — fold one EdgeStream chunk into the carry, optionally
  emitting per-edge results (``parts``) for that chunk;
- ``merge``         — reconcile carries produced by *independent* sub-streams
  (the HEP/CuSP parallel-ingest regime: S workers ingest disjoint chunk
  ranges, their carries are all-reduced at super-chunk boundaries);
- ``finalize``      — extract the consumer-facing result from the carry.

Merge semantics are declared **per field** via :attr:`merge_ops`, one op per
leaf of the carry pytree in ``jax.tree_util`` flattening order:

- ``SUM``        — additive statistics: partition loads, cluster volumes,
  HDRF partial-degree estimates, Θ count-min tables, degree counts.  Merging
  carries that diverged from a common ``base`` sums their *deltas*
  (``base + Σ (cᵢ − base)``), so the base is never double-counted.
- ``COUNTED``    — occupancy counters standing in for what used to be a
  monotone set: replica "bitmaps" are small per-(vertex, partition) int
  counters that **OR-project** (``> 0``) for scoring — the projection is
  bit-identical to the old boolean bitmap on insert-only streams, and the
  counter itself is an abelian-group element, so deletions subtract
  exactly (count hits 0 ⇒ the replica vanishes, no tombstone scan).
  Merge semantics are SUM.
- ``REPLICATED`` — scenario constants threaded through the carry (HDRF λ,
  the padded-k mask, grid row/col tables): identical in every sub-stream,
  merged by taking the first.
- ``OR``/``MAX`` — the legacy monotone ops (boolean union, prefer-any-
  assignment).  Kept for external ``FnCarry``-style consumers, but **no
  in-repo carry declares them anymore**: the decremental refactor moved
  every bitmap to ``COUNTED`` and every assignment/id-counter table to
  ``SUM``-of-transitions (the merged value telescopes ``base + Σ (cᵢ −
  base)``, which equals the writer's value when one sub-stream wrote it
  and a deterministic — clamped-at-projection — resolution otherwise).

Why these laws matter twice over:

1. *Parallel ingest* — ``SUM``/``COUNTED`` over integer arrays are
   associative and commutative with a shared merge base, so the merged
   carry is independent of worker count, merge tree shape, and arrival
   interleaving (``tests/test_carry.py`` pins this property-based).  That
   is the licence ``run_parallel`` needs to all-reduce carries with one
   collective per super-chunk.
2. *Deletions* — every non-replicated field now lives in an abelian
   **group**, not just a monoid: :meth:`PartitionerCarry.signed_delta`
   forms the difference of two carries, :meth:`~PartitionerCarry.negate`
   inverts it, and ``merge(merge(c, δ), −δ) == c`` holds **bitwise**
   (integer arithmetic; uint32 sketch tables are the group ℤ/2³²).
   :meth:`~PartitionerCarry.retract_chunk` is the per-chunk face of the
   same algebra: it subtracts exactly the accounting ``step_chunk`` added
   for those edges (given their recorded per-edge ``parts``), which is
   what makes edge deletion and sliding-window expiry exact for the
   scoring carries.

``CARRY_REPR`` names this representation generation; persisted carries
record it so a pre-refactor (monotone-bitmap) checkpoint is rejected with
a clear error instead of mis-restoring (see ``repro.incremental.store``).
"""

from __future__ import annotations

from typing import Any, Callable, Iterable, Sequence

import jax
import jax.numpy as jnp

__all__ = [
    "SUM",
    "COUNTED",
    "OR",
    "MAX",
    "REPLICATED",
    "MERGE_OPS",
    "CARRY_REPR",
    "PartitionerCarry",
    "FnCarry",
    "RetractCarry",
]

SUM = "sum"
COUNTED = "counted"
OR = "or"
MAX = "max"
REPLICATED = "replicated"

MERGE_OPS = (SUM, COUNTED, OR, MAX, REPLICATED)

#: group ops — fields whose values form an abelian group under merge
#: (exact negation / subtraction; the substrate of edge deletion)
GROUP_OPS = (SUM, COUNTED)

#: representation generation of the carry algebra.  2 = the counted /
#: group-structured representation (decremental); 1 was the monotone
#: OR/MAX generation, whose checkpoints must not seed this code.
CARRY_REPR = 2


def _or_leaf(a, b):
    # ∨ on bools, elementwise max on int-encoded bitmaps — both monotone
    if a.dtype == jnp.bool_:
        return a | b
    return jnp.maximum(a, b)


def _check_ops(ops: Sequence[str], n_leaves: int) -> None:
    if len(ops) != n_leaves:
        raise ValueError(
            f"merge_ops declares {len(ops)} fields but the carry has "
            f"{n_leaves} leaves")
    for op in ops:
        if op not in MERGE_OPS:
            raise ValueError(f"unknown merge op {op!r}; one of {MERGE_OPS}")


class PartitionerCarry:
    """Base class: declare :attr:`merge_ops`, implement ``init``/``step_chunk``.

    ``step_chunk(carry, src, dst, n_valid, *extras) -> (carry, parts)`` must
    be pure and traceable (``n_valid`` arrives as a traced int32 scalar so
    one compiled step serves every chunk; padding entries are (0, 0)
    self-loops, which every consumer already masks).  ``parts`` is the
    per-edge result for the chunk (or ``None`` for state-only consumers
    like clustering and the Θ pass).

    ``merge``/``merge_stacked`` are derived from :attr:`merge_ops`;
    ``finalize`` defaults to the identity.
    """

    #: one merge op per carry leaf, in ``jax.tree_util`` flattening order
    merge_ops: tuple[str, ...] = ()

    #: leaf indices (into the flattened carry) whose SUM merge resolves
    #: *concurrent* writers by keeping the lowest-lane writer's value
    #: instead of the telescoped sum.  For assignment tables (vertex →
    #: cluster ids) the telescoped ``base + Σ (cᵢ − base)`` fabricates an
    #: id whenever two lanes reassigned the same vertex within one
    #: super-chunk; pick-first keeps a *real* id one lane assigned.  When
    #: at most one lane wrote a cell the result is bit-identical to the
    #: telescoped sum, so sequential and conflict-free parallel runs are
    #: unaffected.  The group algebra (signed_delta / apply_delta) still
    #: treats these leaves as plain integers — only merging changes.
    pick_first: tuple[int, ...] = ()

    #: False for state-only consumers whose step_chunk returns parts=None
    emits_parts: bool = True

    #: True once the consumer implements :meth:`retract_chunk`
    supports_retract: bool = False

    #: True when ``retract_chunk(step_chunk(c, chunk), chunk, parts) == c``
    #: holds bitwise for unpadded chunks (the scoring carries); False for
    #: consumers whose retraction is a documented approximation (Alg. 1
    #: clustering — migrations are history-dependent).
    retract_exact: bool = False

    # ------------------------------------------------------------ protocol
    def init(self):
        raise NotImplementedError

    def step_chunk(self, carry, src, dst, n_valid, *extras):
        raise NotImplementedError

    def retract_chunk(self, carry, src, dst, n_valid, parts, *extras):
        """Undo the accounting ``step_chunk`` performed for these edges.

        ``parts`` is the per-edge result recorded when the edges were
        ingested (``None`` for state-only consumers).  Only entries with
        index ``< n_valid`` are retracted — chunk padding is never
        touched, so a deletion batch may be chunked arbitrarily.
        Retraction is order-independent (pure subtraction on the group
        fields), so chunks may be retracted in any order."""
        raise NotImplementedError(
            f"{type(self).__name__} does not support edge deletion")

    def finalize(self, carry):
        return carry

    # ---------------------------------------------------------- lane views
    def lane_shares(self, base, demand):
        """What each ingest lane may use of a bounded resource until the
        next merge, or None when the consumer bounds nothing.  ``base`` is
        the merge base the lanes fold from and ``demand[s]`` the edges lane
        s folds before the next merge; the result is a host array with one
        row per lane, handed to :meth:`for_lane`.  Every parallel backend
        calls this at every merge base, so they stay bit-identical."""
        return None

    def for_lane(self, share):
        """This consumer as a lane with ``share`` (one row of
        :meth:`lane_shares`) folds it; ``share`` may be traced."""
        return self

    # -------------------------------------------------------- group algebra
    def signed_delta(self, after, before):
        """The group difference ``after ⊖ before`` per field.

        SUM/COUNTED fields subtract (ℤ, or ℤ/2³² for unsigned leaves);
        REPLICATED fields pass ``after`` through unchanged.  Raises for
        the legacy monotone ops — they have no inverse."""
        fa, treedef = jax.tree_util.tree_flatten(after)
        fb = jax.tree_util.tree_leaves(before)
        _check_ops(self.merge_ops, len(fa))
        out = []
        for op, a, b in zip(self.merge_ops, fa, fb):
            if op in GROUP_OPS:
                out.append(jnp.asarray(a) - jnp.asarray(b))
            elif op == REPLICATED:
                out.append(a)
            else:
                raise ValueError(
                    f"merge op {op!r} is monotone — it has no signed delta")
        return jax.tree_util.tree_unflatten(treedef, out)

    def negate(self, delta):
        """The group inverse of a signed delta (identity on REPLICATED)."""
        flat, treedef = jax.tree_util.tree_flatten(delta)
        _check_ops(self.merge_ops, len(flat))
        out = []
        for op, x in zip(self.merge_ops, flat):
            if op in GROUP_OPS:
                x = jnp.asarray(x)
                # unsigned leaves negate in ℤ/2³² (two's complement)
                out.append((jnp.zeros((), x.dtype) - x).astype(x.dtype))
            elif op == REPLICATED:
                out.append(x)
            else:
                raise ValueError(
                    f"merge op {op!r} is monotone — it has no inverse")
        return jax.tree_util.tree_unflatten(treedef, out)

    def apply_delta(self, carry, delta):
        """``carry ⊕ delta``: add group fields, keep replicated ones.

        ``apply_delta(apply_delta(c, δ), negate(δ)) == c`` bitwise — the
        group law every decremental consumer builds on."""
        fc, treedef = jax.tree_util.tree_flatten(carry)
        fd = jax.tree_util.tree_leaves(delta)
        _check_ops(self.merge_ops, len(fc))
        out = []
        for op, c, d in zip(self.merge_ops, fc, fd):
            if op in GROUP_OPS:
                c = jnp.asarray(c)
                out.append((c + jnp.asarray(d)).astype(c.dtype))
            elif op == REPLICATED:
                out.append(c)
            else:
                raise ValueError(
                    f"merge op {op!r} is monotone — signed deltas do not "
                    "apply")
        return jax.tree_util.tree_unflatten(treedef, out)

    # ------------------------------------------------------------- merging
    def merge(self, carries: Iterable[Any], base: Any | None = None):
        """Reconcile carries from independent sub-streams.

        With ``base`` given, every carry is treated as a divergence from
        that common ancestor (``SUM`` fields add deltas onto the base);
        without it, carries are deltas from the identity and ``SUM`` fields
        add directly.  ``merge([c])`` returns ``c`` unchanged (bitwise)."""
        carries = list(carries)
        if not carries:
            raise ValueError("merge() needs at least one carry")
        if len(carries) == 1:
            return carries[0]
        flat0, treedef = jax.tree_util.tree_flatten(carries[0])
        _check_ops(self.merge_ops, len(flat0))
        cols = [flat0] + [
            jax.tree_util.tree_flatten(c)[0] for c in carries[1:]
        ]
        base_flat = (jax.tree_util.tree_leaves(base)
                     if base is not None else None)
        out = []
        for i, op in enumerate(self.merge_ops):
            leaves = [jnp.asarray(c[i]) for c in cols]
            if op in GROUP_OPS:
                if i in self.pick_first and base_flat is not None:
                    b = jnp.asarray(base_flat[i])
                    acc = b
                    taken = jnp.zeros(b.shape, jnp.bool_)
                    for x in leaves:
                        ch = x != b
                        acc = jnp.where(ch & ~taken, x, acc)
                        taken = taken | ch
                    out.append(acc.astype(leaves[0].dtype))
                    continue
                acc = leaves[0]
                for x in leaves[1:]:
                    acc = acc + x
                if base_flat is not None:
                    b = jnp.asarray(base_flat[i])
                    acc = acc - (len(leaves) - 1) * b.astype(acc.dtype)
                out.append(acc)
            elif op in (OR, MAX):
                acc = leaves[0]
                for x in leaves[1:]:
                    acc = _or_leaf(acc, x) if op == OR else jnp.maximum(acc, x)
                out.append(acc)
            else:  # REPLICATED
                out.append(leaves[0])
        return jax.tree_util.tree_unflatten(treedef, out)

    def merge_stacked(self, stacked, base: Any | None = None):
        """Merge a carry whose every leaf carries a leading lane axis
        (the vmap parallel backend's layout) in one reduction per field."""
        flat, treedef = jax.tree_util.tree_flatten(stacked)
        _check_ops(self.merge_ops, len(flat))
        base_flat = (jax.tree_util.tree_leaves(base)
                     if base is not None else None)
        out = []
        for i, op in enumerate(self.merge_ops):
            x = jnp.asarray(flat[i])
            if op in GROUP_OPS:
                if i in self.pick_first and base_flat is not None:
                    b = jnp.asarray(base_flat[i])
                    changed = x != b[None, ...]
                    first = jnp.argmax(changed, axis=0)
                    picked = jnp.take_along_axis(x, first[None, ...],
                                                 axis=0)[0]
                    out.append(jnp.where(jnp.any(changed, axis=0), picked,
                                         b).astype(x.dtype))
                    continue
                acc = jnp.sum(x, axis=0)
                if base_flat is not None:
                    b = jnp.asarray(base_flat[i])
                    acc = acc - (x.shape[0] - 1) * b.astype(acc.dtype)
                out.append(acc.astype(x.dtype))
            elif op == OR:
                out.append(jnp.any(x, axis=0) if x.dtype == jnp.bool_
                           else jnp.max(x, axis=0))
            elif op == MAX:
                out.append(jnp.max(x, axis=0))
            else:  # REPLICATED
                out.append(x[0])
        return jax.tree_util.tree_unflatten(treedef, out)

    def occupancy_contest(self, before, after) -> float:
        """How contested the carry's placement state still is, as the
        fraction of active cells whose zero/nonzero projection flipped
        between two consecutive merge bases — the signal the adaptive
        super-chunk cadence (``super_chunk="auto"`` in
        :func:`~repro.streaming.parallel.run_parallel`) backs off on.

        COUNTED fields (replica-occupancy counters: the `(v, p)` bitmap
        projection is ``count > 0``) are the natural churn meter; carries
        without COUNTED fields (linear consumers like the degree/Θ
        sketches) fall back to the same projection over SUM fields, whose
        zero→nonzero transitions die out as the tables fill.  Returns a
        host float in ``[0, 1]`` (0 for carries with no group fields —
        nothing to contest, so auto cadence backs off immediately)."""
        fb = [jnp.asarray(x) for x in jax.tree_util.tree_leaves(before)]
        fa = [jnp.asarray(x) for x in jax.tree_util.tree_leaves(after)]
        _check_ops(self.merge_ops, len(fa))
        for pick in (COUNTED, SUM):
            changed = active = 0
            seen = False
            for op, b, a in zip(self.merge_ops, fb, fa):
                if op != pick:
                    continue
                seen = True
                changed += int(jnp.sum((b != 0) != (a != 0)))
                active += int(jnp.sum(a != 0))
            if seen:
                return changed / max(active, 1)
        return 0.0

    def merge_bytes(self, carry) -> int:
        """Bytes one lane hands to one merge's collectives
        (:meth:`merge_collective`): each SUM/COUNTED field's delta, twice
        for a :attr:`pick_first` field (its lowest-writer ``pmin``, then
        the ``psum``), each OR/MAX field (bools as int32); REPLICATED
        fields are not exchanged."""
        total = 0
        for i, (op, x) in enumerate(zip(self.merge_ops,
                                        jax.tree_util.tree_leaves(carry))):
            if op == REPLICATED:
                continue
            x = jnp.asarray(x)
            n = x.size * (4 if x.dtype == jnp.bool_ else x.dtype.itemsize)
            total += 2 * n if op in GROUP_OPS and i in self.pick_first else n
        return total

    def merge_collective(self, local, base, axis: str):
        """The shard_map form of :meth:`merge`: one collective per field
        (``psum`` of deltas for SUM, ``pmax`` for OR/MAX, base for
        REPLICATED), evaluated on every device of mesh axis ``axis``."""
        flat, treedef = jax.tree_util.tree_flatten(local)
        _check_ops(self.merge_ops, len(flat))
        base_flat = jax.tree_util.tree_leaves(base)
        out = []
        for i, op in enumerate(self.merge_ops):
            x = flat[i]
            if op in GROUP_OPS:
                b = base_flat[i].astype(x.dtype)
                if i in self.pick_first:
                    n = jax.lax.psum(jnp.ones((), jnp.int32), axis)
                    idx = jax.lax.axis_index(axis).astype(jnp.int32)
                    changed = x != b
                    winner = jax.lax.pmin(jnp.where(changed, idx, n), axis)
                    contrib = jnp.where(changed & (idx == winner), x - b,
                                        jnp.zeros((), x.dtype))
                    out.append(b + jax.lax.psum(contrib, axis))
                    continue
                out.append(b + jax.lax.psum(x - b, axis))
            elif op in (OR, MAX):
                if x.dtype == jnp.bool_:
                    out.append(jax.lax.pmax(x.astype(jnp.int32), axis) > 0)
                else:
                    out.append(jax.lax.pmax(x, axis))
            else:  # REPLICATED
                out.append(base_flat[i])
        return jax.tree_util.tree_unflatten(treedef, out)


class FnCarry(PartitionerCarry):
    """Adapter: a bare ``(carry0, chunk_fn)`` pair as a PartitionerCarry.

    Wraps the legacy ``run_scan`` contract (``chunk_fn(carry, src, dst,
    *extras)``) so the engine has one driver code path.  No merge semantics
    are declared — sequential use only."""

    def __init__(self, carry0, chunk_fn: Callable):
        self._carry0 = carry0
        self._chunk_fn = chunk_fn

    def init(self):
        return self._carry0

    def step_chunk(self, carry, src, dst, n_valid, *extras):
        return self._chunk_fn(carry, src, dst, *extras)


class RetractCarry(PartitionerCarry):
    """Adapter: drive a consumer's **retraction** through the fold engines.

    ``step_chunk`` of the adapter is ``retract_chunk`` of the wrapped
    consumer, with the deleted edges' recorded per-edge ``parts`` riding
    along as the first stream extra (state-only consumers pass
    ``parts=None`` and the adapter forwards ``None``).  Because
    retraction is pure subtraction on the carry's group fields, the
    adapted "fold" inherits everything the insertion path has: lane
    masking for exhausted streams, tree / collective merges, and all
    three ``run_parallel`` backends — a deletion batch shards exactly
    like an insertion batch.  State-only by construction
    (``emits_parts=False``); ``finalize`` is the identity because a
    retracted carry composes with further folds.
    """

    emits_parts = False

    def __init__(self, pc: PartitionerCarry, *, with_parts: bool = True):
        if not pc.supports_retract:
            raise NotImplementedError(
                f"{type(pc).__name__} does not support edge deletion")
        self._pc = pc
        self._with_parts = bool(with_parts)

    @property
    def merge_ops(self) -> tuple[str, ...]:
        return self._pc.merge_ops

    def init(self):
        return self._pc.init()

    def step_chunk(self, carry, src, dst, n_valid, *extras):
        if self._with_parts:
            parts, extras = extras[0], extras[1:]
        else:
            parts = None
        return (self._pc.retract_chunk(carry, src, dst, n_valid, parts,
                                       *extras), None)
