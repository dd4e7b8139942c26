"""Pallas TPU megakernels: the whole S5P chunk step in one dispatch.

One ``pallas_call`` per stream chunk covers the entire inner loop of the
streaming partitioners — insert *and* retract.  Layout, as the TPU
compiler lays it out:

- the grid is blocked over the chunk's edges (``block`` edges per step).
  Every per-edge operand (endpoint ids, recorded parts, Alg.-3 extras) and
  the parts output ride **blocked SMEM** specs, so the pipeline DMAs them
  one block at a time and the serial scan reads them with scalar loads.
  SMEM use is therefore set by ``block``, not by the chunk length.  1-D
  int32 arrays tile by 1024 on the chip, so ``block`` is 1024 (or the
  whole chunk, when it is shorter);
- the only scalar prefetch is the ``meta`` vector (limit, sign, cap;
  Alg. 3 under ingest lanes takes a ``(1, W)`` row of caps instead);
- the greedy/HDRF per-vertex state is **one packed int32 row per vertex**,
  ``W = roundup(k + [hdrf], 128)`` lanes wide: lanes ``[0, k)`` hold the
  counted replica table, lane ``k`` holds HDRF's partial degree, the rest
  is zero.  The table stays in HBM (``memory_space=ANY``, donated in
  place through ``input_output_aliases``).  The **fused** rung DMAs the
  whole table into one VMEM scratch at grid step 0 and back at the last
  step; the **tiled** rung DMAs the two endpoint rows of each edge into
  two ``(1, W)`` VMEM buffers and back.  A row DMA must be 128-lane
  aligned, which is what fixes ``W``;
- Algorithm 1's state is all scalar and indexed by data, so it lives in
  SMEM scratch (DMA'd in at step 0, out at the last step) while it fits
  there; past that the arrays SMEM cannot hold sit in VMEM as
  ``(rows, 128)`` scratch, read and written a row at a time through the
  same per-edge body (``_Leaf``);
- Algorithm 3 keeps its load vector as a ``(1, W)`` VMEM block;
- a ``sign`` operand (+1 insert / -1 retract) reuses the same kernel for
  deletion: the counted replica table is an abelian group, so retraction
  is the same scatter arithmetic with negated weights and the recorded
  per-edge parts standing in for the scored pick.

Mosaic has no int32 argmin/argmax and cannot store a scalar to VMEM, so
every pick is ``m = min(x); min(where(x == m, lane, W))`` — the first
index, exactly argmin's tie-break — and every per-vertex write is a row
store or an SMEM scalar store.  ``ops.py`` owns the fused → tiled →
oracle ladder and the byte counts that gate it.

Per-edge math mirrors ``ref.py`` (and ``core.clustering`` /
``core.postprocess``) expression-for-expression (Algorithm 1 skips the
oracle's masked no-op updates), so interpret mode is bit-identical to the
oracles — asserted by tests/test_kernels.py and the
pinned goldens in tests/test_streaming.py.

Padding contract: wrappers pad the chunk to a multiple of ``block`` with
``(0, 0)`` self-loops and ``parts = -1``; a ``limit`` scalar (insert: the
passed chunk length, matching the oracles' unconditional handling of the
chunk's own padding; retract: ``n_valid``) masks everything past it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...runtime import spans

__all__ = [
    "CLUSTER_ARRAYS",
    "DEFAULT_BLOCK",
    "LANES",
    "assign_scan",
    "cluster_leaf_shapes",
    "cluster_scan",
    "dispatch_count",
    "reset_dispatch_count",
    "scoring_scan",
    "stream_scan_tpu",
    "table_width",
]

DEFAULT_BLOCK = 1024  # 1-D int32 arrays tile by 1024 on the chip
LANES = 128
_INF_I32 = 2**30  # python int: jnp constants may not be captured by kernels
_MAX_I32 = 2**31 - 1

_DISPATCHES = "stream_scan.dispatches"  # one per pallas_call issued


def dispatch_count() -> int:
    return spans.counters().get(_DISPATCHES, 0)


def reset_dispatch_count() -> None:
    spans.reset(_DISPATCHES)


def table_width(k: int, mode: str) -> int:
    """Lanes of the packed per-vertex row: k replica counters, plus HDRF's
    partial degree, rounded up to whole 128-lane tiles."""
    need = k + (1 if mode == "hdrf" else 0)
    return -(-need // LANES) * LANES


def _resolve(block, n, interpret):
    """(block, pad, interpret) for an n-edge chunk."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    blk = min(block or DEFAULT_BLOCK, max(n, 1))
    return blk, (-n) % blk, interpret


def _pad_edges(src, dst, parts, pad):
    """Pad with (0, 0) self-loops / -1 parts — guaranteed no-ops."""
    if pad:
        src = jnp.pad(src, (0, pad))
        dst = jnp.pad(dst, (0, pad))
    if parts is None:
        pin = jnp.full((src.shape[0],), -1, jnp.int32)
    elif pad:
        pin = jnp.pad(jnp.asarray(parts, jnp.int32), (0, pad),
                      constant_values=-1)
    else:
        pin = jnp.asarray(parts, jnp.int32)
    return src, dst, pin


def _edge_spec(block):
    return pl.BlockSpec((block,), lambda i, *_: (i,),
                        memory_space=pltpu.SMEM)


def _const_spec(shape):
    return pl.BlockSpec(shape, lambda i, *_: tuple(0 for _ in shape))


_ANY_SPEC = pl.BlockSpec(memory_space=pl.ANY)


def _copy(src, dst, sem):
    cp = pltpu.make_async_copy(src, dst, sem)
    cp.start()
    cp.wait()


def _first_min(x, lane, width):
    """Index of the first minimum of a (1, W) int32 row (argmin's
    tie-break) without an int32 argmin."""
    m = jnp.min(x)
    return jnp.min(jnp.where(x == m, lane, width))


def _any(mask):
    return jnp.max(jnp.where(mask, 1, 0)) > 0


def _compiler_params(vmem_limit):
    if vmem_limit is None:
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=int(vmem_limit))


# ===================================================================
# greedy / HDRF scoring scan
# ===================================================================


def _scoring_kernel(meta_ref, src_ref, dst_ref, pin_ref, load_in, tab_in,
                    *refs, mode, eps, k, block, tiled):
    hdrf = mode == "hdrf"
    if hdrf:
        lam_ref, parts_ref, load_ref, tab_ref, *scratch = refs
    else:
        parts_ref, load_ref, tab_ref, *scratch = refs
        lam_ref = None
    if tiled:
        buf_u, buf_v, sem = scratch
    else:
        tab_v, sem = scratch
    W = load_ref.shape[1]
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        load_ref[...] = load_in[...]
        if not tiled:
            _copy(tab_in, tab_v, sem.at[0])

    limit = meta_ref[0]
    sign = meta_ref[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, W), 1)
    real_lane = lane < k

    def gather(u, v):
        if not tiled:
            return tab_v[pl.ds(u, 1), :], tab_v[pl.ds(v, 1), :]
        cu = pltpu.make_async_copy(tab_ref.at[pl.ds(u, 1), :], buf_u,
                                   sem.at[0])
        cv = pltpu.make_async_copy(tab_ref.at[pl.ds(v, 1), :], buf_v,
                                   sem.at[1])
        cu.start()
        cv.start()
        cu.wait()
        cv.wait()
        return buf_u[...], buf_v[...]

    def scatter(u, v, new_u, new_v):
        # u == v writes the same row twice with the same value
        if not tiled:
            tab_v[pl.ds(u, 1), :] = new_u
            tab_v[pl.ds(v, 1), :] = new_v
            return
        buf_u[...] = new_u
        buf_v[...] = new_v
        cu = pltpu.make_async_copy(buf_u, tab_ref.at[pl.ds(u, 1), :],
                                   sem.at[0])
        cv = pltpu.make_async_copy(buf_v, tab_ref.at[pl.ds(v, 1), :],
                                   sem.at[1])
        cu.start()
        cv.start()
        cu.wait()
        cv.wait()

    def body(e, _):
        g = i * block + e
        u = src_ref[e]
        v = dst_ref[e]
        real = g < limit
        is_ins = sign > 0
        p_ret = pin_ref[e]
        same = u == v
        row_u, row_v = gather(u, v)
        load = load_ref[...]
        if hdrf:
            # the oracle bumps pd unconditionally (self-loops and the
            # chunk's own padding included) *before* scoring; a self-loop
            # bumps the one row twice
            pdw = jnp.where(real, sign, 0) * jnp.where(same, 2, 1)
            bump = jnp.where(lane == k, pdw, 0)
            row_u = row_u + bump
            row_v = row_v + bump
            du = jnp.sum(jnp.where(lane == k, row_u, 0), axis=1,
                         keepdims=True).astype(jnp.float32)
            dv = jnp.sum(jnp.where(lane == k, row_v, 0), axis=1,
                         keepdims=True).astype(jnp.float32)
            ru = (row_u > 0) & real_lane
            rv = (row_v > 0) & real_lane
            theta_u = du / (du + dv)
            theta_v = 1.0 - theta_u
            g_u = jnp.where(ru, 1.0 + (1.0 - theta_u), 0.0)
            g_v = jnp.where(rv, 1.0 + (1.0 - theta_v), 0.0)
            loadf = load.astype(jnp.float32)
            maxl = jnp.max(jnp.where(real_lane, loadf, -jnp.inf))
            minl = jnp.min(jnp.where(real_lane, loadf, jnp.inf))
            den = eps + maxl - minl  # as ref.hdrf_chunk: 0 on a full tie
            bal = (maxl - loadf) / jnp.where(den > 0, den, 1.0)
            score = g_u + g_v + lam_ref[...] * bal
            score = jnp.where(real_lane, score, -jnp.inf)
            best = jnp.max(score)
            pick_ins = jnp.min(jnp.where(score == best, lane, W))
        else:
            ru = (row_u > 0) & real_lane
            rv = (row_v > 0) & real_lane
            both = ru & rv
            either = ru | rv
            case1 = _any(both)
            case2 = _any(ru) & _any(rv)
            case3 = _any(either)
            # Mosaic cannot select between bool vectors: select int rows
            both_i = jnp.where(both, 1, 0)
            either_i = jnp.where(either, 1, 0)
            mask = jnp.where(
                case1, both_i,
                jnp.where(case2, either_i, jnp.where(case3, either_i, 1)))
            score = jnp.where(mask > 0, load, _INF_I32)
            score = jnp.where(real_lane, score, _MAX_I32)
            pick_ins = _first_min(score, lane, W)
        pick = jnp.where(is_ins, pick_ins, jnp.maximum(p_ret, 0))
        placed = real & (~same) & jnp.where(is_ins, True, p_ret >= 0)
        w = jnp.where(placed, sign, 0)
        hit = jnp.where(lane == pick, w, 0)
        load_ref[...] = load + hit
        scatter(u, v, row_u + hit, row_v + hit)
        parts_ref[e] = jnp.where(
            is_ins, jnp.where(real & (~same), pick_ins, -1), p_ret)
        return 0

    jax.lax.fori_loop(0, block, body, 0)

    if not tiled:
        @pl.when(i == pl.num_programs(0) - 1)
        def _():
            _copy(tab_v, tab_ref, sem.at[0])


@functools.partial(jax.jit,
                   static_argnames=("mode", "eps", "k", "block", "tiled",
                                    "vmem_limit", "interpret"))
def _scoring_call(meta, src, dst, pin, load, table, *lam, mode, eps, k,
                  block, tiled, vmem_limit, interpret):
    Epad = src.shape[0]
    V, W = table.shape
    edge = _edge_spec(block)
    in_specs = [edge, edge, edge, _const_spec((1, W)), _ANY_SPEC]
    if mode == "hdrf":
        in_specs.append(_const_spec((1, 1)))
    if tiled:
        scratch = [pltpu.VMEM((1, W), jnp.int32),
                   pltpu.VMEM((1, W), jnp.int32),
                   pltpu.SemaphoreType.DMA((2,))]
    else:
        scratch = [pltpu.VMEM((V, W), jnp.int32),
                   pltpu.SemaphoreType.DMA((1,))]
    kernel = functools.partial(_scoring_kernel, mode=mode, eps=eps, k=k,
                               block=block, tiled=tiled)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(Epad // block,),
        in_specs=in_specs,
        out_specs=[edge, _const_spec((1, W)), _ANY_SPEC],
        scratch_shapes=scratch,
    )
    # aliasing indices count the scalar-prefetch arg (meta)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(s, jnp.int32)
                   for s in ((Epad,), (1, W), (V, W))],
        input_output_aliases={4: 1, 5: 2},
        compiler_params=_compiler_params(vmem_limit),
        interpret=interpret,
        name="scoring",
    )(meta, src, dst, pin, load, table, *lam)


def scoring_scan(src, dst, load, rep, pd=None, lam=None, *, mode: str,
                 sign: int = 1, parts=None, n_valid=None, eps: float = 1e-3,
                 block: int | None = None, tiled: bool = False,
                 vmem_limit: int | None = None,
                 interpret: bool | None = None):
    """One fused greedy/HDRF chunk — insert (``sign=+1``) or retract
    (``sign=-1``, with the recorded per-edge ``parts`` and ``n_valid``).

    src/dst: (E,) int32; load: (k,) int32; rep: (V, k) int32 **counted**
    replica table; pd: (V,) int32 partial degrees (HDRF only); lam:
    scalar f32.  Returns ``(parts (E,), load, rep, pd)`` (``pd`` None for
    greedy).  ``tiled=True`` keeps the packed table in HBM and moves one
    row per endpoint; ``vmem_limit`` raises the compiler's VMEM limit for
    a fused table larger than its default.
    """
    if mode not in ("greedy", "hdrf"):
        raise ValueError(f"unknown mode {mode!r}")
    if sign not in (1, -1):
        raise ValueError(f"sign must be +1 or -1, got {sign!r}")
    if sign < 0 and (n_valid is None or parts is None):
        raise ValueError("retract needs n_valid and recorded parts")
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    load = jnp.asarray(load, jnp.int32)
    rep = jnp.asarray(rep, jnp.int32)
    E = src.shape[0]
    V, k = rep.shape
    hdrf = mode == "hdrf"
    if hdrf:
        pd = jnp.asarray(pd, jnp.int32)
    if E == 0:
        return jnp.zeros((0,), jnp.int32), load, rep, pd
    blk, pad, interpret = _resolve(block, E, interpret)
    src, dst, pin = _pad_edges(src, dst, parts, pad)
    limit = jnp.asarray(E if sign > 0 else n_valid, jnp.int32)
    meta = jnp.stack([limit, jnp.int32(sign)])
    load_w, table = scoring_pack(load, rep, pd if hdrf else None,
                                 width=table_width(k, mode))
    lam_arg = ()
    if hdrf:
        lam_arg = (jnp.asarray(lam, jnp.float32).reshape(1, 1),)
    spans.count(_DISPATCHES)
    parts_out, load2, table2 = _scoring_call(
        meta, src, dst, pin, load_w, table,
        *lam_arg, mode=mode, eps=float(eps), k=k, block=blk,
        tiled=bool(tiled), vmem_limit=vmem_limit, interpret=interpret)
    return scoring_unpack(parts_out, load2, table2, n=E, k=k, hdrf=hdrf)


@functools.partial(jax.jit, static_argnames=("width",))
def scoring_pack(load, rep, pd, *, width):
    """The scoring kernel's operands from the (k,) load, the (V, k)
    counted table and HDRF's (V,) partial degrees (``None`` for greedy):
    the load as one ``(1, width)`` row and the packed ``(V, width)`` table,
    its lane ``k`` holding ``pd``."""
    k = rep.shape[1]
    table = jnp.pad(rep, ((0, 0), (0, width - k)))
    if pd is not None:
        table = jax.lax.dynamic_update_slice(table, pd[:, None], (0, k))
    return jnp.pad(load, (0, width - k)).reshape(1, width), table


@functools.partial(jax.jit, static_argnames=("n", "k", "hdrf"))
def scoring_unpack(parts, load, table, *, n, k, hdrf):
    """The inverse of :func:`scoring_pack` on the kernel's outputs:
    ``(parts[:n], load (k,), table (V, k), pd (V,) or None)``."""
    pd = table[:, k] if hdrf else None
    return parts[:n], load[0, :k], table[:, :k], pd


def stream_scan_tpu(src, dst, load, rep, pd, lam, *, mode: str,
                    eps: float = 1e-3, interpret: bool | None = None):
    """Back-compat single-chunk insert surface (seed API).

    Same contract as the original whole-array kernel, now running the
    blocked megakernel; ``rep`` is the counted replica table and comes
    back with exact counters (the seed version wrote a saturated 0/1
    projection).  Returns ``(parts, load, rep, pd)``.
    """
    parts, load2, rep2, pd2 = scoring_scan(
        src, dst, load, rep, pd if mode == "hdrf" else None, lam,
        mode=mode, sign=1, eps=eps, interpret=interpret)
    if pd2 is None:
        pd2 = jnp.asarray(pd, jnp.int32)
    return parts, load2, rep2, pd2


# ===================================================================
# Algorithm 1 clustering fold
# ===================================================================

# The kernel's per-vertex arrays: the degree table, then the ClusterState
# leaves in their order (``next_h`` and ``next_t`` are one-word id counters).
CLUSTER_ARRAYS = ("deg", "v2c_h", "v2c_t", "vol_h", "vol_t", "ld", "next_h",
                  "next_t", "cnt_h", "cnt_t", "alloc_h")


class _Leaf:
    """Element access to one Algorithm-1 array, held in SMEM (a scalar per
    element) or in VMEM as ``(rows, 128)`` with element ``x`` at row
    ``x // 128``, lane ``x % 128``.  A VMEM read is a row load, a lane
    select and a reduce; a VMEM write is a row load, a select and a row
    store, since Mosaic cannot store a scalar to VMEM.  With ``on`` given,
    every write is predicated on it."""

    def __init__(self, ref, vmem: bool, on=None):
        self.ref = ref
        self.vmem = vmem
        self.on = on

    def _at(self, x):
        row = (pl.ds(x >> 7, 1), slice(None))  # 128 lanes a row
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
        return row, lane == (x & (LANES - 1))

    def __getitem__(self, x):
        if not self.vmem:
            return self.ref[x]
        row, hit = self._at(x)
        return jnp.sum(jnp.where(hit, self.ref[row], 0))

    def __setitem__(self, x, val):
        if not self.vmem:
            self.ref[x] = (val if self.on is None
                           else jnp.where(self.on, val, self.ref[x]))
            return
        row, hit = self._at(x)
        if self.on is not None:
            hit = hit & self.on
        self.ref[row] = jnp.where(hit, val, self.ref[row])

    def add(self, x, d):
        if self.on is not None:
            d = jnp.where(self.on, d, 0)
        if not self.vmem:
            self.ref[x] = self.ref[x] + d
            return
        row, hit = self._at(x)
        self.ref[row] = self.ref[row] + jnp.where(hit, d, 0)


def _cluster_kernel(meta_ref, src_ref, dst_ref, *refs, xi, kappa,
                    global_tail, block, vmem):
    n = len(CLUSTER_ARRAYS)
    ins, outs = refs[:n], refs[n:2 * n - 1]
    held, sem = refs[2 * n - 1:-1], refs[-1]
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        for hbm, buf in zip(ins, held):
            _copy(hbm, buf, sem.at[0])

    def arrays(on=None):
        return [_Leaf(r, name in vmem, on)
                for name, r in zip(CLUSTER_ARRAYS, held)]

    deg = _Leaf(held[0], "deg" in vmem)
    limit = meta_ref[0]

    # An edge is head or tail and changes only its branch's arrays (the
    # oracle's masked updates of the other branch change nothing).  With
    # arrays in VMEM, where a read costs a row load and a reduce, only the
    # edge's branch runs, under ``pl.when``; with all in SMEM both run
    # straight through, writes predicated, which is the faster there (TPU
    # v5e: 0.0106-0.0111 s against 0.0123 for a 65,536-edge chunk).
    def body(e, _):
        u = src_ref[e]
        v = dst_ref[e]
        valid = (i * block + e < limit) & (u != v)
        du = deg[u]
        dv = deg[v]
        is_head = (du > xi) & (dv > xi)

        def head(on):  # global-degree volumes
            _, v2ch, _, volh, _, _, nexth, _, cnth, _, alloch = arrays(on)
            cu = v2ch[u]
            cv = v2ch[v]
            new_u = cu < 0
            new_v = cv < 0
            nh = nexth[0]
            cu2 = jnp.where(new_u, nh, cu)
            nh = nh + jnp.where(new_u, 1, 0)
            cv2 = jnp.where(new_v, nh, cv)
            nexth[0] = nh + jnp.where(new_v, 1, 0)
            volh.add(cu2, jnp.where(new_u, du, 0))
            volh.add(cv2, jnp.where(new_v, dv, 0))
            cnth.add(u, 1)
            cnth.add(v, 1)
            alloch.add(u, jnp.where(new_u, du, 0))
            alloch.add(v, jnp.where(new_v, dv, 0))
            v2ch[u] = cu2
            v2ch[v] = cv2
            vu = volh[cu2]
            vv = volh[cv2]
            u_is_i = vu - du <= vv - dv  # tie → u (matches reference)
            ci = jnp.where(u_is_i, cu2, cv2)
            cj = jnp.where(u_is_i, cv2, cu2)
            di = jnp.where(u_is_i, du, dv)
            vol_j = jnp.where(u_is_i, vv, vu)  # vol_h[cj], unwritten since
            mig = ((vu < kappa) & (vv < kappa) & (cu2 != cv2)
                   & (vol_j + di < kappa))
            volh.add(cj, jnp.where(mig, di, 0))
            volh.add(ci, jnp.where(mig, -di, 0))
            v2ch[jnp.where(u_is_i, u, v)] = jnp.where(mig, cj, ci)

        def tail(on):  # local-degree volumes (global ones for S5P-B)
            _, _, v2ct, _, volt, ld, _, nextt, _, cntt, _ = arrays(on)
            tu = v2ct[u]
            tv = v2ct[v]
            new_u = tu < 0
            new_v = tv < 0
            nt = nextt[0]
            tu2 = jnp.where(new_u, nt, tu)
            nt = nt + jnp.where(new_u, 1, 0)
            tv2 = jnp.where(new_v, nt, tv)
            nextt[0] = nt + jnp.where(new_v, 1, 0)
            if global_tail:
                volt.add(tu2, jnp.where(new_u, du, 0))
                volt.add(tv2, jnp.where(new_v, dv, 0))
                ld_u, ld_v = du, dv
            else:
                volt.add(tu2, 1)
                volt.add(tv2, 1)
                ld_u = ld[u] + 1
                ld[u] = ld_u
                ld_v = ld[v] + 1
                ld[v] = ld_v
            v2ct[u] = tu2
            v2ct[v] = tv2
            cntt.add(u, 1)
            cntt.add(v, 1)
            tvu = volt[tu2]
            tvv = volt[tv2]
            u_is_i = tvu <= tvv
            ci = jnp.where(u_is_i, tu2, tv2)
            cj = jnp.where(u_is_i, tv2, tu2)
            ldi = jnp.where(u_is_i, ld_u, ld_v)
            mig = (tvu < kappa) & (tvv < kappa) & (tu2 != tv2)
            if global_tail:
                mig = mig & (jnp.where(u_is_i, tvv, tvu) + ldi < kappa)
            volt.add(cj, jnp.where(mig, ldi, 0))
            volt.add(ci, jnp.where(mig, -ldi, 0))
            v2ct[jnp.where(u_is_i, u, v)] = jnp.where(mig, cj, ci)

        for on, branch in ((valid & is_head, head), (valid & ~is_head, tail)):
            if vmem:
                pl.when(on)(functools.partial(branch, None))
            else:
                branch(on)
        return 0

    jax.lax.fori_loop(0, block, body, 0)

    @pl.when(i == pl.num_programs(0) - 1)
    def _():
        for buf, hbm in zip(held[1:], outs):
            _copy(buf, hbm, sem.at[0])


def cluster_leaf_shapes(n_vertices: int) -> list[tuple[int]]:
    """1-D shapes of the ClusterState leaves as the kernel holds them (the
    two volume arrays carry a sink slot, the id counters are length 1)."""
    V = n_vertices
    return [(V,), (V,), (V + 1,), (V + 1,), (V,), (1,), (1,), (V,), (V,),
            (V,)]


@functools.partial(jax.jit,
                   static_argnames=("xi", "kappa", "global_tail", "block",
                                    "vmem", "vmem_limit", "interpret"))
def _cluster_call(meta, src, dst, degrees, *state, xi, kappa, global_tail,
                  block, vmem=(), vmem_limit=None, interpret):
    arrays = []
    for name, a in zip(CLUSTER_ARRAYS, (degrees, *state)):
        if name in vmem:
            rows = -(-a.shape[0] // (8 * LANES)) * 8  # whole (8, 128) tiles
            a = jnp.pad(a, (0, rows * LANES - a.shape[0])).reshape(rows, LANES)
        arrays.append(a)
    edge = _edge_spec(block)
    kernel = functools.partial(_cluster_kernel, xi=xi, kappa=kappa,
                               global_tail=global_tail, block=block,
                               vmem=vmem)
    n = len(arrays)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(src.shape[0] // block,),
        in_specs=[edge, edge] + [_ANY_SPEC] * n,
        out_specs=[_ANY_SPEC] * (n - 1),
        scratch_shapes=(
            [(pltpu.VMEM if name in vmem else pltpu.SMEM)(a.shape, jnp.int32)
             for name, a in zip(CLUSTER_ARRAYS, arrays)]
            + [pltpu.SemaphoreType.DMA((1,))]),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(a.shape, jnp.int32)
                   for a in arrays[1:]],
        input_output_aliases={4 + i: i for i in range(n - 1)},
        compiler_params=_compiler_params(vmem_limit),
        interpret=interpret,
        name="cluster",
    )(meta, src, dst, *arrays)
    return [o.reshape(-1)[:s.shape[0]] for o, s in zip(out, state)]


def cluster_scan(state, src, dst, degrees, *, xi: int, kappa: int,
                 global_tail: bool = False, vmem: tuple[str, ...] = (),
                 vmem_limit: int | None = None, block: int | None = None,
                 interpret: bool | None = None):
    """One fused Algorithm-1 chunk (insert path).

    ``state`` is the 10-leaf ``ClusterState`` tuple (plain arrays — this
    module cannot import ``core``); returns the updated leaves in the
    same order.  ``vmem`` names the arrays of :data:`CLUSTER_ARRAYS` the
    kernel holds in VMEM instead of SMEM, with ``vmem_limit`` the
    compiler's VMEM limit.  Per-edge transitions are
    ``core.clustering._edge_step``'s, without its masked no-op updates,
    so every leaf comes out bitwise equal.
    """
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    E = src.shape[0]
    if E == 0:
        return tuple(state)
    blk, pad, interpret = _resolve(block, E, interpret)
    src, dst, _ = _pad_edges(src, dst, None, pad)
    leaves = [jnp.asarray(s, jnp.int32).reshape(-1) for s in state]
    meta = jnp.stack([jnp.int32(E), jnp.int32(1)])
    spans.count(_DISPATCHES)
    out = _cluster_call(meta, src, dst,
                        jnp.asarray(degrees, jnp.int32).reshape(-1),
                        *leaves, xi=int(xi), kappa=int(kappa),
                        global_tail=bool(global_tail), block=blk,
                        vmem=tuple(a for a in CLUSTER_ARRAYS if a in vmem),
                        vmem_limit=vmem_limit, interpret=interpret)
    return tuple(o[0] if o.shape == (1,) else o for o in out)


# ===================================================================
# Algorithm 3 placement pass
# ===================================================================


def _assign_kernel(meta_ref, src_ref, dst_ref, head_ref, pcu_ref, pcv_ref,
                   pin_ref, load_in, *refs, k, block, per_part_cap):
    if per_part_cap:
        cap_ref, parts_ref, load_ref = refs
    else:
        parts_ref, load_ref = refs
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        load_ref[...] = load_in[...]

    limit = meta_ref[0]
    sign = meta_ref[1]
    cap = meta_ref[2]
    W = load_ref.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, W), 1)
    real_lane = lane < k

    def body(e, _):
        g = i * block + e
        u = src_ref[e]
        v = dst_ref[e]
        real = g < limit
        is_ins = sign > 0
        head = head_ref[e] != 0
        pcu = pcu_ref[e]
        pcv = pcv_ref[e]
        load = load_ref[...]
        lu = jnp.sum(jnp.where(lane == pcu, load, 0))
        lv = jnp.sum(jnp.where(lane == pcv, load, 0))
        if per_part_cap:  # one capacity per partition (an ingest lane's)
            caps = cap_ref[...]
            over_u = lu >= jnp.sum(jnp.where(lane == pcu, caps, 0))
            over_v = lv >= jnp.sum(jnp.where(lane == pcv, caps, 0))
            room = (load < caps) & real_lane
        else:
            over_u = lu >= cap
            over_v = lv >= cap
            room = (load < cap) & real_lane
        any_room = _any(room)
        first_room = jnp.min(jnp.where(room, lane, W))
        # integer-equal to the oracle's k-1-argmax(room[::-1]) whenever
        # any_room holds (the only case the value is consumed)
        last_room = jnp.max(jnp.where(room, lane, -1))
        fallback = _first_min(jnp.where(real_lane, load, _MAX_I32), lane, W)
        overflow_choice = jnp.where(
            any_room, jnp.where(head, first_room, last_room), fallback)
        endpoint_choice = jnp.where(lu > lv, pcv, pcu)
        if per_part_cap:  # one endpoint full: the other, whatever the loads
            endpoint_choice = jnp.where(
                over_u != over_v, jnp.where(over_u, pcv, pcu),
                endpoint_choice)
        part_ins = jnp.where(over_u & over_v, overflow_choice,
                             endpoint_choice)
        p_ret = pin_ref[e]
        pick = jnp.where(is_ins, part_ins, jnp.maximum(p_ret, 0))
        placed = real & (u != v) & jnp.where(is_ins, True, p_ret >= 0)
        w = jnp.where(placed, sign, 0)
        load_ref[...] = load + jnp.where(lane == pick, w, 0)
        parts_ref[e] = jnp.where(
            is_ins, jnp.where(real & (u != v), part_ins, -1), p_ret)
        return 0

    jax.lax.fori_loop(0, block, body, 0)


@functools.partial(jax.jit, static_argnames=("k", "block", "interpret"))
def _assign_call(meta, src, dst, head, pcu, pcv, pin, load, caps=None, *, k,
                 block, interpret):
    """``caps``, a (1, W) row, replaces ``meta``'s one capacity with one
    per partition."""
    Epad = src.shape[0]
    W = load.shape[1]
    edge = _edge_spec(block)
    per_part_cap = caps is not None
    kernel = functools.partial(_assign_kernel, k=k, block=block,
                               per_part_cap=per_part_cap)
    rows = [_const_spec((1, W))] * (2 if per_part_cap else 1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(Epad // block,),
        in_specs=[edge] * 6 + rows,
        out_specs=[edge, _const_spec((1, W))],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(s, jnp.int32)
                   for s in ((Epad,), (1, W))],
        input_output_aliases={7: 1},
        interpret=interpret,
        name="assign",
    )(meta, src, dst, head, pcu, pcv, pin, load,
      *([caps] if per_part_cap else []))


def assign_scan(load, src, dst, is_head_edge, pcu, pcv, *, max_load,
                sign: int = 1, parts=None, n_valid=None,
                block: int | None = None, interpret: bool | None = None):
    """One fused Algorithm-3 chunk — insert or retract.

    ``pcu``/``pcv`` are the endpoint **partition** ids (``c2p`` gathered
    outside, exactly as the oracle does).  ``max_load`` is one capacity
    or a (k,) vector of one per partition.  Returns ``(parts, load)``.
    Mirrors ``core.postprocess._assign_chunk`` / ``_retract_load``.
    """
    if sign not in (1, -1):
        raise ValueError(f"sign must be +1 or -1, got {sign!r}")
    if sign < 0 and (n_valid is None or parts is None):
        raise ValueError("retract needs n_valid and recorded parts")
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    load = jnp.asarray(load, jnp.int32)
    E = src.shape[0]
    k = load.shape[0]
    if E == 0:
        return jnp.zeros((0,), jnp.int32), load
    blk, pad, interpret = _resolve(block, E, interpret)
    head = jnp.asarray(is_head_edge, jnp.int32)
    pcu = jnp.asarray(pcu, jnp.int32)
    pcv = jnp.asarray(pcv, jnp.int32)
    if pad:
        head = jnp.pad(head, (0, pad))
        pcu = jnp.pad(pcu, (0, pad))
        pcv = jnp.pad(pcv, (0, pad))
    src, dst, pin = _pad_edges(src, dst, parts, pad)
    limit = jnp.asarray(E if sign > 0 else n_valid, jnp.int32)
    max_load = jnp.asarray(max_load, jnp.int32)
    W = table_width(k, "assign")
    caps = None
    if max_load.ndim:
        caps = jnp.pad(max_load, (0, W - k)).reshape(1, W)
        max_load = jnp.int32(0)
    meta = jnp.stack([limit, jnp.int32(sign), max_load])
    spans.count(_DISPATCHES)
    parts_out, load2 = _assign_call(
        meta, src, dst, head, pcu, pcv, pin,
        jnp.pad(load, (0, W - k)).reshape(1, W), caps, k=k, block=blk,
        interpret=interpret)
    return parts_out[:E], load2[0, :k]
