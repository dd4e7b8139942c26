"""Compile-only checks of the stream_scan megakernels for a TPU v5e.

The TPU compiler is installed even where no chip is attached: it compiles
for a *described* v5e and raises what the chip's compiler would raise
(tile alignment, VMEM/SMEM overruns, unsupported ops).  Interpret mode
cannot see any of that.  Each test compiles one kernel at a width on one
side of a ladder boundary (about a second per compile):

- a state the gate admits compiles on the rung it was given;
- the next width up steps down a rung, and that rung compiles;
- forcing the old rung at that width is refused by the compiler, so the
  gate's byte count is tight, not merely safe.

The four-lane S5P cell's shard_map super-steps also compile here for the
2x2 host, their kernels inside, one all-reduce merge each.

The topology is described inside a module fixture (never at import), and
the persistent compilation cache is off around these compiles: a compile
for a described chip is written to it but cannot be read back without one.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import stream_scan as ss
from repro.kernels.stream_scan import kernel as K

CHUNK = 1 << 16  # the S5PConfig / partition CLI default chunk
K_PARTS = 32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was_on)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(sharding, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args, **static):
    compiled = fn.lower(*args, **static).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _scoring(sharding, mode, V, tiled, budget):
    W = K.table_width(K_PARTS, mode)
    args = [_shape(sharding, (2,))]
    args += [_shape(sharding, (CHUNK,))] * 3
    args += [_shape(sharding, (1, W)), _shape(sharding, (V, W))]
    if mode == "hdrf":
        args.append(_shape(sharding, (1, 1), jnp.float32))
    return _compile(K._scoring_call, *args, mode=mode, eps=1e-3, k=K_PARTS,
                    block=K.DEFAULT_BLOCK, tiled=tiled, vmem_limit=budget,
                    interpret=False)


def _cluster(sharding, V, vmem=(), budget=None):
    args = [_shape(sharding, (2,)), _shape(sharding, (CHUNK,)),
            _shape(sharding, (CHUNK,)), _shape(sharding, (V,))]
    args += [_shape(sharding, s) for s in K.cluster_leaf_shapes(V)]
    return _compile(K._cluster_call, *args, xi=16, kappa=1 << 20,
                    global_tail=False, block=K.DEFAULT_BLOCK, vmem=vmem,
                    vmem_limit=budget, interpret=False)


def _last_true(pred, hi):
    """Largest V in [1, hi] with pred(V), for a monotone pred."""
    lo = 1
    assert pred(lo) and not pred(hi)
    while hi - lo > 1:
        mid = (lo + hi) // 2
        lo, hi = (mid, hi) if pred(mid) else (lo, mid)
    return lo


@pytest.mark.parametrize("mode", ["greedy", "hdrf"])
def test_scoring_fused_tiled_boundary_compiles(one_chip, mode):
    budget = ss.DEFAULT_VMEM_BUDGET
    path = functools.partial(ss.select_path, k=K_PARTS, chunk_size=CHUNK,
                             mode=mode, budget=budget)
    v_max = _last_true(lambda V: path(V) == "fused", 1 << 20)
    _scoring(one_chip, mode, v_max, tiled=False, budget=budget)
    assert path(v_max + 1) == "tiled"
    _scoring(one_chip, mode, v_max + 1, tiled=True, budget=budget)
    with pytest.raises(Exception, match="vmem"):
        _scoring(one_chip, mode, v_max + 1, tiled=False, budget=budget)


@pytest.mark.parametrize("mode", ["greedy", "hdrf"])
def test_scoring_tiled_oracle_boundary_compiles(one_chip, mode):
    V = 1 << 20  # rmat:20's vertex count: only the tiled rung holds it
    tiled = ss.scoring_state_bytes(V, K_PARTS, mode, tiled=True)
    assert ss.select_path(V, K_PARTS, CHUNK, mode=mode,
                          budget=tiled) == "tiled"
    _scoring(one_chip, mode, V, tiled=True, budget=tiled)
    assert ss.select_path(V, K_PARTS, CHUNK, mode=mode,
                          budget=tiled - 1) == "oracle"


def test_cluster_boundary_compiles(one_chip):
    budget = ss.DEFAULT_VMEM_BUDGET
    path = functools.partial(ss.select_path, k=1, chunk_size=CHUNK,
                             consumer="cluster", budget=budget)
    v_max = _last_true(lambda V: path(V) == "fused", 1 << 20)
    _cluster(one_chip, v_max)
    assert path(v_max + 1) == "tiled"
    _cluster(one_chip, v_max + 1, ss.cluster_vmem_arrays(v_max + 1, CHUNK),
             budget)
    with pytest.raises(Exception, match="smem"):
        _cluster(one_chip, v_max + 1)


def test_cluster_tiled_compiles_at_the_s5p_cell_shape(one_chip):
    """The S5P cell's V = 65,536 with 65,536-edge chunks takes the tiled
    rung under the default budget, and the gate's VMEM count is the least
    limit the compiler accepts."""
    V = 1 << 16
    budget = ss.DEFAULT_VMEM_BUDGET
    assert ss.select_path(V, 1, CHUNK, consumer="cluster",
                          budget=budget) == "tiled"
    vmem = ss.cluster_vmem_arrays(V, CHUNK)
    _cluster(one_chip, V, vmem, budget)
    need = ss.cluster_state_bytes(V, CHUNK, tiled=True)
    _cluster(one_chip, V, vmem, need)
    with pytest.raises(Exception, match="vmem"):
        _cluster(one_chip, V, vmem, need - 1)


def test_cluster_tiled_oracle_boundary_compiles(one_chip):
    budget = ss.DEFAULT_VMEM_BUDGET
    path = functools.partial(ss.select_path, k=1, chunk_size=CHUNK,
                             consumer="cluster", budget=budget)
    v_max = _last_true(lambda V: path(V) != "oracle", 1 << 20)
    assert path(v_max) == "tiled"
    _cluster(one_chip, v_max, ss.cluster_vmem_arrays(v_max, CHUNK), budget)
    assert path(v_max + 1) == "oracle"
    with pytest.raises(Exception, match="vmem"):
        _cluster(one_chip, v_max + 1,
                 ss.cluster_vmem_arrays(v_max + 1, CHUNK), budget)


@pytest.mark.parametrize("k", [8, K_PARTS])
def test_assign_compiles_at_default_chunk(one_chip, k):
    assert ss.select_path(0, k, CHUNK, consumer="assign") == "fused"
    W = K.table_width(k, "assign")
    args = [_shape(one_chip, (3,))] + [_shape(one_chip, (CHUNK,))] * 6
    args.append(_shape(one_chip, (1, W)))
    _compile(K._assign_call, *args, k=k, block=K.DEFAULT_BLOCK,
             interpret=False)


def test_assign_with_per_partition_caps_compiles(one_chip):
    """Alg. 3 as an ingest lane runs it: one capacity per partition."""
    W = K.table_width(K_PARTS, "assign")
    args = [_shape(one_chip, (3,))] + [_shape(one_chip, (CHUNK,))] * 6
    args += [_shape(one_chip, (1, W))] * 2
    _compile(K._assign_call, *args, k=K_PARTS, block=K.DEFAULT_BLOCK,
             interpret=False)


@pytest.mark.parametrize("consumer", ["alg1", "theta", "alg3"])
def test_lane_super_steps_compile_on_a_2x2_mesh(topo, monkeypatch, consumer):
    """The super-steps of the four-lane S5P cell (V = 65,536, k = 32,
    65,536-edge chunks) for one lane per chip of a v5e 2x2: Alg. 1 on its
    tiled rung over a lane's 4 chunks, the Theta sketch over 3 chunks of
    2**18 pairs, Alg. 3 on its fused rung over 2 rounds, each lane under
    its own per-partition capacities."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core.clustering import ClusterCarry
    from repro.core.cms import SketchCarry, suggest_params
    from repro.core.postprocess import AssignCarry
    from repro.streaming import parallel

    resolve = K._resolve  # the kernels, not their interpreter, on the chip
    monkeypatch.setattr(K, "_resolve", lambda b, n, i: resolve(b, n, False))
    mesh = Mesh(np.asarray(topo.devices), ("streams",))
    rep = NamedSharding(mesh, P())
    by_lane = NamedSharding(mesh, P("streams"))
    V, S = 1 << 16, 4
    if consumer == "alg1":
        pc = ClusterCarry(jnp.zeros(V, jnp.int32), V, xi=27, kappa=56875,
                          use_kernel=True)
        R, B, extras, shares = 4, CHUNK, (), ()
    elif consumer == "theta":
        w, d = suggest_params()
        pc = SketchCarry(w * 108, d)
        R, B, extras, shares = 3, 1 << 18, (), ()
    else:
        pc = AssignCarry(K_PARTS, 28438, jnp.zeros(11819, jnp.int32),
                         use_kernel=True)
        R, B, extras = 2, CHUNK, (jnp.bool_, jnp.int32, jnp.int32)
        shares = (_shape(by_lane, (S, K_PARTS)),)
    arrays, _ = parallel._split_consumer(pc)
    step = parallel._make_super_step(pc, tuple(arrays), mesh, "streams", R,
                                     len(extras), len(shares))
    args = [tuple(_shape(rep, a.shape, a.dtype) for a in arrays.values()),
            jax.tree_util.tree_map(lambda x: _shape(rep, x.shape, x.dtype),
                                   pc.init()), *shares]
    args += [_shape(by_lane, (S, R, B))] * 2 + [_shape(by_lane, (S, R))]
    args += [_shape(by_lane, (S, R, B), dt) for dt in extras]
    text = step.lower(*args).compile().as_text()
    assert " all-reduce(" in text
    assert ("tpu_custom_call" in text) == (consumer != "theta")
