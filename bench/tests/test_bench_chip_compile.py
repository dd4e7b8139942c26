"""Compile-only checks, for a described TPU v5e, of the kernels the cells
drive at the cells' own shapes: HDRF's scoring kernel at V = 262,144,
k = 32 (the tiled rung, as the ladder picks it) and Alg. 3's placement
kernel at k = 32 for both cells, each with 65,536-edge chunks.

The topology is described inside a fixture, never at import, and the
persistent compilation cache is off around these compiles."""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from bench import harness
from repro.kernels import stream_scan as ss
from repro.kernels.stream_scan import kernel as K


@pytest.fixture(scope="module")
def one_chip():
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
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    cc.reset_cache()


def _config(name):
    import json

    return json.loads((harness.BENCH / "configs" / f"{name}.json")
                      .read_text())


def _shape(sharding, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiles(fn, *args, **static):
    compiled = fn.lower(*args, **static).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_hdrf_cell_scoring_kernel_compiles(one_chip):
    cfg = _config("hdrf-g500-s18-k32")
    V = 1 << cfg["graph"]["scale"]
    k = cfg["partitioner"]["k"]
    chunk = cfg["partitioner"]["chunk_size"]
    budget = ss.vmem_budget()
    path = ss.select_path(V, k, chunk, mode="hdrf", budget=budget)
    assert path == "tiled"  # the 128 MiB packed table stays in HBM
    W = K.table_width(k, "hdrf")
    args = [_shape(one_chip, (2,))] + [_shape(one_chip, (chunk,))] * 3
    args += [_shape(one_chip, (1, W)), _shape(one_chip, (V, W)),
             _shape(one_chip, (1, 1), jnp.float32)]
    _compiles(K._scoring_call, *args, mode="hdrf", eps=1e-3, k=k,
              block=K.DEFAULT_BLOCK, tiled=True, vmem_limit=budget,
              interpret=False)


@pytest.mark.parametrize("name", ["s5p-g500-s16-k32", "hdrf-g500-s18-k32"])
def test_cell_assign_kernel_compiles(one_chip, name):
    part = _config(name)["partitioner"]
    k, chunk = part["k"], part["chunk_size"]
    assert ss.select_path(0, k, chunk, consumer="assign") == "fused"
    W = K.table_width(k, "assign")
    args = [_shape(one_chip, (3,))] + [_shape(one_chip, (chunk,))] * 6
    args.append(_shape(one_chip, (1, W)))
    _compiles(K._assign_call, *args, k=k, block=K.DEFAULT_BLOCK,
              interpret=False)
