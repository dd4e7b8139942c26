"""Distributed pieces needing >1 device run in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (the main process keeps
1 device so all other tests see the real topology)."""

import json
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

# each test here boots a fresh 8-device subprocess and recompiles the full
# pipeline — minutes apiece on CPU; run explicitly with `-m slow`
pytestmark = pytest.mark.slow

SRC = str(Path(__file__).resolve().parent.parent / "src")


def _run(code: str) -> dict:
    prog = textwrap.dedent(code)
    out = subprocess.run(
        [sys.executable, "-c", prog],
        capture_output=True, text=True, timeout=900,
        env={"PYTHONPATH": SRC, "JAX_PLATFORMS": "cpu",
             "XLA_FLAGS":
             "--xla_force_host_platform_device_count=8", "PATH": "/usr/bin:/bin",
             "HOME": "/root"},
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_distributed_partition_quality():
    """shard_map S5P ≈ single-host S5P quality; every edge assigned."""
    res = _run("""
        import json
        import jax
        import numpy as np
        from repro.core import S5PConfig, s5p_partition, replication_factor
        from repro.core.distributed import distributed_partition
        from repro.graphs.generators import community_graph

        src, dst, n = community_graph(1200, n_communities=24, avg_degree=8, seed=1)
        k = 4
        mesh = jax.make_mesh((8,), ("data",))
        cfg = S5PConfig(k=k, use_cms=True)
        parts, info = distributed_partition(src, dst, n, cfg, mesh)
        rf_dist = replication_factor(src, dst, parts, n_vertices=n, k=k)
        single = s5p_partition(src, dst, n, cfg)
        rf_single = replication_factor(src, dst, single.parts, n_vertices=n, k=k)
        valid = np.asarray(src) != np.asarray(dst)
        all_assigned = bool((np.asarray(parts)[valid] >= 0).all())
        print(json.dumps(dict(rf_dist=rf_dist, rf_single=rf_single,
                              all_assigned=all_assigned, **info)))
    """)
    assert res["all_assigned"]
    assert res["converged"]
    # distributed clustering sees shard-local streams: allow 35% quality gap
    assert res["rf_dist"] <= res["rf_single"] * 1.35 + 0.2, res


def test_ep_moe_on_divisible_mesh():
    """Expert parallelism: 4 experts over a 4-wide model axis compiles and
    matches the single-device forward."""
    res = _run("""
        import json
        import jax, jax.numpy as jnp
        import numpy as np
        from repro.models import lm as LM
        from repro.sharding import use_rules, DEFAULT_RULES

        cfg = LM.LMConfig(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                          d_head=16, d_ff=128, vocab=128, n_experts=4, top_k=2,
                          attn_chunk=32, dtype=jnp.float32)
        params = LM.init_params(cfg, jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, 128,
                                  dtype=jnp.int32)
        ref, _ = LM.forward(params, toks, cfg)
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        rules = dict(DEFAULT_RULES)
        rules["expert"] = ("model",)  # true EP: 4 experts / 4-wide axis
        with use_rules(mesh, rules):
            out, _ = jax.jit(lambda p, t: LM.forward(p, t, cfg))(params, toks)
        err = float(jnp.max(jnp.abs(out - ref)))
        print(json.dumps(dict(err=err)))
    """)
    assert res["err"] < 1e-3


def test_sharded_lm_train_step_matches_single():
    """One DP×TP train step on 8 devices == single-device step (numerics)."""
    res = _run("""
        import json
        import jax, jax.numpy as jnp
        import numpy as np
        from repro.launch.cells import build_cell
        from repro.launch.dryrun import lower_cell
        from repro.launch.mesh import make_test_mesh
        from repro.sharding import use_rules, DEFAULT_RULES

        cell = build_cell("llama3-8b", "train_4k", smoke=True)
        key = jax.random.PRNGKey(0)
        state = cell.init_state(key)
        batch = cell.make_batch(key)
        ref_state, ref_metrics = jax.jit(cell.step_fn)(state, *batch)
        mesh = make_test_mesh()
        with use_rules(mesh, DEFAULT_RULES):
            out_state, out_metrics = jax.jit(cell.step_fn)(state, *batch)
        err = abs(float(ref_metrics["loss"]) - float(out_metrics["loss"]))
        print(json.dumps(dict(err=err, loss=float(out_metrics["loss"]))))
    """)
    assert res["err"] < 5e-3, res
