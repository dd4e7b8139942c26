"""S5P with four ingest lanes (``PARTITIONERS["s5p-lanes"]``) against the
benchmark's plain four-lane reference, ``bench/reference/s5p-lanes.py``.

The program folds the lanes on the threads and vmap backends here, and on
the shard_map backend over four forced host devices in a subprocess (the
main process keeps one device); every backend gives the reference's
parts edge for edge, and no partition ends above the capacity.  With one
lane the reference is the one-stream reference.  The shard_map
super-steps are compiled once and reused by later jobs.
"""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402
from repro.core.baselines import PARTITIONERS  # noqa: E402
from repro.streaming import parallel  # noqa: E402

K = 32
CHUNK = 1024
PARAMS = {"tau": 1.0, "beta": 1.0, "cms_epsilon": 0.1, "cms_nu": 0.01,
          "game_batch_size": 256, "game_max_rounds": 96,
          "game_accept_prob": 0.9, "chunk_size": CHUNK, "num_streams": 4,
          "shard": "range", "super_chunk": "auto", "touch_up": True,
          "refine_rounds": 16}


def _graph(seed, scale=10):
    g = {"generator": "kronecker", "scale": scale, "edgefactor": 16,
         "A": 0.57, "B": 0.19, "C": 0.19}
    return harness.load("graphs", "kronecker").generate(g, seed)


def _program(src, dst, n, seed=0):
    return np.asarray(PARTITIONERS["s5p-lanes"](
        src, dst, n, K, seed, chunk_size=CHUNK, num_streams=4,
        shard="range", super_chunk="auto"))


@pytest.mark.parametrize("seed", [1, 2])
@pytest.mark.parametrize("backend", ["threads", "vmap"])
def test_lanes_equal_the_reference(monkeypatch, backend, seed):
    src, dst, n = _graph(seed)
    monkeypatch.setattr(parallel, "_resolve_backend", lambda b, S: backend)
    got = _program(src, dst, n)
    assert parallel.last_ingest_stats().backend == backend
    assert parallel.last_ingest_stats().num_streams == 4
    want, _ = harness.load("reference", "s5p-lanes").partition(
        src, dst, n, K, 0, PARAMS)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("seed", [1, 2])
def test_one_lane_reference_is_the_one_stream_reference(seed):
    src, dst, n = _graph(seed)
    lanes, _ = harness.load("reference", "s5p-lanes").partition(
        src, dst, n, K, 0, dict(PARAMS, num_streams=1))
    one, _ = harness.load("reference", "s5p").partition(
        src, dst, n, K, 0, PARAMS)
    np.testing.assert_array_equal(lanes, one)


@pytest.mark.parametrize("seed", [1, 2])
@pytest.mark.parametrize("backend", ["threads", "vmap"])
def test_lanes_hold_the_capacity(monkeypatch, backend, seed):
    """No partition ends above ceil(tau |E| / k), as in one stream."""
    src, dst, n = _graph(seed)
    monkeypatch.setattr(parallel, "_resolve_backend", lambda b, S: backend)
    parts = _program(src, dst, n)
    load = np.bincount(parts[parts >= 0], minlength=K)
    assert load.max() <= -(-src.size // K)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_lane_shares_deal_out_the_room(seed):
    """The lanes' limits never add up past the capacity, and each lane
    has a slot for every edge it folds while the room covers them all."""
    import jax.numpy as jnp

    from repro.core.postprocess import AssignCarry

    rng = np.random.default_rng(seed)
    cap, S = 50, 4
    base = rng.integers(0, cap + 1, K)
    demand = rng.integers(0, 200, S)
    demand[rng.integers(S)] = 0  # a lane that ran out of chunks
    limits = AssignCarry(K, cap, jnp.zeros(4, jnp.int32)).lane_shares(
        jnp.asarray(base, jnp.int32), demand)
    slots = limits.astype(np.int64) - base
    assert limits.shape == (S, K) and (slots >= 0).all()
    assert (base + slots.sum(axis=0) <= cap).all()
    assert (slots.sum(axis=1) >= demand).all()
    assert (slots[demand == 0] == 0).all()


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_assign_kernel_with_per_partition_caps_equals_the_oracle(seed):
    """The fused Alg. 3 kernel (interpreted) under one capacity per
    partition, as a lane runs it, equals the scan oracle edge for edge."""
    import jax.numpy as jnp

    from repro.core.postprocess import _assign_chunk
    from repro.kernels import stream_scan as ss

    rng = np.random.default_rng(seed)
    E, n_cl = 700, 12
    src = jnp.asarray(rng.integers(0, 64, E), jnp.int32)
    dst = jnp.asarray(rng.integers(0, 64, E), jnp.int32)
    c2p = jnp.asarray(rng.integers(0, K, n_cl), jnp.int32)
    cu = jnp.asarray(rng.integers(0, n_cl, E), jnp.int32)
    cv = jnp.asarray(rng.integers(0, n_cl, E), jnp.int32)
    head = jnp.asarray(rng.integers(0, 2, E), jnp.int32)
    load0 = jnp.asarray(rng.integers(0, 10, K), jnp.int32)
    caps = load0 + jnp.asarray(rng.integers(0, 30, K), jnp.int32)
    want_load, want = _assign_chunk(load0, caps, src, dst, head, cu, cv,
                                    c2p, k=K)
    got, load = ss.assign_scan(load0, src, dst, head, c2p[cu], c2p[cv],
                               max_load=caps, block=64)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(load), np.asarray(want_load))


def test_s5p_lanes_refuses_one_lane():
    src, dst, n = _graph(1)
    with pytest.raises(ValueError, match="num_streams >= 2"):
        PARTITIONERS["s5p-lanes"](src, dst, n, K, 0, num_streams=1)


def test_super_step_key_leaves_out_the_job_arrays():
    import jax.numpy as jnp

    from repro.core.clustering import ClusterCarry

    a = ClusterCarry(jnp.zeros(8, jnp.int32), 8, xi=2, kappa=9)
    b = ClusterCarry(jnp.ones(8, jnp.int32), 8, xi=2, kappa=9)
    c = ClusterCarry(jnp.ones(8, jnp.int32), 8, xi=3, kappa=9)
    arrays_a, key_a = parallel._split_consumer(a)
    arrays_b, key_b = parallel._split_consumer(b)
    assert list(arrays_a) == ["degrees"] and arrays_b["degrees"] is b.degrees
    assert key_a == key_b != parallel._split_consumer(c)[1]


def test_shard_map_lanes_equal_the_reference_and_reuse_super_steps():
    """Four forced host devices: run_parallel picks shard_map, every job
    equals the reference, and a second job compiles nothing."""
    prog = textwrap.dedent("""
        import json, sys
        import jax
        import numpy as np
        sys.path.insert(0, %r)
        from bench import harness
        from repro.core.baselines import PARTITIONERS
        from repro.streaming import last_ingest_stats

        PARAMS = json.loads(%r)
        compiles = []
        jax.monitoring.register_event_duration_secs_listener(
            lambda event, d, **kw: compiles.append(event)
            if event == "/jax/core/compile/backend_compile_duration"
            else None)
        ref = harness.load("reference", "s5p-lanes")
        out = {}
        for seed in (1, 2):
            g = {"generator": "kronecker", "scale": 10, "edgefactor": 16,
                 "A": 0.57, "B": 0.19, "C": 0.19}
            src, dst, n = harness.load("graphs", "kronecker").generate(
                g, seed)
            def job():
                return np.asarray(PARTITIONERS["s5p-lanes"](
                    src, dst, n, 32, 0, chunk_size=PARAMS["chunk_size"]))
            first = job()
            backend = last_ingest_stats().backend
            before = len(compiles)
            second = job()
            second_compiles = len(compiles) - before
            want, _ = ref.partition(src, dst, n, 32, 0, PARAMS)
            out[seed] = dict(
                backend=backend, second_compiles=second_compiles,
                first=int((first != want).sum()),
                second=int((second != want).sum()))
        print(json.dumps(out))
    """) % (str(ROOT), json.dumps(PARAMS))
    run = subprocess.run(
        [sys.executable, "-c", prog], capture_output=True, text=True,
        timeout=600,
        env={**{k: os.environ[k] for k in ("HOME", "TMPDIR", "PATH")
                if k in os.environ},
             "PYTHONPATH": str(ROOT / "src"), "JAX_PLATFORMS": "cpu",
             "XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    assert run.returncode == 0, run.stderr[-3000:]
    res = json.loads(run.stdout.strip().splitlines()[-1])
    for seed, r in res.items():
        assert r == {"backend": "shard_map", "second_compiles": 0,
                     "first": 0, "second": 0}, (seed, r)
