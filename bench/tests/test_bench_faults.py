"""A run whose timed path is broken underneath comes out not correct.

Each test drives the whole run (generator, warm-up, window, reference,
comparison) on the CPU at a test-sized graph, skipping only the look for
a chip, with one fault planted in the program's per-chunk step:

- a step that returns its state unchanged;
- half of each chunk left out (the second half never reaches the fold);
- an answer altered where it is produced (one edge's partition).

The cells run on one chip, so there is no exchange between chips to
leave out.
"""

import time

import jax.numpy as jnp
import pytest

from bench import harness
from bench_small import CELLS, small_cell

STEPS = {
    "hdrf": ["repro.kernels.stream_scan.ops:HdrfCarry"],
    # Alg. 1 carries state only; Alg. 3 produces the answers
    "s5p": ["repro.core.clustering:ClusterCarry",
            "repro.core.postprocess:AssignCarry"],
}


def _unchanged(orig):
    def step(self, carry, src, dst, n_valid, *extras):
        _, parts = orig(self, carry, src, dst, n_valid, *extras)
        return carry, parts
    return step


def _half(orig):
    def step(self, carry, src, dst, n_valid, *extras):
        keep = jnp.arange(src.shape[0]) < src.shape[0] // 2
        src = jnp.where(keep, src, 0)
        dst = jnp.where(keep, dst, 0)  # (0, 0): a self-loop, never placed
        return orig(self, carry, src, dst, n_valid, *extras)
    return step


def _altered(orig):
    def step(self, carry, src, dst, n_valid, *extras):
        carry, parts = orig(self, carry, src, dst, n_valid, *extras)
        if parts is not None:
            parts = parts.at[0].set((parts[0] + 1) % 32)
        return carry, parts
    return step


FAULTS = {"state_unchanged": _unchanged, "half_batch": _half,
          "answer_altered": _altered}


def _run(cell):
    return harness.run_cell(cell, 12345, 0.0, False,
                            t_start=time.perf_counter(), require_tpu=False)


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(workload):
    r = _run(small_cell(workload))
    assert r["correct"] and r["checks"]["parts_mismatch"]["value"] == 0


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("workload", CELLS)
def test_fault_makes_run_incorrect(monkeypatch, workload, fault):
    cell = small_cell(workload)
    import importlib

    targets = STEPS[cell.config["partitioner"]["name"]]
    if fault == "answer_altered":
        targets = targets[-1:]  # only a step that emits parts can
    for t in targets:
        mod_name, cls_name = t.split(":")
        cls = getattr(importlib.import_module(mod_name), cls_name)
        monkeypatch.setattr(cls, "step_chunk", FAULTS[fault](cls.step_chunk))
    r = _run(cell)
    assert not r["correct"]
    assert r["checks"]["parts_mismatch"]["value"] > \
        r["checks"]["parts_mismatch"]["limit"]
    assert r["failed"] == r["attempted"]
