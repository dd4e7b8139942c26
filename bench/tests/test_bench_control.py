"""The control: each reference computed one precision step below the
configuration's float32 (bfloat16) and put in the place of the
partitioner's library entry.  A whole run through ``harness.run_cell``
has to come out not correct.  On the chip the same control runs at the
cells' own sizes through ``bench/control.py``."""

import time

import pytest

from bench import control, harness
from bench_small import CELLS, small_cell


@pytest.mark.parametrize("seed", [1, 2])
@pytest.mark.parametrize("workload", CELLS)
def test_bfloat16_reference_fails_the_comparison(workload, seed):
    cell = small_cell(workload, scale=11, chunk=4096)
    with control.control_in_place(cell):
        r = harness.run_cell(cell, seed, 0.0, False,
                             t_start=time.perf_counter(), require_tpu=False)
    assert not r["correct"]
    assert r["checks"]["parts_mismatch"]["value"] > \
        r["checks"]["parts_mismatch"]["limit"]
