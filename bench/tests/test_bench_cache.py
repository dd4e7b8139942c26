"""The persistent compilation cache: the first run of a cell in a checkout
fills it, and later runs of the cell only read from it, so a program
compiled for one graph's shapes is compiled anew by every run that
partitions another graph, whichever seeds ran before."""

import time

import jax
import pytest

from bench import harness
from bench_small import CELLS, small_cell


@pytest.fixture
def filled_dir(monkeypatch, tmp_path):
    monkeypatch.setattr(harness, "FILLED_DIR", tmp_path / "filled")
    before = jax.config.jax_persistent_cache_min_compile_time_secs
    yield tmp_path / "filled"
    jax.config.update("jax_persistent_cache_min_compile_time_secs", before)


@pytest.mark.parametrize("workload", CELLS)
def test_first_run_fills_later_runs_only_read(filled_dir, workload):
    cell = small_cell(workload)
    harness.run_cell(cell, 21, 0.0, False, t_start=time.perf_counter(),
                     require_tpu=False)
    assert (filled_dir / cell.name).exists()
    assert harness.enable_cache(cell.name) is None
    assert jax.config.jax_persistent_cache_min_compile_time_secs == \
        harness.NEVER_WRITE_S
    r = harness.run_cell(cell, 22, 0.0, False, t_start=time.perf_counter(),
                         require_tpu=False)
    assert r["correct"]


def test_each_cell_fills_its_own(filled_dir):
    assert harness.enable_cache(CELLS[0]) == filled_dir / CELLS[0]
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    (filled_dir / CELLS[0]).touch()
    assert harness.enable_cache(CELLS[0]) is None
    assert harness.enable_cache(CELLS[1]) == filled_dir / CELLS[1]
