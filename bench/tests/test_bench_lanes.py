"""The four-lane S5P cell at a test-sized graph: a sound run is correct,
and the control and two faults of the lanes' own semantics are not.

- the control: the lane reference computed in bfloat16 in the place of
  the library entry;
- last writer wins: the vertex-to-cluster tables merged to the highest
  lane that changed a vertex, where the merge laws give the lowest;
- the touch-up left out (at this size it moves clusters on every seed);
- lanes that place against their own view of the loads with no share of
  the capacity, which overfills partitions.

On one CPU device the lanes run on the threads backend, whose merges are
the ones every backend shares."""

import copy
import time

import pytest

from bench import control, harness

CELL = "s5p-lanes4-g500-s16-k32.random"


def _small(scale=11, chunk=2048):
    cell = harness.load_cell(CELL)
    cell.config = copy.deepcopy(cell.config)
    cell.config["graph"]["scale"] = scale
    cell.config["partitioner"]["chunk_size"] = chunk
    cell.config["partitioner"]["params"]["chunk_size"] = chunk
    return cell


def _run(cell, seed=12345):
    return harness.run_cell(cell, seed, 0.0, False,
                            t_start=time.perf_counter(), require_tpu=False)


def _incorrect(r):
    assert not r["correct"]
    assert r["checks"]["parts_mismatch"]["value"] > \
        r["checks"]["parts_mismatch"]["limit"]


def test_sound_run_is_correct():
    r = _run(_small())
    assert r["correct"] and r["checks"]["parts_mismatch"]["value"] == 0


@pytest.mark.parametrize("seed", [1, 2])
def test_bfloat16_lane_reference_fails_the_comparison(seed):
    cell = _small()
    with control.control_in_place(cell):
        _incorrect(_run(cell, seed))


def test_last_writer_wins_merge_fails_the_comparison(monkeypatch):
    from repro.core.clustering import ClusterCarry
    from repro.streaming.carry import PartitionerCarry

    def last_wins(self, carries, base=None):
        return PartitionerCarry.merge(self, list(carries)[::-1], base)

    monkeypatch.setattr(ClusterCarry, "merge", last_wins)
    _incorrect(_run(_small()))


def test_skipped_touch_up_fails_the_comparison(monkeypatch):
    from repro.core import s5p

    def skipped(src, dst, n, config, plan, res, inputs, bs, cu, cv,
                is_head, sizes, parts, load, c2p, k, max_load):
        return parts, load, c2p, {}

    monkeypatch.setattr(s5p, "_touch_up", skipped)
    _incorrect(_run(_small()))


def test_lanes_without_capacity_shares_fail_the_comparison(monkeypatch):
    from repro.core.postprocess import AssignCarry

    monkeypatch.setattr(AssignCarry, "lane_shares",
                        lambda self, base, demand: None)
    _incorrect(_run(_small()))
