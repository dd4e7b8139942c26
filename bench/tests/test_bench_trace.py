"""The trace reduction: busy time, idle share, op time by program, gaps.

One test builds a trace by hand, where every number is known; the other
reads a small trace recorded on a TPU v5e (an HDRF run at scale 12,
8,192-edge chunks, through ``bench/run.py``'s traced window), kept gzipped
in ``fixtures/``."""

from pathlib import Path

import pytest

from bench import harness
from bench import trace as tm

FIXTURE = Path(__file__).parent / "fixtures" / "hdrf_s12.xplane.pb.gz"


def _view(tr, edges):
    view = harness.RunView(spans={}, compiles=0, jobs_in_window=1,
                           edges_in_window=edges, k=32,
                           peaks={"hbm_bytes_per_s": 819e9})
    view.trace = tr
    view.busy_s = tm.busy_s(tr)
    view.edges_traced = edges
    return view


def test_reduction_of_a_known_trace():
    ms = 1_000_000
    tr = tm.Trace(window=(0, 100 * ms))
    tr.device_ops["/device:TPU:0"] = [
        ("fusion.1", 10 * ms, 30 * ms, "jit__scoring_call"),
        ("fusion.2", 20 * ms, 40 * ms, "jit__scoring_call"),  # overlaps
        ("copy.3", 60 * ms, 70 * ms, "jit_pad"),
        ("copy.4", 95 * ms, 120 * ms, "jit_pad"),  # runs past the window
    ]
    tr.host_events = [("bench:job", 0, 100 * ms), ("np.unique", 42 * ms,
                                                   58 * ms)]
    assert tm.busy_s(tr) == pytest.approx(0.045)  # 30 + 10 + 5 ms
    assert tm.program_seconds(tr) == pytest.approx(
        {"jit__scoring_call": 0.040, "jit_pad": 0.015})
    # gaps, longest first, each named by the shortest host event over it
    assert tm.idle_gaps(tr) == [["bench:job", pytest.approx(0.025)],
                                ["np.unique", pytest.approx(0.020)],
                                ["bench:job", pytest.approx(0.010)]]
    view = _view(tr, 1000)
    idle = harness.load("metrics", "device_idle_share").read(view)
    assert idle == pytest.approx(55.0)
    us = harness.load("metrics", "scoring_us_per_edge").read(view)
    assert us == pytest.approx(40.0)  # 40 ms over 1,000 edges
    roof = harness.load("metrics", "scoring_roofline").read(view)
    assert roof == pytest.approx(100 * 1000 * 540 / 819e9 / 0.040)


def test_reduction_of_a_recorded_tpu_trace():
    tr = tm.load(str(FIXTURE))
    assert list(tr.device_ops) == ["/device:TPU:0"]
    # the numbers the run that recorded it printed
    assert tr.window_s == pytest.approx(0.20380671)
    busy = tm.busy_s(tr)
    assert busy == pytest.approx(0.077316236)
    by_prog = tm.program_seconds(tr)
    ops = tr.ops_in_window()["/device:TPU:0"]
    op_union = 1e-9 * sum(e - s for s, e in tm.merge(
        (s, e) for _, s, e, _ in ops))
    # programs add the time between their ops; ops alone never exceed it
    assert op_union == pytest.approx(0.077134957) and op_union <= busy
    assert sum(by_prog.values()) >= op_union * (1 - 1e-9)
    view = _view(tr, 1)
    scoring = harness.load("metrics", "scoring_us_per_edge").scoring_seconds(
        view)
    assert scoring == pytest.approx(0.076461951)  # jit__scoring_call only
    idle = harness.load("metrics", "device_idle_share").read(view)
    assert idle == pytest.approx(100 * (1 - 0.077316236 / 0.20380671))
    seen = tm.summary(tr)
    assert seen["programs"] > 0 and seen["in_flight_at_stop"] == 0
    assert not seen["closed_before_orphan_ops"]
    bd = tm.breakdown(tr)
    assert bd["device_ops"][0][0] == "jit__scoring_call/%_scoring_call.1"
    assert len(bd["device_ops"]) == 10 and len(bd["idle_gaps"]) == 10


def test_a_program_in_flight_counts_as_busy():
    ms = 1_000_000
    tr = tm.Trace(window=(0, 100 * ms))
    # a loop program ran 10..60 ms; the next was still running when the
    # profile stopped at 100 ms: its inner ops are recorded, its while op
    # is not, and its program event is cut at the stop
    tr.programs["/device:TPU:0"] = [("jit_loop", 10 * ms, 60 * ms),
                                    ("jit_loop", 62 * ms, 100 * ms)]
    tr.device_ops["/device:TPU:0"] = [
        ("%while.1", 10 * ms, 60 * ms, "jit_loop"),
        ("%fusion.2", 20 * ms, 21 * ms, "jit_loop"),
        ("%fusion.2", 70 * ms, 71 * ms, "jit_loop"),
        ("%fusion.2", 90 * ms, 91 * ms, "jit_loop"),
    ]
    assert tm.busy_s(tr) == pytest.approx(0.088)
    tm.close_before_orphans(tr)  # every op lies inside a recorded program
    assert tr.window == (0, 100 * ms)
    assert tm.summary(tr)["in_flight_at_stop"] == 0  # cut exactly at the end
    assert tm.idle_gaps(tr) == [["no host event", pytest.approx(0.010)],
                                ["no host event", pytest.approx(0.002)]]


def test_a_program_whose_execution_was_lost_closes_the_window():
    ms = 1_000_000
    tr = tm.Trace(window=(0, 100 * ms), annotated_end=100 * ms)
    # the second loop program's module event is missing: its ops are
    # orphans, so the window closes where the first program ended
    tr.programs["/device:TPU:0"] = [("jit_loop", 10 * ms, 60 * ms)]
    tr.device_ops["/device:TPU:0"] = [
        ("%while.1", 10 * ms, 60 * ms, "jit_loop"),
        ("%fusion.2", 70 * ms, 71 * ms, ""),
        ("%fusion.2", 90 * ms, 91 * ms, ""),
    ]
    tm.close_before_orphans(tr)
    assert tr.closed_before_orphan_ops and tr.window == (0, 60 * ms)
    assert tm.busy_s(tr) == pytest.approx(0.050)
    assert tm.summary(tr) == {"programs": 1, "in_flight_at_stop": 0,
                              "closed_before_orphan_ops": True}
