"""``repro.runtime.spans``: the span tree, self time, the bounded buffer,
counts per root, recording off; and the span tree of one S5P job."""

import threading

import jax
import numpy as np
import pytest

from repro.runtime import spans


@pytest.fixture(autouse=True)
def _fresh():
    spans.disable()
    spans.reset()
    yield
    spans.disable()
    spans.reset()


def _tree(recs, parent=None):
    """Nested ``(name, children)`` in start order, from one parent down."""
    kids = sorted((r for r in recs if r.parent == parent), key=lambda r: r.t0)
    return [(r.name, _tree(recs, r.id)) for r in kids]


def test_nesting_and_root_ids():
    spans.enable()
    with spans.span("a") as a:
        with spans.span("b") as b:
            with spans.span("c"):
                pass
        with spans.span("d"):
            pass
    with spans.span("e") as e:
        pass
    recs = spans.records()
    by = {r.name: r for r in recs}
    assert [r.name for r in recs] == ["c", "b", "d", "a", "e"]  # by end
    assert _tree(recs) == [("a", [("b", [("c", [])]), ("d", [])]), ("e", [])]
    assert {r.root for r in recs if r.name in "abcd"} == {by["a"].id}
    assert by["a"].parent is None and by["e"].root == by["e"].id
    assert by["c"].parent == by["b"].id and by["b"].parent == by["a"].id
    assert (a.id, b.id, e.id) == (by["a"].id, by["b"].id, by["e"].id)
    for r in recs:
        assert r.t0 <= r.t1
    assert by["a"].t0 <= by["b"].t0 and by["d"].t1 <= by["a"].t1


def test_self_time_is_the_root_less_its_children():
    recs = [spans.Record("job", 0, 100, 1, None, 1),
            spans.Record("x", 10, 40, 2, 1, 1),
            spans.Record("y", 50, 90, 3, 1, 1),
            spans.Record("x.pull", 20, 30, 4, 2, 1)]  # a grandchild
    assert spans.self_ns(recs[0], recs) == 100 - 30 - 40
    assert spans.self_ns(recs[1], recs) == 30 - 10
    assert spans.self_ns(recs[3], recs) == 10


def test_the_buffer_keeps_the_newest_records():
    spans.enable()
    n = spans.MAX_RECORDS + 10
    for i in range(n):
        with spans.span(f"s{i}"):
            pass
    recs = spans.records()
    assert len(recs) == spans.MAX_RECORDS
    assert recs[0].name == "s10" and recs[-1].name == f"s{n - 1}"


def test_counts_per_root_and_in_total():
    spans.count("n", 5)  # recording off: the total alone
    spans.enable()
    spans.count("n", 1)  # no open span: the total alone
    with spans.span("r1") as r1:
        spans.count("n", 2)
        with spans.span("child"):
            spans.count("n", 3)
            spans.count("m")
    with spans.span("r2") as r2:
        spans.count("n", 7)
    assert spans.counters(r1.id) == {"n": 5, "m": 1}
    assert spans.counters(r2.id) == {"n": 7}
    assert spans.counters(-1) == {}
    assert spans.counters() == {"n": 5 + 1 + 5 + 7, "m": 1}
    spans.reset("n")
    assert spans.counters() == {"m": 1}


def test_recording_off_records_nothing_and_waits_for_nothing(monkeypatch):
    waited = []
    monkeypatch.setattr(jax, "block_until_ready", waited.append)
    x = jax.numpy.arange(4)
    with spans.span("off") as sp:
        assert sp.wait_for(x) is x
    host = spans.to_host(x)
    assert spans.records() == [] and waited == []
    assert host.tolist() == [0, 1, 2, 3]
    assert spans.counters() == {"host.pull_bytes": 16}
    spans.enable()
    with spans.span("on") as sp:
        sp.wait_for(x)
    assert waited == [x]
    assert [r.name for r in spans.records()] == ["on"]


def test_to_host_pulls_device_arrays_alone():
    spans.enable()
    a = np.arange(3)
    assert spans.to_host(a) is a
    assert spans.to_host([1, 2]).tolist() == [1, 2]
    assert spans.records() == [] and spans.counters() == {}


def test_a_thread_keeps_its_own_span_tree():
    spans.enable()

    def work():
        with spans.span("worker"):
            pass

    with spans.span("main"):
        t = threading.Thread(target=work)
        t.start()
        t.join(timeout=30)
    assert not t.is_alive()
    by = {r.name: r for r in spans.records()}
    assert by["worker"].parent is None and by["worker"].root == by["worker"].id
    assert by["main"].parent is None


def test_s5p_job_span_tree_and_pulled_bytes():
    from repro.core import S5PConfig, s5p_partition
    from repro.graphs.generators import rmat_graph

    src, dst = rmat_graph(10, seed=3)[:2]
    V = 1 << 10
    cfg = S5PConfig(k=8, chunk_size=2048)
    off = np.asarray(s5p_partition(src, dst, V, cfg).parts)
    assert spans.records() == []

    spans.enable()
    out = s5p_partition(src, dst, V, cfg)
    on = np.asarray(out.parts)
    np.testing.assert_array_equal(on, off)

    recs = spans.records()
    pull = ("host.pull", [])
    assert _tree(recs) == [("s5p.job", [
        pull, pull,  # the stream's host copy of the edges
        ("s5p.alg1", []),
        ("s5p.compact", [pull] * 3),
        ("s5p.theta", [pull] * 6),
        ("s5p.game", [pull]),
        ("s5p.alg3", [pull] * 3),  # the short last chunk's extras, padded
        pull,
    ])]
    root = next(r for r in recs if r.parent is None)
    assert {r.root for r in recs} == {root.id}
    E, C = src.size, out.n_clusters
    last = E % cfg.chunk_size
    assert E > cfg.chunk_size and last
    # int32 src and dst; 6 (E,) int32 pair arrays; 3 (V,) int32 tables;
    # (C,) f32 sizes; the last chunk's bool head flags and int32 endpoint
    # clusters; the (C,) int32 cluster -> partition table
    assert spans.counters(root.id)["host.pull_bytes"] == (
        2 * E * 4 + 6 * E * 4 + 3 * V * 4 + C * 4 + last * (1 + 4 + 4)
        + C * 4)
    assert 0 <= spans.self_ns(root, recs) <= root.t1 - root.t0


def test_s5p_touch_up_is_a_phase_of_the_job():
    from repro.core import S5PConfig, s5p_partition
    from repro.graphs.generators import rmat_graph

    src, dst = rmat_graph(10, seed=4)[:2]
    cfg = S5PConfig(k=8, chunk_size=1024, num_streams=2, super_chunk=1)
    off = np.asarray(s5p_partition(src, dst, 1 << 10, cfg).parts)
    spans.enable()
    out = s5p_partition(src, dst, 1 << 10, cfg)
    np.testing.assert_array_equal(np.asarray(out.parts), off)
    # lanes on threads of their own record their pulls as roots of their own
    (job, phases), = [t for t in _tree(spans.records()) if t[0] == "s5p.job"]
    assert [name for name, _ in phases] == [
        "host.pull", "host.pull", "s5p.alg1", "s5p.compact", "s5p.theta",
        "s5p.game", "s5p.alg3", "host.pull", "s5p.touch_up"]
    pulls = phases[-1][1]
    assert len(pulls) >= 4 and all(p == ("host.pull", []) for p in pulls)


def _lanes_job(monkeypatch, backend="vmap"):
    """One S5P job with four lanes on ``backend`` at scale 11 (several
    chunks per lane, and a touch-up that moves clusters); returns the
    output and the stats of every lane drive it made."""
    from repro.core import S5PConfig, s5p_partition
    from repro.graphs.generators import rmat_graph
    from repro.streaming import parallel

    drives = []
    publish = parallel._publish_stats

    def keep(pc, stats):
        drives.append(stats)
        return publish(pc, stats)

    monkeypatch.setattr(parallel, "_publish_stats", keep)
    monkeypatch.setattr(parallel, "_resolve_backend", lambda b, S: backend)
    src, dst = rmat_graph(11, seed=3)[:2]
    cfg = S5PConfig(k=32, chunk_size=1024, num_streams=4, super_chunk="auto")
    out = s5p_partition(src, dst, 1 << 11, cfg)
    return out, [d for d in drives if d.num_streams > 1], src.size


def test_lane_spans_and_counters_are_under_the_job(monkeypatch):
    spans.enable()
    out, drives, _ = _lanes_job(monkeypatch)
    recs = spans.records()
    (job, phases), = [t for t in _tree(recs) if t[0] == "s5p.job"]
    by_phase = {name: kids for name, kids in phases}
    # Alg. 3's lanes read the merge base for their capacity shares
    for phase, stage in (("s5p.alg1", []), ("s5p.alg3", [("host.pull", [])])):
        drive, = [kids for name, kids in by_phase[phase]
                  if name == "lanes.drive"]
        assert ("lanes.stage", stage) in drive
    assert "s5p.touch_up" in by_phase
    root = next(r for r in recs if r.name == "s5p.job" and r.parent is None)
    counts = spans.counters(root.id)
    assert len([r for r in recs if r.name == "lanes.drive"]) == len(drives)
    assert counts["lanes.merges"] == sum(len(d.schedule) for d in drives)
    assert counts["lanes.rounds"] == sum(max(l.chunks for l in d.lanes)
                                         for d in drives)
    assert counts["lanes.merge_bytes"] > 0
    tu = out.aux["touch_up"]
    assert tu["moved_clusters"] > 0
    assert counts["s5p.touch_up.contested"] == tu["contested_clusters"]
    assert counts["s5p.touch_up.moved"] == tu["moved_clusters"]
    assert counts["s5p.touch_up.replayed_edges"] == tu["replayed_edges"]


def test_lane_metrics_read_a_recorded_job(monkeypatch):
    import sys
    from pathlib import Path

    root = str(Path(__file__).resolve().parents[1])
    if root not in sys.path:
        sys.path.insert(0, root)
    from bench import harness, trace

    names = ("lanes_ingest_s", "lane_stage_s", "lane_merge_s",
             "lane_merge_mib", "touch_up_s", "lanes_game_s")
    readers = {n: harness.load("metrics", n).read for n in names}
    empty = harness.RunView(spans={}, compiles=0, jobs_in_window=1,
                            edges_in_window=1, k=32, peaks={})
    assert {n: r(empty) for n, r in readers.items()} == dict.fromkeys(names)

    spans.enable()
    _, _, E = _lanes_job(monkeypatch)
    # a device trace of the job: one merge all-reduce on each of two
    # devices, and one op of another program
    op = "%psum.7 = s32[32]{0:T(128)} all-reduce(%fusion.4), channel_id=1"
    tr = trace.Trace(device_ops={
        "/device:TPU:0": [(op, 0, 3e6, "jit_lanes_super_step(7)"),
                          ("%fusion.1 = s32[8] fusion()", 3e6, 9e6,
                           "jit_lanes_super_step(7)")],
        "/device:TPU:1": [(op, 0, 1e6, "jit_lanes_super_step(7)"),
                          (op, 1e6, 2e6, "jit_other(3)")]},
        window=(0.0, 1e7))
    run = harness.RunView(spans={}, compiles=0, jobs_in_window=1,
                          edges_in_window=E, k=32, peaks={}, trace=tr,
                          edges_traced=E)
    got = {n: r(run) for n, r in readers.items()}
    assert all(isinstance(v, float) and v > 0 for v in got.values()), got
    assert got["lane_merge_s"] == pytest.approx((3e-3 + 1e-3) / 2)


def test_game_counts_its_batch_updates_and_adjacency_entries(monkeypatch):
    """``game.adj_entries`` is rounds × (2P + the call's chunk padding):
    each round's windows read every real pair from both sides once;
    ``game.batch_updates`` is rounds × the batches of a round."""
    from repro.core import S5PConfig, s5p_partition
    from repro.core import game as game_mod
    from repro.graphs.generators import rmat_graph

    calls = []
    run = game_mod.run_game

    def keep(inputs, n_clusters, **kw):
        res = run(inputs, n_clusters, **kw)
        calls.append((inputs, n_clusters, kw["batch_size"], res))
        return res

    monkeypatch.setattr(game_mod, "run_game", keep)
    src, dst = rmat_graph(10, seed=3)[:2]
    spans.enable()
    s5p_partition(src, dst, 1 << 10, S5PConfig(k=8, chunk_size=2048))
    root = next(r for r in spans.records() if r.name == "s5p.job")
    (inputs, C, bs, res), = calls
    pa, pb = np.asarray(inputs.pair_a), np.asarray(inputs.pair_b)
    real = (pa < C) & (pb < C)
    P = int(real.sum())
    assert P > 0
    # rows' entry counts, and the windows of one round: leaders from 0,
    # cut at n_head; followers from n_head
    row_len = np.bincount(np.concatenate([pa[real], pb[real]]), minlength=C)
    rowptr = np.concatenate([[0], np.cumsum(row_len)])
    H = inputs.n_head
    nb_h, nb_t = max(1, -(-H // bs)), max(1, -(-(C - H) // bs))
    lo = np.concatenate([np.arange(nb_h) * bs, H + np.arange(nb_t) * bs])
    hi = np.minimum(lo + bs, np.r_[np.full(nb_h, H), np.full(nb_t, C)])
    n = rowptr[hi] - rowptr[lo]
    assert n.sum() == 2 * P
    L = game_mod.ADJ_CHUNK
    pad = int(np.sum(-(-n // L) * L - n))
    rounds = int(res.rounds)
    counts = spans.counters(root.id)
    assert counts["game.adj_entries"] == rounds * (2 * P + pad)
    assert counts["game.batch_updates"] == rounds * (nb_h + nb_t)
