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
