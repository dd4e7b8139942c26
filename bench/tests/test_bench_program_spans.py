"""A traced run of the small S5P cell reads the program's own spans: the
six per-layer metrics of the S5P job, and the spans on the profiler's
clock (the saved profile's host events hold them beside the device's)."""

import time

from bench import harness
from bench import trace as tm
from bench_small import small_cell

S5P_METRICS = ("theta_s", "compact_s", "alg3_us_per_edge", "host_pull_s",
               "host_pull_mib", "entry_s")


def test_traced_s5p_run_reads_the_program_spans():
    cell = small_cell("s5p-g500-s16-k32.random")
    r = harness.run_cell(cell, 2**31 + 11, 0.0, True,
                         t_start=time.perf_counter(), require_tpu=False)
    assert r["correct"]
    got = r["metrics"]
    assert set(S5P_METRICS) <= set(got)
    for name in S5P_METRICS:
        assert got[name]["value"] > 0, name

    src, _, n = harness.make_graph(cell, 2**31 + 11)
    # src and dst, six pair arrays, three vertex tables; then at most two
    # arrays of one entry per cluster and the last chunk's three extras
    fixed = (8 * src.size + 3 * n) * 4
    chunk = cell.config["partitioner"]["chunk_size"]
    mib = got["host_pull_mib"]["value"] * 2**20
    assert fixed < mib < fixed + (2 * n + 3 * chunk) * 4

    path = tm.find_xplane(str(harness.TRACE_DIR / cell.name))
    names = {name for name, _, _ in tm.load(path).host_events}
    assert {"s5p.job", "s5p.theta", "host.pull", tm.WINDOW} <= names
