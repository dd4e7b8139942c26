"""Alg. 1 clustering pass (``core/clustering.py`` ``cluster_stream``, its
degree precompute and its ``stream_scan`` rung): host-clock microseconds
per edge folded, each call ended by ``block_until_ready``."""

SPANS = {"alg1": "repro.core.clustering:cluster_stream"}


def read(run):
    spans = run.spans.get("alg1")
    if not spans or not run.edges_in_window:
        return None
    return 1e6 * sum(t1 - t0 for t0, t1 in spans) / run.edges_in_window
