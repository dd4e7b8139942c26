"""``kernels/stream_scan`` scoring fold (greedy / HDRF): device
microseconds per edge, from the profiler trace.

The fold's device time is the time of every op that ran inside one of
the programs named in ``PROGRAMS``: the Pallas megakernel's jitted call
(fused and tiled rungs) and the ``lax.scan`` oracle rung.  Only a trace
that covers whole jobs is read."""

PROGRAMS = ("_scoring_call", "greedy_chunk", "hdrf_chunk")


def scoring_seconds(run):
    if run.trace is None or not run.edges_traced:
        return None
    total = sum(sec for prog, sec in run.program_seconds().items()
                if any(p in prog for p in PROGRAMS))
    return total or None


def read(run):
    sec = scoring_seconds(run)
    return None if sec is None else 1e6 * sec / run.edges_traced
