"""``kernels/stream_scan`` scoring table repack (greedy / HDRF): device
microseconds per edge spent packing the scoring kernel's table and load
before each chunk and unpacking them after it, from the profiler trace.

The time of every op that ran inside the programs named in
``PROGRAMS``.  Only a trace that covers whole jobs is read; a program
whose repack runs as unnamed eager ops has neither program, and reads
nothing."""

PROGRAMS = ("scoring_pack", "scoring_unpack")


def read(run):
    if run.trace is None or not run.edges_traced:
        return None
    sec = sum(s for prog, s in run.program_seconds().items()
              if any(p in prog for p in PROGRAMS))
    return 1e6 * sec / run.edges_traced if sec else None
