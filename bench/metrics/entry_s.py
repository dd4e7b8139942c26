"""Entry of the S5P job (``core/s5p.py`` ``s5p_partition`` outside its
phases: the edge arrays put on the device, the stream, the degrees, the
game's inputs, the result): seconds per job, the self time of the
program's root span ``s5p.job``, less the time its child spans cover."""

from bench import program_spans


def read(run):
    jobs = program_spans.window_jobs(run)
    if not jobs:
        return None
    return 1e-9 * sum(program_spans.spans.self_ns(root, recs)
                      for root, recs, _ in jobs) / len(jobs)
