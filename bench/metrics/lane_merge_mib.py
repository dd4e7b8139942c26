"""Lane merge collectives: MiB each lane hands to the carry merges per
job, the program's counter ``lanes.merge_bytes`` under each job's root
span.  Fixed by the shapes: per merge every summed field's delta, twice
for the vertex-to-cluster tables (a ``pmin`` for the lowest writer, then
the ``psum``)."""

from bench import program_spans


def read(run):
    jobs = program_spans.window_jobs(run)
    if not jobs:
        return None
    total = sum(counts.get("lanes.merge_bytes", 0) for _, _, counts in jobs)
    return total / 2**20 / len(jobs) if total else None
