"""Host round trips of the S5P job: MiB copied from the device to the
host per job, the program's counter ``host.pull_bytes`` under each job's
root span.  Fixed by the shapes: 8 (E,) and 3 (V,) int32 arrays, two
arrays of one entry per cluster, and the short last chunk's extras."""

from bench import program_spans


def read(run):
    jobs = program_spans.window_jobs(run)
    if not jobs:
        return None
    total = sum(counts.get("host.pull_bytes", 0) for _, _, counts in jobs)
    return total / 2**20 / len(jobs)
