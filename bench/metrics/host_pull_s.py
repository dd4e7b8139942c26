"""Host round trips of the S5P job: seconds per job spent in the
program's ``host.pull`` spans, each a device-to-host copy on the job's
thread, wherever it is nested: the stream's host copy of the edges, the
pair arrays of the Theta pass, the tables of the compaction, the game's
cluster sizes, the short last chunk's per-edge extras in Alg. 3, the
cluster-to-partition table."""

from bench import program_spans


def read(run):
    return program_spans.per_job(run, "host.pull")
