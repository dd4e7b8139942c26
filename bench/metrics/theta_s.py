"""Theta/CMS pass (``core/s5p.py`` ``cluster_statistics``: the cluster
sizes, the six (E,) pair arrays pulled to the host and deduplicated with
``np.unique``, the count-min sketch pass over the pairs and its query):
seconds per job, from the program's span ``s5p.theta``."""

from bench import program_spans


def read(run):
    return program_spans.per_job(run, "s5p.theta")
