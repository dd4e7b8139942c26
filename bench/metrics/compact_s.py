"""Cluster compaction (``core/clustering.py`` ``compact_clusters``: the
three (V,) tables pulled to the host, renumbered there and put back):
seconds per job, from the program's span ``s5p.compact``."""

from bench import program_spans


def read(run):
    return program_spans.per_job(run, "s5p.compact")
