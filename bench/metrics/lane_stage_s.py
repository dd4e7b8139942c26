"""Lane staging (``streaming/parallel.py``: each super-step's (S, R, B)
blocks of edges and their extras built on the host and put on the lanes'
devices): seconds per job in the program's spans ``lanes.stage``, each
ended once the blocks are on the devices."""

from bench import program_spans


def read(run):
    return program_spans.per_job(run, "lanes.stage")
