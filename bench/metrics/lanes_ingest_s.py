"""Lane-parallel ingest (``streaming/parallel.py`` ``run_parallel`` with
S > 1 lanes: the plan, the staging, every super-step and its merge, the
parts brought together): seconds per job in the program's spans
``lanes.drive``, one per drive (Alg. 1, the Theta sketch, Alg. 3), each
ended once the merged carry is on the device."""

from bench import program_spans


def read(run):
    return program_spans.per_job(run, "lanes.drive")
