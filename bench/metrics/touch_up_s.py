"""Post-ingest touch-up (``core/s5p.py`` ``_touch_up``: the clusters two
or more lanes wrote, a masked game over them of at most
``refine_rounds`` rounds, the moved clusters' edges placed again):
seconds per job, from the program's span ``s5p.touch_up``."""

from bench import program_spans


def read(run):
    return program_spans.per_job(run, "s5p.touch_up")
