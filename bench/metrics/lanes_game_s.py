"""Stackelberg game of the four-lane S5P job (``core/game.py`` ``run_game``
on the lanes' merged clusters, Alg. 2), the touch-up's masked game left
out: seconds per job, from the program's span ``s5p.game``."""

from bench import program_spans


def read(run):
    return program_spans.per_job(run, "s5p.game")
