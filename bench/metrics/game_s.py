"""Stackelberg game (``core/game.py`` ``run_game``, Alg. 2): host-clock
seconds per job, each call ended by ``block_until_ready``."""

SPANS = {"game": "repro.core.game:run_game"}


def read(run):
    spans = run.spans.get("game")
    if not spans or not run.jobs_in_window:
        return None
    return sum(t1 - t0 for t0, t1 in spans) / run.jobs_in_window
