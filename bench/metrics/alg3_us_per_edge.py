"""Alg. 3 placement (``core/postprocess.py`` ``assign_edges_stream``, with
the per-edge cluster lookups that feed it): microseconds per edge placed,
from the program's span ``s5p.alg3``, which ends once the parts and the
load are on the device."""

from bench import program_spans


def read(run):
    sec = program_spans.seconds(run, "s5p.alg3")
    if sec is None or not run.edges_in_window:
        return None
    return 1e6 * sec / run.edges_in_window
