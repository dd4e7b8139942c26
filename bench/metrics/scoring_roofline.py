"""``kernels/stream_scan`` scoring fold: share of its roofline.

The least time the chip could take for the algorithm's bytes, over the
fold's device time (``scoring_us_per_edge``).  Per edge the algorithm
reads and writes both endpoints' k replica counters and partial degree,
reads the two endpoint ids and writes the part: ``2 * 2 * (k + 1) * 4 +
3 * 4`` bytes, from k and never from the implementation's padded lanes.
Its operations (about ten per partition) are far below the compute
roof, so HBM bandwidth bounds it."""

def bytes_per_edge(k: int) -> int:
    return 2 * 2 * (k + 1) * 4 + 3 * 4


def read(run):
    sec = run.load("metrics", "scoring_us_per_edge").scoring_seconds(run)
    if sec is None:
        return None
    least = run.edges_traced * bytes_per_edge(run.k) / run.peaks[
        "hbm_bytes_per_s"]
    return 100.0 * least / sec
