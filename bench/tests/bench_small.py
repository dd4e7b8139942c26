"""Small versions of the benchmark's cells, for CPU tests."""

import copy

from bench import harness

CELLS = ("s5p-g500-s16-k32.random", "hdrf-g500-s18-k32.random")


def small_cell(workload: str, scale: int = 10, chunk: int = 1024):
    """The cell as BENCHMARK.json defines it, at a test-sized scale and
    chunk (several chunks per job)."""
    cell = harness.load_cell(workload)
    cell.config = copy.deepcopy(cell.config)
    cell.config["graph"]["scale"] = scale
    cell.config["partitioner"]["chunk_size"] = chunk
    return cell
