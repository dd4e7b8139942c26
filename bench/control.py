"""The control of a cell's comparison, on the chip at the cell's own size.

    python bench/control.py --workload hdrf-g500-s18-k32.random \
        --seeds 11 12 13

The cell's reference, computed in bfloat16 (the step below the float32
the configuration states), is put in the place of the partitioner's
library entry, ``repro.core.baselines.PARTITIONERS[<name>]``, and a whole
run goes through ``harness.run_cell``: set-up, a window of one job, and
the comparison with the float32 reference that decides ``correct``.  For
each seed it prints the run's result line; ``correct`` has to come out
false on every seed.  The benchmark's own runs never run this.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from contextlib import contextmanager  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
# the TPU runtime's logs stay inside the checkout, not at a fixed /tmp path
if "TPU_LOG_DIR" not in os.environ:
    os.environ["TPU_LOG_DIR"] = str(ROOT / "build" / "bench" / "tpu_logs")
    os.makedirs(os.environ["TPU_LOG_DIR"], exist_ok=True)

from bench import harness  # noqa: E402


def bfloat16_entry(cell: harness.Cell):
    """The cell's reference in bfloat16, called as the library entry is."""
    part = cell.config["partitioner"]
    ref = harness.load("reference", part["name"])

    def entry(src, dst, n, k, seed, **_):
        parts, _ = ref.partition(src, dst, n, k, seed,
                                 part.get("params", {}), dtype="bfloat16")
        return parts
    return entry


@contextmanager
def control_in_place(cell: harness.Cell):
    from repro.core.baselines import PARTITIONERS

    name = cell.config["partitioner"]["name"]
    orig = PARTITIONERS[name]
    PARTITIONERS[name] = bfloat16_entry(cell)
    try:
        yield
    finally:
        PARTITIONERS[name] = orig


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    failed_all = True
    for seed in args.seeds:
        t0 = time.perf_counter()
        try:
            with control_in_place(cell):
                r = harness.run_cell(cell, seed, 0.0, False, t_start=t0)
        except harness.NoChip as e:
            print(f"no measurement: {e}", file=sys.stderr)
            return 3
        failed_all &= not r["correct"]
        print(json.dumps(dict(workload=cell.name, seed=seed, control="bfloat16",
                              run_s=time.perf_counter() - t0, **r)),
              flush=True)
    return 0 if failed_all else 1


if __name__ == "__main__":
    sys.exit(main())
