"""Run one benchmark cell on the chip(s) of this machine.

    python bench/run.py --workload s5p-g500-s16-k32.random --seed 7 \
        --seconds 10 --trace 0

Prints progress and the compared numbers on standard error, and as the
last line of standard output one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device``, ``breakdown`` (traced
runs) and ``checks``.  Exits non-zero, printing no result, when JAX finds
no TPU or fewer chips than the cell asks for.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
# the TPU runtime's logs stay inside the checkout, not at a fixed /tmp path
if "TPU_LOG_DIR" not in os.environ:
    os.environ["TPU_LOG_DIR"] = str(ROOT / "build" / "bench" / "tpu_logs")
    os.makedirs(os.environ["TPU_LOG_DIR"], exist_ok=True)

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    try:
        result = harness.run_cell(cell, args.seed, args.seconds,
                                  bool(args.trace), t_start=T_START)
    except harness.NoChip as e:
        print(f"no measurement: {e}", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
