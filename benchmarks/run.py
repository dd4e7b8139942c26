"""Benchmark driver: one module per paper table/figure or system study.

``PYTHONPATH=src python -m benchmarks.run [--full] [--only NAME] [--out F]``
prints ``name,us_per_call,derived`` CSV rows and writes every row from the
run into one merged JSON file (default ``BENCH_RESULTS.json``).

Modules are auto-discovered: every ``benchmarks/*.py`` exposing a
``run(quick: bool)`` callable is a bench module (no manual registry to
forget when adding one).  ``--only`` accepts either the full module name
(``incremental_bench``) or its alias — the name up to the first
underscore (``table3_rf`` → ``table3``, ``oocstream_bench`` →
``oocstream``, ``parallel_ingest`` → ``parallel``) — and filters *before
import*, so one bench re-runs without paying (or risking) every other
module's import.
"""

import argparse
import importlib
import json
import pkgutil
import sys
import traceback
from pathlib import Path


def _module_names() -> list[str]:
    """Candidate bench module names, no imports performed."""
    pkg_dir = Path(__file__).resolve().parent
    return sorted(
        info.name for info in pkgutil.iter_modules([str(pkg_dir)])
        if info.name not in ("run", "common") and not info.name.startswith("_")
    )


def discover(only: str | None = None) -> tuple[dict, list]:
    """Map alias → module for every bench module in this package.

    Returns ``(modules, broken)`` — a module that fails at *import* time
    lands in ``broken`` instead of crashing the driver, so one WIP file
    cannot take down the whole nightly sweep.  ``only`` (an alias or a
    full module name) filters before import."""
    modules, broken = {}, []
    for name in _module_names():
        alias = name.split("_")[0]
        # with a filter, exactly one module runs: a full-name match, or
        # the first importable holder of the alias (never both of two
        # modules that happen to share a prefix)
        if only is not None and name != only and not (
                alias == only and alias not in modules):
            continue
        try:
            mod = importlib.import_module(f"benchmarks.{name}")
        except Exception:
            traceback.print_exc()
            broken.append(name)
            continue
        if not callable(getattr(mod, "run", None)):
            continue
        if alias in modules:  # alias collision: fall back to the full name
            alias = name
        modules[alias] = mod
    return modules, broken


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="paper-scale sweeps")
    ap.add_argument("--only", default=None, metavar="NAME",
                    help="run a single bench module (alias or full name)")
    ap.add_argument("--out", default="BENCH_RESULTS.json",
                    help="merged JSON output path ('' disables)")
    args = ap.parse_args()
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()

    from . import common

    modules, failed = discover(args.only)
    if args.only and not modules and not failed:
        names = _module_names()
        aliases = sorted({n.split("_")[0] for n in names})
        ap.error(f"unknown bench {args.only!r}; aliases {aliases} "
                 f"or full names {names}")
    print("name,us_per_call,derived")
    ran = []
    for name, mod in modules.items():
        try:
            mod.run(quick=not args.full)
            ran.append(name)
        except Exception:
            traceback.print_exc()
            failed.append(name)
    if args.out:
        merged = {
            "quick": not args.full,
            "modules_ran": ran,
            "modules_failed": failed,
            "rows": [
                {"name": n, "us_per_call": us, "derived": derived}
                for n, us, derived in common.ROWS
            ],
        }
        Path(args.out).write_text(json.dumps(merged, indent=1))
        print(f"[bench] wrote {len(common.ROWS)} rows to {args.out}",
              file=sys.stderr)
    if failed:
        print(f"FAILED: {failed}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
