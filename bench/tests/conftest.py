"""The benchmark's own tests: ``pytest bench/tests`` from the repository
root, on the CPU (the tier-1 suite does not collect them)."""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)


import pytest  # noqa: E402


@pytest.fixture(autouse=True, scope="session")
def _build_dirs_of_their_own(tmp_path_factory):
    """Runs made by the tests keep their compilation cache, its markers and
    traces apart from the checkout's ``build/bench``, so that they cannot
    stand for a chip run's first run of a cell."""
    from bench import harness

    tmp = tmp_path_factory.mktemp("build_bench")
    saved = (harness.CACHE_DIR, harness.FILLED_DIR, harness.TRACE_DIR)
    harness.CACHE_DIR = tmp / "jax_cache"
    harness.FILLED_DIR = tmp / "cache_filled"
    harness.TRACE_DIR = tmp / "traces"
    yield
    harness.CACHE_DIR, harness.FILLED_DIR, harness.TRACE_DIR = saved
