"""The entry points' persistent compilation cache (repro.runtime.compile_cache).

Each case runs in a fresh interpreter: JAX initialises its cache once per
process, and the test process must not start writing one.
"""

import json
import os
import subprocess
import sys
import textwrap

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

_PROBE = """
import json, jax, jax.numpy as jnp
from repro.runtime.compile_cache import REPO_CACHE_DIR, enable_compile_cache
path = enable_compile_cache()
if {compile}:
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(8)).block_until_ready()
print(json.dumps({{"path": path, "repo": str(REPO_CACHE_DIR),
                  "config": jax.config.jax_compilation_cache_dir}}))
"""


def _probe(env_dir, compile_):
    env = {"PYTHONPATH": SRC, "JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
           "HOME": os.environ.get("HOME", "/")}
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(_PROBE.format(compile=compile_))],
        capture_output=True, text=True, timeout=300, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_env_dir_receives_compiled_programs(tmp_path):
    got = _probe(tmp_path, compile_=True)
    assert got["path"] == str(tmp_path)
    assert got["config"] == str(tmp_path)  # as JAX read it from the variable
    assert any(tmp_path.iterdir())


def test_default_dir_is_fixed_repo_cache():
    got = _probe(None, compile_=False)
    assert got["path"] == got["config"] == got["repo"]
    assert got["repo"].endswith(os.path.join("", ".jax_cache"))
    assert os.path.dirname(got["repo"]) == os.path.dirname(
        os.path.abspath(SRC))
