"""Where the entry points keep JAX's persistent compilation cache.

Each case runs in a fresh interpreter: the cache is process-global JAX
configuration, and turning it on here would change every later test in
this worker.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]

PROBE = """
import json, sys, jax
import repro.api
before = jax.config.jax_compilation_cache_dir
from repro.runtime.compile_cache import enable_compile_cache
path = enable_compile_cache(sys.argv[1])
print(json.dumps({"before": before, "path": path,
                  "after": jax.config.jax_compilation_cache_dir,
                  "min_s": jax.config.jax_persistent_cache_min_compile_time_secs}))
"""


@pytest.mark.parametrize("env_dir", [None, "given"])
def test_enable_compile_cache_location(tmp_path, env_dir):
    """Importing ``repro`` leaves the cache off. ``enable_compile_cache``
    keeps ``JAX_COMPILATION_CACHE_DIR`` where it is set and otherwise
    uses ``.jax_cache`` in the checkout the entry point names."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = str(REPO / "src")
    env["JAX_PLATFORMS"] = "cpu"
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / env_dir)
    out = subprocess.run([sys.executable, "-c", PROBE, str(REPO)], env=env,
                         check=True, capture_output=True, text=True,
                         timeout=120)
    got = json.loads(out.stdout.strip().splitlines()[-1])
    want = str(tmp_path / env_dir) if env_dir else str(REPO / ".jax_cache")
    assert got["before"] == (want if env_dir else None)
    assert got["path"] == got["after"] == want
    assert got["min_s"] == 0.0
