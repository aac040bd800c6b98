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


PINNED = """
import hashlib, json, sys
import jax, numpy as np
from jax.experimental.layout import Layout
from repro.api import Partitioner, partitioner
from repro.core import EngineConfig
from repro.graph import stream as gstream
from repro.graph.generators import make_graph
from repro.runtime.compile_cache import enable_compile_cache
enable_compile_cache(sys.argv[1])
# a layout other than the CPU's default, as a TPU's default differs from
# the session's row-major one
partitioner.ADJ_LAYOUT = Layout(major_to_minor=(1, 0))
g = make_graph("social", 90, 260, seed=2)
s = gstream.interleaved_churn(g, warmup_frac=0.2, del_every=3,
                              edge_del_every=5, seed=4)
part = Partitioner.from_stream(s, EngineConfig(k_max=8, k_init=1,
                                               max_cap=100), window=32)
part.feed(s).grow_to(n=2 * part.n)
h = hashlib.sha256()
for leaf in jax.tree_util.tree_leaves(part.state):
    h.update(np.asarray(leaf).tobytes())
print(json.dumps({"layout": part.state.adj.format.layout.major_to_minor,
                  "relayouts": part.metrics()["relayouts"],
                  "state": h.hexdigest()}))
"""


def test_pinned_session_programs_survive_a_warm_cache(tmp_path):
    """A session whose programs pin ``adj`` to a non-default layout runs
    the same from a cold and from a warm persistent cache: JAX restores a
    cached executable without its pinned layouts, so those programs are
    never written to it."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(PYTHONPATH=str(REPO / "src"), JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    runs = [json.loads(subprocess.run(
        [sys.executable, "-c", PINNED, str(REPO)], env=env, check=True,
        capture_output=True, text=True, timeout=300,
    ).stdout.strip().splitlines()[-1]) for _ in range(2)]
    assert any((tmp_path / "cache").iterdir()), "the cache was not used"
    assert runs[0] == runs[1]
    assert runs[0]["layout"] == [1, 0] and runs[0]["relayouts"] == 1
