"""Test set-up: the repository's ``src`` and root on the path;
``tiny_root``, a copy of the benchmark whose configurations and traffic
are cut to a size the CPU runs in seconds; and ``cpu_chips``, which lets
a run take the host's CPU for its chip, with a memory reading supplied
here (the CPU keeps no device memory counters)."""
import json
import os
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("JAX_PLATFORMS", "cpu")

TINY_CONFIG = {"snapshot_vertices": 4096,
               "session": {"n": 1 << 17, "max_deg": 24, "window": 32,
                           "engine": {"k_max": 16, "k_init": 1,
                                      "autoscale": True, "max_cap": 2000}}}
TINY_TRAFFIC = {"replay": {"chunk_events": 512, "stream_events": 100000},
                "serve": {"rate": 4000, "stream_events": 60000,
                          "queries": 256}}
MEMORY_PEAK_BYTES = 1 << 30


def copy_benchmark(dst: Path) -> Path:
    """``BENCHMARK.json`` and ``bench/`` (without caches) under ``dst``."""
    shutil.copy(ROOT / "BENCHMARK.json", dst / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", dst / "bench",
                    ignore=shutil.ignore_patterns(".jax_cache", ".trace",
                                                  "__pycache__", "tests"))
    return dst


def edit_json(path: Path, **changes) -> None:
    data = json.loads(path.read_text())
    for key, value in changes.items():
        if isinstance(value, dict) and isinstance(data.get(key), dict):
            data[key].update(value)
        else:
            data[key] = value
    path.write_text(json.dumps(data, indent=2))


@pytest.fixture
def tiny_root(tmp_path) -> Path:
    root = copy_benchmark(tmp_path)
    for cfg in (root / "bench" / "configs").glob("*.json"):
        edit_json(cfg, **TINY_CONFIG)
    for tr in (root / "bench" / "traffic").glob("*.json"):
        driver = json.loads(tr.read_text())["driver"]
        edit_json(tr, **TINY_TRAFFIC[driver])
    return root


@pytest.fixture
def cpu_chips(monkeypatch):
    """Runs take the host's CPU devices for chips, keep no compilation
    cache, and read ``MEMORY_PEAK_BYTES`` for their memory peak."""
    import jax

    from bench import harness

    def chips(n):
        return jax.devices()[:n]

    def read_memory_peak(run):
        run.memory_peak_bytes = MEMORY_PEAK_BYTES
        run.e2e["peak_hbm_bytes"] = float(MEMORY_PEAK_BYTES)
        return MEMORY_PEAK_BYTES

    monkeypatch.setattr(harness, "chips", chips)
    monkeypatch.setattr(harness, "enable_compile_cache", lambda root: None)
    monkeypatch.setattr(harness.Run, "read_memory_peak", read_memory_peak)
