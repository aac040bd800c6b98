"""The harness end to end on the CPU at a tiny size: every cell runs and
proves correct; every fault planted under the timed path makes ``correct``
false; a cell and a metric added as files are found by name; a run
without a TPU prints no result."""
import json
import subprocess
import sys
import time

import numpy as np
import pytest

from bench import harness
from conftest import ROOT, copy_benchmark, edit_json

CELLS = [c["name"] for c in
         json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]
REPLAY, SERVE = "livejournal1m-replay-churn", "livejournal1m-serve-poisson"
SEED = 2**31 + 17


def run_cell(root, cell, *, seed=SEED, seconds=0.5) -> tuple:
    r = harness.execute(harness.plan(root, cell), seed=seed, seconds=seconds,
                        trace=False, t_start=time.perf_counter())
    return r, harness.result_line(r)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_and_is_correct(tiny_root, cpu_chips, cell):
    r, line = run_cell(tiny_root, cell)
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    names = {m["name"] for m in r.plan.end_to_end}
    assert set(line["metrics"]) == names
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert r.counters["compiles_in_window"] == 0
    assert line["window"]["compiles"] == 0
    assert list(line)[-1] == "checks"


# --- faults planted under the timed path ------------------------------------

def _unchanged(monkeypatch):
    from repro.api.partitioner import Partitioner

    def unchanged(self, et, vx, nb):
        time.sleep(1e-3)            # the time a window takes, and no more
    monkeypatch.setattr(Partitioner, "_feed_window", unchanged)


def _half_batch(monkeypatch):
    from repro.api.partitioner import Partitioner
    orig = Partitioner._feed_window

    def half(self, et, vx, nb):
        h = (len(et) + 1) // 2
        orig(self, et[:h], vx[:h], nb[:h])
    monkeypatch.setattr(Partitioner, "_feed_window", half)


def _altered_label(monkeypatch):
    from repro.api.partitioner import Partitioner
    orig = Partitioner._feed_window

    def altered(self, et, vx, nb):
        orig(self, et, vx, nb)
        v = int(vx[0])
        a = self._state.assignment
        self._state = self._state._replace(
            assignment=a.at[v].set(a[v] + 1))
    monkeypatch.setattr(Partitioner, "_feed_window", altered)


def _altered_answer(monkeypatch):
    from repro.api.serve import PartitionService
    orig = PartitionService.where_many

    def altered(self, vs):
        out = np.array(orig(self, vs))
        out[0] += 1
        return out
    monkeypatch.setattr(PartitionService, "where_many", altered)


FAULTS = {"unchanged": _unchanged, "half_batch": _half_batch,
          "altered_label": _altered_label, "altered_answer": _altered_answer}
CASES = [
    (REPLAY, "unchanged"),
    (REPLAY, "half_batch"),
    (REPLAY, "altered_label"),
    (SERVE, "unchanged"),
    (SERVE, "half_batch"),
    (SERVE, "altered_label"),
    (SERVE, "altered_answer"),
]


@pytest.mark.parametrize("cell, fault", CASES)
def test_planted_fault_is_not_correct(tiny_root, cpu_chips, monkeypatch,
                                      cell, fault):
    FAULTS[fault](monkeypatch)
    _, line = run_cell(tiny_root, cell)
    assert not line["correct"], line["checks"]


# --- discovery by name ------------------------------------------------------

def test_cell_and_metric_added_as_files(tiny_root, cpu_chips):
    bench = tiny_root / "bench"
    (bench / "traffic" / "replay-churn-small-chunks.json").write_text(
        (bench / "traffic" / "replay-churn.json").read_text())
    edit_json(bench / "traffic" / "replay-churn-small-chunks.json",
              chunk_events=64)
    (bench / "metrics" / "replay.events_per_window.py").write_text(
        "def read(run):\n"
        "    return run.attempted / run.counters['windows']\n")
    spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    spec["workloads"].append({
        "name": "lj1m-replay-small-chunks", "config": "livejournal-1m",
        "traffic": "replay-churn-small-chunks", "chips": 1,
        "why": "a cell added by files alone"})
    for m in spec["end_to_end"]:
        if m["name"] == "events_per_s":
            m["workloads"].append("lj1m-replay-small-chunks")
    spec["per_layer"].append({
        "name": "replay.events_per_window", "unit": "events",
        "better": "higher", "source": "program_counter",
        "layer": "session: api/partitioner.py", "moves": "events_per_s",
        "workloads": ["lj1m-replay-small-chunks"]})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(spec))

    p = harness.plan(tiny_root, "lj1m-replay-small-chunks")
    assert [m["name"] for m in p.per_layer] == ["replay.events_per_window"]
    r, line = run_cell(tiny_root, "lj1m-replay-small-chunks")
    assert line["correct"], line["checks"]
    assert p.reader("replay.events_per_window").read(r) == 32.0


def test_every_named_file_exists():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for cell in spec["workloads"]:
        p = harness.plan(ROOT, cell["name"])
        assert p.config["chips"] == cell["chips"]
        assert p.driver().run
        for m in p.per_layer:
            assert p.reader(m["name"]).read
    for cfg in spec["configs"]:
        assert (ROOT / cfg["file"]).is_file()
        on_file = json.loads((ROOT / cfg["file"]).read_text())
        assert sorted(on_file["reduced"]) == sorted(cfg["reduced"])


# --- no chip, no result -------------------------------------------------------

def test_no_tpu_no_result(tmp_path):
    root = copy_benchmark(tmp_path)
    edit_json(root / "bench" / "traffic" / "replay-churn.json",
              stream_events=4096)
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         REPLAY, "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=root, capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert out.returncode != 0
    assert out.stdout.strip() == ""
