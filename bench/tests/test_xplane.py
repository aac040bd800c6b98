"""The trace reduction: on made-up intervals, and on a trace recorded on
a v5e chip (``data/replay_v5e.xplane.pb.gz``: two windows of the
replay-churn cell's traffic at n = 2**22, each fed inside a ``bench.feed``
span and waited for inside a ``bench.wait`` span)."""
import gzip
import shutil
from pathlib import Path

import pytest

from bench import xplane

DATA = Path(__file__).with_name("data") / "replay_v5e.xplane.pb.gz"
MS = 1_000_000


def test_summarize_made_up_trace():
    spans = [("bench.window", 0, 100 * MS), ("bench.feed", 0, 10 * MS),
             ("bench.wait", 10 * MS, 100 * MS)]
    dev0 = [("fusion.1", 5 * MS, 30 * MS), ("fusion.1", 20 * MS, 40 * MS),
            ("all-reduce.2", 50 * MS, 60 * MS), ("copy.3", 90 * MS, 120 * MS)]
    dev1 = [("fusion.1", 5 * MS, 15 * MS)]
    t = xplane.summarize(spans, [dev0, dev1])
    assert t.window_s == pytest.approx(0.1)
    assert t.busy_s == pytest.approx([0.055, 0.010])
    assert t.allreduce_s == pytest.approx([0.010, 0.0])
    assert t.top_ops[0] == ("fusion.1", pytest.approx(0.045))
    assert t.idle_gaps == [("wait", pytest.approx(0.030)),
                           ("wait", pytest.approx(0.010)),
                           ("feed", pytest.approx(0.005))]
    assert t.spans == {"feed": (1, pytest.approx(0.010)),
                       "wait": (1, pytest.approx(0.090))}


def test_summarize_needs_one_window():
    with pytest.raises(RuntimeError):
        xplane.summarize([("bench.feed", 0, 1)], [[("op", 0, 1)]])


def test_reduce_recorded_chip_trace(tmp_path):
    path = tmp_path / "replay_v5e.xplane.pb"
    with gzip.open(DATA) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    t = xplane.reduce(str(path))
    assert len(t.busy_s) == 1
    assert 0 < t.busy_s[0] <= t.window_s
    assert t.allreduce_s == [0.0]
    assert t.top_ops and all(s > 0 for _, s in t.top_ops)
    assert max(s for _, s in t.top_ops) <= t.busy_s[0]
    assert all(" " not in name for name, _ in t.top_ops)
    assert {name for name, _ in t.idle_gaps} <= {"feed", "wait", "other"}
    assert t.spans["feed"][0] == 2
    # the mixed window's slot loop and its two whole-adjacency copies
    assert {"while.35", "copy.20", "copy.22"} <= {n for n, _ in t.top_ops[:3]}
