"""The four-chip cell ``friendster32m-sharded-churn`` on tiny CPU runs of
it (four host devices, ``bench/conftest.py``). Its readers: nothing from
the device or span readers without a trace; values read from a given
trace summary and from the session's own spans; nothing where the
program records no spans; a refusal where the recorder evicted records
inside the window. And every fault planted under its timed path makes
the run not correct."""
import math
import sys
import time

import pytest

from bench import xplane
from test_harness import run_cell

CELL = "friendster32m-sharded-churn"
# the replay cells' readers, which read the sharded cell as they are
DEVICE = ["window.device_ms_per_window.replay",
          "window.allreduce_ms_per_window.sharded",
          "device.idle_share.replay"]
SPANS = ["session.dispatch_ms_per_window.replay",
         "session.shard_put_ms_per_window.sharded"]
OTHERS = ["session.host_ms_per_window.replay", "window_roofline",
          "compiles_in_window.replay", "session.coerce_ms_per_window.replay",
          "session.relayouts_in_window"]


def _summary(r, *, busy, allreduce) -> xplane.TraceSummary:
    """A summary whose window covers the run's measured window."""
    lo = r.t_start + r.e2e["setup_s"]
    return xplane.TraceSummary(
        window_s=time.perf_counter() - lo, busy_s=busy,
        allreduce_s=allreduce, top_ops=[], idle_gaps=[],
        spans={"feed": (1, 0.25)})


def test_sharded_readers_on_a_tiny_run(tiny_root, cpu_chips, monkeypatch):
    r, line = run_cell(tiny_root, CELL)
    assert line["correct"], line["checks"]
    p = r.plan
    assert r.devices is not None and len(r.devices) == 4
    assert {m["name"] for m in p.per_layer} == set(DEVICE + SPANS + OTHERS)
    assert [p.reader(m).read(r) for m in DEVICE + SPANS] \
        == [None] * (len(DEVICE) + len(SPANS))
    assert p.reader("compiles_in_window.replay").read(r) == 0

    r.trace = _summary(r, busy=[0.4, 0.5, 0.3, 0.2],
                       allreduce=[0.04, 0.02, 0.03, 0.01])
    windows = r.counters["windows"]
    device = [p.reader(m).read(r) for m in DEVICE]
    assert device[0] == pytest.approx(500.0 / windows)
    assert device[1] == pytest.approx(20.0 / windows)   # the busiest chip's
    assert device[2] == pytest.approx(
        100.0 * (1.0 - 0.35 / r.trace.window_s))

    dispatch, put = (p.reader(m).read(r) for m in SPANS)
    assert math.isfinite(dispatch) and 0 < put <= dispatch
    assert p.reader("session.relayouts_in_window").read(r) == 0
    assert p.reader("session.host_ms_per_window.replay").read(r) \
        == pytest.approx(250.0 / windows)
    from bench import peaks
    with monkeypatch.context() as m:      # the CPU has no published peak
        m.setattr(peaks, "peak", lambda kind: {"hbm_bytes_per_s": 819e9})
        assert 0 < p.reader("window_roofline").read(r) < 100

    from repro.runtime import telemetry
    with monkeypatch.context() as m:
        m.setattr(telemetry, "dropped", lambda since=None: 3)
        for name in SPANS:
            with pytest.raises(RuntimeError, match="evicted"):
                p.reader(name).read(r)

    # a program without the recorder (an older commit): nothing to read
    import repro.runtime
    monkeypatch.delattr(repro.runtime, "telemetry")
    monkeypatch.setitem(sys.modules, "repro.runtime.telemetry", None)
    assert [p.reader(m).read(r) for m in SPANS] == [None, None]


def test_shard_put_reader_is_silent_without_the_span(tiny_root, cpu_chips,
                                                     monkeypatch):
    """A session that records dispatches but no ``session.shard_put``
    span (the program before the span) gives no reading, and the
    dispatch reader still reads."""
    r, line = run_cell(tiny_root, CELL)
    assert line["correct"], line["checks"]
    r.trace = _summary(r, busy=[0.1] * 4, allreduce=[0.0] * 4)
    from repro.runtime import telemetry
    real = telemetry.spans
    monkeypatch.setattr(
        telemetry, "spans", lambda since=None, until=None: [
            s for s in real(since, until) if s.name != "session.shard_put"])
    assert r.plan.reader(SPANS[1]).read(r) is None
    assert r.plan.reader(SPANS[0]).read(r) > 0


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "altered_label"])
def test_planted_fault_in_the_sharded_cell_is_not_correct(
        tiny_root, cpu_chips, monkeypatch, fault):
    """A fault planted under the four-chip cell's timed path (a window
    that does nothing, half a window, one altered label) makes its run
    not correct, as in the one-chip cells."""
    from test_harness import FAULTS
    FAULTS[fault](monkeypatch)
    _, line = run_cell(tiny_root, CELL)
    assert not line["correct"], line["checks"]
