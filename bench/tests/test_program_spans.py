"""The readers of the program's own spans, on tiny CPU runs of each cell
with a made-up trace summary over the run's window: each gives a finite
number; none without a trace, or where the program records no spans;
and each refuses a window whose records the recorder evicted."""
import math
import sys
import time

import pytest

from bench import xplane
from test_harness import REPLAY, SERVE, run_cell

READERS = {
    REPLAY: ["session.coerce_ms_per_window.replay",
             "session.dispatch_ms_per_window.replay"],
    SERVE: ["serve.queued_ms_p50", "serve.held_ms_p50",
            "serve.in_flight_ms_p50", "serve.pad_share",
            "serve.ingest_hold_ms_max"],
}


def _trace_over_window(r) -> xplane.TraceSummary:
    """A summary whose window covers the run's measured window."""
    lo = r.t_start + r.e2e["setup_s"]
    return xplane.TraceSummary(
        window_s=time.perf_counter() - lo, busy_s=[0.0], allreduce_s=[0.0],
        top_ops=[], idle_gaps=[], spans={})


@pytest.mark.parametrize("cell", [REPLAY, SERVE])
def test_readers_on_a_tiny_run(tiny_root, cpu_chips, monkeypatch, cell):
    r, line = run_cell(tiny_root, cell)
    assert line["correct"], line["checks"]
    p = r.plan
    assert set(READERS[cell]) <= {m["name"] for m in p.per_layer}
    assert [p.reader(m).read(r) for m in READERS[cell]] \
        == [None] * len(READERS[cell])

    r.trace = _trace_over_window(r)
    for m in READERS[cell]:
        value = p.reader(m).read(r)
        assert value is not None and math.isfinite(value) and value >= 0, m
    if cell == SERVE:
        assert p.reader("serve.pad_share").read(r) < 100.0

    import repro.runtime
    from repro.runtime import telemetry
    monkeypatch.setattr(telemetry, "dropped", lambda since=None: 3)
    for m in READERS[cell]:
        with pytest.raises(RuntimeError, match="evicted"):
            p.reader(m).read(r)

    # a program without the recorder (an older commit): nothing to read
    monkeypatch.delattr(repro.runtime, "telemetry")
    monkeypatch.setitem(sys.modules, "repro.runtime.telemetry", None)
    assert [p.reader(m).read(r) for m in READERS[cell]] \
        == [None] * len(READERS[cell])
