"""The reader of ``session.relayouts_in_window``, on tiny CPU runs of each
cell with a made-up trace summary over the run's window: 0 on a session
that never moved its adjacency; it counts a ``session.relayout`` span
inside the window and not one outside it; nothing without a trace, where
the program records no spans, or where the session keeps no pinned
layout; and it refuses a window whose records the recorder evicted."""
import sys

import pytest

from test_harness import REPLAY, SERVE, run_cell
from test_program_spans import _trace_over_window

METRIC = "session.relayouts_in_window"


@pytest.mark.parametrize("cell", [REPLAY, SERVE])
def test_relayouts_reader_counts_moves_in_the_window(tiny_root, cpu_chips,
                                                    monkeypatch, cell):
    r, line = run_cell(tiny_root, cell)
    assert line["correct"], line["checks"]
    assert METRIC in {m["name"] for m in r.plan.per_layer}
    reader = r.plan.reader(METRIC)
    assert reader.read(r) is None

    r.trace = _trace_over_window(r)
    assert reader.read(r) == 0.0

    import repro.runtime
    from repro.runtime import telemetry
    lo = r.t_start + r.e2e["setup_s"]
    telemetry.record("session.relayout", lo + 1e-6, lo + 2e-6, bytes=8)
    telemetry.record("session.relayout", lo - 2e-6, lo - 1e-6, bytes=8)
    assert reader.read(r) == 1.0

    with monkeypatch.context() as m:
        m.setattr(telemetry, "dropped", lambda since=None: 3)
        with pytest.raises(RuntimeError, match="evicted"):
            reader.read(r)

    # a session that keeps no pinned layout (an older commit)
    from repro.api import partitioner
    with monkeypatch.context() as m:
        m.delattr(partitioner, "adj_format")
        assert reader.read(r) is None

    # a program without the recorder: nothing to read
    monkeypatch.delattr(repro.runtime, "telemetry")
    monkeypatch.setitem(sys.modules, "repro.runtime.telemetry", None)
    assert reader.read(r) is None
