"""The program's own spans inside a traced window, for per-layer readers.

The session and the service record their steps with
``repro.runtime.telemetry`` (``session.*`` and ``serve.*`` spans and
``serve.chunk`` records), stamped with ``time.perf_counter()``, the
clock of ``Run.t_start``. A traced window runs from
``t_start + e2e["setup_s"]`` for ``trace.window_s`` seconds. A program
without that recorder has nothing to read: every reader then gives
``None``.
"""
from __future__ import annotations


def in_window(run, *names: str):
    """The records named ``names`` that lie inside the traced window;
    ``None`` in an untraced run or where the program records no spans.
    Raises if the recorder evicted a record that ended inside the
    window, since the sums and medians would then miss part of it."""
    if run.trace is None:
        return None
    try:
        from repro.runtime import telemetry
    except ImportError:
        return None
    lo = run.t_start + run.e2e["setup_s"]
    hi = lo + run.trace.window_s
    lost = telemetry.dropped(since=lo)
    if lost:
        raise RuntimeError(
            f"the span recorder evicted records inside the traced window "
            f"({lost} evicted in all): its readings would be partial")
    return [s for s in telemetry.spans(lo, hi) if s.name in names]


def seconds(records) -> float:
    """The summed length of ``records``."""
    return sum(s.end - s.start for s in records)
