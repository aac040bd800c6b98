"""Median milliseconds a served chunk was in flight, from its batch's
dispatch to that batch's commit: ``committed - dispatched`` over the
``serve.chunk`` records inside the traced window."""
import statistics

from bench import program_spans


def read(run):
    found = program_spans.in_window(run, "serve.chunk")
    if not found:
        return None
    return statistics.median(
        (s.attrs["committed"] - s.attrs["dispatched"]) * 1e3 for s in found)
