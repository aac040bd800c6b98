"""Median milliseconds a served chunk waited in the service's queue, from
its submission to its dequeue by the ingest thread: ``dequeued -
submitted`` over the ``serve.chunk`` records inside the traced window."""
import statistics

from bench import program_spans


def read(run):
    found = program_spans.in_window(run, "serve.chunk")
    if not found:
        return None
    return statistics.median(
        (s.attrs["dequeued"] - s.attrs["submitted"]) * 1e3 for s in found)
