"""Median milliseconds a served chunk was held by the ingest thread, from
its dequeue to the return of its batch's dispatch (coercion, the wait
for the previous batch, merge, dispatch): ``dispatched - dequeued`` over
the ``serve.chunk`` records inside the traced window."""
import statistics

from bench import program_spans


def read(run):
    found = program_spans.in_window(run, "serve.chunk")
    if not found:
        return None
    return statistics.median(
        (s.attrs["dispatched"] - s.attrs["dequeued"]) * 1e3 for s in found)
