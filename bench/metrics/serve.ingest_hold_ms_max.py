"""The longest stretch, in milliseconds, the service's ingest thread held
one batch without waiting on the device: from the start of the batch's
first ``serve.coerce`` span to the end of its ``serve.dispatch`` span,
less its ``serve.wait_prev`` span, over the batches inside the traced
window. A thread holding the interpreter for long shows here."""
from collections import defaultdict

from bench import program_spans


def read(run):
    found = program_spans.in_window(run, "serve.coerce", "serve.wait_prev",
                                    "serve.dispatch")
    if not found:
        return None
    by_batch = defaultdict(list)
    for s in found:
        by_batch[s.attrs["batch"]].append(s)
    holds = []
    for batch in by_batch.values():
        first = min((s.start for s in batch if s.name == "serve.coerce"),
                    default=None)
        last = max((s.end for s in batch if s.name == "serve.dispatch"),
                   default=None)
        if first is None or last is None:
            continue
        waited = program_spans.seconds(
            s for s in batch if s.name == "serve.wait_prev")
        holds.append(last - first - waited)
    return max(holds) * 1e3 if holds else None
