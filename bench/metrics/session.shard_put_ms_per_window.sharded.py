"""Host milliseconds per window the vertex-sharded session spent placing
each window's event tensors on every chip of its mesh: its
``session.shard_put`` spans inside the traced window. ``None`` where the
session records no such span."""
from bench import program_spans


def read(run):
    found = program_spans.in_window(run, "session.shard_put")
    if not found:
        return None
    return program_spans.seconds(found) * 1e3 / run.counters["windows"]
