"""Host milliseconds per window the session spent dispatching window
programs: its ``session.dispatch`` spans, one per program call, inside
the traced window. Where this approaches the device's time a window, the
host enqueue runs in lockstep with the device."""
from bench import program_spans


def read(run):
    found = program_spans.in_window(run, "session.dispatch")
    if not found:
        return None
    return program_spans.seconds(found) * 1e3 / run.counters["windows"]
