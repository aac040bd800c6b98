"""Host milliseconds per window the session spent coercing what it was
fed: its ``session.prepare`` spans (dtype coercion, the required
geometry) and ``session.ingest`` spans (id translation, geometry checks,
row re-widthing) inside the traced window."""
from bench import program_spans


def read(run):
    found = program_spans.in_window(run, "session.prepare",
                                    "session.ingest")
    if not found:
        return None
    return program_spans.seconds(found) * 1e3 / run.counters["windows"]
