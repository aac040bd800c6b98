"""Share of the slots the served session dispatched that were padding:
``100 * (1 - sum(events) / sum(slots))`` over the ``session.dispatch``
spans inside the traced window (a batch's tail window is padded to
whole windows under ``engine="windowed"``)."""
from bench import program_spans


def read(run):
    found = program_spans.in_window(run, "session.dispatch")
    if not found:
        return None
    events = sum(s.attrs["events"] for s in found)
    slots = sum(s.attrs["slots"] for s in found)
    return 100.0 * (1.0 - events / slots)
