"""Moves of the dense session's adjacency into the row-major layout its
window programs take, inside the traced window: the session's
``session.relayout`` spans there. Only a step outside those programs (a
regeometry, repack, rebalance, restore or ``place``) makes one, so this
should read 0. ``None`` where the session keeps no such layout."""
from bench import program_spans


def read(run):
    try:
        from repro.api import partitioner
    except ImportError:
        return None
    if not hasattr(partitioner, "adj_format"):
        return None
    found = program_spans.in_window(run, "session.relayout")
    if found is None:
        return None
    return float(len(found))
