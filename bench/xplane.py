"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's device numbers.

The harness records its own host spans into the trace with
``jax.profiler.TraceAnnotation``: ``bench.window`` around the traced
window and ``bench.<step>`` around each step of a driver (``feed``,
``wait``, ``submit``, ...). ``reduce`` reads, inside ``bench.window``:

- each device's busy time, the union of the intervals in which an
  operation ran on it (the ``XLA Ops`` line of each ``/device:`` plane);
- each device's all-reduce time, the union of its all-reduce operations;
- the operations that took most time on the busiest device (an
  operation that holds others, such as a loop, counts their time too);
- the idle gaps of the busiest device, each named by the harness span
  that covers most of it (what the host was doing meanwhile);
- the count and total seconds of each harness span.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from collections import defaultdict

SPAN_PREFIX = "bench."
WINDOW_SPAN = SPAN_PREFIX + "window"
OPS_LINE = "XLA Ops"
TOP = 10


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: list[float]                  # per device, inside the window
    allreduce_s: list[float]             # per device, inside the window
    top_ops: list[tuple[str, float]]     # busiest device: name, seconds
    idle_gaps: list[tuple[str, float]]   # busiest device: span, seconds
    spans: dict[str, tuple[int, float]]  # harness span: count, seconds


def find_trace(log_dir: str) -> str:
    """The one ``.xplane.pb`` file a trace into ``log_dir`` wrote."""
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {len(found)}")
    return found[0]


def _union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged intervals, clipped to ``[lo, hi]``."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _length(intervals) -> float:
    return sum(b - a for a, b in intervals)


def _op_name(text: str) -> str:
    """An operation's name: a TPU trace names each event by its whole HLO
    instruction (``%copy.20 = s32[...] copy(...)``)."""
    return text.split(" = ", 1)[0].lstrip("%")


def _is_allreduce(name: str) -> bool:
    low = name.lower()
    return "all-reduce" in low or "allreduce" in low


def reduce(path: str) -> TraceSummary:
    """Read the trace at ``path`` (see the module docstring)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    spans: list[tuple[str, float, float]] = []
    devices: list[list[tuple[str, float, float]]] = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices.append([(_op_name(e.name), e.start_ns, e.end_ns)
                                    for e in line.events])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.end_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    if not devices:
        raise RuntimeError(f"no device plane with an {OPS_LINE!r} line in "
                           f"{path}")
    return summarize(spans, devices)


def summarize(spans, devices) -> TraceSummary:
    """The summary of host ``spans`` (name, start ns, end ns) and each
    device's operations (name, start ns, end ns)."""
    windows = [(a, b) for name, a, b in spans if name == WINDOW_SPAN]
    if len(windows) != 1:
        raise RuntimeError(f"expected one {WINDOW_SPAN} span, found "
                           f"{len(windows)}")
    w0, w1 = windows[0]
    busy, allreduce = [], []
    for ops in devices:
        busy.append(_union([(a, b) for _, a, b in ops], w0, w1))
        allreduce.append(_union([(a, b) for name, a, b in ops
                                 if _is_allreduce(name)], w0, w1))
    k = max(range(len(busy)), key=lambda i: _length(busy[i]))

    per_op: dict[str, float] = defaultdict(float)
    for name, a, b in devices[k]:
        a, b = max(a, w0), min(b, w1)
        if b > a:
            per_op[name] += b - a
    top_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]

    # the driver's steps run one after another on its thread, so sorted
    # by start they are sorted by end too
    steps = sorted(((name[len(SPAN_PREFIX):], a, b) for name, a, b in spans
                    if name != WINDOW_SPAN), key=lambda st: st[1])
    starts = [a for _, a, _ in steps]
    gaps = []
    edges = [w0] + [t for iv in busy[k] for t in iv] + [w1]
    for a, b in zip(edges[::2], edges[1::2]):
        if b <= a:
            continue
        cover: dict[str, float] = defaultdict(float)
        i = bisect.bisect_left(starts, b) - 1
        while i >= 0 and steps[i][2] > a:
            name, s0, s1 = steps[i]
            cover[name] += min(b, s1) - max(a, s0)
            i -= 1
        gaps.append((max(cover, key=cover.get) if cover else "other", b - a))
    gaps.sort(key=lambda g: -g[1])

    span_stats: dict[str, tuple[int, float]] = {}
    for name, a, b in steps:
        if a >= w0 and b <= w1:
            c, s = span_stats.get(name, (0, 0.0))
            span_stats[name] = (c + 1, s + (b - a) * 1e-9)
    return TraceSummary(
        window_s=(w1 - w0) * 1e-9,
        busy_s=[_length(iv) * 1e-9 for iv in busy],
        allreduce_s=[_length(iv) * 1e-9 for iv in allreduce],
        top_ops=[(name, s * 1e-9) for name, s in top_ops],
        idle_gaps=[(name, s * 1e-9) for name, s in gaps[:TOP]],
        spans=span_stats)
