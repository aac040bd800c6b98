"""Closed-loop replay: the stream through ``Partitioner.feed`` in whole chunks.

Each chunk holds ``chunk_events`` events, a whole number of windows, so
every dispatch is a full window and no scan tail runs. The loop keeps at
most two chunks in flight: it feeds a chunk, then waits for the one
before it. The window's rate is every event fed in the window over the
time from its start until the last of them is committed.

Before the window the configuration's snapshot is loaded through the
same ``feed`` (the adds program), then ``warmup_chunks``
chunks of the churn are fed and committed (the mixed program), so every
window program the window runs is compiled.

Traffic parameters: ``mix``, the event mix; ``chunk_events``;
``warmup_chunks``; ``stream_events``, the capacity a run must not
exhaust.
"""
from __future__ import annotations

import gc
import time


def run(r) -> None:
    import jax.numpy as jnp

    from bench import harness

    s = r.stream
    chunk = int(r.traffic["chunk_events"])
    window = int(r.session_cfg["window"])
    if chunk % window:
        raise ValueError(f"chunk_events={chunk} is not a whole number of "
                         f"{window}-event windows")
    part = r.session()
    r.note("session built")

    def feed(t: int):
        if t + chunk > s.num_events:
            raise RuntimeError(
                f"the stream ran out after {t} events: raise stream_events "
                "in the traffic file")
        with r.span("feed"):
            part.feed(s.chunk(t, t + chunk))
        return jnp.add(part.state.cut_edges, 0)   # done when the chunk is

    t = harness.load_snapshot(r, part)
    for _ in range(int(r.traffic["warmup_chunks"])):
        feed(t).block_until_ready()
        t += chunk
    t_warm = t

    with r.window():
        t0 = time.perf_counter()
        deadline = t0 + r.window_seconds
        prev = None
        while True:
            token = feed(t)
            t += chunk
            if prev is not None:
                with r.span("wait"):
                    prev.block_until_ready()
            prev = token
            if time.perf_counter() >= deadline:
                break
        with r.span("wait"):
            prev.block_until_ready()
        t1 = time.perf_counter()

    events = t - t_warm
    r.attempted = events
    r.e2e["events_per_s"] = events / (t1 - t0)
    r.counters.update(windows=events // window, window_begin=t_warm,
                      window_end=t)
    r.read_memory_peak()
    snap = harness.snapshot(part.state, s, t)
    del part
    gc.collect()
    r.check_against_reference(snap, t)
