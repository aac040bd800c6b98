"""Open-loop serving: Poisson arrivals into ``PartitionService`` at a fixed rate.

Chunks of Poisson-distributed size (mean ``mean_chunk_events``) are due
at the times of a Poisson process whose long-run rate is ``rate`` events
per second; every seed offers the same seconds of arrivals, bursts and
all, in its own order (see ``schedule``). The load generator sleeps until each chunk is due and submits
it, stamped with its due time, whether or not the service keeps up; a
chunk's commit latency runs from its due time to the completion of the
batch that holds it. Every chunk due in the window is counted, including
those still waited for when the window's last chunk has been submitted.

Before the window the configuration's snapshot is loaded through the
session's ``feed``; warm-up then commits one chunk of each length from 1
to ``window`` events, each alone, through a service of its own: a
served batch is padded to whole windows, and the padding of each tail
length is a program of its own. After the window the service answers
``where_many`` for ``queries`` ids drawn from the seed, half of them
vertices the stream added.

Traffic parameters: ``mix``, the event mix; ``rate``,
``mean_chunk_events``, ``schedule_seed``, ``block_seconds``, ``queries``,
``max_pending_chunks`` (large enough that ``submit`` never blocks),
``stream_events`` (the capacity a run must not exhaust), and ``session``
(the serving engine, as docs/SERVING.md configures it).
"""
from __future__ import annotations

import gc
import time

import numpy as np


def warm_up(r, part) -> int:
    """Load the snapshot, then commit the warm-up chunks through a service
    of their own; returns the events they took."""
    from repro.api import PartitionService

    from bench import harness

    s = r.stream
    w = int(part.window)
    t = harness.load_snapshot(r, part)
    with PartitionService(part, max_pending_chunks=4) as svc:
        for size in range(1, w + 1):
            svc.submit(s.chunk(t, t + size))
            svc.flush()
            t += size
    return t


def schedule(r, available: int, rate: float, seconds: float):
    """The window's chunks: ``(bounds, due)``, chunk ``i`` being events
    ``bounds[i]:bounds[i+1]`` after the warm-up, due ``due[i]`` seconds
    into the window. The chunks are those of one Poisson schedule drawn
    from the traffic's ``schedule_seed`` and due within ``seconds``, cut
    into blocks of ``block_seconds``; the run's seed orders the blocks,
    and each block keeps its chunks' sizes and times within it. So every
    seed offers the same work, with the same bursts, over the same time
    (the increments of a Poisson process are exchangeable)."""
    from bench.traffic import poisson_arrivals
    bounds, due = poisson_arrivals(
        available, rate=rate, mean_batch=float(r.traffic["mean_chunk_events"]),
        seed=int(r.traffic["schedule_seed"]))
    k = int(np.searchsorted(due, seconds))
    if k == len(due):
        raise RuntimeError(
            f"the stream ran out inside the window: {len(due)} chunks are "
            f"all due before {seconds} s; raise stream_events in the "
            "traffic file")
    sizes = np.diff(bounds[:k + 1])
    due = due[:k]
    block = float(r.traffic["block_seconds"])
    blk = np.floor(due / block).astype(np.int64)
    order = np.random.default_rng(r.seed).permutation(int(np.ceil(seconds
                                                                   / block)))
    idx = np.concatenate([np.flatnonzero(blk == b) for b in order])
    pos = np.empty_like(order)
    pos[order] = np.arange(order.size)
    new_due = due[idx] + (pos[blk[idx]] - blk[idx]) * block
    return np.concatenate([[0], np.cumsum(sizes[idx])]), new_due


def serve_window(r, part, t_warm: int, rate: float, seconds: float):
    """Offer ``rate`` events/s for ``seconds`` from event ``t_warm`` on.
    Returns the service (flushed, still open), the chunks' bounds, their
    due times, their commit latencies and how late each was submitted, and
    the window's length from its start to its last commit."""
    from repro.api import PartitionService

    s = r.stream
    bounds, due = schedule(r, s.num_events - t_warm, rate, seconds)
    k = len(due)
    bounds = bounds + t_warm
    lag = np.empty(k)
    svc = PartitionService(part, policy="block",
                           max_pending_chunks=int(r.traffic["max_pending_chunks"]))
    with r.window():
        t0 = time.perf_counter()
        for i in range(k):
            target = t0 + due[i]
            ahead = target - time.perf_counter()
            if ahead > 0:
                with r.span("wait"):
                    time.sleep(ahead)
            now = time.perf_counter()
            lag[i] = now - target
            with r.span("submit"):
                svc.submit(s.chunk(int(bounds[i]), int(bounds[i + 1])),
                           arrival=target)
        with r.span("flush"):
            svc.flush()
    lat = svc.latencies()
    length = float(np.max(due + lat))
    return svc, bounds, due, lat, lag, length


def worst(due, lat, count: int = 5) -> list:
    """The ``count`` slowest chunks as ``[due s, latency ms]``."""
    i = np.argsort(lat)[::-1][:count]
    return [[float(due[j]), float(lat[j] * 1e3)] for j in i]


def run(r) -> None:
    from bench import harness

    s = r.stream
    part = r.session()
    r.note("session built")
    t_warm = warm_up(r, part)
    svc, bounds, due, lat, lag, length = serve_window(
        r, part, t_warm, float(r.traffic["rate"]), r.window_seconds)
    events = int(bounds[-1] - bounds[0])
    m = svc.metrics()
    r.attempted = len(lat)
    r.e2e["commit_p50_ms"] = float(np.percentile(lat, 50) * 1e3)
    r.counters.update(
        commit_p99_ms=float(np.percentile(lat, 99) * 1e3),
        events=events, chunks=len(lat),
        batches_dispatched=m["batches_dispatched"],
        coercion_s=m["coercion_s"], window_s=length,
        lag_p99_ms=float(np.percentile(lag, 99) * 1e3),
        max_queue_depth=m["max_queue_depth"], worst_chunks=worst(due, lat))

    consumed = int(bounds[-1])
    rng = np.random.default_rng(r.seed + 2)
    q = int(r.traffic["queries"])
    added = harness.touched_ids(s, consumed)
    ids = np.concatenate([rng.choice(added, q // 2),
                          rng.integers(0, s.n, q - q // 2)]).astype(np.int32)
    where = svc.where_many(ids)
    r.read_memory_peak()
    snap = harness.snapshot(part.state, s, consumed)
    svc.close()
    del svc, part
    gc.collect()
    r.check_against_reference(snap, consumed, where=(ids, where))
