"""Beyond-paper Fig. 14: serving throughput and latency under Poisson
arrivals — the ROADMAP's "heavy traffic" number.

Event batches arrive on a Poisson process (``stream.poisson_arrivals``:
Poisson-sized bursts, exponential gaps, long-run rate λ events/s). Two
drivers ingest the identical workload:

* **sync_feed** — the naive request loop: per arrival, ``feed()`` then
  ``sync()`` (block) before touching the next batch. The host idles
  while the device runs and vice versa, and every ~mean_batch-event
  arrival occupies a full engine window.
* **service** — ``repro.api.serve.PartitionService``: submits are cheap
  enqueues; the double-buffered ingest thread coerces batch *t+1* while
  the device runs batch *t* and coalesces everything queued into full
  windows (continuous batching).

Both sessions pin ``engine="windowed"`` so every dispatch is the same
``(window,)`` shape — one compile each for the adds/mixed kernels,
warmed by the reference run, so the measurement is steady-state serving,
not recompiles. λ is calibrated to 2× the sync driver's unthrottled
capacity: the sync driver saturates (its p99 explodes — the point) while
the service has headroom to show its sustained rate.

``feed`` is bit-identical under any chopping, so both drivers — and the
service's coalesced batches — must land exactly on the whole-stream
reference state; asserted per run. Writes BENCH_serving.json (mirrored
to the repo root; CI bench-smoke runs fig14 and uploads it).
"""
from __future__ import annotations

import time

import numpy as np

from benchmarks import common as C
from repro.api import Partitioner, PartitionService
from repro.core import EngineConfig
from repro.graph import stream as gstream

WINDOW = 128
MEAN_BATCH = 24.0
OVERLOAD = 4.0          # λ = OVERLOAD × sync capacity: firm saturation
MAX_PENDING = 64


def _stream(quick: bool) -> gstream.VertexStream:
    # deliberately larger than the usual quick scale (0.25): serving runs
    # must be long enough (≥ ~0.5 s) that 1-core thread-scheduling noise
    # does not swamp the throughput signal
    from repro.graph.datasets import load_dataset
    g = load_dataset("3elt", scale=0.75 if quick else 1.0)
    return gstream.interleaved_churn(g, warmup_frac=0.25, del_every=3,
                                     edge_del_every=7, seed=0)


def _cfg(s: gstream.VertexStream) -> EngineConfig:
    return EngineConfig(k_max=16, k_init=1, autoscale=True,
                        max_cap=max(s.num_events // 6, 30))


def _session(s, cfg) -> Partitioner:
    return Partitioner.from_stream(s, cfg, seed=0, engine="windowed",
                                   window=WINDOW)


def _batches(s, bounds):
    return [(s.etype[a:b], s.vertex[a:b], s.nbrs[a:b])
            for a, b in zip(bounds[:-1], bounds[1:])]


def _percentiles(lat: np.ndarray) -> dict:
    return {"p50_ms": float(np.percentile(lat, 50) * 1e3),
            "p99_ms": float(np.percentile(lat, 99) * 1e3)}


def _assert_match(ref, got, who: str) -> None:
    if not all(np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(ref, got)):
        raise AssertionError(
            f"{who} final state diverged from the synchronous whole-stream "
            "reference — feed() chop-invariance must hold under serving")


def _run_sync(s, cfg, batches, due):
    """The naive per-arrival loop: sleep to the due time, feed, block."""
    part = _session(s, cfg)
    lat = np.empty(len(batches))
    t0 = time.perf_counter()
    for i, chunk in enumerate(batches):
        ahead = due[i] - (time.perf_counter() - t0)
        if ahead > 0:
            time.sleep(ahead)
        part.feed(chunk).sync()
        lat[i] = (time.perf_counter() - t0) - due[i]
    return part, time.perf_counter() - t0, lat


def _run_service(s, cfg, batches, due):
    part = _session(s, cfg)
    svc = PartitionService(part, max_pending_chunks=MAX_PENDING,
                           policy="block")
    t0 = time.perf_counter()
    for i, chunk in enumerate(batches):
        ahead = due[i] - (time.perf_counter() - t0)
        if ahead > 0:
            time.sleep(ahead)
        svc.submit(chunk, arrival=t0 + due[i])
    svc.flush()
    dur = time.perf_counter() - t0
    lat = svc.latencies()
    m = svc.metrics()
    svc.close()
    return part, dur, lat, m


def run(quick: bool = True) -> list:
    s = _stream(quick)
    cfg = _cfg(s)
    T = s.num_events

    # reference: one synchronous whole-stream feed — the bit-identity
    # anchor AND the compile warmup (every serving dispatch below reuses
    # these (WINDOW,)-shaped kernels)
    ref = _session(s, cfg).feed(s).sync().state

    # calibrate: unthrottled sync capacity (everything due at t=0).
    # Run twice and keep the second — the first pays one-off process
    # warmup (kernel-cache population for the per-arrival chunking) that
    # would understate capacity and leave λ below saturation.
    bounds, _ = gstream.poisson_arrivals(s, rate=1.0,
                                         mean_batch=MEAN_BATCH, seed=1)
    batches = _batches(s, bounds)
    _run_sync(s, cfg, batches, np.zeros(len(batches)))
    part, dur0, lat0 = _run_sync(s, cfg, batches, np.zeros(len(batches)))
    _assert_match(ref, part.state, "unthrottled sync")
    cap_sync = T / max(dur0, 1e-9)
    lam = OVERLOAD * cap_sync
    _, due = gstream.poisson_arrivals(s, rate=lam, mean_batch=MEAN_BATCH,
                                      seed=1)

    part, dur_sync, lat_sync = _run_sync(s, cfg, batches, due)
    _assert_match(ref, part.state, "sync_feed")
    eps_sync = T / max(dur_sync, 1e-9)

    part, dur_svc, lat_svc, svc_m = _run_service(s, cfg, batches, due)
    _assert_match(ref, part.state, "service")
    eps_svc = T / max(dur_svc, 1e-9)

    base = {"events": T, "chunks": len(batches), "mean_batch": MEAN_BATCH,
            "window": WINDOW, "arrival_rate_eps": lam,
            "states_match_reference": True}
    rows = [
        dict(base, variant="sync_unthrottled", seconds=dur0,
             events_per_s=cap_sync, **_percentiles(lat0)),
        dict(base, variant="sync_feed", seconds=dur_sync,
             events_per_s=eps_sync, **_percentiles(lat_sync)),
        dict(base, variant="service", seconds=dur_svc, events_per_s=eps_svc,
             speedup_vs_sync=eps_svc / max(eps_sync, 1e-9),
             batches_dispatched=svc_m["batches_dispatched"],
             coercion_s=svc_m["coercion_s"],
             device_wait_s=svc_m["device_wait_s"],
             submit_blocked_s=svc_m["submit_blocked_s"],
             max_queue_depth=svc_m["max_queue_depth"],
             **_percentiles(lat_svc)),
    ]
    C.save_rows("fig14_serving", rows)
    C.save_rows("BENCH_serving", rows)
    return rows


def summarize(rows) -> list[str]:
    d = {r["variant"]: r for r in rows}
    svc, sync = d["service"], d["sync_feed"]
    return [
        f"fig14/serving,{svc['seconds']:.3f},"
        f"events_per_s={svc['events_per_s']:.0f}"
        f";speedup_vs_sync={svc['speedup_vs_sync']:.2f}x"
        f";p99_ms={svc['p99_ms']:.1f}(sync={sync['p99_ms']:.1f})"
        f";states_match={svc['states_match_reference']}"
    ]
