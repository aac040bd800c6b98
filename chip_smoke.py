#!/usr/bin/env python3
"""Chip smoke test: drive the partitioner's main path once on a TPU.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # the vertex-sharded session, 4 chips

One chip. A dense ``Partitioner`` holds n = 2**22 vertices at
max_deg = 192 with up to K = 16 autoscaled partitions (about 3.2 GB of
state). It takes a seeded power-law churn stream (adds, vertex deletes and
edge deletes, with ids spread over the whole universe) through ``feed()``
in uneven chunks, so full windows and scan tails both run. Its state after
the first events is checked against the pure-Python oracle
(``repro.core.ref``). The same events then run through an
``engine="scan"`` session, a ``use_kernel=True`` session (the compiled
Pallas kernels) and a ``PartitionService`` (``submit``, ``flush``,
``where_many``, ``route``). Each must end in the windowed session's state,
bit for bit. Last, a ``Sweep`` of sdp, greedy and ldg lanes at n = 2**16
runs through the compiled fused chooser (``.kernel()``) and must match
the XLA window sweep lane for lane.

Four chips (``--four-chips``; this phase alone). A ``sharded=True``
session at n = 2**22 across the four chips must match a dense session on
chip 0 bit for bit. A sharded session at n = 2**24 (about 12.9 GB of
state, more than one chip holds) then takes a few windows through
``PartitionService`` and answers ``where_many``.

Earlier lines of the output report each phase's wall and compile seconds,
state bytes and every device's peak bytes in use. The last line is one
JSON object naming the device. The script exits non-zero, without that
line, when JAX finds no TPU or when any check fails.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parent
sys.path.insert(0, str(REPO / "src"))

MAX_DEG = 192
WINDOW = 256
N_ONE_CHIP = 1 << 22
N_BEYOND_ONE_CHIP = 1 << 24
EVENTS = 20_000
ORACLE_EVENTS = 4_096
CHUNKS = (1000, 333, 2500, 77, 1800, 5)   # uneven feed() sizes, cycled
QUERIES = 300
SWEEP_N = 1 << 16
SWEEP_LANES = ("sdp", "greedy", "ldg")
ROW_FIELDS = ("assignment", "present", "adj")


def cfg():
    from repro.core.config import EngineConfig
    return EngineConfig(k_max=16, k_init=1, autoscale=True, max_cap=3000)


class Clock:
    """Wall and backend-compile seconds of one phase, and what it left on
    the devices."""

    compile_s = 0.0

    @classmethod
    def install(cls):
        import jax

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                cls.compile_s += duration

        jax.monitoring.register_event_duration_secs_listener(on_duration)

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self.t0, self.c0 = time.perf_counter(), Clock.compile_s
        return self

    def __exit__(self, *exc):
        if exc[0] is None:
            import jax
            peaks = [d.memory_stats()["peak_bytes_in_use"]
                     for d in jax.devices()]
            print(f"phase {self.name}: wall_s="
                  f"{time.perf_counter() - self.t0:.3f} compile_s="
                  f"{Clock.compile_s - self.c0:.3f} "
                  f"peak_bytes_in_use={peaks}", flush=True)


def chunks_of(stream, lo: int = 0, hi: int | None = None):
    """``stream[lo:hi]`` as uneven (etype, vertex, nbrs) chunks."""
    hi = stream.num_events if hi is None else hi
    out, t, i = [], lo, 0
    while t < hi:
        end = min(t + CHUNKS[i % len(CHUNKS)], hi)
        out.append((stream.etype[t:end], stream.vertex[t:end],
                    stream.nbrs[t:end]))
        t, i = end, i + 1
    return out


def full_windows(chunks) -> int:
    return sum(len(c[0]) // WINDOW for c in chunks)


def host_state(state, n: int) -> dict:
    return {f: (np.asarray(getattr(state, f))[:n] if f in ROW_FIELDS
                else np.asarray(getattr(state, f)))
            for f in state._fields}


def check_same(name: str, got: dict, want: dict) -> None:
    bad = [f for f in want if not np.array_equal(got[f], want[f])]
    if bad:
        raise AssertionError(f"{name}: state differs in {bad}")
    print(f"check {name}: bit-identical in {len(want)} fields", flush=True)


def check_oracle(stream, n_events: int, got: dict, c) -> None:
    """The session's state after the first ``n_events`` events against
    the dict-based oracle of Algorithm 1."""
    from repro.core.ref import run_reference
    from repro.graph.stream import VertexStream
    prefix = VertexStream(stream.etype[:n_events], stream.vertex[:n_events],
                          stream.nbrs[:n_events], stream.n)
    ref = run_reference(prefix, policy="sdp", cfg=c, seed=0)
    want_assign = np.full(stream.n, -1, np.int64)
    for v, p in ref.assignment.items():
        want_assign[v] = p
    live = np.where(got["present"], got["assignment"], -1)
    checks = {
        "assignment": np.array_equal(live, want_assign),
        "edge_load": list(got["edge_load"]) == ref.edge_load,
        "vertex_count": list(got["vertex_count"]) == ref.vertex_count,
        "active": list(got["active"]) == ref.active,
        "total_edges": int(got["total_edges"]) == ref.total_edges,
        "cut_edges": int(got["cut_edges"]) == ref.cut_edges,
        "scale_events": int(got["scale_events"]) == ref.scale_events,
        "cut_matrix": np.array_equal(got["cut_matrix"], ref.cut_matrix),
    }
    bad = [k for k, ok in checks.items() if not ok]
    if bad:
        raise AssertionError(f"oracle: the session differs in {bad}")
    print(f"check oracle: {n_events} events, {len(ref.assignment)} vertices"
          f", {ref.scale_events} scale events, equal in {len(checks)} "
          "fields", flush=True)


def feed_all(part, chunks):
    for ch in chunks:
        part.feed(ch)
    return part.sync()


def one_chip(n: int = N_ONE_CHIP, events: int = EVENTS,
             oracle_events: int = ORACLE_EVENTS, seed: int = 0) -> None:
    from repro.api import PartitionService, Partitioner
    from repro.graph.stream import powerlaw_churn
    from repro.kernels.common import default_interpret

    c = cfg()
    with Clock("generate"):
        stream = powerlaw_churn(n, events, max_deg=MAX_DEG, seed=seed)
    head = chunks_of(stream, 0, oracle_events)
    tail = chunks_of(stream, oracle_events)
    chunks = head + tail
    print(f"stream: events={stream.num_events} adds="
          f"{int(np.sum(stream.etype == 0))} vertex_deletes="
          f"{int(np.sum(stream.etype == 1))} edge_deletes="
          f"{int(np.sum(stream.etype == 2))} chunks={len(chunks)} "
          f"full_windows={full_windows(chunks)}", flush=True)
    assert full_windows(chunks) >= 64, "fewer than 64 full windows"

    def session(**kw):
        return Partitioner(c, n=n, max_deg=MAX_DEG, policy="sdp", seed=seed,
                           window=WINDOW, **kw)

    with Clock("windowed"):
        part = feed_all(session(), head)
        check_oracle(stream, oracle_events, host_state(part.state, n), c)
        feed_all(part, tail)
        want = host_state(part.state, n)
        m = part.metrics()
    print(f"windowed: state_bytes={m['state_bytes']} num_partitions="
          f"{m['num_partitions']} scale_events={m['scale_events']} "
          f"total_edges={int(want['total_edges'])} cut_edges="
          f"{int(want['cut_edges'])}", flush=True)
    assert int(want["scale_events"]) > 0, "autoscale never fired"
    assert int(want["total_edges"]) > 0
    del part
    gc.collect()

    with Clock("scan"):
        part = feed_all(session(engine="scan"), chunks)
        got = host_state(part.state, n)
    check_same("scan vs windowed", got, want)
    del part
    gc.collect()

    assert not default_interpret(), "Pallas would run in interpret mode"
    with Clock("kernel"):
        part = feed_all(session(use_kernel=True), chunks)
        got = host_state(part.state, n)
        windows = part.metrics()["windows"]
        kernel_windows = windows["mixed_kernel"] + windows["adds_kernel"]
    assert kernel_windows >= 64, f"only {kernel_windows} kernel windows"
    check_same("use_kernel vs windowed", got, want)
    del part
    gc.collect()

    rng = np.random.default_rng(seed + 1)
    with Clock("service"):
        with PartitionService(session(), max_pending_chunks=16) as svc:
            for ch in chunks:
                svc.submit(ch)
            svc.flush()
            ids = np.concatenate([
                rng.choice(stream.vertex, QUERIES // 2),
                rng.integers(0, n, QUERIES - QUERIES // 2)]).astype(np.int32)
            where = svc.where_many(ids)
            adds = np.flatnonzero(stream.etype == 0)
            picks = rng.choice(adds, QUERIES)
            edges = np.stack([stream.vertex[picks],
                              stream.nbrs[picks, 0]], axis=1)
            edges = edges[edges[:, 1] >= 0]
            routed = svc.route(edges)
            got = host_state(svc.partitioner.state, n)
            sm = svc.metrics()
    check_same("service vs windowed", got, want)
    live = np.where(want["present"], want["assignment"], -1)
    assert np.array_equal(where, live[ids]), "where_many disagrees"
    src, dst = live[edges[:, 0]], live[edges[:, 1]]
    assert np.array_equal(routed.src_part, src)
    assert np.array_equal(routed.dst_part, dst)
    assert np.array_equal(routed.cut, (src != dst) & (src >= 0) & (dst >= 0))
    print(f"check service queries: where_many={len(ids)} ids, route="
          f"{len(edges)} edges, cut={int(routed.cut.sum())}; "
          f"batches_dispatched={sm['batches_dispatched']}", flush=True)

    sweep_kernel(seed)


def sweep_kernel(seed: int) -> None:
    """``Sweep(...).kernel()``: lanes of several policies through the
    compiled fused chooser, lane for lane equal to the XLA window sweep."""
    from repro.api import Sweep
    from repro.graph.stream import powerlaw_churn

    stream = powerlaw_churn(SWEEP_N, 8 * WINDOW, max_deg=MAX_DEG,
                            seed=seed + 3)
    runs = [(p, cfg(), seed) for p in SWEEP_LANES]
    with Clock("sweep kernel"):
        want = Sweep(stream).lanes(runs).windowed(WINDOW).run()
        got = Sweep(stream).lanes(runs).windowed(WINDOW).kernel().run()
    for w, g in zip(want, got):
        check_same(f"sweep kernel lane {w.policy} vs XLA lane",
                   host_state(g.state, SWEEP_N), host_state(w.state, SWEEP_N))


def four_chips(n: int = N_ONE_CHIP, n_big: int = N_BEYOND_ONE_CHIP,
               events: int = 8_192, big_windows: int = 8,
               seed: int = 0) -> None:
    from repro.api import PartitionService, Partitioner
    from repro.graph.stream import powerlaw_churn
    import jax

    devices = jax.devices()
    assert len(devices) == 4, f"--four-chips needs 4 devices, has {len(devices)}"
    c = cfg()
    stream = powerlaw_churn(n, events, max_deg=MAX_DEG, seed=seed)
    chunks = chunks_of(stream)

    def session(n_, **kw):
        return Partitioner(c, n=n_, max_deg=MAX_DEG, policy="sdp", seed=seed,
                           window=WINDOW, **kw)

    with Clock("dense on chip 0"):
        part = feed_all(session(n), chunks)
        want = host_state(part.state, n)
    assert {d for a in jax.tree.leaves(part.state)
            for d in a.devices()} == {devices[0]}
    del part
    gc.collect()

    with Clock(f"sharded n=2**{n.bit_length() - 1}"):
        part = feed_all(session(n, sharded=True, shard_devices=4), chunks)
        got = host_state(part.state, n)
        m = part.metrics()
    assert {sh.device for sh in part.state.adj.addressable_shards} \
        == set(devices), "adj does not span the four devices"
    print(f"sharded n={n}: shard_devices={m['shard_devices']} "
          f"per_device_state_bytes={m['per_device_state_bytes']} "
          f"state_bytes={m['state_bytes']}", flush=True)
    check_same("sharded vs dense", got, want)
    del part
    gc.collect()

    big = powerlaw_churn(n_big, big_windows * WINDOW + 100, max_deg=MAX_DEG,
                         seed=seed + 1)
    rng = np.random.default_rng(seed + 2)
    with Clock(f"sharded n=2**{n_big.bit_length() - 1}"):
        part = session(n_big, sharded=True, shard_devices=4)
        with PartitionService(part, max_pending_chunks=16) as svc:
            for ch in chunks_of(big):
                svc.submit(ch)
            svc.flush()
            ids = rng.choice(big.vertex, QUERIES).astype(np.int32)
            where = svc.where_many(ids)
            m = svc.metrics()
    assert m["per_device_state_bytes"] * 3 < m["state_bytes"]
    assert m["cursor"] == big.num_events
    present_ids = np.asarray(part.state.present)[ids]
    assert np.array_equal(where >= 0, present_ids), "where_many disagrees"
    assert int(np.sum(present_ids)) > 0
    print(f"sharded n={n_big}: state_bytes={m['state_bytes']} "
          f"per_device_state_bytes={m['per_device_state_bytes']} "
          f"events={m['cursor']} where_many={len(ids)} ids, "
          f"{int(np.sum(where >= 0))} assigned", flush=True)


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--four-chips", action="store_true",
                   help="run the vertex-sharded session on four chips "
                        "(and nothing else)")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args()

    from repro.runtime.compile_cache import enable_compile_cache
    cache = enable_compile_cache(REPO)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform})",
              file=sys.stderr)
        return 1
    d = devices[0]
    print(f"device: platform={d.platform} kind={d.device_kind} "
          f"count={len(devices)} compile_cache={cache}", flush=True)
    Clock.install()
    if args.four_chips:
        four_chips(seed=args.seed)
    else:
        one_chip(seed=args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
