"""``PartitionService`` must serve without changing the answer: after any
sequence of async submits the state is bit-identical to a synchronous
``feed`` of the same events in submission order — under coalescing,
backpressure (block and drop), mid-stream elastic auto-grow, and
queries racing ingest. Plus the host-side seams the service is built
from: ``prepare``/``feed_prepared``/``sync`` and ``poisson_arrivals``."""
import time

import numpy as np
import pytest

from repro.api import Partitioner, PartitionService, PreparedChunk
from repro.core import run_stream
from repro.graph import stream as gstream
from repro.runtime import telemetry

from tests.test_api_partitioner import _churn_fixture, _identical


def _chunks(s, size):
    return [(s.etype[t:t + size], s.vertex[t:t + size], s.nbrs[t:t + size])
            for t in range(0, s.num_events, size)]


def _session(s, cfg, **kw):
    kw.setdefault("window", 32)
    return Partitioner.from_stream(s, cfg, seed=0, **kw)


# -- bit-identity under serving ---------------------------------------------

def test_service_state_bit_identical_to_sync_feed():
    """N async submits (coalesced however the ingest thread pleases)
    land exactly on the whole-stream run_stream state."""
    s, cfg = _churn_fixture()
    ref, _ = run_stream(s, policy="sdp", cfg=cfg, seed=0)
    with PartitionService(_session(s, cfg), max_pending_chunks=4) as svc:
        for chunk in _chunks(s, 17):
            svc.submit(chunk)
        svc.flush()
        _identical(ref, svc.partitioner.state)
        assert svc.partitioner.cursor == s.num_events


def test_where_consistency_after_async_feeds():
    """Mid-stream: flush() then where_many == a synchronous session fed
    the same prefix (read-your-submits after the barrier)."""
    s, cfg = _churn_fixture()
    chunks = _chunks(s, 13)
    k = len(chunks) // 2
    sync = _session(s, cfg)
    for c in chunks[:k]:
        sync.feed(c)
    labels_sync = np.asarray(sync.state.assignment)

    with PartitionService(_session(s, cfg)) as svc:
        for c in chunks[:k]:
            svc.submit(c)
        svc.flush()
        got = svc.where_many(np.arange(s.n))
        present = np.asarray(sync.state.present)
        np.testing.assert_array_equal(got, labels_sync)
        assert svc.where(int(np.flatnonzero(present)[0])) >= 0
        # out-of-range ids answer -1, not raise
        assert svc.where(-3) == -1 and svc.where(s.n + 99) == -1
        # the remainder still feeds afterwards — and lands on the ref
        for c in chunks[k:]:
            svc.submit(c)
        svc.flush()
        ref, _ = run_stream(s, policy="sdp", cfg=cfg, seed=0)
        _identical(ref, svc.partitioner.state)


def test_service_survives_midstream_auto_grow():
    """A session born tiny (n=10, max_deg=2) auto-grows under the
    service's coalesced feeds and still matches the same session grown
    synchronously — elastic geometry is chop- and serve-invariant."""
    s, cfg = _churn_fixture()
    sync = Partitioner(cfg, n=10, max_deg=2, seed=0, window=32)
    sync.feed(s)
    assert sync.regeometries >= 1

    part = Partitioner(cfg, n=10, max_deg=2, seed=0, window=32)
    with PartitionService(part, max_pending_chunks=4) as svc:
        for chunk in _chunks(s, 29):
            svc.submit(chunk)
        svc.flush()
        assert part.regeometries >= 1
        assert (part.n, part.max_deg) == (sync.n, sync.max_deg)
        _identical(sync.state, part.state)


# -- backpressure -----------------------------------------------------------

def test_drop_policy_sheds_and_counts():
    """queue-full + policy='drop': submit returns False, the chunk is
    counted dropped, and the final state is exactly the admitted
    prefix."""
    s, cfg = _churn_fixture()
    chunks = _chunks(s, 11)
    svc = PartitionService(_session(s, cfg), max_pending_chunks=2,
                          policy="drop", autostart=False)
    assert svc.submit(chunks[0]) and svc.submit(chunks[1])
    assert svc.submit(chunks[2]) is False        # queue full: shed
    m = svc.metrics()
    assert m["chunks_dropped"] == 1
    assert m["chunks_submitted"] == 3
    svc.start()
    svc.flush()
    svc.close()
    sync = _session(s, cfg).feed(chunks[0]).feed(chunks[1])
    _identical(sync.state, svc.partitioner.state)


def test_block_policy_times_out_then_drains():
    """queue-full + policy='block': submit waits; with a timeout it
    raises TimeoutError and the chunk is NOT admitted; once started the
    queue drains and further submits go through."""
    s, cfg = _churn_fixture()
    chunks = _chunks(s, 11)
    svc = PartitionService(_session(s, cfg), max_pending_chunks=2,
                          policy="block", autostart=False)
    svc.submit(chunks[0])
    svc.submit(chunks[1])
    t0 = time.perf_counter()
    with pytest.raises(TimeoutError, match="queue slot"):
        svc.submit(chunks[2], timeout=0.05)
    assert time.perf_counter() - t0 >= 0.05
    assert svc.metrics()["submit_blocked_s"] > 0
    svc.start()
    for c in chunks[2:]:
        svc.submit(c)                            # blocks at most briefly now
    svc.flush()
    svc.close()
    ref, _ = run_stream(s, policy="sdp", cfg=cfg, seed=0)
    _identical(ref, svc.partitioner.state)


def test_block_policy_unblocks_when_ingest_drains():
    """A submit blocked on a full queue completes (no timeout) as soon
    as the started ingest thread frees a slot."""
    s, cfg = _churn_fixture()
    chunks = _chunks(s, 11)
    svc = PartitionService(_session(s, cfg), max_pending_chunks=1,
                          policy="block", autostart=False)
    svc.submit(chunks[0])
    import threading
    done = threading.Event()

    def late_start():
        time.sleep(0.05)
        svc.start()

    threading.Thread(target=late_start, daemon=True).start()
    assert svc.submit(chunks[1]) is True         # blocks until start() drains
    done.set()
    svc.flush()
    svc.close()


# -- queries ----------------------------------------------------------------

def test_route_semantics_and_input_forms():
    s, cfg = _churn_fixture()
    with PartitionService(_session(s, cfg)) as svc:
        for c in _chunks(s, 40):
            svc.submit(c)
        svc.flush()
        ids = np.arange(s.n, dtype=np.int32)
        labels = svc.where_many(ids)
        rng = np.random.default_rng(0)
        edges = rng.integers(0, s.n, size=(32, 2)).astype(np.int32)
        r = svc.route(edges)
        np.testing.assert_array_equal(r.src_part, labels[edges[:, 0]])
        np.testing.assert_array_equal(r.dst_part, labels[edges[:, 1]])
        np.testing.assert_array_equal(
            r.cut, (r.src_part != r.dst_part) & (r.src_part >= 0)
            & (r.dst_part >= 0))
        # one (u, v) edge and a (src, dst) pair of arrays
        one = svc.route((int(edges[0, 0]), int(edges[0, 1])))
        assert one.src_part.shape == (1,)
        assert one.src_part[0] == r.src_part[0]
        pair = svc.route((edges[:, 0], edges[:, 1]))
        np.testing.assert_array_equal(pair.cut, r.cut)
        with pytest.raises(ValueError, match="route"):
            svc.route(np.zeros((3, 4), np.int32))


def test_metrics_counters_and_lifecycle():
    s, cfg = _churn_fixture()
    chunks = _chunks(s, 13)
    t0 = time.perf_counter()
    svc = PartitionService(_session(s, cfg), max_pending_chunks=8)
    for c in chunks:
        svc.submit(c)
    svc.flush()
    m = svc.metrics()
    assert m["chunks_ingested"] == len(chunks)
    assert m["events_ingested"] == s.num_events
    assert 1 <= m["batches_dispatched"] <= len(chunks)
    assert m["queue_depth"] == 0
    assert m["chunks_dropped"] == 0
    # each chunk's life, stamp by stamp, from its due time to its commit
    lives = [r.attrs for r in telemetry.spans(since=t0)
             if r.name == "serve.chunk"]
    assert len(lives) == len(chunks)
    assert all(c["due"] <= c["submitted"] <= c["dequeued"]
               <= c["dispatched"] <= c["committed"] for c in lives)
    assert m["feed_p50_ms"] is not None and m["feed_p99_ms"] is not None
    assert m["feed_p50_ms"] <= m["feed_p99_ms"] + 1e-9
    assert m["events_per_s"] > 0
    # the session's metrics ride along (cursor uniformity: satellite fix)
    assert m["cursor"] == s.num_events
    assert m["events_ingested"] == m["cursor"]
    assert "edge_cut" in m and "regeometries" in m
    assert svc.latencies().shape == (len(chunks),)
    svc.close()
    svc.close()                                  # idempotent
    with pytest.raises(RuntimeError, match="closed"):
        svc.submit(chunks[0])
    # queries outlive close()
    assert svc.where(0) in (-1, *range(cfg.k_max))
    assert "closed=True" in repr(svc)


def test_chunk_stages_add_up_to_latencies():
    """For every chunk, lag (submitted - due) + queued + held + in flight
    is its ``latencies()`` entry; chunk ids are dense in submission
    order and batch ids increase with them."""
    s, cfg = _churn_fixture()
    chunks = _chunks(s, 13)
    t0 = time.perf_counter()
    svc = PartitionService(_session(s, cfg), max_pending_chunks=len(chunks),
                           autostart=False)
    for i, c in enumerate(chunks[:4]):     # staged: one batch takes them
        svc.submit(c, arrival=time.perf_counter() - 0.001 * i)
    svc.start()
    for c in chunks[4:]:
        svc.submit(c, arrival=time.perf_counter() - 0.002)
    svc.flush()
    lat = svc.latencies()
    svc.close()
    lives = sorted((r.attrs for r in telemetry.spans()
                    if r.name == "serve.chunk" and r.attrs["submitted"] >= t0),
                   key=lambda a: a["chunk"])
    assert [a["chunk"] for a in lives] == list(range(len(chunks)))
    batches = [a["batch"] for a in lives]
    assert batches == sorted(batches) and batches[:4] == [0, 0, 0, 0]
    assert batches[-1] == svc.metrics()["batches_dispatched"] - 1
    for a in lives:
        stages = (a["submitted"] - a["due"], a["dequeued"] - a["submitted"],
                  a["dispatched"] - a["dequeued"],
                  a["committed"] - a["dispatched"])
        assert min(stages) >= 0
        assert sum(stages) == pytest.approx(lat[a["chunk"]], abs=1e-6)


def test_constructor_validation_and_flush_guard():
    s, cfg = _churn_fixture()
    part = _session(s, cfg)
    with pytest.raises(ValueError, match="policy"):
        PartitionService(part, policy="nope")
    with pytest.raises(ValueError, match="max_pending_chunks"):
        PartitionService(part, max_pending_chunks=0)
    with pytest.raises(ValueError, match="max_batch_events"):
        PartitionService(part, max_batch_events=0)
    svc = PartitionService(part, autostart=False)
    with pytest.raises(RuntimeError, match="never-started"):
        svc.flush()
    svc.start()
    svc.close()


def test_ingest_error_surfaces_not_hangs():
    """A poison chunk kills the ingest loop; flush() must raise the
    error (wrapped), not wait forever."""
    s, cfg = _churn_fixture()
    svc = PartitionService(_session(s, cfg), max_pending_chunks=4)
    svc.submit(42)                               # prepare() will TypeError
    with pytest.raises(RuntimeError, match="ingest loop died"):
        svc.flush(timeout=30)
    svc.close()


def test_max_batch_events_caps_coalescing():
    s, cfg = _churn_fixture()
    chunks = _chunks(s, 10)
    svc = PartitionService(_session(s, cfg),
                          max_pending_chunks=len(chunks) + 1,
                          max_batch_events=10, autostart=False)
    for c in chunks:
        svc.submit(c)
    svc.start()
    svc.flush()
    m = svc.metrics()
    svc.close()
    assert m["batches_dispatched"] == len(chunks)   # no merge allowed
    ref, _ = run_stream(s, policy="sdp", cfg=cfg, seed=0)
    _identical(ref, svc.partitioner.state)


# -- host-side seams the service is built from ------------------------------

def test_prepare_feed_prepared_equals_feed():
    s, cfg = _churn_fixture()
    ref, _ = run_stream(s, policy="sdp", cfg=cfg, seed=0)
    part = _session(s, cfg)
    for c in _chunks(s, 23):
        p = part.prepare(c)
        assert isinstance(p, PreparedChunk)
        assert p.etype.dtype == np.int32 and p.nbrs.ndim == 2
        assert p.num_events == len(c[0])
        part.feed_prepared(p)
    assert part.sync() is part
    _identical(ref, part.state)
    with pytest.raises(TypeError, match="VertexStream"):
        part.prepare(object())
    with pytest.raises(ValueError, match="shapes disagree"):
        part.prepare((s.etype[:4], s.vertex[:3], s.nbrs[:4]))


def test_poisson_arrivals_generator():
    s, _ = _churn_fixture()
    bounds, due = gstream.poisson_arrivals(s, rate=500.0, mean_batch=8.0,
                                           seed=3)
    sizes = np.diff(bounds)
    assert bounds[0] == 0 and bounds[-1] == s.num_events
    assert (sizes >= 1).all()
    assert due.shape == (len(sizes),)
    assert (np.diff(due) >= 0).all() and (due > 0).all()
    # long-run rate roughly lambda (loose: it's a Poisson process)
    assert s.num_events / due[-1] == pytest.approx(500.0, rel=0.5)
    # deterministic per seed; different seed, different schedule
    b2, d2 = gstream.poisson_arrivals(s, rate=500.0, mean_batch=8.0, seed=3)
    np.testing.assert_array_equal(bounds, b2)
    np.testing.assert_array_equal(due, d2)
    with pytest.raises(ValueError, match="rate"):
        gstream.poisson_arrivals(s, rate=0.0)
    with pytest.raises(ValueError, match="mean_batch"):
        gstream.poisson_arrivals(s, rate=1.0, mean_batch=-1.0)
