"""The span recorder (``repro.runtime.telemetry``): nesting and parents,
thread names, explicit stamps, the ring's bound and its eviction count,
the profiler trace it marks, and the names a feed and a served run
record."""
import glob
import threading
import time

import jax

from repro.api import Partitioner, PartitionService
from repro.runtime import telemetry

from tests.test_api_partitioner import _churn_fixture


def _named(records, name):
    return [r for r in records if r.name == name]


def test_nesting_and_parents():
    t0 = time.perf_counter()
    with telemetry.span("test.outer", k=1) as outer:
        with telemetry.span("test.inner"):
            pass
        with telemetry.span("test.inner"):
            pass
    got = telemetry.spans(since=t0)
    (o,) = _named(got, "test.outer")
    inner = _named(got, "test.inner")
    assert o.parent is None and o.attrs == {"k": 1}
    assert o.start == outer.start
    assert [r.parent for r in inner] == ["test.outer", "test.outer"]
    assert o.start <= inner[0].start <= inner[0].end <= inner[1].start \
        <= inner[1].end <= o.end
    # inner spans end first, so they are recorded first
    assert [r.name for r in got] == ["test.inner", "test.inner",
                                     "test.outer"]


def test_thread_names_and_per_thread_parents():
    t0 = time.perf_counter()

    def work():
        with telemetry.span("test.worker"):
            pass

    with telemetry.span("test.main"):
        th = threading.Thread(target=work, name="test-worker-7")
        th.start()
        th.join(timeout=10)
    assert not th.is_alive()
    got = telemetry.spans(since=t0)
    (w,) = _named(got, "test.worker")
    (m,) = _named(got, "test.main")
    assert w.thread == "test-worker-7" and w.parent is None
    assert m.thread == threading.current_thread().name


def test_record_with_explicit_stamps():
    t0 = time.perf_counter()
    with telemetry.span("test.around"):
        telemetry.record("test.life", t0 - 5.0, t0 - 1.0, chunk=3)
    (r,) = _named(telemetry.spans(since=t0 - 6.0, until=t0), "test.life")
    assert (r.start, r.end) == (t0 - 5.0, t0 - 1.0)
    assert r.attrs == {"chunk": 3} and r.parent == "test.around"
    # outside the interval asked for, it is left out
    assert not _named(telemetry.spans(since=t0 - 4.0), "test.life")
    assert not _named(telemetry.spans(since=t0 - 6.0, until=t0 - 2.0),
                      "test.life")


def test_ring_is_bounded_and_counts_evictions():
    held0, dropped0 = len(telemetry.spans()), telemetry.dropped()
    t0 = time.perf_counter()
    extra = 10
    for i in range(telemetry.CAPACITY + extra):
        telemetry.record("test.fill", t0, t0, i=i)
    held = telemetry.spans()
    assert len(held) == telemetry.CAPACITY
    assert telemetry.dropped() - dropped0 == held0 + extra
    assert [r.attrs["i"] for r in held] == list(
        range(extra, telemetry.CAPACITY + extra))
    # records ending at t0 were evicted: an interval from t0 is partial,
    # one that starts after every evicted record ended is whole
    assert telemetry.dropped(since=t0) == telemetry.dropped()
    assert telemetry.dropped(since=time.perf_counter()) == 0


def test_span_marks_the_profiler_trace(tmp_path):
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        with telemetry.span("test.traced"):
            pass
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    names = {e.name for plane in ProfileData.from_file(path).planes
             for line in plane.lines for e in line.events}
    assert "test.traced" in names


def test_feed_and_service_record_their_layers_and_no_bench_names():
    """The harness collects ``bench.*`` host spans as its own; the program
    records ``<layer>.<step>`` names from its own layers only."""
    s, cfg = _churn_fixture()
    t0 = time.perf_counter()
    part = Partitioner.from_stream(s, cfg, seed=0, window=32)
    half = s.num_events // 2
    part.feed((s.etype[:half], s.vertex[:half], s.nbrs[:half]))
    with PartitionService(part, max_pending_chunks=8) as svc:
        for a in range(half, s.num_events, 13):
            b = min(a + 13, s.num_events)
            svc.submit((s.etype[a:b], s.vertex[a:b], s.nbrs[a:b]))
        svc.flush()
    names = {r.name for r in telemetry.spans(since=t0)}
    assert {"session.feed", "session.prepare", "session.ingest",
            "session.dispatch", "serve.coerce", "serve.dispatch",
            "serve.commit_wait", "serve.chunk"} <= names
    assert names <= {"session.feed", "session.prepare", "session.ingest",
                     "session.dispatch", "serve.coerce", "serve.wait_prev",
                     "serve.dispatch", "serve.idle", "serve.commit_wait",
                     "serve.chunk"}
    assert not any(n.startswith("bench.") for n in names)

