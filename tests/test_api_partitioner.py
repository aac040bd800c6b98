"""The stateful ``Partitioner`` session must be bit-identical to one
whole-stream ``run_stream`` no matter how the stream is chopped across
``feed()`` calls (chunks of 1, 7, window-straddling sizes; autoscale
events landing exactly on a boundary) and across ``snapshot()`` →
``restore()`` → ``feed(rest)``."""
import os
import time

import jax
import numpy as np
import pytest
from jax.experimental.layout import Layout

from repro.api import Partitioner
from repro.api import partitioner as part_api
from repro.checkpoint.manager import CheckpointManager
from repro.core import EngineConfig, run_stream
from repro.core.state import PartitionState
from repro.graph.generators import make_graph
from repro.graph import stream as gstream
from repro.runtime import telemetry


def _churn_fixture():
    """Delete-heavy interleaved churn with autoscale on — the regime where
    every transition type (add / del vertex / del edge / scale-out /
    scale-in) crosses chunk boundaries."""
    g = make_graph("social", 90, 260, seed=2)
    s = gstream.interleaved_churn(g, warmup_frac=0.2, del_every=3,
                                  edge_del_every=5, seed=4)
    cfg = EngineConfig(k_max=8, k_init=1, max_cap=100)
    return s, cfg


def _identical(ref: PartitionState, got: PartitionState):
    for f in ("assignment", "present", "adj", "edge_load", "vertex_count",
              "active", "cut_matrix"):
        np.testing.assert_array_equal(np.asarray(getattr(ref, f)),
                                      np.asarray(getattr(got, f)), f)
    for f in ("num_partitions", "total_edges", "cut_edges",
              "denied_scaleout", "scale_events"):
        assert int(getattr(ref, f)) == int(getattr(got, f)), f


def _feed_chunked(part: Partitioner, s, chunk: int):
    t = 0
    while t < s.num_events:
        e = min(t + chunk, s.num_events)
        part.feed((s.etype[t:e], s.vertex[t:e], s.nbrs[t:e]))
        t = e
    return part


@pytest.mark.parametrize("engine", ["auto", "scan", "windowed"])
@pytest.mark.parametrize("chunk", [1, 7, 50])
def test_feed_chunked_bit_identical_to_run_stream(engine, chunk):
    """Chunks of 1, 7, and window-straddling 50 (window=32) through every
    backend == one whole-stream run_stream, bitwise."""
    s, cfg = _churn_fixture()
    ref, _ = run_stream(s, policy="sdp", cfg=cfg, seed=0)
    part = Partitioner.from_stream(s, cfg, seed=0, engine=engine, window=32)
    _feed_chunked(part, s, chunk)
    assert part.cursor == s.num_events
    _identical(ref, part.state)


def test_feed_whole_stream_and_vertexstream_input():
    s, cfg = _churn_fixture()
    ref, _ = run_stream(s, policy="sdp", cfg=cfg, seed=0)
    part = Partitioner.from_stream(s, cfg, seed=0, window=32).feed(s)
    _identical(ref, part.state)
    m = part.metrics()
    assert m["events_ingested"] == s.num_events
    assert m["edge_cut"] == int(ref.cut_edges)


def test_feed_split_exactly_at_autoscale_event():
    """Chop the stream exactly where a scale event fires: the first event
    of the second chunk sees the post-scale state, RNG still aligned."""
    g = make_graph("social", 90, 260, seed=2)
    s = gstream.dynamic_schedule(g, add_pct=25.0, del_pct=10.0,
                                 n_intervals=4, seed=3,
                                 del_edges_per_interval=5)
    cfg = EngineConfig(k_max=8, k_init=1, max_cap=40, tolerance_param=35.0)
    ref, trace = run_stream(s, policy="sdp", cfg=cfg, seed=0)
    parts = np.asarray(trace.num_partitions)
    bounds = np.flatnonzero(np.diff(parts)) + 1     # event AFTER each scale
    assert bounds.size >= 2, "fixture must actually autoscale"
    for cut in (int(bounds[0]), int(bounds[-1])):
        part = Partitioner.from_stream(s, cfg, seed=0, window=32)
        part.feed((s.etype[:cut], s.vertex[:cut], s.nbrs[:cut]))
        part.feed((s.etype[cut:], s.vertex[cut:], s.nbrs[cut:]))
        _identical(ref, part.state)


def test_trace_chunked_matches_run_stream():
    s, cfg = _churn_fixture()
    _, ref_trace = run_stream(s, policy="sdp", cfg=cfg, seed=0)
    part = Partitioner.from_stream(s, cfg, seed=0, collect_trace=True)
    _feed_chunked(part, s, 23)
    tr = part.trace()
    for f in tr._fields:
        np.testing.assert_array_equal(np.asarray(getattr(tr, f)),
                                      np.asarray(getattr(ref_trace, f)), f)


def test_snapshot_restore_feed_rest(tmp_path):
    """snapshot() -> restore() -> feed(rest) == one uninterrupted run."""
    s, cfg = _churn_fixture()
    ref, _ = run_stream(s, policy="sdp", cfg=cfg, seed=0)
    mid = s.num_events // 2
    part = Partitioner.from_stream(s, cfg, seed=0, window=32)
    part.feed((s.etype[:mid], s.vertex[:mid], s.nbrs[:mid]))
    step = part.snapshot(str(tmp_path))
    assert step == mid

    sess = Partitioner.restore(str(tmp_path), cfg, n=s.n, max_deg=s.max_deg,
                               window=32)
    assert sess.cursor == mid
    sess.feed((s.etype[mid:], s.vertex[mid:], s.nbrs[mid:]))
    _identical(ref, sess.state)


def test_snapshot_nonblocking_wait(tmp_path):
    """snapshot(blocking=False) + wait() persists; the session reuses one
    manager per directory so pending writers are joined, not leaked."""
    s, cfg = _churn_fixture()
    part = Partitioner.from_stream(s, cfg, seed=0, window=32).feed(s)
    part.snapshot(str(tmp_path), blocking=False)
    assert part._managers[str(tmp_path)] is not None
    part.wait()
    sess = Partitioner.restore(str(tmp_path), cfg, n=s.n, max_deg=s.max_deg)
    assert sess.cursor == s.num_events
    _identical(part.state, sess.state)


def test_restore_pre_cut_matrix_checkpoint(tmp_path):
    """A bare PartitionState checkpoint WITHOUT the trailing cut_matrix
    leaf (the pre-PR-3 layout) restores via fill_missing, is healed with
    recount_cut_matrix, and the resumed session stays bit-identical."""
    import collections
    s, cfg = _churn_fixture()
    ref, _ = run_stream(s, policy="sdp", cfg=cfg, seed=0)
    mid = s.num_events // 2
    part = Partitioner.from_stream(s, cfg, seed=0, window=32)
    part.feed((s.etype[:mid], s.vertex[:mid], s.nbrs[:mid]))
    # same field names so key paths align by attribute, no cut_matrix leaf
    Legacy = collections.namedtuple("Legacy", PartitionState._fields[:-1])
    legacy = Legacy(*tuple(part.state)[:-1])
    CheckpointManager(str(tmp_path), interval=1).maybe_save(
        mid, legacy, blocking=True)

    sess = Partitioner.restore(str(tmp_path), cfg, n=s.n, max_deg=s.max_deg,
                               window=32)
    assert sess.cursor == mid
    sess.feed((s.etype[mid:], s.vertex[mid:], s.nbrs[mid:]))
    _identical(ref, sess.state)


def test_restore_grows_larger_rejects_impossible(tmp_path):
    """Restore takes its shapes from the checkpoint's recorded geometry:
    a larger requested geometry grows the restored state (semantics
    no-op); a smaller one shrinks into it (PR 8) unless the live content
    cannot fit even densely packed, which raises."""
    s, cfg = _churn_fixture()
    part = Partitioner.from_stream(s, cfg, seed=0)
    part.feed(s)
    part.snapshot(str(tmp_path))
    big = Partitioner.restore(str(tmp_path), cfg, n=s.n + 5,
                              max_deg=s.max_deg + 2)
    assert (big.n, big.max_deg) == (s.n + 5, s.max_deg + 2)
    assert big.cursor == s.num_events
    np.testing.assert_array_equal(np.asarray(part.state.assignment),
                                  np.asarray(big.state.assignment)[:s.n])
    assert not np.asarray(big.state.present)[s.n:].any()
    with pytest.raises(ValueError, match="packed"):
        Partitioner.restore(str(tmp_path), cfg, n=5, max_deg=s.max_deg)
    with pytest.raises(ValueError, match="k_max"):
        Partitioner.restore(
            str(tmp_path),
            EngineConfig(k_max=cfg.k_max - 2, k_init=1, max_cap=100))
    with pytest.raises(FileNotFoundError):
        Partitioner.restore(os.path.join(str(tmp_path), "empty"), cfg,
                            n=s.n, max_deg=s.max_deg)


def test_constructor_and_feed_validation():
    s, cfg = _churn_fixture()
    with pytest.raises(ValueError, match="policy"):
        Partitioner.from_stream(s, cfg, policy="nope")
    with pytest.raises(ValueError, match="engine"):
        Partitioner.from_stream(s, cfg, engine="nope")
    with pytest.raises(ValueError, match="window"):
        Partitioner.from_stream(s, cfg, window=0)
    with pytest.raises(ValueError, match="collect_trace"):
        Partitioner.from_stream(s, cfg, engine="windowed",
                                collect_trace=True)
    with pytest.raises(ValueError, match="> 0"):
        Partitioner(cfg, n=0, max_deg=3)
    part = Partitioner(cfg, n=s.n, max_deg=s.max_deg)
    with pytest.raises(RuntimeError, match="collect_trace"):
        part.trace()
    with pytest.raises(TypeError, match="VertexStream"):
        part.feed(42)
    with pytest.raises(ValueError, match="shapes disagree"):
        part.feed((s.etype[:4], s.vertex[:3], s.nbrs[:4]))


def test_feed_grows_instead_of_raising():
    """The old fixed-shape feed errors (vertex id beyond the universe,
    wider neighbour rows, mismatched stream n) are gone: feed auto-grows
    the session geometry and keeps going (tests/test_geometry.py holds
    the bit-identity coverage)."""
    s, cfg = _churn_fixture()
    part = Partitioner(cfg, n=10, max_deg=2, seed=0)
    part.feed(s)                      # ids up to s.n-1, rows s.max_deg wide
    assert part.n >= s.n and part.max_deg >= s.max_deg
    assert part.regeometries >= 1
    assert part.metrics()["regeometries"] == part.regeometries
    other = gstream.VertexStream(etype=s.etype[:1], vertex=s.vertex[:1],
                                 nbrs=s.nbrs[:1], n=4 * part.n)
    part.feed(other)                  # larger declared universe grows too
    assert part.n >= 4 * s.n


def test_feed_narrow_and_padded_wide_rows():
    """Neighbour rows narrower than the session pad with -1; wider rows
    whose extra columns are all -1 trim losslessly."""
    s, cfg = _churn_fixture()
    ref, _ = run_stream(s, policy="sdp", cfg=cfg, seed=0)
    wide = np.concatenate(
        [s.nbrs, np.full((s.num_events, 3), -1, np.int32)], axis=1)
    part = Partitioner.from_stream(s, cfg, seed=0, window=32)
    part.feed((s.etype, s.vertex, wide))
    _identical(ref, part.state)

    sess = Partitioner(cfg, n=s.n, max_deg=s.max_deg + 2, seed=0)
    sess.feed(s)   # narrower stream rows pad up to the session width
    assert int(sess.state.cut_edges) == int(ref.cut_edges)
    np.testing.assert_array_equal(np.asarray(ref.assignment),
                                  np.asarray(sess.state.assignment))


def test_empty_feed_is_noop():
    s, cfg = _churn_fixture()
    part = Partitioner.from_stream(s, cfg, collect_trace=True)
    part.feed((s.etype[:0], s.vertex[:0], s.nbrs[:0]))
    assert part.cursor == 0
    assert part.trace().cut_edges.shape == (0,)


# -- what a feed records: one dispatch span per program call ------------------

def _expected_dispatches(s, engine, kernel, *, window, chunk):
    """(path, events, slots) of each program call ``_feed_chunked`` makes,
    by the session's documented chopping: full windows, then a tail that
    the per-event scan takes under "auto" and a padded window under
    "windowed"; "scan" takes each call whole."""
    out = []
    for a in range(0, s.num_events, chunk):
        b = min(a + chunk, s.num_events)
        t = a
        while t < b:
            e = min(t + window, b)
            if engine == "scan" or (engine == "auto" and e - t < window):
                out.append(("scan", b - t, b - t))
                break
            kind = "adds" if np.all(s.etype[t:e] == gstream.EVENT_ADD) \
                else "mixed"
            out.append((kind + ("_kernel" if kernel else ""), e - t, window))
            t = e
    return out


@pytest.mark.parametrize("engine, kernel", [("auto", False),
                                            ("scan", False),
                                            ("windowed", False),
                                            ("windowed", True)])
def test_one_dispatch_span_per_program_call(engine, kernel):
    s, cfg = _churn_fixture()
    part = Partitioner.from_stream(s, cfg, seed=0, engine=engine, window=32,
                                   use_kernel=kernel)
    t0 = time.perf_counter()
    _feed_chunked(part, s, 50)
    recs = telemetry.spans(since=t0)
    got = [(r.attrs["path"], r.attrs["events"], r.attrs["slots"])
           for r in recs if r.name == "session.dispatch"]
    want = _expected_dispatches(s, engine, kernel, window=32, chunk=50)
    assert got == want
    assert {r.parent for r in recs if r.name == "session.dispatch"} \
        == {"session.feed"}
    assert len([r for r in recs if r.name == "session.feed"]) == 3
    m = part.metrics()
    assert sum(m["windows"].values()) == len(got)
    for path, count in m["windows"].items():
        assert count == sum(p == path for p, _, _ in got), path
    assert m["pad_slots"] == sum(sl - ev for _, ev, sl in got)
    _identical(run_stream(s, policy="sdp", cfg=cfg, seed=0)[0], part.state)


def test_pad_slots_of_a_known_chopping():
    """300 events at window 256 on engine="windowed": one full window and
    one of 44 events padded with 212 no-op slots."""
    g = make_graph("social", 400, 1200, seed=2)
    s = gstream.interleaved_churn(g, warmup_frac=0.2, del_every=3,
                                  edge_del_every=5, seed=4)
    part = Partitioner.from_stream(s, EngineConfig(k_max=8, k_init=1,
                                                   max_cap=400),
                                   seed=0, engine="windowed", window=256)
    part.feed((s.etype[:300], s.vertex[:300], s.nbrs[:300]))
    m = part.metrics()
    assert m["pad_slots"] == 212
    assert sum(m["windows"].values()) == 2
    assert m["windows"]["scan"] == 0


# -- the layout the session keeps adj in --------------------------------------

def _lifecycle(layout, directory):
    """Run a session through every step that makes its state outside its
    programs, with ``adj`` pinned to ``layout``; return the final state
    and, per step, the moves of ``adj`` its ``session.relayout`` spans
    and ``metrics()["relayouts"]`` agree on."""
    s, cfg = _churn_fixture()
    mid = s.num_events // 2

    def feed(p, a, b, chunk):
        for t in range(a, b, chunk):
            e = min(t + chunk, b)
            p.feed((s.etype[t:e], s.vertex[t:e], s.nbrs[t:e]))
        return p

    def then(p, _):
        return p

    steps = [  # each returns the session: a restore makes a new one
        lambda p: feed(p, 0, mid, 40),                  # grows on demand
        lambda p: p.grow_to(n=4 * p.n),
        lambda p: p.compact(),
        lambda p: then(p, p.rebalance(m=8, passes=1)),
        lambda p: then(p, p.snapshot(directory)),
        lambda p: Partitioner.restore(directory, cfg, window=32),
        lambda p: feed(p, mid, s.num_events, 45),       # scan tails
        lambda p: p.place(jax.devices()[0]),
    ]
    part = Partitioner(cfg, seed=0, window=32)
    assert part.metrics()["relayouts"] == 0
    moves = []
    for fn in steps:
        assert part.state.adj.format.layout.major_to_minor == layout
        t = time.perf_counter()
        before = part.metrics()["relayouts"]
        nxt = fn(part)
        if nxt is not part:
            before, part = 0, nxt
        recs = [r for r in telemetry.spans(since=t)
                if r.name == "session.relayout"]
        assert part.metrics()["relayouts"] - before == len(recs)
        assert all(r.attrs["bytes"] == part.state.adj.nbytes for r in recs)
        moves.append(len(recs))
    assert part.state.adj.format.layout.major_to_minor == layout
    return part.sync().state, moves


@pytest.mark.parametrize("layout", [(0, 1), (1, 0)])
def test_adj_keeps_the_programs_layout(tmp_path, monkeypatch, layout):
    """After init, grows, a compaction, a rebalance, a restore, scan
    tails and a ``place``, the session's ``adj`` is in ``adj_format`` and
    ``metrics()["relayouts"]`` counts exactly the moves ``_pin`` made.
    Row-major is the CPU's default, so under the real pin nothing moves;
    a column-major pin, which the CPU also runs, makes each step outside
    the programs move ``adj`` once, and leaves the state bit-identical."""
    monkeypatch.setattr(part_api, "ADJ_LAYOUT",
                        Layout(major_to_minor=layout))
    state, moves = _lifecycle(layout, str(tmp_path / "a"))
    if layout == (0, 1):
        assert moves == [0] * 8
        return
    feed_grows, *rest = moves
    assert feed_grows >= 1
    # grow, compact, rebalance, snapshot, restore, tails, place
    assert rest == [1, 1, 1, 0, 1, 0, 1]
    monkeypatch.setattr(part_api, "ADJ_LAYOUT", Layout(major_to_minor=(0, 1)))
    _identical(_lifecycle((0, 1), str(tmp_path / "b"))[0], state)
