"""The dense session's mixed windows run the factored window body
(``repro.runtime.factored_window``): a prep scan over (adj, int32
presence), vectorised source-label and touch tables, the K-carry slot
loop, and a W-row scatter apply. The vertex-sharded session runs the same
body on its row blocks.

Each case below is a stream with one hard shape inside a window, fed in a
chopping that puts it where it bites. The session's state must equal
``run_stream``'s bit for bit, and the benchmark's plain reference
(``bench/reference.run_reference``) by the harness's own comparison, the
one that decides a benchmark run's ``correct``. A dense session and a
one-device ``Partitioner(sharded=True)`` fed the same stream must end in
identical states, since they run one body.
"""
import os
import sys

import numpy as np
import pytest

from repro.api import Partitioner
from repro.core import EngineConfig, run_stream
from repro.graph import stream as gstream

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
from bench import harness, reference, traffic  # noqa: E402

W, D, SEED = 16, 12, 2**31 + 17


def _base(n, events, seed, width=D):
    """A seeded churn stream from the benchmark's generator, its rows
    ``width`` wide, padded to ``D``."""
    s = traffic.powerlaw_churn(n, events, max_deg=width, seed=seed,
                               mix=traffic.ChurnMix(lead_in_adds=48))
    nb = np.pad(s.nbrs, ((0, 0), (0, D - width)), constant_values=-1)
    return s.etype, s.vertex, nb.astype(np.int32)


def _block(*events):
    """Hand-written events: ("add", v, row), ("dv", v), ("de", v, u)."""
    code = {"add": gstream.EVENT_ADD, "dv": gstream.EVENT_DEL_VERTEX,
            "de": gstream.EVENT_DEL_EDGE}
    et = np.asarray([code[e[0]] for e in events], np.int32)
    vx = np.asarray([e[1] for e in events], np.int32)
    nb = np.full((len(events), D), -1, np.int32)
    for i, e in enumerate(events):
        row = e[2] if e[0] == "add" else [e[2]] if e[0] == "de" else []
        nb[i, :len(row)] = row
    return et, vx, nb


def _cat(*parts):
    return tuple(np.concatenate([p[j] for p in parts]) for j in range(3))


def _readd():
    # add -> delete -> re-add of vertex 100 inside one window, with
    # neighbours scoring it in each of its lives
    return _cat(_base(96, 64, 1), _block(
        ("add", 100, [1, 2, 3]), ("add", 101, [100, 4]), ("dv", 100),
        ("add", 102, [100, 101]), ("add", 100, [101, 5, 102]),
        ("de", 100, 101), ("add", 103, [100, 101, 102]), ("dv", 100),
        ("add", 100, [103]))), None


def _self_loop():
    # DEL_EDGE with u == v: on a self-listed row, a plain row, an absent
    # vertex, and a vertex deleted earlier in the window
    return _cat(_base(96, 64, 2), _block(
        ("add", 110, [110, 1, 2]), ("de", 110, 110), ("add", 111, [111]),
        ("add", 112, [110, 111, 112]), ("de", 111, 111), ("de", 113, 113),
        ("de", 112, 112), ("dv", 110), ("de", 110, 110),
        ("de", 112, 110))), None


def _del_added():
    # deletes of vertices the same window added, with edges among them
    return _cat(_base(96, 64, 3), _block(
        ("add", 120, [1]), ("add", 121, [120, 2]), ("add", 122, [120, 121]),
        ("de", 121, 120), ("dv", 120), ("add", 123, [120, 121, 122]),
        ("dv", 122), ("dv", 121), ("dv", 123), ("de", 122, 121))), None


def _scaling():
    # a low cap: K scales out on adds and back in on deletes, mid-window
    return _base(512, 640, 4), [W] * 40


def _padded():
    # every feed is a partial window, padded with no-op slots
    return _base(256, 300, 5), [5, 11, 15, 1, 9, 13, 3] * 5 + [15]


def _uneven():
    # chunks that straddle windows: full windows plus padded tails
    return _base(256, 300, 6), [37, 5, 16, 1, 50, 3, 64, 17, 107]


def _growth():
    # the second feed names ids past the first tier and wider rows
    early = _base(128, 96, 7, width=6)
    late = _block(
        ("add", 200, [1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11]),
        ("add", 201, [200, 1]), ("dv", 3), ("de", 200, 1),
        ("add", 202, [200, 201, 5, 6, 7, 8, 9, 10]), ("dv", 200))
    return _cat(early, late), [96, 6]


CASES = {"readd_in_window": _readd, "del_edge_self_loop": _self_loop,
         "delete_added_in_window": _del_added,
         "scale_out_and_in_mid_window": _scaling,
         "padded_partial_windows": _padded, "uneven_chunks": _uneven,
         "growth_between_feeds": _growth}
CFG = {"scale_out_and_in_mid_window": EngineConfig(
    k_max=8, k_init=1, autoscale=True, max_cap=60)}


def _cfg(case):
    return CFG.get(case, EngineConfig(k_max=8, k_init=2, autoscale=True,
                                      max_cap=400))


def _feed(case, **kw):
    """Feed ``case``'s stream in its chopping (None: whole windows, the
    last one part-filled, so a hand-written block lies in one window)."""
    (et, vx, nb), chunks = CASES[case]()
    if chunks is None:
        chunks = [W] * (et.shape[0] // W) + [et.shape[0] % W]
    cfg = _cfg(case)
    start = {} if case == "growth_between_feeds" else {"n": 256,
                                                       "max_deg": D}
    part = Partitioner(cfg, policy="sdp", seed=SEED, engine="windowed",
                       window=W, **start, **kw)
    lo = 0
    for size in chunks:
        part.feed((et[lo:lo + size], vx[lo:lo + size], nb[lo:lo + size]))
        lo += size
    assert lo == et.shape[0]
    return (et, vx, nb), cfg, part.sync()


def _assert_same(got, want, n):
    """Every field equal on the first ``n`` rows, the rows and columns
    beyond them (a wider geometry tier) empty."""
    d = min(got.adj.shape[1], want.adj.shape[1])
    for f in got._fields:
        a, b = np.asarray(getattr(got, f)), np.asarray(getattr(want, f))
        if f == "adj":
            for x in (a, b):
                assert (x[n:] == -1).all() and (x[:, d:] == -1).all()
            a, b = a[:n, :d], b[:n, :d]
        elif f in ("assignment", "present"):
            a, b = a[:n], b[:n]
        np.testing.assert_array_equal(a, b, err_msg=f)


@pytest.mark.parametrize("case", sorted(CASES))
def test_dense_session_equals_run_stream_and_reference(case):
    """Every window rides the dense mixed program (no scan tail), and the
    state is ``run_stream``'s and the plain reference's."""
    (et, vx, nb), cfg, part = _feed(case)
    m = part.metrics()["windows"]
    assert m["mixed"] > 0 and m["scan"] == 0 and m["sharded"] == 0
    n = part.n
    want, _ = run_stream(gstream.VertexStream(et, vx, nb, n), policy="sdp",
                         cfg=cfg, seed=SEED)
    _assert_same(part.state, want, n)

    ref = reference.run_reference(
        et, vx, nb, n, policy="sdp", seed=SEED, knobs=reference.Knobs(
            k_max=cfg.k_max, k_init=cfg.k_init, max_cap=cfg.max_cap,
            autoscale=cfg.autoscale))
    stream = traffic.Stream(et, vx, nb, n)
    snap = harness.snapshot(part.state, stream, et.shape[0])
    assert {c.name: c.value for c in harness.compare(snap, ref, SEED)} == {
        "assignment_mismatch": 0, "present_mismatch": 0,
        "adj_row_mismatch": 0, "adj_untouched_entries": 0,
        "k_state_mismatch": 0}
    if case == "scale_out_and_in_mid_window":
        ins = (ref.scale_events - (ref.num_partitions - cfg.k_init)) // 2
        assert ins > 0 and ref.scale_events - ins > 0, \
            "K did not scale both out and in"


@pytest.mark.parametrize("case", sorted(CASES))
def test_dense_and_one_device_sharded_sessions_agree(case):
    """A dense session and a one-device vertex-sharded session, fed one
    stream in one chopping, end in identical states."""
    _, _, dense = _feed(case)
    _, _, sharded = _feed(case, sharded=True, shard_devices=1)
    assert sharded.metrics()["windows"]["sharded"] > 0
    assert sharded.n == dense.n
    _assert_same(sharded.state, dense.state, dense.n)
