"""The fused Pallas window chooser (gather → score → argmax → commit in
one kernel, repro.kernels.fused_chooser) must be bit-identical to the
faithful per-event engine on delete-heavy interleaved streams — for every
policy, with autoscale on, through every surface it is wired to
(run_stream_windowed, the Partitioner session, the Sweep lanes), and for
both the Pallas kernel and its lax.scan oracle (``variant="ref"``).

CI runs these in interpret mode (repro.kernels.common.default_interpret
resolves ``jax.default_backend() != "tpu"``); on a real TPU the same
tests exercise the compiled kernel.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.api import Partitioner, Sweep
from repro.core import EngineConfig, run_stream, run_stream_windowed
from repro.core import transition as tx
from repro.core import windowed as wnd
from repro.graph.generators import make_graph
from repro.graph import stream as gstream
from repro.kernels import common as kcommon
from repro.kernels.fused_chooser.ops import run_window_mixed_fused

POLICIES6 = ["sdp", "greedy", "ldg", "fennel", "hash", "random"]


def _identical(a, b):
    for f in a._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(a, f)), np.asarray(getattr(b, f)), f)


def _churn_stream(seed=1, n=120, m=360):
    g = make_graph("social", n, m, seed=0)
    s = gstream.interleaved_churn(g, warmup_frac=0.15, del_every=2,
                                  edge_del_every=4, readd_every=6, seed=seed)
    dels = (s.etype == gstream.EVENT_DEL_VERTEX) | \
        (s.etype == gstream.EVENT_DEL_EDGE)
    assert float(np.mean(dels)) >= 0.30, "stream not delete-heavy enough"
    return s


def _cfg_for(policy, **kw):
    kw.setdefault("k_max", 6)
    kw.setdefault("max_cap", 110)
    kw.setdefault("k_init", 1 if policy == "sdp" else 4)
    kw.setdefault("autoscale", policy == "sdp")
    return EngineConfig(**kw)


# ---------------------------------------------------------------------------
# full-stream bit-identity: fused engine vs faithful per-event scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("policy", POLICIES6)
def test_fused_full_stream_all_policies(policy):
    """Interleaved churn, every policy, fused kernel == faithful engine."""
    s = _churn_stream(seed=7)
    cfg = _cfg_for(policy)
    a, _ = run_stream(s, policy=policy, cfg=cfg, seed=3)
    b = run_stream_windowed(s, policy=policy, cfg=cfg, seed=3, window=32,
                            use_kernel=True)
    _identical(a, b)


@pytest.mark.parametrize("window", [8, 32, 256])
def test_fused_autoscale_windows(window):
    """Autoscale on (scale-out + scale-in inside windows), window sizes
    spanning smaller-than-tile to larger-than-stream."""
    s = _churn_stream()
    cfg = EngineConfig(k_max=8, k_init=1, max_cap=100, autoscale=True)
    a, _ = run_stream(s, policy="sdp", cfg=cfg, seed=2)
    b = run_stream_windowed(s, policy="sdp", cfg=cfg, seed=2, window=window,
                            use_kernel=True)
    _identical(a, b)


def test_fused_alg1_guard():
    s = _churn_stream(seed=9)
    cfg = EngineConfig(k_max=6, k_init=1, max_cap=90, autoscale=True,
                       balance_guard="alg1")
    a, _ = run_stream(s, policy="sdp", cfg=cfg, seed=5)
    b = run_stream_windowed(s, policy="sdp", cfg=cfg, seed=5, window=64,
                            use_kernel=True)
    _identical(a, b)


def test_ref_oracle_matches_kernel_and_faithful():
    """variant="ref" (the lax.scan oracle sharing make_slot_step) ==
    the Pallas kernel == the faithful engine, window by window."""
    s = _churn_stream(seed=11)
    cfg = _cfg_for("sdp", k_max=6)
    w = 32
    T = (s.num_events // w) * w
    state_x = state_k = state_r = None
    from repro.core.state import init_state
    state_x = init_state(s.n, s.max_deg, cfg.k_max, cfg.k_init, 4)
    state_k = init_state(s.n, s.max_deg, cfg.k_max, cfg.k_init, 4)
    state_r = init_state(s.n, s.max_deg, cfg.k_max, cfg.k_init, 4)
    et, vx = jnp.asarray(s.etype), jnp.asarray(s.vertex)
    nb = jnp.asarray(s.nbrs)
    for t in range(0, T, w):
        sl = slice(t, t + w)
        args = (et[sl], vx[sl], nb[sl], jnp.int32(t))
        state_x = wnd.run_window_mixed(state_x, *args, policy="sdp", cfg=cfg)
        state_k = run_window_mixed_fused(state_k, *args, policy="sdp",
                                         cfg=cfg)
        state_r = run_window_mixed_fused(state_r, *args, policy="sdp",
                                         cfg=cfg, variant="ref")
    _identical(state_x, state_k)
    _identical(state_x, state_r)


# ---------------------------------------------------------------------------
# geometry edges: off-tile shapes, k_max=1, deletion holes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("window,k_max", [(13, 5), (8, 3), (48, 7)])
def test_fused_off_tile_geometry(window, k_max):
    """W, max_deg, k_max all coprime with the (8, 128) VPU tile — the
    fused kernel carries whole-window values, so no shape may assume
    tile-multiple padding."""
    s = _churn_stream(seed=5, n=90, m=250)
    assert s.max_deg % 128 != 0
    cfg = EngineConfig(k_max=k_max, k_init=1, max_cap=80, autoscale=True)
    a, _ = run_stream(s, policy="sdp", cfg=cfg, seed=1)
    b = run_stream_windowed(s, policy="sdp", cfg=cfg, seed=1, window=window,
                            use_kernel=True)
    _identical(a, b)


def test_fused_k_max_one():
    """k_max=1: every chooser must return partition 0; the scale hooks
    are structurally inert (no room to scale out)."""
    s = _churn_stream(seed=3, n=60, m=150)
    cfg = EngineConfig(k_max=1, k_init=1, max_cap=10**9, autoscale=False)
    for policy in ("sdp", "greedy", "hash"):
        a, _ = run_stream(s, policy=policy, cfg=cfg, seed=2)
        b = run_stream_windowed(s, policy=policy, cfg=cfg, seed=2, window=16,
                                use_kernel=True)
        _identical(a, b)
        assert np.asarray(b.assignment)[np.asarray(b.present)].max(
            initial=0) == 0


def test_fused_resumes_from_deletion_holes():
    """Start a window from a state with deletion holes (present=False
    vertices whose adjacency rows still name them as neighbours): the
    touch-table apply must keep the holes at label -1 while the remap
    composes committed labels."""
    s = _churn_stream(seed=13)
    cfg = _cfg_for("sdp")
    half = (s.num_events // 2 // 32) * 32
    first = gstream.VertexStream(etype=s.etype[:half], vertex=s.vertex[:half],
                                 nbrs=s.nbrs[:half], n=s.n)
    mid, _ = run_stream(first, policy="sdp", cfg=cfg, seed=6)
    assert not bool(np.asarray(mid.present).all()), "no holes to test"
    w = 64
    sl = slice(half, half + w)
    args = (jnp.asarray(s.etype[sl]), jnp.asarray(s.vertex[sl]),
            jnp.asarray(s.nbrs[sl]), jnp.int32(half))
    a = wnd.run_window_mixed(mid, *args, policy="sdp", cfg=cfg)
    b = run_window_mixed_fused(mid, *args, policy="sdp", cfg=cfg)
    _identical(a, b)
    holes = ~np.asarray(a.present)
    assert (np.asarray(a.assignment)[holes] == -1).all()


# ---------------------------------------------------------------------------
# session + sweep surfaces
# ---------------------------------------------------------------------------

def test_partitioner_use_kernel_parity_and_coverage():
    """The session with use_kernel=True is bit-identical to run_stream,
    and metrics() reports the kernel/fallback window split (full windows
    ride the kernel, the auto engine's small tails stay XLA scan)."""
    s = _churn_stream(seed=3)
    cfg = _cfg_for("sdp")
    ref, _ = run_stream(s, policy="sdp", cfg=cfg, seed=0)
    p = Partitioner(cfg, n=s.n, max_deg=s.max_deg, policy="sdp", seed=0,
                    window=32, use_kernel=True)
    t = 0
    while t < s.num_events:
        sl = slice(t, min(t + 100, s.num_events))
        p.feed((s.etype[sl], s.vertex[sl], s.nbrs[sl]))
        t = sl.stop
    _identical(ref, p.state)
    m = p.metrics()["windows"]
    assert m["mixed_kernel"] + m["adds_kernel"] > 0
    assert m["scan"] > 0                      # the 100-event calls leave tails
    q = Partitioner(cfg, n=s.n, max_deg=s.max_deg, policy="sdp", seed=0,
                    window=32)
    q.feed(s)
    m = q.metrics()["windows"]
    assert m["mixed_kernel"] + m["adds_kernel"] == 0  # default: all XLA
    _identical(ref, q.state)


def test_sweep_kernel_lanes_parity():
    """Sweep(...).windowed().kernel() == the XLA windowed lanes, per-lane
    streams, mixed policies/autoscale."""
    cfgs = [_cfg_for("sdp"), _cfg_for("greedy"), _cfg_for("ldg")]
    runs = [("sdp", cfgs[0], 0), ("greedy", cfgs[1], 1), ("ldg", cfgs[2], 2)]
    streams = [_churn_stream(seed=i) for i in range(3)]
    rx = Sweep(streams).lanes(runs).windowed(32).run()
    rk = Sweep(streams).lanes(runs).windowed(32).kernel().run()
    for a, b in zip(rx, rk):
        _identical(a.state, b.state)


def test_sweep_kernel_shared_stream_sharded():
    """Shared-stream broadcast + shard_map path (check_vma off for the
    pallas_call) stays bit-identical, even forced onto one device."""
    s = _churn_stream(seed=2)
    cfg = _cfg_for("sdp", autoscale=False, k_init=3)
    runs = [("sdp", cfg, i) for i in range(3)]
    rx = Sweep(s).lanes(runs).windowed(32).run()
    rk = Sweep(s).lanes(runs).windowed(32).kernel().sharded().run()
    for a, b in zip(rx, rk):
        _identical(a.state, b.state)


def test_sweep_kernel_requires_windowed_engine():
    s = _churn_stream(seed=2)
    with pytest.raises(ValueError, match="windowed engine"):
        Sweep(s).lane("sdp", _cfg_for("sdp")).kernel().run()


# ---------------------------------------------------------------------------
# seams: RNG table, interpret resolution
# ---------------------------------------------------------------------------

def test_rand_index_table_matches_per_event_randint():
    """tab[i, m-1] must equal the faithful engine's tie-break draw
    randint(fold_in(key, t0+i), 0, m) for every live partition count m —
    the whole reason the kernel can avoid tracing threefry per slot."""
    key = jax.random.PRNGKey(42)
    t0, w, k_max = 37, 19, 6
    tab = np.asarray(tx.rand_index_table(key, jnp.int32(t0), w, k_max))
    assert tab.shape == (w, k_max)
    for i in range(w):
        ek = jax.random.fold_in(key, t0 + i)
        for m in range(1, k_max + 1):
            assert tab[i, m - 1] == int(jax.random.randint(ek, (), 0, m))


def test_interpret_resolution():
    """One definition site: default follows the backend, and an explicit
    argument beats it."""
    backend_default = jax.default_backend() != "tpu"
    assert kcommon.default_interpret() is backend_default
    assert kcommon.resolve_interpret(None) is backend_default
    assert kcommon.resolve_interpret(True) is True
    assert kcommon.resolve_interpret(False) is False
