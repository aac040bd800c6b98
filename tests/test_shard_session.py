"""Vertex-sharded sessions: one session's vertex axis split across the
device mesh (repro.runtime.shard_session / repro.core.sharded_state).

The correctness gate is BIT-identity to the dense engines: every test
compares against ``run_stream`` (or a dense ``Partitioner``) on the same
stream. The sharded step runs the chooser oracle replicated over
psum-assembled window tables, so identity is structural, and these tests
must pass at ANY device count — CI runs this file both single-device and
under ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.

Also here: the adaptive rebalance cadence (``rebalance_drift=``) and the
chunked device→host checkpoint staging, both of which ride this PR.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import Partitioner, Sweep, SweepRun
from repro.checkpoint.manager import CheckpointManager, _stage_host
from repro.core import EngineConfig, run_stream
from repro.core.sharded_state import (
    gather_state, pad_rows, per_device_state_bytes, shard_state,
    unshard_state,
)
from repro.core.state import init_state
from repro.graph.generators import make_graph
from repro.graph import stream as gstream
from repro.launch.mesh import make_grid_mesh, make_vertices_mesh
from repro.runtime.shard_session import run_stream_sharded

multi_device = pytest.mark.skipif(
    jax.device_count() < 2,
    reason="needs >1 device (XLA_FLAGS=--xla_force_host_platform_device_count=8)",
)

W = 64  # small window: more windows (and psums) per stream


def _mixed_stream(n=80, m=240, seed=0):
    """ADD/DEL_EDGE/DEL_VERTEX mix — exercises every round-1 branch."""
    g = make_graph("social", n, m, seed=seed)
    return gstream.interleaved_churn(g, warmup_frac=0.25, del_every=3,
                                     seed=seed + 1)


def _assert_states_equal(dense, sharded, n):
    for f in dense._fields:
        a = np.asarray(getattr(dense, f))
        b = np.asarray(getattr(sharded, f))
        if f in ("assignment", "present", "adj"):
            a, b = a[:n], b[:n]
        if f == "adj":
            # sessions may sit at a wider max_deg tier than run_stream's
            # exact stream width — the extra columns must be -1 padding
            d = min(a.shape[1], b.shape[1])
            assert (a[:, d:] == -1).all() and (b[:, d:] == -1).all(), \
                "adj width padding leaked real neighbours"
            a, b = a[:, :d], b[:, :d]
        np.testing.assert_array_equal(a, b,
                                      err_msg=f"field {f!r} diverged")


# -- run_stream_sharded: the bit-identity gate ---------------------------

@pytest.mark.parametrize("policy,autoscale", [
    ("sdp", True), ("ldg", False), ("fennel", False)])
def test_run_stream_sharded_bit_identical(policy, autoscale):
    s = _mixed_stream()
    cfg = EngineConfig(k_max=8, k_init=2, autoscale=autoscale, max_cap=90)
    dense, _ = run_stream(s, policy=policy, cfg=cfg, seed=3)
    sharded = run_stream_sharded(s, policy=policy, cfg=cfg, seed=3,
                                 window=W)
    _assert_states_equal(dense, sharded, n=dense.assignment.shape[0])


@multi_device
def test_run_stream_sharded_every_mesh_width():
    """The same stream over every divisor-width mesh (1, 2, ..., all
    devices) — gathered results must all equal the dense run."""
    s = _mixed_stream(n=60, m=150, seed=7)
    cfg = EngineConfig(k_max=8, k_init=1, max_cap=60)
    dense, _ = run_stream(s, policy="sdp", cfg=cfg, seed=0)
    for width in range(1, jax.device_count() + 1):
        sharded = run_stream_sharded(
            s, policy="sdp", cfg=cfg, seed=0, window=W,
            mesh=make_vertices_mesh(width))
        _assert_states_equal(dense, sharded, n=dense.assignment.shape[0])


def test_heterogeneous_padding_no_phantom_vertices():
    """n=37 never divides a 2/4/8-device mesh: the padded rows must stay
    inert — absent, unassigned, and invisible to every counter."""
    g = make_graph("social", 37, 90, seed=2)
    s = gstream.build_stream(g, seed=2)
    cfg = EngineConfig(k_max=8, k_init=2, max_cap=40)
    dense, _ = run_stream(s, policy="sdp", cfg=cfg, seed=1)
    sharded = run_stream_sharded(s, policy="sdp", cfg=cfg, seed=1, window=W)
    _assert_states_equal(dense, sharded, n=37)
    # counters: phantom (padding) vertices would inflate these
    assert int(sharded.total_edges) == int(dense.total_edges)
    np.testing.assert_array_equal(np.asarray(sharded.vertex_count),
                                  np.asarray(dense.vertex_count))
    assert int(np.asarray(sharded.vertex_count).sum()) \
        == int(np.asarray(dense.present).sum())


def test_pad_rows_and_state_bytes():
    mesh = make_vertices_mesh()
    p = mesh.shape["vertices"]
    assert pad_rows(37, p) % p == 0 and pad_rows(37, p) >= 37
    state = shard_state(init_state(64, 4, 8, 2, 0), mesh)
    assert per_device_state_bytes(state) > 0
    # round-trip through the canonical dense layout is lossless
    back = unshard_state(state, n=64)
    ref = init_state(64, 4, 8, 2, 0)
    _assert_states_equal(ref, back, n=64)
    host = gather_state(state, n=64)
    assert isinstance(host.assignment, np.ndarray)
    assert host.assignment.shape == (64,)


# -- the session facade: Partitioner(sharded=True) -----------------------

def test_sharded_session_chop_and_grow():
    """Uneven chunk sizes + on-demand geometry growth: the sharded
    session must match a dense windowed session AND run_stream."""
    s = _mixed_stream(n=90, m=260, seed=5)
    cfg = EngineConfig(k_max=8, k_init=2, max_cap=90)
    et = np.asarray(s.etype)
    vx = np.asarray(s.vertex)
    nb = np.asarray(s.nbrs)
    shard = Partitioner(cfg, policy="sdp", sharded=True, window=W)
    dense = Partitioner(cfg, policy="sdp", engine="windowed", window=W)
    cuts = [0, 17, 130, 131, s.num_events]     # includes a 1-event chunk
    for a, b in zip(cuts[:-1], cuts[1:]):
        chunk = (et[a:b], vx[a:b], nb[a:b])
        shard.feed(chunk)
        dense.feed(chunk)
    shard.sync(), dense.sync()
    n_sem = shard._sem_geom.n
    assert n_sem == dense.n, "sharded session left the dense tier ladder"
    _assert_states_equal(dense.state,
                         unshard_state(shard.state, n=n_sem), n=n_sem)
    m = shard.metrics()
    assert m["shard_devices"] == jax.device_count()
    assert m["per_device_state_bytes"] > 0
    # every slice rode the sharded window, its tail padded to W slots
    assert sum(m["windows"].values()) == m["windows"]["sharded"]
    assert m["pad_slots"] == m["windows"]["sharded"] * W - s.num_events


@multi_device
def test_per_device_bytes_shrink_with_mesh_width():
    """The point of sharding: each device holds ~1/P of the O(n) state."""
    state = init_state(1024, 8, 8, 2, 0)
    b1 = per_device_state_bytes(shard_state(state, make_vertices_mesh(1)))
    bp = per_device_state_bytes(
        shard_state(init_state(1024, 8, 8, 2, 0), make_vertices_mesh()))
    assert bp < b1


def test_sharded_snapshot_restore_cross_layout(tmp_path):
    """Snapshot from a sharded session restores into BOTH a dense and a
    sharded session (any mesh width) and both resume bit-identically —
    the checkpoint is the canonical gathered layout."""
    s = _mixed_stream(n=70, m=200, seed=9)
    cfg = EngineConfig(k_max=8, k_init=2, max_cap=70)
    et, vx, nb = np.asarray(s.etype), np.asarray(s.vertex), np.asarray(s.nbrs)
    half = s.num_events // 2
    ref, _ = run_stream(s, policy="sdp", cfg=cfg, seed=0)

    live = Partitioner(cfg, policy="sdp", sharded=True, window=W, seed=0)
    live.feed((et[:half], vx[:half], nb[:half]))
    d = str(tmp_path / "ck")
    live.snapshot(d)
    live.feed((et[half:], vx[half:], nb[half:]))

    restored_dense = Partitioner.restore(d, cfg, policy="sdp",
                                         engine="windowed", window=W)
    restored_shard = Partitioner.restore(d, cfg, policy="sdp",
                                         sharded=True, window=W)
    for p in (restored_dense, restored_shard):
        assert p.cursor == half
        p.feed((et[half:], vx[half:], nb[half:]))

    n = ref.assignment.shape[0]
    _assert_states_equal(ref, unshard_state(live.state, n=n), n=n)
    _assert_states_equal(ref, restored_dense.state, n=n)
    _assert_states_equal(
        ref, unshard_state(restored_shard.state,
                           n=restored_shard._sem_geom.n), n=n)


def test_reshard_and_remesh_mid_session(tmp_path):
    """Mesh-width change mid-stream (gather → re-pad → re-place) is not
    semantics; RecoverableSession.remesh routes sharded sessions to it."""
    from repro.runtime.recovery import RecoverableSession
    s = _mixed_stream(n=50, m=140, seed=11)
    cfg = EngineConfig(k_max=8, k_init=2, max_cap=50)
    et, vx, nb = np.asarray(s.etype), np.asarray(s.vertex), np.asarray(s.nbrs)
    half = s.num_events // 2
    ref, _ = run_stream(s, policy="sdp", cfg=cfg, seed=0)

    part = Partitioner(cfg, policy="sdp", sharded=True, window=W, seed=0)
    sess = RecoverableSession(part, str(tmp_path / "rs"),
                              snapshot_every=10**9)
    sess.feed((et[:half], vx[:half], nb[:half]))
    sess.remesh(devices=1)           # "device loss": fall back to width 1
    assert part._mesh.shape["vertices"] == 1
    sess.feed((et[half:], vx[half:], nb[half:]))
    n = ref.assignment.shape[0]
    _assert_states_equal(ref, unshard_state(part.state, n=n), n=n)

    # dense sessions still need an explicit target device
    dense = Partitioner(cfg, policy="sdp", window=W)
    ds = RecoverableSession(dense, str(tmp_path / "rs2"),
                            snapshot_every=10**9)
    with pytest.raises(ValueError, match="needs the target device"):
        ds.remesh()


# -- sweep integration ---------------------------------------------------

def test_sweep_sharded_vertices_matches_run_stream():
    s = _mixed_stream(n=60, m=160, seed=13)
    runs = [SweepRun("sdp", EngineConfig(k_max=8, k_init=1, max_cap=60), 0),
            SweepRun("ldg", EngineConfig(k_max=8, k_init=3,
                                         autoscale=False), 1)]
    results = (Sweep(s).lanes(runs).windowed(W).sharded_vertices().run())
    for r in results:
        ref, _ = run_stream(s, policy=r.policy, cfg=r.cfg, seed=r.seed)
        assert r.trace is None
        _assert_states_equal(ref, r.state, n=ref.assignment.shape[0])


def test_sweep_sharded_vertices_validation():
    s = _mixed_stream(n=30, m=60, seed=1)
    with pytest.raises(ValueError, match="mutually exclusive"):
        Sweep(s).lane().windowed(W).sharded().sharded_vertices().run()
    with pytest.raises(ValueError, match="windowed engine"):
        Sweep(s).lane().scan().sharded_vertices().run()
    with pytest.raises(ValueError, match="Pallas"):
        Sweep(s).lane().windowed(W).kernel().sharded_vertices().run()
    with pytest.raises(ValueError, match="rebalance"):
        (Sweep(s).lane().windowed(W).rebalance(m=4, every=W)
         .sharded_vertices().run())


def test_sharded_session_validation():
    with pytest.raises(ValueError, match="Pallas"):
        Partitioner(sharded=True, use_kernel=True)
    with pytest.raises(ValueError, match="scan"):
        Partitioner(sharded=True, engine="scan")
    with pytest.raises(ValueError, match="scan"):
        Partitioner(sharded=True, collect_trace=True)
    p = Partitioner(sharded=True)
    with pytest.raises(ValueError, match="reshard"):
        p.place(jax.devices()[0])
    dense = Partitioner()
    with pytest.raises(ValueError, match="sharded=True sessions"):
        dense.reshard()


def test_mesh_builders_compose_or_raise():
    n_dev = jax.device_count()
    mesh = make_vertices_mesh()
    assert mesh.shape == {"vertices": n_dev}
    with pytest.raises(ValueError, match="local devices"):
        make_vertices_mesh(n_dev + 1)
    grid = make_grid_mesh(1, n_dev)
    assert grid.shape == {"lanes": 1, "vertices": n_dev}
    with pytest.raises(ValueError, match=r"lanes.*vertices|×|x"):
        make_grid_mesh(n_dev + 1, n_dev + 1)


# -- adaptive rebalance cadence (rebalance_drift=) -----------------------

def _feed_chunks(part, s, start, end, step):
    et, vx, nb = np.asarray(s.etype), np.asarray(s.vertex), np.asarray(s.nbrs)
    for t in range(start, end, step):
        part.feed((et[t:t + step], vx[t:t + step], nb[t:t + step]))


def test_drift_cadence_fires_on_hub_burst():
    """hub_arrivals drifts both signals up after the warmup baseline —
    the adaptive cadence must fire (the fixed cadence is off)."""
    g = make_graph("social", 200, 800, seed=0)
    s = gstream.hub_arrivals(g, hub_frac=0.05, warmup_frac=0.4, seed=0)
    warm = s.intervals[0]
    cfg = EngineConfig(k_max=8, k_init=4, autoscale=False)
    p = Partitioner(cfg, policy="sdp", rebalance_drift=0.05,
                    rebalance_m=16, rebalance_passes=1, window=W)
    _feed_chunks(p, s, 0, warm, warm)          # baseline = post-warmup
    assert p._drift_base is not None and p._drift_fires == 0
    _feed_chunks(p, s, warm, s.num_events, W)
    m = p.metrics()
    assert m["rebalance_drift_fires"] >= 1
    assert m["rebalances"] == m["rebalance_drift_fires"]
    # every fire re-bases: the recorded events carry the improvement
    assert len(p.rebalance_events) == m["rebalance_drift_fires"]


def test_drift_cadence_quiet_on_stable_stream():
    """A stable stream (signals near their baseline) must never fire."""
    g = make_graph("social", 200, 800, seed=0)
    s = gstream.build_stream(g, seed=1)
    cfg = EngineConfig(k_max=8, k_init=4, autoscale=False)
    p = Partitioner(cfg, policy="sdp", rebalance_drift=0.5,
                    rebalance_m=16, window=W)
    k = int(s.num_events * 0.8)
    _feed_chunks(p, s, 0, k, k)                # baseline after the bulk
    _feed_chunks(p, s, k, s.num_events, 32)
    assert p.metrics()["rebalance_drift_fires"] == 0
    assert p.metrics()["rebalances"] == 0


def test_drift_base_rides_checkpoints(tmp_path):
    g = make_graph("social", 120, 360, seed=3)
    s = gstream.build_stream(g, seed=3)
    cfg = EngineConfig(k_max=8, k_init=2)
    p = Partitioner(cfg, policy="sdp", rebalance_drift=0.05,
                    rebalance_m=8, window=W)
    _feed_chunks(p, s, 0, s.num_events // 2, W)
    assert p._drift_base is not None
    d = str(tmp_path / "ck")
    p.snapshot(d)
    q = Partitioner.restore(d, cfg, policy="sdp", rebalance_drift=0.05,
                            rebalance_m=8, window=W)
    assert q._drift_base == pytest.approx(p._drift_base)


# -- chunked device→host checkpoint staging ------------------------------

def test_stage_host_chunked_equals_direct():
    tree = {"big": jnp.arange(4096, dtype=jnp.int32).reshape(256, 16),
            "small": jnp.float32(3.5),
            "host": np.arange(7)}
    # chunk far smaller than a leaf → many row slices per leaf
    staged = _stage_host(tree, chunk_bytes=128)
    assert all(isinstance(v, np.ndarray) or np.isscalar(v)
               for v in jax.tree_util.tree_leaves(staged))
    np.testing.assert_array_equal(staged["big"], np.asarray(tree["big"]))
    np.testing.assert_array_equal(staged["small"], 3.5)
    np.testing.assert_array_equal(staged["host"], tree["host"])
    # chunk size that does not divide the row count exactly
    np.testing.assert_array_equal(
        _stage_host(tree, chunk_bytes=100)["big"], np.asarray(tree["big"]))


def test_checkpoint_manager_chunked_round_trip(tmp_path):
    """save_now under a tiny host_chunk_bytes stages in many chunks and
    the restored tree is bit-identical (no timing assertions)."""
    state = init_state(128, 6, 8, 2, 0)
    mgr = CheckpointManager(str(tmp_path), interval=1, host_chunk_bytes=64)
    mgr.save_now(5, state, blocking=True, geometry=None)
    like = init_state(128, 6, 8, 2, 0)
    restored, step = mgr.restore(like)
    assert step == 5
    for f in state._fields:
        np.testing.assert_array_equal(np.asarray(getattr(state, f)),
                                      np.asarray(getattr(restored, f)))
    with pytest.raises(ValueError, match="host_chunk_bytes"):
        CheckpointManager(str(tmp_path), host_chunk_bytes=0)


def test_sharded_snapshot_uses_canonical_rows(tmp_path):
    """A sharded session's checkpoint must record the SEMANTIC geometry
    (padding sliced off) so any layout can restore it."""
    if jax.device_count() == 1:
        pytest.skip("padding only exists on multi-device meshes")
    g = make_graph("social", 37, 90, seed=4)
    s = gstream.build_stream(g, seed=4)
    cfg = EngineConfig(k_max=8, k_init=2, max_cap=40)
    p = Partitioner(cfg, policy="sdp", sharded=True, window=W)
    p.feed(s)
    d = str(tmp_path / "ck")
    p.snapshot(d)
    mgr = CheckpointManager(d, interval=1)
    geom = mgr.geometry(mgr.latest())
    assert geom.n == p._sem_geom.n
    assert geom.n % jax.device_count() != 0 or geom.n == p.n
