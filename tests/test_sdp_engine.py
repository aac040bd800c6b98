"""Faithful engine vs the dict/set oracle (paper Algorithm 1 semantics)."""
import numpy as np
import pytest

from repro.core import (EngineConfig, run_reference, run_stream,
                        recompute_counters, state_metrics)
from repro.graph.datasets import load_dataset
from repro.graph.generators import make_graph
from repro.graph import stream as gstream


def _assert_match(state, ref):
    """JAX engine state must match the oracle exactly."""
    n = state.assignment.shape[0]
    a = np.asarray(state.assignment)
    for v in range(n):
        if v in ref.assignment:
            assert a[v] == ref.assignment[v], f"vertex {v}"
        else:
            assert a[v] == -1, f"vertex {v} should be absent"
    np.testing.assert_array_equal(np.asarray(state.edge_load), ref.edge_load)
    np.testing.assert_array_equal(np.asarray(state.vertex_count),
                                  ref.vertex_count)
    np.testing.assert_array_equal(np.asarray(state.active), ref.active)
    assert int(state.total_edges) == ref.total_edges
    assert int(state.cut_edges) == ref.cut_edges
    assert int(state.num_partitions) == ref.num_partitions
    assert int(state.denied_scaleout) == ref.denied
    assert int(state.scale_events) == ref.scale_events
    # pairwise cut matrix: engine's incremental O(K²) maintenance must
    # match the oracle's, and its invariants must hold (the oracle's
    # cut_edges is recomputed from scratch at scale-in, so the half-sum
    # check pits the engine's incremental merge against an independent
    # from-scratch count)
    cm = np.asarray(state.cut_matrix)
    np.testing.assert_array_equal(cm, ref.cut_matrix)
    np.testing.assert_array_equal(cm, cm.T)
    np.testing.assert_array_equal(cm.sum(axis=1), np.asarray(state.edge_load))
    assert (cm.sum() - np.trace(cm)) // 2 == int(state.cut_edges)


CASES = [
    ("sdp", EngineConfig(k_max=8, k_init=1, max_cap=150)),
    ("sdp", EngineConfig(k_max=4, k_init=2, max_cap=80,
                         balance_guard="alg1")),
    ("sdp", EngineConfig(k_max=8, k_init=1, max_cap=10**9)),  # no scaling
    ("greedy", EngineConfig(k_max=6, k_init=4, autoscale=False)),
    ("ldg", EngineConfig(k_max=6, k_init=4, autoscale=False)),
    ("fennel", EngineConfig(k_max=6, k_init=4, autoscale=False)),
    ("hash", EngineConfig(k_max=6, k_init=3, autoscale=False)),
    ("random", EngineConfig(k_max=6, k_init=3, autoscale=False)),
]


@pytest.mark.parametrize("policy,cfg", CASES)
def test_engine_matches_oracle_static(policy, cfg):
    g = make_graph("mesh", 120, 350, seed=1)
    s = gstream.build_stream(g, seed=2)
    state, _ = run_stream(s, policy=policy, cfg=cfg, seed=3)
    ref = run_reference(s, policy=policy, cfg=cfg, seed=3)
    _assert_match(state, ref)


@pytest.mark.parametrize("policy,cfg", CASES[:3])
def test_engine_matches_oracle_dynamic(policy, cfg):
    """Add/delete protocol (§5.3.1) including vertex+edge deletions."""
    g = make_graph("social", 90, 260, seed=4)
    s = gstream.dynamic_schedule(g, n_intervals=4, seed=5,
                                 del_edges_per_interval=5)
    state, _ = run_stream(s, policy=policy, cfg=cfg, seed=6)
    ref = run_reference(s, policy=policy, cfg=cfg, seed=6)
    _assert_match(state, ref)


def test_counters_match_recompute():
    """Incremental counters == from-scratch recomputation (Eq. 9/10)."""
    g = load_dataset("grqc", scale=0.05)
    s = gstream.dynamic_schedule(g, n_intervals=3, seed=0)
    cfg = EngineConfig(k_max=8, k_init=1, max_cap=120)
    state, _ = run_stream(s, policy="sdp", cfg=cfg)
    rec = recompute_counters(np.asarray(state.assignment),
                             np.asarray(state.present),
                             np.asarray(state.adj), cfg.k_max)
    assert int(state.total_edges) == rec["total_edges"]
    assert int(state.cut_edges) == rec["cut_edges"]
    np.testing.assert_array_equal(np.asarray(state.edge_load),
                                  rec["edge_load"])
    np.testing.assert_array_equal(np.asarray(state.vertex_count),
                                  rec["vertex_count"])


def test_scale_out_triggers():
    """Eq. 5: small MAXCAP forces extra partitions."""
    g = make_graph("mesh", 150, 400, seed=0)
    s = gstream.build_stream(g, seed=0)
    small, _ = run_stream(s, policy="sdp",
                          cfg=EngineConfig(k_max=8, k_init=1, max_cap=60))
    big, _ = run_stream(s, policy="sdp",
                        cfg=EngineConfig(k_max=8, k_init=1, max_cap=10**9))
    assert int(small.num_partitions) > int(big.num_partitions) == 1
    assert int(small.scale_events) > 0


def test_scale_in_merges_partitions():
    """Deleting most vertices should trigger §4.2.3 scale-in migration."""
    g = make_graph("mesh", 100, 300, seed=1)
    add = gstream.build_stream(g, seed=1)
    rng = np.random.default_rng(2)
    present = np.asarray(add.vertex)
    dels = rng.choice(present, size=int(0.9 * present.size), replace=False)
    del_stream = gstream.VertexStream(
        etype=np.full(dels.size, gstream.EVENT_DEL_VERTEX, np.int32),
        vertex=dels.astype(np.int32),
        nbrs=-np.ones((dels.size, add.max_deg), np.int32),
        n=add.n)
    s = gstream.concat_streams([add, del_stream])
    cfg = EngineConfig(k_max=8, k_init=1, max_cap=60,
                       tolerance_param=60.0, dest_param=5.0)
    state, trace = run_stream(s, policy="sdp", cfg=cfg)
    peak = int(np.asarray(trace.num_partitions).max())
    assert int(state.num_partitions) < peak, "scale-in never fired"


def test_nth_active_clamps_out_of_range():
    """Regression: i >= popcount(active) used to argmax an all-False mask
    and silently return slot 0 — possibly an *inactive* partition. Now i
    wraps modulo the active count."""
    import jax.numpy as jnp
    from repro.core.transition import nth_active
    active = jnp.asarray([False, True, False, True, False])
    assert int(nth_active(active, jnp.int32(0))) == 1
    assert int(nth_active(active, jnp.int32(1))) == 3
    assert int(nth_active(active, jnp.int32(2))) == 1   # wraps, stays active
    assert int(nth_active(active, jnp.int32(5))) == 3
    assert bool(active[int(nth_active(active, jnp.int32(17)))])


def test_host_and_traced_imbalance_agree_after_scaling():
    """Eq. 10 is defined once (metrics.load_imbalance, active-partition
    count as denominator): the host-side state_metrics and the traced
    load_stats in the event trace must agree after scale-out AND scale-in
    events (they used to divide by popcount(active) vs num_partitions
    respectively, which drift apart the moment the two invariants do)."""
    from repro.core.metrics import load_imbalance
    g = make_graph("mesh", 100, 300, seed=1)
    add = gstream.build_stream(g, seed=1)
    rng = np.random.default_rng(2)
    present = np.asarray(add.vertex)
    dels = rng.choice(present, size=int(0.9 * present.size), replace=False)
    del_stream = gstream.VertexStream(
        etype=np.full(dels.size, gstream.EVENT_DEL_VERTEX, np.int32),
        vertex=dels.astype(np.int32),
        nbrs=-np.ones((dels.size, add.max_deg), np.int32),
        n=add.n)
    s = gstream.concat_streams([add, del_stream])
    cfg = EngineConfig(k_max=8, k_init=1, max_cap=60,
                       tolerance_param=60.0, dest_param=5.0)
    state, trace = run_stream(s, policy="sdp", cfg=cfg)
    assert int(state.scale_events) > 0
    m = state_metrics(state)
    ref = load_imbalance(np.asarray(state.edge_load), np.asarray(state.active))
    assert m["load_imbalance"] == ref
    np.testing.assert_allclose(float(np.asarray(trace.load_std)[-1]), ref,
                               rtol=1e-5, atol=1e-6)


def test_sdp_beats_hash_on_edge_cut():
    """Directional claim from the paper: SDP ≪ hash/random edge-cut."""
    g = load_dataset("3elt", scale=0.2)
    s = gstream.build_stream(g, seed=0)
    cfg = EngineConfig(k_max=4, k_init=4, autoscale=False)
    cuts = {}
    for pol in ("sdp", "hash"):
        st, _ = run_stream(s, policy=pol, cfg=cfg)
        cuts[pol] = state_metrics(st)["edge_cut_ratio"]
    assert cuts["sdp"] < 0.5 * cuts["hash"]


def test_duplicate_add_ignored():
    g = make_graph("mesh", 30, 80, seed=0)
    s1 = gstream.build_stream(g, seed=0)
    dup = gstream.concat_streams([s1, s1])  # every vertex added twice
    cfg = EngineConfig(k_max=4, k_init=2, autoscale=False)
    st1, _ = run_stream(s1, policy="greedy", cfg=cfg)
    st2, _ = run_stream(dup, policy="greedy", cfg=cfg)
    np.testing.assert_array_equal(np.asarray(st1.assignment),
                                  np.asarray(st2.assignment))
    assert int(st1.total_edges) == int(st2.total_edges)


def test_chunked_run_equals_single_shot():
    """run_stream(chunk=...) must be resumable without drift."""
    g = make_graph("mesh", 80, 220, seed=3)
    s = gstream.build_stream(g, seed=3)
    cfg = EngineConfig(k_max=4, k_init=1, max_cap=100)
    a, _ = run_stream(s, policy="sdp", cfg=cfg)
    b, _ = run_stream(s, policy="sdp", cfg=cfg, chunk=17)
    np.testing.assert_array_equal(np.asarray(a.assignment),
                                  np.asarray(b.assignment))
    assert int(a.cut_edges) == int(b.cut_edges)


def test_mask_form_index_helpers_match_numpy():
    """The iota/select/reduce forms the fused kernel lowers equal plain
    indexing, argmin and cumsum — on 1-D vectors (the XLA engines) and on
    (1, K) rows (the kernel) alike."""
    import jax.numpy as jnp
    from repro.core import transition as tx
    rng = np.random.default_rng(0)
    for _ in range(40):
        k = int(rng.integers(1, 17))
        x = rng.integers(0, 5, k).astype(np.int32)
        mask = rng.random(k) < 0.5
        i = int(rng.integers(0, k))
        for shape in ((k,), (1, k)):
            xj, mj = jnp.asarray(x).reshape(shape), jnp.asarray(mask).reshape(shape)
            assert int(tx.take_at(xj, i)) == x[i]
            assert bool(tx.take_at(mj, i)) == mask[i]
            np.testing.assert_array_equal(
                np.asarray(tx.add_at(xj, i, 7)).ravel(),
                x + 7 * (np.arange(k) == i))
            want = int(np.argmin(np.where(mask, x, 2**30)))
            assert int(tx.masked_argmin(xj, mj)) == want
            cum = np.cumsum(mask) - 1
            want = int(np.argmax((cum == i % max(mask.sum(), 1)) & mask))
            assert int(tx.nth_active(mj, jnp.int32(i))) == want
            np.testing.assert_array_equal(
                np.asarray(tx.row_to_col(xj)), x[:, None])
        m = rng.integers(0, 9, (k, k)).astype(np.int32)
        j = int(rng.integers(0, k))
        want = m.copy()
        want[i, :] += x
        want[:, i] += x
        np.testing.assert_array_equal(
            np.asarray(tx.matrix_add_cross(jnp.asarray(m), i, jnp.asarray(x))),
            want)
        assert int(tx.matrix_at(jnp.asarray(m), i, j)) == m[i, j]


def test_powerlaw_churn_discipline():
    """Seeded and reproducible; ids spread over the universe; rows list
    every edge at both endpoints; deletes name present vertices and live
    edges between present vertices."""
    s = gstream.powerlaw_churn(1 << 20, 3000, max_deg=48, seed=4)
    t = gstream.powerlaw_churn(1 << 20, 3000, max_deg=48, seed=4)
    np.testing.assert_array_equal(s.nbrs, t.nbrs)
    assert set(np.unique(s.etype)) == {gstream.EVENT_ADD,
                                       gstream.EVENT_DEL_VERTEX,
                                       gstream.EVENT_DEL_EDGE}
    assert s.vertex.max() > (1 << 19)          # not packed at the bottom
    rows = {int(v): set(r[r >= 0].tolist())
            for v, r, e in zip(s.vertex, s.nbrs, s.etype)
            if e == gstream.EVENT_ADD}
    for v, nb in rows.items():
        assert v not in nb and all(v in rows[u] for u in nb)
    present, dead = set(), set()
    for e, v, r in zip(s.etype, s.vertex, s.nbrs):
        v = int(v)
        if e == gstream.EVENT_ADD:
            assert v not in present
            present.add(v)
        elif e == gstream.EVENT_DEL_VERTEX:
            present.remove(v)
        else:
            u = int(r[0])
            edge = (min(u, v), max(u, v))
            assert u in present and v in present and u in rows[v]
            assert edge not in dead
            dead.add(edge)


@pytest.mark.parametrize("use_kernel", [False, True])
def test_engine_matches_oracle_powerlaw_churn(use_kernel):
    """Power-law churn with hubs at the row cap and autoscale thrashing K
    up and down: the windowed engine (XLA or the fused kernel) and the
    scan agree with the oracle."""
    from repro.core import run_stream_windowed
    s = gstream.powerlaw_churn(1 << 12, 1500, max_deg=32, seed=6)
    cfg = EngineConfig(k_max=8, k_init=1, max_cap=300)
    ref = run_reference(s, policy="sdp", cfg=cfg, seed=2)
    assert ref.scale_events > 2
    scan, _ = run_stream(s, policy="sdp", cfg=cfg, seed=2)
    _assert_match(scan, ref)
    win = run_stream_windowed(s, policy="sdp", cfg=cfg, seed=2, window=64,
                              use_kernel=use_kernel)
    _assert_match(win, ref)
