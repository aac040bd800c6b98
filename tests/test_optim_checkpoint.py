"""Optimizer, gradient compression, checkpointing, fault-tolerant loop."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.ckpt import restore_pytree, save_pytree
from repro.checkpoint.manager import CheckpointManager
from repro.optim.compression import (compressed_allreduce_grads,
                                     init_error_feedback, int8_compress,
                                     int8_decompress)
from repro.optim.optimizers import (adamw, apply_updates,
                                    clip_by_global_norm, cosine_schedule,
                                    linear_warmup_cosine, sgd_momentum)
from repro.runtime.fault import FaultTolerantLoop


def test_adamw_converges_quadratic():
    w = {"a": jnp.asarray([5.0, -3.0]), "b": jnp.asarray(2.0)}
    opt = adamw(0.2, weight_decay=0.0)
    state = opt.init(w)

    def loss(w):
        return jnp.sum(w["a"] ** 2) + w["b"] ** 2

    for _ in range(120):
        g = jax.grad(loss)(w)
        upd, state = opt.update(g, state, w)
        w = apply_updates(w, upd)
    assert float(loss(w)) < 1e-3


def test_weight_decay_shrinks_params():
    w = {"a": jnp.ones(4) * 10.0}
    opt = adamw(0.1, weight_decay=0.5)
    state = opt.init(w)
    zero_g = {"a": jnp.zeros(4)}
    for _ in range(20):
        upd, state = opt.update(zero_g, state, w)
        w = apply_updates(w, upd)
    assert float(jnp.abs(w["a"]).max()) < 10.0


def test_clip_by_global_norm():
    g = {"x": jnp.ones(16) * 100.0}
    clipped, norm = clip_by_global_norm(g, 1.0)
    assert abs(float(norm) - 400.0) < 1e-3
    total = jnp.sqrt(jnp.sum(clipped["x"] ** 2))
    np.testing.assert_allclose(float(total), 1.0, rtol=1e-4)


def test_schedules():
    lr = cosine_schedule(1.0, 100)
    assert float(lr(0)) == pytest.approx(1.0)
    assert float(lr(100)) == pytest.approx(0.1, abs=1e-6)
    lrw = linear_warmup_cosine(1.0, 10, 100)
    assert float(lrw(0)) < float(lrw(9))
    assert float(lrw(10)) == pytest.approx(1.0, rel=1e-3)


def test_sgd_momentum_descends():
    w = jnp.asarray([4.0])
    opt = sgd_momentum(0.02)   # heavy-ball stable region for f=x²
    state = opt.init(w)
    for _ in range(150):
        g = 2 * w
        upd, state = opt.update(g, state, w)
        w = apply_updates(w, upd)
    assert abs(float(w[0])) < 0.1


# ---------------------------------------------------------------------------
# gradient compression
# ---------------------------------------------------------------------------

def test_int8_roundtrip_accuracy():
    x = jax.random.normal(jax.random.PRNGKey(0), (256,)) * 3.0
    q, s = int8_compress(x)
    err = np.abs(np.asarray(int8_decompress(q, s) - x))
    assert err.max() <= float(s) * 0.5 + 1e-6


def test_error_feedback_unbiased_over_time():
    """Mean compressed signal ≈ mean true signal once EF accumulates."""
    g = {"w": jnp.full((64,), 0.01)}   # tiny values → large relative quant
    err = init_error_feedback(g)
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1,), ("dp",))

    def run(err):
        f = jax.shard_map(
            lambda gg, ee: compressed_allreduce_grads(gg, ee, "dp"),
            mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()))
        return f(g, err)

    total = jnp.zeros(64)
    for _ in range(16):
        out, err = run(err)
        total = total + out["w"]
    np.testing.assert_allclose(np.asarray(total / 16), 0.01, rtol=0.1)


# ---------------------------------------------------------------------------
# checkpointing + fault tolerance
# ---------------------------------------------------------------------------

def test_save_restore_roundtrip(tmp_path):
    tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "nested": {"b": jnp.asarray([1, 2], jnp.int32)}}
    path = os.path.join(tmp_path, "ckpt.npz")
    save_pytree(path, tree, step=7)
    like = jax.tree.map(lambda x: jnp.zeros_like(x), tree)
    out = restore_pytree(path, like)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_restore_legacy_partition_state_fill_missing(tmp_path):
    """A pre-cut_matrix PartitionState checkpoint (12 leaves) restores into
    today's 13-leaf state: fill_missing aligns by key path, the trailing
    cut_matrix leaf keeps `like`'s value — which recount_cut_matrix rebuilds
    exactly from the restored (assignment, present, adj)."""
    import collections
    from repro.core import EngineConfig, run_stream
    from repro.core.state import PartitionState, recount_cut_matrix
    from repro.graph.generators import make_graph
    from repro.graph import stream as gstream

    g = make_graph("mesh", 40, 100, seed=0)
    s = gstream.build_stream(g, seed=0)
    state, _ = run_stream(
        s, policy="sdp", cfg=EngineConfig(k_max=4, k_init=2, autoscale=False))
    # a faithful stand-in for the pre-cut_matrix state type: same field
    # names (key paths align by attribute), no trailing cut_matrix leaf
    Legacy = collections.namedtuple("Legacy", PartitionState._fields[:-1])
    legacy = Legacy(*tuple(state)[:-1])
    path = os.path.join(tmp_path, "legacy.npz")
    save_pytree(path, legacy, step=1)

    like = jax.tree.map(jnp.zeros_like, state)
    with pytest.raises(ValueError, match="fill_missing"):
        restore_pytree(path, like)
    out = restore_pytree(path, like, fill_missing=True)
    restored = recount_cut_matrix(out)
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_manager_retention_and_latest(tmp_path):
    m = CheckpointManager(str(tmp_path), interval=1, keep=2)
    tree = {"w": jnp.zeros(3)}
    for step in (1, 2, 3, 4):
        m.maybe_save(step, tree, blocking=True)
    assert m.latest() == 4
    assert m._steps() == [3, 4]          # retention gc
    restored, step = m.restore(tree)
    assert step == 4


def test_fault_loop_recovers_from_poison(tmp_path):
    """A step that raises → restore from checkpoint → continue."""
    m = CheckpointManager(str(tmp_path), interval=1)
    loop = FaultTolerantLoop(m, max_retries=2)
    state = {"w": jnp.zeros(2)}

    batches = [1.0, 2.0, "poison", 3.0]

    def step_fn(state, batch):
        if batch == "poison":
            raise RuntimeError("node failure")
        return {"w": state["w"] + batch}, {}

    final, steps = loop.run(state, iter(batches), step_fn, like=state)
    # poison batch skipped; recovery restored from the last checkpoint
    assert any(e["event"] == "failure" for e in loop.events)
    assert np.isfinite(np.asarray(final["w"])).all()


def test_straggler_detection(tmp_path):
    m = CheckpointManager(str(tmp_path), interval=10**9,
                          straggler_factor=2.0)
    for i in range(16):
        m.record_step(i, 0.1)
    assert not m.is_straggler(0.15)
    assert m.is_straggler(0.5)
