"""Compile the main path for a described TPU v5e, at real widths.

Nothing here runs: the TPU compiler, which is installed with JAX,
compiles for the chips of a described ``v5e:2x2`` topology and reports
what it would refuse on the chip — a kernel Mosaic cannot lower, a
program that does not fit HBM, a mesh it cannot partition. The topology
is described inside a fixture, never while a module is imported, so every
test worker collects the same tests; where it cannot be described the
tests skip.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding

from repro.api import partitioner as part_api
from repro.core import transition as tx
from repro.core.config import EngineConfig
from repro.core.sharded_state import state_specs
from repro.core.state import init_state
from repro.kernels.fused_chooser.fused_chooser import (
    EV_COLS, SCAL_N, fused_window_choose,
)
from repro.kernels.fused_chooser.ops import (
    _run_window_mixed_fused, sweep_window_mixed_fused,
)
from repro.kernels.partition_affinity.partition_affinity import (
    partition_affinity,
)
from repro.runtime.shard_session import sharded_stream_fn

W, D, K = 256, 192, 16
HBM_BYTES = 15.75e9          # what XLA lets one v5e program use
CFG = EngineConfig(k_max=K, k_init=1, autoscale=True)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _state_shapes(n, shardings):
    """``init_state(n, D, K)`` as shapes, placed by ``shardings``: one
    sharding for every leaf, or a ``PartitionState`` of them."""
    st = jax.eval_shape(lambda: init_state(n, D, K, 1, 0))
    if not isinstance(shardings, tuple):
        shardings = type(st)(*[shardings] * len(st))
    return jax.tree_util.tree_map(
        lambda a, s: _shape(a.shape, a.dtype, s), st, shardings)


def test_partition_affinity_compiles(one_chip):
    f = functools.partial(partition_affinity, k_max=K, interpret=False)
    c = jax.jit(f).lower(_shape((W, D), jnp.int32, one_chip)).compile()
    assert "tpu_custom_call" in c.as_text()


def _chooser_args(sharding):
    i32 = jnp.int32
    return [_shape(s, dt, sharding) for s, dt in (
        ((W, EV_COLS), i32), ((W, D), i32), ((W, D), i32), ((W, K), i32),
        ((K,), jnp.bool_), ((K,), i32), ((K,), i32), ((K, K), i32),
        ((SCAL_N,), i32), ((7,), jnp.float32), ((2,), i32))]


def _chooser(policy):
    """A string is one session's policy; a tuple is a sweep's lanes,
    switched on a traced index with the per-lane autoscale gate."""
    lanes = isinstance(policy, tuple)
    return functools.partial(
        fused_window_choose, n=1 << 22, policy=policy, balance_guard="text",
        autoscaling=lanes or policy == "sdp", dynamic=lanes, interpret=False)


@pytest.mark.parametrize("policy", [
    "sdp", "greedy", "ldg", "hash", "random",
    ("sdp", "ldg", "hash", "random", "greedy")])
def test_fused_chooser_compiles(one_chip, policy):
    """The fused window chooser lowers through Mosaic for every policy
    whose body it can express, and for a sweep's switch over them."""
    c = jax.jit(_chooser(policy)).lower(*_chooser_args(one_chip)).compile()
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("policy", ["fennel", ("sdp", "fennel")])
def test_fused_chooser_refuses_float_power(one_chip, policy):
    """Fennel's float power has no Mosaic lowering: the compiled kernel
    refuses it by name, alone or as one of a sweep's lanes, instead of
    failing inside Mosaic."""
    with pytest.raises(NotImplementedError, match="fennel"):
        jax.jit(_chooser(policy)).lower(*_chooser_args(one_chip))


def test_sweep_kernel_lanes_compile(one_chip):
    """``Sweep(...).kernel()``'s lane-batched program (vmap over the
    pallas_call) compiles when no lane runs fennel: the kernel's switch
    holds only the lanes' policies."""
    n, lanes = 1 << 12, ("sdp", "greedy")

    def inputs():
        states = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs),
            *[init_state(n, D, K, 1, i) for i in range(len(lanes))])
        kns = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs),
            *[tx.knobs_arrays(CFG, n) for _ in lanes])
        pidx = jnp.asarray([tx.POLICY_INDEX[p] for p in lanes], jnp.int32)
        return states, kns, pidx, jnp.ones(len(lanes), bool)

    args = jax.tree_util.tree_map(
        lambda a: _shape(a.shape, a.dtype, one_chip), jax.eval_shape(inputs))
    ev = [_shape((W,), jnp.int32, one_chip), _shape((W,), jnp.int32, one_chip),
          _shape((W, D), jnp.int32, one_chip), _shape((), jnp.int32, one_chip)]
    fn = jax.jit(functools.partial(
        sweep_window_mixed_fused, balance_guard="text",
        autoscale_mode="dynamic", policies=lanes, window=W,
        shared_stream=True, interpret=False))
    c = fn.lower(*args, *ev).compile()
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("use_kernel", [False, True])
def test_dense_mixed_window_fits_one_chip(one_chip, use_kernel):
    """The session's mixed-window program at n = 2**22 (about 3.2 GB of
    state) fits one chip with its carried state donated."""
    n = 1 << 22
    st = _state_shapes(n, one_chip)
    ev = [_shape((W,), jnp.int32, one_chip), _shape((W,), jnp.int32, one_chip),
          _shape((W, D), jnp.int32, one_chip), _shape((), jnp.int32, one_chip)]
    if use_kernel:
        fn = jax.jit(_run_window_mixed_fused,
                     static_argnames=("policy", "cfg", "interpret", "variant"),
                     donate_argnums=(0,))
        kw = {"interpret": False}
    else:
        fn, kw = part_api._mixed_donated, {}
    c = fn.lower(st, *ev, policy="sdp", cfg=CFG, **kw).compile()
    m = c.memory_analysis()
    assert m.alias_size_in_bytes >= 3e9, "the state was not donated"
    peak = m.argument_size_in_bytes + m.temp_size_in_bytes
    assert peak < HBM_BYTES, f"{peak / 1e9:.2f} GB does not fit one chip"
    assert ("tpu_custom_call" in c.as_text()) == use_kernel


def test_sharded_window_compiles_on_four_chips(topo):
    """The vertex-sharded window at n = 2**24 (about 12.9 GB of state,
    more than one chip holds) partitions over a mesh of the four chips,
    about 3.2 GB each, with exactly two all-reduces per window."""
    n = 1 << 24
    mesh = Mesh(topo.devices, ("vertices",))
    st = _state_shapes(n, type(state_specs())(
        *(NamedSharding(mesh, s) for s in state_specs())))
    rep = NamedSharding(mesh, jax.sharding.PartitionSpec())
    fn = sharded_stream_fn(mesh, n_sem=n, policy="sdp", cfg=CFG, window=W,
                           n_events=W)
    c = fn.lower(st, _shape((W,), jnp.int32, rep),
                 _shape((W,), jnp.int32, rep),
                 _shape((W, D), jnp.int32, rep),
                 _shape((), jnp.int32, rep)).compile()
    m = c.memory_analysis()
    per_chip = m.argument_size_in_bytes + m.temp_size_in_bytes
    assert 3e9 < per_chip < HBM_BYTES
    assert len(re.findall(r" all-reduce(?:-start)?\(", c.as_text())) == 2
