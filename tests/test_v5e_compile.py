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
from repro.kernels.fused_chooser.ops import sweep_window_mixed_fused
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


def _session_program(kind, sharding):
    """The dense session's donated program ``kind`` on ``sharding``, as
    ``Partitioner`` binds it, with its event arguments as shapes."""
    i32 = functools.partial(_shape, dtype=jnp.int32, sharding=sharding)
    fmt = part_api.adj_format(sharding)
    ev = [i32((W,)), i32((W,)), i32((W, D)), i32(())]
    if kind == "adds":
        return part_api._adds_donated(fmt, "sdp", CFG), ev[1:]
    if kind == "mixed_kernel":
        return part_api._mixed_fused_donated(fmt, "sdp", CFG,
                                             interpret=False), ev
    return part_api._mixed_donated(fmt, "sdp", CFG), ev


@pytest.mark.parametrize("use_kernel", [False, True])
def test_dense_mixed_window_fits_one_chip(one_chip, use_kernel):
    """The session's mixed-window program at n = 2**22 (about 4.3 GB of
    state) fits one chip with its carried state donated."""
    fn, ev = _session_program("mixed_kernel" if use_kernel else "mixed",
                              one_chip)
    c = fn.lower(_state_shapes(1 << 22, one_chip), *ev).compile()
    m = c.memory_analysis()
    assert m.alias_size_in_bytes >= 3e9, "the state was not donated"
    peak = m.argument_size_in_bytes + m.temp_size_in_bytes
    assert peak < HBM_BYTES, f"{peak / 1e9:.2f} GB does not fit one chip"
    assert ("tpu_custom_call" in c.as_text()) == use_kernel


@pytest.mark.parametrize("kind", ["adds", "mixed", "mixed_kernel"])
def test_session_program_keeps_adj_row_major(one_chip, kind):
    """At n = 2**22 each dense session program takes and returns ``adj``
    row-major, aliased in place: no copy of the whole adjacency on entry
    or exit, and no second adjacency as temp."""
    n = 1 << 22
    fn, ev = _session_program(kind, one_chip)
    c = fn.lower(_state_shapes(n, one_chip), *ev).compile()
    assert c.input_formats[0][0].adj.layout.major_to_minor == (0, 1)
    assert c.output_formats.adj.layout.major_to_minor == (0, 1)
    assert not re.search(rf"s32\[{n},{D}\]\{{[^}}]*\}} copy\(", c.as_text())
    m = c.memory_analysis()
    assert m.temp_size_in_bytes < 64e6
    assert m.alias_size_in_bytes >= 3e9, "the state was not donated"


def test_session_init_builds_adj_row_major(one_chip):
    """The dense session's initial state is built straight into the
    programs' row-major ``adj``, with no temp."""
    n = 1 << 22
    init = part_api._init_pinned(n, D, K, 1, part_api.adj_format(one_chip))
    c = init.lower(_shape((2,), jnp.uint32, one_chip)).compile()
    assert c.output_formats.adj.layout.major_to_minor == (0, 1)
    assert c.memory_analysis().temp_size_in_bytes == 0


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


def _friendster_state(mesh):
    """The ``friendster-32m`` session's state at n = 2**25 on ``mesh``, as
    shapes: 2**23 rows a chip."""
    return _state_shapes(1 << 25, type(state_specs())(
        *(NamedSharding(mesh, s) for s in state_specs())))


@pytest.fixture(scope="module")
def friendster_window(topo):
    """The vertex-sharded window at n = 2**25 on a mesh of the four
    chips, with ``adj`` pinned as ``Partitioner`` pins it, compiled once."""
    n = 1 << 25
    mesh = Mesh(topo.devices, ("vertices",))
    rep = NamedSharding(mesh, jax.sharding.PartitionSpec())
    fn = sharded_stream_fn(mesh, n_sem=n, policy="sdp", cfg=CFG, window=W,
                           n_events=W, adj_layout=part_api.ADJ_LAYOUT)
    return fn.lower(_friendster_state(mesh), _shape((W,), jnp.int32, rep),
                    _shape((W,), jnp.int32, rep),
                    _shape((W, D), jnp.int32, rep),
                    _shape((), jnp.int32, rep)).compile()


def test_sharded_window_at_2_25_fits_four_chips(friendster_window):
    """At n = 2**25 each chip holds its 2**23-row block, row-major (192
    lanes padded to 256): 8.63 GB of arguments, aliased in place, and
    some 2 MB of temp, which leaves 7 GB of the 15.75 GB a program may
    use for the rest of a run (the benchmark's state check takes about
    1.9 GB)."""
    m = friendster_window.memory_analysis()
    per_chip = m.argument_size_in_bytes + m.temp_size_in_bytes
    assert 8.5e9 < m.argument_size_in_bytes < 8.7e9
    assert m.alias_size_in_bytes >= 8.5e9, "the state was not donated"
    assert HBM_BYTES - per_chip > 7e9


def test_sharded_window_at_2_25_has_two_allreduces(friendster_window):
    """A window's only communication is its two psums."""
    text = friendster_window.as_text()
    assert len(re.findall(r" all-reduce(?:-start)?\(", text)) == 2


def test_sharded_window_at_2_25_keeps_adj_row_major(friendster_window):
    """The sharded window takes and returns each chip's ``adj`` block
    row-major and never copies it whole."""
    c = friendster_window
    assert c.input_formats[0][0].adj.layout.major_to_minor == (0, 1)
    assert c.output_formats.adj.layout.major_to_minor == (0, 1)
    assert not re.search(r"s32\[8388608,192\]\{[^}]*\} copy\(", c.as_text())


def test_sharded_window_at_2_25_applies_labels_by_scatter(
        friendster_window):
    """The apply writes each touched vertex's label with a W-row scatter:
    no gather runs over the 2**23 rows of a chip, and the window's temp
    holds no row-sized buffer."""
    text = friendster_window.as_text()
    assert not re.search(r"s32\[8388608\]\{[^}]*\} gather\(", text)
    assert re.search(r"s32\[8388608\]\{[^}]*\} scatter\(", text)
    assert friendster_window.memory_analysis().temp_size_in_bytes < 16e6


def test_sharded_window_at_2_25_writes_presence_as_int32(
        friendster_window):
    """The prep scan's per-slot presence writes go to an int32 block:
    no single-element update of a 2**23-entry bool array, whose per-slot
    writes put a v5e chip's K-state off the reference's."""
    text = friendster_window.as_text()
    assert not re.search(
        r"pred\[8388608\]\{[^}]*\} dynamic-update-slice\(", text)
    assert re.search(
        r"s32\[8388608\]\{[^}]*\} dynamic-update-slice\(", text)


def test_touched_row_gather_at_2_25_fits_beside_the_state(topo):
    """The row gather a benchmark run's state check makes after the
    window (``bench/harness.snapshot``: 2**20 touched rows and the count
    of real entries) reads a row-major block where it lies, with no
    all-gather and no copy of the block: about 1.9 GB of temp and output
    beside the 8.6 GB state. From the TPU's default column-major block
    the same gather copies the block first (9.7 GB of temp)."""
    mesh = Mesh(topo.devices, ("vertices",))
    st = _friendster_state(mesh)
    rep = NamedSharding(mesh, jax.sharding.PartitionSpec())
    gather = jax.jit(
        lambda adj, i: (adj[i], jnp.sum(adj >= 0, dtype=jnp.int32)),
        in_shardings=(part_api.adj_format(st.adj.sharding), rep))
    c = gather.lower(st.adj, _shape((1 << 20,), jnp.int32, rep)).compile()
    text = c.as_text()
    assert not re.search(r" all-gather(?:-start)?\(", text)
    assert not re.search(r"s32\[8388608,192\]\{[^}]*\} copy\(", text)
    m = c.memory_analysis()
    per_chip = m.argument_size_in_bytes + m.temp_size_in_bytes \
        + m.output_size_in_bytes
    assert m.temp_size_in_bytes + m.output_size_in_bytes < 2.5e9
    assert per_chip < HBM_BYTES - 4e9
