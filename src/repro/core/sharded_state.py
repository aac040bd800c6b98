"""Vertex-sharded placement of `PartitionState`.

One session's O(n) leaves (the dense label journal ``assignment``, the
presence mask, the adjacency rows) are laid out as per-device row blocks
along a 1-D "vertices" mesh axis; every O(K)/O(K²) leaf (loads, active
mask, cut matrix) and the scalar counters stay fully replicated — the
transformer-shard idiom of sharding the one big axis and replicating the
small state that every step needs whole.

The persistent representation is plain GSPMD global arrays carrying
`NamedSharding`s: the same `PartitionState` NamedTuple as the dense
engines, so geometry helpers (`geometry_of`, `grow_state`), checkpoint
serialization (which gathers via ``np.asarray``), and metrics all work
unchanged. Only the window step itself (repro.runtime.shard_session)
drops into `shard_map` over these shardings.

Row padding: the row count must divide the mesh; `shard_state` pads rows
up to the next multiple with the same inert (-1/0) fill `grow_state`
uses. Padded rows are semantically absent vertices — no event ever
references an id ≥ the semantic n, so they never enter counters (the
heterogeneous-padding test in tests/test_shard_session.py is the gate).
"""
from __future__ import annotations

import jax
import numpy as np
from jax.experimental.layout import Format, Layout
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.geometry import Geometry, geometry_of
from repro.core.state import PartitionState, grow_state, init_state


def n_shards(mesh: jax.sharding.Mesh) -> int:
    return mesh.shape["vertices"]


def pad_rows(n: int, shards: int) -> int:
    """Smallest multiple of ``shards`` that is >= n (and >= shards)."""
    return max(-(-n // shards), 1) * shards


def state_specs() -> PartitionState:
    """PartitionSpec per leaf: row leaves split on "vertices", the rest
    replicated. Ranks written out in full (scalar leaves get ``P()``)."""
    return PartitionState(
        assignment=P("vertices"),
        present=P("vertices"),
        adj=P("vertices", None),
        edge_load=P(None),
        vertex_count=P(None),
        active=P(None),
        num_partitions=P(),
        total_edges=P(),
        cut_edges=P(),
        denied_scaleout=P(),
        scale_events=P(),
        key=P(None),
        cut_matrix=P(None, None),
    )


def state_shardings(mesh: jax.sharding.Mesh,
                    adj_layout: Layout | None = None) -> PartitionState:
    """`state_specs` bound to a mesh as a NamedSharding pytree (the
    leaves are shardings, so this is safe to pass to `jax.device_put`).
    With ``adj_layout``, ``adj``'s leaf is a `Format` that also fixes the
    device layout of each row block; without it the compiler picks."""
    sh = PartitionState(*(NamedSharding(mesh, s) for s in state_specs()))
    if adj_layout is None:
        return sh
    return sh._replace(adj=Format(adj_layout, sh.adj))


def shard_state(state: PartitionState, mesh: jax.sharding.Mesh,
                adj_layout: Layout | None = None) -> PartitionState:
    """Place a (dense or differently-sharded) state on the vertices mesh,
    padding rows up to a multiple of the shard count first."""
    shards = n_shards(mesh)
    g = geometry_of(state)
    target = pad_rows(g.n, shards)
    if target != g.n:
        state = grow_state(state, Geometry(target, g.max_deg, g.k_max))
    return jax.device_put(state, state_shardings(mesh, adj_layout))


def init_sharded_state(n: int, max_deg: int, k_max: int, k_init: int,
                       seed: int, mesh: jax.sharding.Mesh,
                       adj_layout: Layout | None = None) -> PartitionState:
    """``init_state`` built directly in its vertices-mesh layout, rows
    padded to a multiple of the shard count. Each device allocates only
    its own row blocks, so a state larger than one device's memory can
    be created at all (``shard_state(init_state(...))`` would first
    materialize the whole state on one device)."""
    n_pad = pad_rows(n, n_shards(mesh))
    build = jax.jit(init_state, static_argnums=(0, 1, 2, 3, 4),
                    out_shardings=state_shardings(mesh, adj_layout))
    return build(n_pad, max_deg, k_max, k_init, seed)


def gather_state(state: PartitionState,
                 n: int | None = None) -> PartitionState:
    """Gather to host numpy in the canonical dense layout, optionally
    slicing the row padding back off (``n`` = semantic row count). This
    is what checkpoints persist, so sharded and dense sessions round-trip
    interchangeably."""
    host = jax.tree.map(np.asarray, state)
    if n is not None and n < host.assignment.shape[0]:
        host = host._replace(assignment=host.assignment[:n],
                             present=host.present[:n],
                             adj=host.adj[:n])
    return host


def unshard_state(state: PartitionState,
                  n: int | None = None) -> PartitionState:
    """Gather back to ordinary single-device arrays (row padding sliced
    off when ``n`` is given) — the exact shapes a dense run produces."""
    import jax.numpy as jnp
    return jax.tree.map(jnp.asarray, gather_state(state, n))


def per_device_state_bytes(state: PartitionState) -> int:
    """Peak resident state bytes on any one device: each device pays for
    its own row blocks plus a full copy of every replicated leaf. On a
    dense (unsharded) state this degenerates to `state_bytes`."""
    per: dict = {}
    for leaf in jax.tree.leaves(state):
        if isinstance(leaf, jax.Array) and hasattr(leaf, "addressable_shards"):
            for sh in leaf.addressable_shards:
                per[sh.device] = per.get(sh.device, 0) + sh.data.nbytes
        else:
            arr = np.asarray(leaf)
            per[None] = per.get(None, 0) + arr.nbytes
    return max(per.values()) if per else 0
