"""Vertex-sharded session runtime: one session's (n, max_deg) state split
into per-device row blocks on a "vertices" mesh axis.

Why this shape
--------------
Event slots inside a window are sequentially dependent through the
K-sized counters (every placement shifts the loads the next slot scores
against), so the slot loop itself cannot be split across devices without
changing results. What CAN be split is everything O(n). The factored
window (`repro.runtime.factored_window`, the body the dense session runs
too) splits the mixed window into exactly that:

    prep (O(n + W·D), choice-independent) → slot loop (O(W·K), tiny)
    → apply (O(n))

so the sharded step runs prep and apply shard-locally on (n/P)-row
blocks and runs the *identical* slot loop — `fused_window_choose_ref`,
the oracle the Pallas kernel is tested against — replicated on every
device over psum-assembled window tables. Replication of the tiny loop
makes the per-window communication exactly two `lax.psum`s of O(W·D)
payloads (one all-reduce of per-window deltas instead of per event) and
makes bit-identity to the dense engines structural: every device
executes the same f32 ops in the same order on the same values.

Each device's block starts at row ``axis_index * n_loc``; psum #1 merges
the prep scan's owner-masked emissions, psum #2 the one-hop gather of
committed labels (see `factored_window` for the four rounds).

The O(K²) cut matrix, K-vector loads, and scalar counters ride the
replicated carry. State between windows is GSPMD global arrays with the
shardings of `repro.core.sharded_state`; `run_stream_sharded` is the
whole-stream entry (the bit-identity gate against `run_stream`), and
`sharded_stream_fn` is the cached jitted step the session facade feeds
windows through.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.config import EngineConfig
from repro.core.geometry import Geometry, resolve_geometry
from repro.core.sharded_state import (
    init_sharded_state, state_shardings, state_specs, unshard_state,
)
from repro.core.state import PartitionState
from repro.graph.stream import VertexStream, normalize_rows, pad_stream
from repro.launch.mesh import make_vertices_mesh
from repro.runtime.factored_window import factored_window

AXIS = "vertices"


def _sharded_window(state: PartitionState, ets, vs, rows, t0,
                    *, n_sem: int, policy: str, cfg: EngineConfig):
    """One mixed window, executing INSIDE shard_map: row leaves of
    ``state`` are this device's (n_loc, ...) block, everything else is
    replicated. The rounds are `factored_window`'s, with this device's
    row offset and a psum over the mesh as the merge."""
    n_loc = state.assignment.shape[0]
    lo = jax.lax.axis_index(AXIS).astype(jnp.int32) * n_loc
    return factored_window(
        state, ets, vs, rows, t0, n_sem=n_sem, policy=policy, cfg=cfg,
        lo=lo, merge=functools.partial(jax.lax.psum, axis_name=AXIS))


@functools.lru_cache(maxsize=None)
def sharded_stream_fn(mesh: jax.sharding.Mesh, *, n_sem: int, policy: str,
                      cfg: EngineConfig, window: int, n_events: int,
                      donate: bool = True, adj_layout: Layout | None = None):
    """The jitted sharded step: ``fn(state, ets, vs, rows, t0) -> state``
    processing ``n_events`` (a multiple of ``window``) through a
    lax.scan of `_sharded_window` under one `shard_map`. ``state`` is a
    GSPMD global `PartitionState` with `sharded_state.state_specs`
    shardings (donated when ``donate``); events are replicated. With
    ``adj_layout`` the step takes and returns each ``adj`` row block in
    that device layout. Cached per (mesh, geometry-tier, policy, config,
    window, length, layout) — the sharded analogue of the dense session's
    per-tier re-jit."""
    if n_events % window != 0:
        raise ValueError(
            f"sharded_stream_fn(n_events={n_events}, window={window}): "
            "the event tensor must be padded to a multiple of the window "
            "(graph.stream.pad_stream, or the session's tail padding)")

    def body_stream(state, ets, vs, rows, t0):
        def body(s, wdx):
            i0 = wdx * window
            s = _sharded_window(
                s,
                jax.lax.dynamic_slice_in_dim(ets, i0, window),
                jax.lax.dynamic_slice_in_dim(vs, i0, window),
                jax.lax.dynamic_slice_in_dim(rows, i0, window),
                t0 + i0, n_sem=n_sem, policy=policy, cfg=cfg)
            return s, None
        state, _ = jax.lax.scan(
            body, state, jnp.arange(n_events // window, dtype=jnp.int32))
        return state

    specs = state_specs()
    fn = jax.shard_map(
        body_stream, mesh=mesh,
        in_specs=(specs, P(), P(), P(), P()),
        out_specs=specs, check_vma=False)
    st, rep = state_shardings(mesh, adj_layout), NamedSharding(mesh, P())
    return jax.jit(fn, in_shardings=(st,) + (rep,) * 4, out_shardings=st,
                   donate_argnums=(0,) if donate else ())


def run_stream_sharded(
    stream: VertexStream,
    *,
    policy: str = "sdp",
    cfg: EngineConfig | None = None,
    seed: int = 0,
    window: int = 256,
    geometry: Geometry | None = None,
    mesh: jax.sharding.Mesh | None = None,
    devices=None,
) -> PartitionState:
    """Whole-stream entry: run ``stream`` vertex-sharded over ``mesh``
    (default: all local devices) and gather the final state back dense —
    bit-identical to ``run_stream(stream, ...)[0]`` at the same
    geometry, for any device count. This is the correctness gate and the
    lane body of `Sweep.sharded_vertices()`."""
    cfg = cfg if cfg is not None else EngineConfig()
    geom = resolve_geometry(stream, cfg, geometry)
    if mesh is None:
        mesh = make_vertices_mesh(devices=devices)
    state = init_sharded_state(geom.n, geom.max_deg, geom.k_max, cfg.k_init,
                               seed, mesh)
    s = pad_stream(stream, window)
    ets = jnp.asarray(s.etype)
    vs = jnp.asarray(s.vertex)
    rows = jnp.asarray(normalize_rows(s.nbrs, geom.max_deg))
    fn = sharded_stream_fn(mesh, n_sem=geom.n, policy=policy, cfg=cfg,
                           window=window, n_events=s.num_events)
    state = fn(state, ets, vs, rows, jnp.int32(0))
    return unshard_state(state, n=geom.n)
