"""Faithful one-pass streaming engine (paper Algorithm 1) as a lax.scan.

Every event (add vertex / delete vertex / delete edge) is processed in
arrival order, exactly one pass, with the partition decision taken from the
state as of that event — the TPU-native equivalent of the paper's Java
event loop. Policies: SDP (Alg. 1 + §4.2.2 balance guard + §4.2.3 scaling)
and the streaming baselines (LDG, Fennel, hash, random, pure greedy).

The transition bodies (policy dispatch, apply_add / apply_del_* branches,
scale_out / scale_in) live in ``repro.core.transition`` — the single
definition site shared with the windowed kernels and the sweep runtime.
This module is the *static-knob* driver: policy and config are Python
values, so XLA sees one specialized program per (policy, cfg).

The driver is split in two: ``_run_events`` is the unjitted body and
``run_events`` its plain jitted binding; the session facade
(repro.api.partitioner) re-jits the body with the carried state donated,
so streaming ``feed()`` calls reuse buffers instead of copying the state
per call. ``run_stream`` stays the whole-stream reference entry.

The windowed engine (repro.core.windowed) is bit-identical to this one but
restructures the hot affinity scoring into a batched kernel; this module is
the semantic reference. For the same reason it is deliberately OUTSIDE the
``use_kernel`` surface: the Pallas kernels (partition_affinity scoring,
the fused_chooser window loop) attach to the windowed paths only, and
their bit-identity gates all compare against this scan — a session on
``engine="scan"`` (or its small-tail fallback) therefore always scores
with XLA gathers, counted under ``"scan"`` in
``Partitioner.metrics()["windows"]``. The carried ``PartitionState`` includes the
incremental pairwise ``cut_matrix`` (see the transition-module docstring
for its invariant), so autoscale scale-ins here — like everywhere — merge
cuts in O(K²) with no adjacency recompute.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.config import EngineConfig
from repro.core.geometry import Geometry, check_row_width, resolve_geometry
from repro.core.state import PartitionState, init_state
from repro.core.transition import (
    EventTrace, Knobs, make_knobs, knobs_arrays, neighbor_stats, nth_active,
    masked_argmin, load_stats, policy_fns, POLICY_INDEX, scale_out, scale_in,
    scale_in_trigger, make_transition, scan_events,
)
from repro.graph.stream import VertexStream, normalize_rows

__all__ = [
    "EventTrace", "Knobs", "make_knobs", "knobs_arrays", "neighbor_stats",
    "nth_active", "masked_argmin", "load_stats", "policy_fns", "POLICY_INDEX",
    "scale_out", "scale_in", "scale_in_trigger", "run_events", "run_stream",
    "trace_at",
]


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def _run_events(
    state: PartitionState,
    etype: jax.Array,     # (T,)
    vertex: jax.Array,    # (T,)
    nbrs: jax.Array,      # (T, max_deg)
    t0: jax.Array,        # () global index of first event (RNG alignment)
    *,
    policy: str,
    cfg: EngineConfig,
) -> tuple[PartitionState, EventTrace]:
    """Process a chunk of events; resumable (checkpoint state between chunks).

    Unjitted body — ``run_events`` is the plain jitted binding; the session
    facade (repro.api.partitioner) jits it again with the carried state
    donated, so back-to-back ``feed()`` calls reuse the (n, max_deg)
    adjacency buffers instead of copying them per call.
    """
    check_row_width(state, nbrs)
    n = state.assignment.shape[0]
    trn = make_transition(
        make_knobs(cfg, n), n,
        balance_guard=cfg.balance_guard, policy=policy,
        autoscale=cfg.autoscale and policy == "sdp",
    )
    return scan_events(trn.step, state, etype, vertex, nbrs, t0)


run_events = functools.partial(
    jax.jit, static_argnames=("policy", "cfg"))(_run_events)


def run_stream(
    stream: VertexStream,
    *,
    policy: str = "sdp",
    cfg: EngineConfig | None = None,
    seed: int = 0,
    chunk: int | None = None,
    geometry: Geometry | None = None,
) -> tuple[PartitionState, EventTrace]:
    """Host entry: run a full stream through the faithful engine.

    ``geometry`` overrides the state allocation (default: the stream's
    declared ``(n, max_deg)`` with the config's ``k_max``) — how an
    elastic session's auto-grown run is replayed whole-stream at its
    final geometry, and how heterogeneous sweep lanes are checked
    against their padded shape. Must cover the stream's
    ``required_geometry()``; growing is a semantics no-op for every
    policy except LDG (see repro.core.geometry)."""
    cfg = cfg or EngineConfig()
    geom = resolve_geometry(stream, cfg, geometry)
    state = init_state(geom.n, geom.max_deg, geom.k_max, cfg.k_init, seed)
    et = jnp.asarray(stream.etype)
    vx = jnp.asarray(stream.vertex)
    nb = jnp.asarray(normalize_rows(stream.nbrs, geom.max_deg))
    if chunk is None:
        return run_events(state, et, vx, nb, jnp.int32(0), policy=policy, cfg=cfg)
    traces = []
    t = 0
    while t < stream.num_events:
        sl = slice(t, min(t + chunk, stream.num_events))
        state, tr = run_events(
            state, et[sl], vx[sl], nb[sl], jnp.int32(t), policy=policy, cfg=cfg
        )
        traces.append(tr)
        t = sl.stop
    trace = EventTrace(*(jnp.concatenate([getattr(tr, f) for tr in traces])
                         for f in EventTrace._fields))
    return state, trace


def trace_at(trace: EventTrace, indices) -> dict[str, np.ndarray]:
    """Sample the trace at interval boundaries (paper's capture points)."""
    idx = np.asarray(indices, dtype=np.int64) - 1
    idx = np.clip(idx, 0, np.asarray(trace.total_edges).shape[0] - 1)
    tot = np.asarray(trace.total_edges)[idx]
    cut = np.asarray(trace.cut_edges)[idx]
    return {
        "total_edges": tot,
        "cut_edges": cut,
        "edge_cut_ratio": cut / np.maximum(tot, 1),
        "num_partitions": np.asarray(trace.num_partitions)[idx],
        "load_std": np.asarray(trace.load_std)[idx],
    }
