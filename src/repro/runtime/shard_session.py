"""Vertex-sharded session runtime: one session's (n, max_deg) state split
into per-device row blocks on a "vertices" mesh axis.

Why this shape
--------------
Event slots inside a window are sequentially dependent through the
K-sized counters (every placement shifts the loads the next slot scores
against), so the slot loop itself cannot be split across devices without
changing results. What CAN be split is everything O(n). The fused
chooser (PR 7) already factored the mixed window into exactly that
split:

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

Round structure per window (W slots, D = max_deg, P shards):

  round 1 — shard-local prep scan over W. Each device carries only its
    (adj block, present block); per slot it applies the faithful
    adjacency/presence writes localized to its block (drop-mode
    scatters, preserving the dense scan's self-loop write order) and
    emits owner-masked scalars: the deleted vertex's adjacency row,
    freshness/presence bits, DEL_EDGE existence halves. Values are
    encoded +2 (ids/labels live in {-1} ∪ [0, n)) so 0 is the psum
    identity and exactly one owner contributes.
  psum #1 — merges the emissions; every device now holds the same (W,)
    scalars the dense `_prepare_window` scan produces.
  round 2 — the (W, D) score-source row table is now replicated (ADD
    rows come from the event stream, DEL_VERTEX rows from psum #1), so
    each device contributes the committed labels of the entries it
    owns, plus the label0[v]/label0[u] columns.
  psum #2 — merges that one-hop halo gather. Touch tables need NO
    communication: which earlier slot last relabeled a vertex is a pure
    function of the (etype, vertex) event structure, so they are
    recomputed replicatedly with O(W²·D) vectorized compares (W is the
    window size — bounded and small; this is the same
    choice-independence trick the fused chooser's prep scan exploits).
  round 3 — `fused_window_choose_ref` over the assembled tables, with
    the *semantic* n (row padding must not perturb LDG's capacity knob).
  round 4 — shard-local apply: scatter-max of touch slots per block,
    then the journal rebuild `w_label[last_touch] / remap[label0]`.

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
from jax.sharding import PartitionSpec as P

from repro.core import transition as tx
from repro.core.config import EngineConfig
from repro.core.geometry import Geometry, resolve_geometry
from repro.core.sharded_state import (
    init_sharded_state, state_specs, unshard_state,
)
from repro.core.state import PartitionState
from repro.graph.stream import (
    EVENT_ADD, EVENT_DEL_EDGE, EVENT_DEL_VERTEX, EVENT_PAD,
    VertexStream, normalize_rows, pad_stream,
)
from repro.kernels.fused_chooser import fused_chooser as fk
from repro.kernels.fused_chooser.ref import fused_window_choose_ref
from repro.launch.mesh import make_vertices_mesh

AXIS = "vertices"


def _sharded_window(state: PartitionState, ets, vs, rows, t0,
                    *, n_sem: int, policy: str, cfg: EngineConfig):
    """One mixed window, executing INSIDE shard_map: row leaves of
    ``state`` are this device's (n_loc, ...) block, everything else is
    replicated. See the module docstring for the round structure."""
    n_loc = state.assignment.shape[0]
    w = vs.shape[0]
    k_max = state.edge_load.shape[0]
    i32 = jnp.int32
    lo = jax.lax.axis_index(AXIS).astype(i32) * n_loc

    ets = jnp.where(vs >= 0, ets, EVENT_PAD)
    is_add = ets == EVENT_ADD
    is_dv = ets == EVENT_DEL_VERTEX
    is_de = ets == EVENT_DEL_EDGE
    safe_vs = jnp.where(vs >= 0, vs, 0)
    rows_add = jnp.where(is_add[:, None], rows, -1)
    label0_loc = jnp.where(state.present, state.assignment, -1)

    def owned(g):
        return (g >= lo) & (g < lo + n_loc)

    def loc(g):                      # clamped local index (gathers)
        return jnp.clip(g - lo, 0, n_loc - 1)

    def tgt(g, cond):                # local scatter target, drop unowned
        return jnp.where(cond & owned(g), g - lo, n_loc)

    # ---- round 1: shard-local prep scan -----------------------------
    # Mirrors ops._prepare_window op-for-op on this block, including the
    # self-loop aliasing order of the two DEL_EDGE row writes. All reads
    # of v/u rows are garbage off-owner; every consumer is owner-masked.
    def step(carry, i):
        adj, present = carry
        v = safe_vs[i]
        row = rows[i]
        add_i, dv_i, de_i = is_add[i], is_dv[i], is_de[i]
        own_row = adj[loc(v)]
        u = row[0]
        safe_u = jnp.maximum(u, 0)
        o_v = owned(v)
        o_u = owned(safe_u)

        pv = present[loc(v)]
        fresh = add_i & ~pv
        was = dv_i & pv
        in_adj = jnp.any(own_row == u) & (u >= 0)

        em = (
            jnp.where(dv_i & o_v, own_row + 2, 0),              # dv row
            jnp.where(o_v, fresh.astype(i32), 0),
            jnp.where(o_v, was.astype(i32), 0),
            jnp.where(o_v, (de_i & pv & in_adj).astype(i32), 0),
            jnp.where(o_u, present[loc(safe_u)].astype(i32), 0),
        )

        present = present.at[tgt(v, add_i | dv_i)].set(add_i, mode="drop")

        row_v_de = jnp.where((own_row == u) & (u >= 0), -1, own_row)
        w1_val = jnp.where(add_i, row, jnp.where(de_i, row_v_de, own_row))
        adj = adj.at[tgt(v, fresh | de_i)].set(w1_val, mode="drop")
        row_u = adj[loc(safe_u)]     # after write 1 (self-loop aliasing)
        row_u_de = jnp.where((row_u == v) & (u >= 0), -1, row_u)
        adj = adj.at[tgt(safe_u, de_i)].set(row_u_de, mode="drop")
        return (adj, present), em

    (adj_loc, _), em = jax.lax.scan(
        step, (state.adj, state.present), jnp.arange(w, dtype=i32))
    with jax.named_scope("window.psum_emit"):
        rows_dv2, fresh_c, was_c, e1_c, e2_c = jax.lax.psum(em, AXIS)
    fresh = fresh_c != 0
    was = was_c != 0
    exists = is_de & (e1_c != 0) & (e2_c != 0)
    rows_dv = rows_dv2 - 2           # the deleted vertex's row, where is_dv

    # ---- round 2: replicated source rows, one halo gather -----------
    src_row = jnp.where(is_add[:, None], rows_add,
                        jnp.where(is_dv[:, None], rows_dv, -1))
    src_safe = jnp.maximum(src_row, 0)
    us = jnp.maximum(rows[:, 0], 0)
    contrib = (
        jnp.where(owned(src_safe), label0_loc[loc(src_safe)] + 2, 0),
        jnp.where(owned(safe_vs), label0_loc[loc(safe_vs)] + 2, 0),
        jnp.where(owned(us), label0_loc[loc(us)] + 2, 0),
    )
    with jax.named_scope("window.psum_halo"):
        sl2, l0v2, l0u2 = jax.lax.psum(contrib, AXIS)
    src_lbl = jnp.where(src_row >= 0, sl2 - 2, -1)

    # touch tables: replicated recompute. The dense scan reads
    # last_touch[x] at slot i before slot i's own update lands, so the
    # value is the last j < i with (ADD_j | DEL_VERTEX_j) and vs_j == x.
    iota = jnp.arange(w, dtype=i32)
    touches = is_add | is_dv
    before = iota[None, :] < iota[:, None]                  # (W, W)

    def last_touch_of(entries):      # (W, ...) ids -> (W, ...) slot idx
        m = (entries[..., None] == safe_vs) & touches
        m = m & before.reshape((w,) + (1,) * (entries.ndim - 1) + (w,))
        return jnp.max(jnp.where(m, iota, -1), axis=-1)

    touch = jnp.where(src_row >= 0, last_touch_of(src_safe), -1)
    lt_v = last_touch_of(safe_vs)
    lt_u = last_touch_of(us)

    ev = jnp.stack([
        ets, safe_vs, fresh.astype(i32), was.astype(i32),
        exists.astype(i32), l0v2 - 2, lt_v, l0u2 - 2, lt_u], axis=1)

    # ---- round 3: the replicated slot loop (the tested oracle) ------
    kn = tx.make_knobs(cfg, n_sem)
    knobs = jnp.stack([jnp.float32(x) for x in kn])
    flags = jnp.array([0, 1], i32)
    rand_tab = tx.rand_index_table(state.key, t0, w, k_max)
    scalars = jnp.stack([
        state.num_partitions, state.total_edges, state.cut_edges,
        state.denied_scaleout, state.scale_events])
    w_label, _psel, remap, active, loads, cut_matrix, scal = \
        fused_window_choose_ref(
            ev, src_lbl, touch, rand_tab,
            state.active, state.edge_load, state.vertex_count,
            state.cut_matrix, scalars, knobs, flags, n=n_sem,
            policy=policy, balance_guard=cfg.balance_guard,
            autoscaling=policy == "sdp" and cfg.autoscale, dynamic=False)

    # ---- round 4: shard-local apply ---------------------------------
    lt_loc = jnp.full((n_loc,), -1, i32)
    lt_loc = lt_loc.at[tgt(safe_vs, touches)].max(iota, mode="drop")
    lbl_touched = w_label[jnp.clip(lt_loc, 0, w - 1)]
    lbl_kept = jnp.where(label0_loc >= 0,
                         remap[jnp.maximum(label0_loc, 0)], -1)
    label_final = jnp.where(lt_loc >= 0, lbl_touched, lbl_kept)
    return state._replace(
        assignment=label_final, present=label_final >= 0, adj=adj_loc,
        active=active != 0, edge_load=loads[0], vertex_count=loads[1],
        num_partitions=scal[fk.SCAL_NP], total_edges=scal[fk.SCAL_TOTAL],
        cut_edges=scal[fk.SCAL_CUT], denied_scaleout=scal[fk.SCAL_DENIED],
        scale_events=scal[fk.SCAL_SCALE], cut_matrix=cut_matrix)


@functools.lru_cache(maxsize=None)
def sharded_stream_fn(mesh: jax.sharding.Mesh, *, n_sem: int, policy: str,
                      cfg: EngineConfig, window: int, n_events: int,
                      donate: bool = True):
    """The jitted sharded step: ``fn(state, ets, vs, rows, t0) -> state``
    processing ``n_events`` (a multiple of ``window``) through a
    lax.scan of `_sharded_window` under one `shard_map`. ``state`` is a
    GSPMD global `PartitionState` with `sharded_state.state_specs`
    shardings (donated when ``donate``); events are replicated. Cached
    per (mesh, geometry-tier, policy, config, window, length) — the
    sharded analogue of the dense session's per-tier re-jit."""
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
    return jax.jit(fn, donate_argnums=(0,) if donate else ())


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
