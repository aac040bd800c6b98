"""The factored mixed window: the one production body of a session's
window of interleaved ADD / DEL_VERTEX / DEL_EDGE events.

A slot's choice depends on the slots before it only through the K-sized
counters and the labels earlier slots gave; presence, adjacency,
freshness and "which earlier slot last touched this vertex" depend on
the event structure alone. So the window splits into

    prep (O(n + W·D), choice-independent) → slot loop (O(W·K), tiny)
    → apply (O(n))

and the sequential loop carries only K-sized state. Round structure
per window (W slots, D = max_deg, a row block of n_loc rows at ``lo``):

  round 1 — prep scan over W. It carries only the (adj block, int32
    presence block); per slot it applies the faithful adjacency and
    presence writes localized to the block (drop-mode scatters,
    preserving the dense scan's self-loop write order) and emits
    owner-masked scalars: the deleted vertex's adjacency row,
    freshness/presence bits, DEL_EDGE existence halves. Values are
    encoded +2 (ids and labels live in {-1} ∪ [0, n)) so 0 is the
    merge's identity and exactly one owner contributes.
  merge #1 — every block now holds the same (W,) scalars.
  round 2 — the (W, D) score-source rows (ADD rows from the events,
    DEL_VERTEX rows from merge #1); each block contributes the committed
    labels of the entries it owns, plus the label0[v]/label0[u] columns.
  merge #2 — that one-hop gather. The touch tables need no merge: which
    earlier slot last relabeled a vertex is a function of the
    (etype, vertex) structure, recomputed with O(W²·D) vectorised
    compares.
  round 3 — `fused_window_choose_ref` over the tables, with the
    *semantic* n (row padding must not perturb LDG's capacity knob). Its
    lax.scan carries the K-sized counters, the (K, K) cut matrix, a
    K-entry scale-in remap and the W in-window labels.
  round 4 — apply: every row takes its committed label through the
    remap, then each vertex touched in the window takes the label of its
    last touching slot (a W-row scatter).

Two callers: the dense session's mixed-window program
(`run_window_factored`: the whole state is one block, ``lo`` 0, the
merge the identity) and the vertex-sharded step
(`repro.runtime.shard_session`: one block a device inside ``shard_map``,
the merge a ``psum`` over the mesh). Both are bit-identical to
`run_stream`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import transition as tx
from repro.core.config import EngineConfig
from repro.core.geometry import check_row_width
from repro.core.state import PartitionState
from repro.graph.stream import (
    EVENT_ADD, EVENT_DEL_EDGE, EVENT_DEL_VERTEX, EVENT_PAD,
)
from repro.kernels.fused_chooser import fused_chooser as fk
from repro.kernels.fused_chooser.ref import fused_window_choose_ref


def factored_window(state: PartitionState, ets, vs, rows, t0, *, n_sem: int,
                    policy: str, cfg: EngineConfig, lo, merge):
    """One mixed window over a row block. Row leaves of ``state`` are the
    (n_loc, ...) block of global rows ``[lo, lo + n_loc)``; everything
    else is whole. ``merge`` sums each owner-masked emission over every
    block (the identity when the block is the whole state). See the
    module docstring for the rounds."""
    n_loc = state.assignment.shape[0]
    w = vs.shape[0]
    k_max = state.edge_load.shape[0]
    i32 = jnp.int32

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

    # ---- round 1: block-local prep scan ------------------------------
    # Mirrors ops._prepare_window op-for-op on this block, including the
    # self-loop aliasing order of the two DEL_EDGE row writes. All reads
    # of v/u rows are garbage off-owner; every consumer is owner-masked.
    # Presence rides the scan as int32 (1 present). Carried as bool, the
    # per-slot writes to a 2**23-entry block went wrong on a v5e chip:
    # the K-state left the reference's five windows into the churn, while
    # the int32 carry stays exact (the same block of 2**20 rows was
    # exact either way).
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

        pv = present[loc(v)] != 0
        fresh = add_i & ~pv
        was = dv_i & pv
        in_adj = jnp.any(own_row == u) & (u >= 0)

        em = (
            jnp.where(dv_i & o_v, own_row + 2, 0),              # dv row
            jnp.where(o_v, fresh.astype(i32), 0),
            jnp.where(o_v, was.astype(i32), 0),
            jnp.where(o_v, (de_i & pv & in_adj).astype(i32), 0),
            jnp.where(o_u, present[loc(safe_u)], 0),
        )

        present = present.at[tgt(v, add_i | dv_i)].set(
            add_i.astype(i32), mode="drop")

        row_v_de = jnp.where((own_row == u) & (u >= 0), -1, own_row)
        w1_val = jnp.where(add_i, row, jnp.where(de_i, row_v_de, own_row))
        adj = adj.at[tgt(v, fresh | de_i)].set(w1_val, mode="drop")
        row_u = adj[loc(safe_u)]     # after write 1 (self-loop aliasing)
        row_u_de = jnp.where((row_u == v) & (u >= 0), -1, row_u)
        adj = adj.at[tgt(safe_u, de_i)].set(row_u_de, mode="drop")
        return (adj, present), em

    with jax.named_scope("window.prep"):
        (adj_loc, _), em = jax.lax.scan(
            step, (state.adj, state.present.astype(i32)),
            jnp.arange(w, dtype=i32))
    with jax.named_scope("window.psum_emit"):
        rows_dv2, fresh_c, was_c, e1_c, e2_c = merge(em)
    fresh = fresh_c != 0
    was = was_c != 0
    exists = is_de & (e1_c != 0) & (e2_c != 0)
    rows_dv = rows_dv2 - 2           # the deleted vertex's row, where is_dv

    # ---- round 2: source rows, one one-hop gather --------------------
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
        sl2, l0v2, l0u2 = merge(contrib)
    src_lbl = jnp.where(src_row >= 0, sl2 - 2, -1)

    # touch tables. The dense scan reads last_touch[x] at slot i before
    # slot i's own update lands, so the value is the last j < i with
    # (ADD_j | DEL_VERTEX_j) and vs_j == x.
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

    # ---- round 3: the slot loop ---------------------------------------
    kn = tx.make_knobs(cfg, n_sem)
    knobs = jnp.stack([jnp.float32(x) for x in kn])
    flags = jnp.array([0, 1], i32)
    rand_tab = tx.rand_index_table(state.key, t0, w, k_max)
    scalars = jnp.stack([
        state.num_partitions, state.total_edges, state.cut_edges,
        state.denied_scaleout, state.scale_events])
    with jax.named_scope("window.slot_loop"):
        w_label, _psel, remap, active, loads, cut_matrix, scal = \
            fused_window_choose_ref(
                ev, src_lbl, touch, rand_tab,
                state.active, state.edge_load, state.vertex_count,
                state.cut_matrix, scalars, knobs, flags, n=n_sem,
                policy=policy, balance_guard=cfg.balance_guard,
                autoscaling=policy == "sdp" and cfg.autoscale,
                dynamic=False)

    # ---- round 4: block-local apply -----------------------------------
    # A slot is its vertex's last touch when no later slot touches it;
    # those slots' vertices are distinct, so the scatter has one writer
    # a row.
    with jax.named_scope("window.apply"):
        later_touch = before.T & touches[None, :] \
            & (safe_vs[None, :] == safe_vs[:, None])
        last = touches & ~jnp.any(later_touch, axis=1)
        lbl_kept = jnp.where(label0_loc >= 0,
                             remap[jnp.maximum(label0_loc, 0)], -1)
        label_final = lbl_kept.at[tgt(safe_vs, last)].set(
            w_label, mode="drop")
    return state._replace(
        assignment=label_final, present=label_final >= 0, adj=adj_loc,
        active=active != 0, edge_load=loads[0], vertex_count=loads[1],
        num_partitions=scal[fk.SCAL_NP], total_edges=scal[fk.SCAL_TOTAL],
        cut_edges=scal[fk.SCAL_CUT], denied_scaleout=scal[fk.SCAL_DENIED],
        scale_events=scal[fk.SCAL_SCALE], cut_matrix=cut_matrix)


def run_window_factored(state: PartitionState, ets, vs, rows, t0, *,
                        policy: str, cfg: EngineConfig) -> PartitionState:
    """One mixed window of a dense session: `factored_window` with the
    whole state as its one block. Bit-identical to
    `windowed._run_window_mixed`, which stays as the sweeps' lane body and
    the tests' oracle. Unjitted body — repro.api.partitioner jits it with
    the carried state donated and ``adj`` pinned."""
    check_row_width(state, rows)
    return factored_window(state, ets, vs, rows, t0,
                           n_sem=state.assignment.shape[0], policy=policy,
                           cfg=cfg, lo=0, merge=lambda x: x)
