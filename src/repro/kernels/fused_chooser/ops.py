"""Wrappers wiring the fused chooser kernel into the engines.

Pipeline per mixed window (see fused_chooser.py for the design):

  1. `_prepare_window` — choice-independent prep: a lean lax.scan over the
     W slots carrying (adj, present, last_touch) that emits the per-slot
     scalar rows and the (W, D) committed-label / touch-index tables, and
     performs the faithful adjacency row writes (adjacency evolution never
     depends on partition choices). Batched XLA, outside the kernel.
  2. `transition.rand_index_table` — the per-slot random draw precomputed
     for every possible partition count (bit-identical to the engines'
     fold_in/randint scheme).
  3. ONE `fused_window_choose` pallas_call — gather (from VMEM-resident
     touch tables) → score → policy argmax → counter/cut_matrix commit
     for all W slots.
  4. `_apply` — two O(n) gathers rebuild the final journal from
     (w_label, remap): ``label = w_label[last_touch]`` where touched,
     else ``remap[committed]``.

`run_window_mixed_fused` is the static-knob drop-in for
`windowed.run_window_mixed`; `sweep_window_mixed_fused` is the traced-knob
lane-batched drop-in for `windowed.sweep_window_mixed` (vmapped
pallas_call). ``variant="ref"`` swaps the kernel for the `ref.py` oracle.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

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
from repro.kernels.fused_chooser.fused_chooser import fused_window_choose
from repro.kernels.fused_chooser.ref import fused_window_choose_ref


class WindowPrep(NamedTuple):
    """Choice-independent window tables (see module docstring)."""
    ev: jax.Array          # (W, EV_COLS) per-slot scalars
    src_lbl: jax.Array     # (W, D) committed labels of score sources
    touch: jax.Array       # (W, D) last label-touching slot (< i), -1 none
    label0: jax.Array      # (n,) committed journal (present ? label : -1)
    last_touch: jax.Array  # (n,) final label-touching slot per vertex
    adj: jax.Array         # (n, D) post-window adjacency


def _prepare_window(state: PartitionState, ets, vs, rows) -> WindowPrep:
    """The prep scan. Presence, adjacency, freshness, and touch indices
    depend only on the event structure — never on partition choices — so
    this runs as plain batched XLA and the kernel's slot loop needs no
    O(n) state at all. The adjacency row writes replicate
    `_window_mixed_lane` op-for-op (incl. the self-loop aliasing order of
    the two DEL_EDGE row writes)."""
    n = state.assignment.shape[0]
    w = vs.shape[0]
    ets = jnp.where(vs >= 0, ets, EVENT_PAD)
    is_add = ets == EVENT_ADD
    is_dv = ets == EVENT_DEL_VERTEX
    is_de = ets == EVENT_DEL_EDGE
    safe_vs = jnp.where(vs >= 0, vs, 0)
    label0 = jnp.where(state.present, state.assignment, -1)
    rows_add = jnp.where(is_add[:, None], rows, -1)

    def step(carry, i):
        adj, present, last_touch = carry
        v = safe_vs[i]
        row = rows[i]
        add_i, dv_i, de_i = is_add[i], is_dv[i], is_de[i]
        own_row = adj[v]
        u = row[0]
        safe_u = jnp.maximum(u, 0)

        fresh = add_i & ~present[v]
        was = dv_i & present[v]
        in_adj = jnp.any(own_row == u) & (u >= 0)
        exists = de_i & present[v] & present[safe_u] & in_adj

        src_row = jnp.where(add_i, rows_add[i], jnp.where(dv_i, own_row, -1))
        src_safe = jnp.maximum(src_row, 0)
        src_lbl = jnp.where(src_row >= 0, label0[src_safe], -1)
        touch = jnp.where(src_row >= 0, last_touch[src_safe], -1)

        ev = jnp.stack([
            ets[i], v, fresh.astype(jnp.int32), was.astype(jnp.int32),
            exists.astype(jnp.int32), label0[v], last_touch[v],
            label0[safe_u], last_touch[safe_u],
        ])

        # presence / touch updates (add and del_vertex touch the subject)
        tgt = jnp.where(add_i | dv_i, v, n)
        present = present.at[tgt].set(add_i, mode="drop")
        last_touch = last_touch.at[tgt].set(i, mode="drop")

        # faithful adjacency row writes (windowed._window_mixed_lane)
        row_v_de = jnp.where((own_row == u) & (u >= 0), -1, own_row)
        w1_val = jnp.where(add_i, row, jnp.where(de_i, row_v_de, own_row))
        w1_tgt = jnp.where(fresh | de_i, v, n)
        adj = adj.at[w1_tgt].set(w1_val, mode="drop")
        row_u = adj[safe_u]                   # after write 1 (self-loops)
        row_u_de = jnp.where((row_u == v) & (u >= 0), -1, row_u)
        adj = adj.at[jnp.where(de_i, safe_u, n)].set(row_u_de, mode="drop")
        return (adj, present, last_touch), (ev, src_lbl, touch)

    last_touch0 = jnp.full((n,), -1, jnp.int32)
    (adj, _, last_touch), (ev, src_lbl, touch) = jax.lax.scan(
        step, (state.adj, state.present, last_touch0),
        jnp.arange(w, dtype=jnp.int32))
    return WindowPrep(ev, src_lbl, touch, label0, last_touch, adj)


def _fused_lane(
    state: PartitionState,
    ets, vs, rows, t0,
    knobs,               # (7,) f32 (transition.Knobs field order)
    flags,               # (2,) int32 [index into a policy tuple, do_scale]
    *,
    policy: str | tuple[str, ...],
    balance_guard: str,
    autoscaling: bool,
    dynamic: bool,
    interpret: bool | None = None,
    variant: str = "pallas",
) -> PartitionState:
    """One mixed window through prep → rand table → kernel → apply."""
    n = state.assignment.shape[0]
    w = vs.shape[0]
    k_max = state.edge_load.shape[0]
    with jax.named_scope("window.prep"):
        prep = _prepare_window(state, ets, vs, rows)
        rand_tab = tx.rand_index_table(state.key, t0, w, k_max)
        scalars = jnp.stack([
            state.num_partitions, state.total_edges, state.cut_edges,
            state.denied_scaleout, state.scale_events])
    call = fused_window_choose if variant == "pallas" else \
        fused_window_choose_ref
    kwargs = {} if variant == "ref" else {"interpret": interpret}
    with jax.named_scope("window.slot_loop"):
        w_label, _psel, remap, active, loads, cut_matrix, scal = call(
            prep.ev, prep.src_lbl, prep.touch, rand_tab,
            state.active, state.edge_load, state.vertex_count,
            state.cut_matrix, scalars, knobs, flags, n=n, policy=policy,
            balance_guard=balance_guard, autoscaling=autoscaling,
            dynamic=dynamic, **kwargs)

    # apply: rebuild the journal from the window-local decisions — two
    # O(n) gathers, no scatter ordering to get wrong
    with jax.named_scope("window.apply"):
        lbl_touched = w_label[jnp.clip(prep.last_touch, 0, w - 1)]
        lbl_kept = jnp.where(prep.label0 >= 0,
                             remap[jnp.maximum(prep.label0, 0)], -1)
        label_final = jnp.where(prep.last_touch >= 0, lbl_touched,
                                lbl_kept)
        present = label_final >= 0
    return state._replace(
        assignment=label_final, present=present, adj=prep.adj,
        active=active != 0, edge_load=loads[0], vertex_count=loads[1],
        num_partitions=scal[fk.SCAL_NP], total_edges=scal[fk.SCAL_TOTAL],
        cut_edges=scal[fk.SCAL_CUT], denied_scaleout=scal[fk.SCAL_DENIED],
        scale_events=scal[fk.SCAL_SCALE], cut_matrix=cut_matrix,
    )


def _run_window_mixed_fused(
    state: PartitionState,
    ets, vs, rows, t0,
    *,
    policy: str,
    cfg: EngineConfig,
    interpret: bool | None = None,
    variant: str = "pallas",
) -> PartitionState:
    """Drop-in for `windowed._run_window_mixed` under the static knob,
    bit-identical to the faithful engine. Unjitted body —
    `run_window_mixed_fused` is the plain jitted binding;
    repro.api.partitioner re-jits it with the carried state donated."""
    check_row_width(state, rows)
    n = state.assignment.shape[0]
    kn = tx.make_knobs(cfg, n)
    knobs = jnp.stack([jnp.float32(x) for x in kn])
    flags = jnp.array([0, 1], jnp.int32)
    return _fused_lane(
        state, ets, vs, rows, t0, knobs, flags,
        policy=policy, balance_guard=cfg.balance_guard,
        autoscaling=policy == "sdp" and cfg.autoscale,
        dynamic=False, interpret=interpret, variant=variant)


run_window_mixed_fused = functools.partial(
    jax.jit, static_argnames=("policy", "cfg", "interpret", "variant"),
)(_run_window_mixed_fused)


def sweep_window_mixed_fused(
    states: PartitionState,   # stacked (L, ...) lanes
    kns: tx.Knobs,            # stacked (L,) f32 knobs
    policy_idx: jax.Array,    # (L,) int32 into POLICIES order
    autoscale: jax.Array,     # (L,) bool (cfg.autoscale per lane)
    ets, vs, rows,            # (L, T) per-lane — or (T,) shared — events
    t0,
    *,
    balance_guard: str,
    autoscale_mode: str,      # "off" | "dynamic"
    policies: tuple[str, ...],  # every lane's policy is one of these
    window: int = 256,
    shared_stream: bool = False,
    interpret: bool | None = None,
    variant: str = "pallas",
) -> PartitionState:
    """Drop-in for `windowed.sweep_window_mixed` with the slot loop fused
    into the Pallas chooser: per lane, lax.scan over windows whose body
    dynamic-slices the next window and runs `_fused_lane` under the traced
    knob (policy via lax.switch over ``policies`` on a kernel scalar,
    autoscale via the per-lane runtime gate). The vmap over lanes lifts
    the pallas_call's batch to a grid axis — one kernel launch per window
    step covering all lanes. Same contract as the XLA version: T a
    multiple of ``window``, ``shared_stream`` broadcast semantics, not
    jitted here (the sweep runtime wraps it)."""
    check_row_width(states, rows)
    dynamic = autoscale_mode == "dynamic"
    sdp_idx = tx.POLICY_INDEX["sdp"]

    def one_lane(state, kn, pidx, auto, ets_l, vs_l, rows_l):
        do = auto & (pidx == sdp_idx)
        knobs = jnp.stack([jnp.float32(x) for x in kn])
        gate = do if dynamic else jnp.bool_(True)
        branch = sum(jnp.where(pidx == tx.POLICY_INDEX[p], j, 0)
                     for j, p in enumerate(policies))
        flags = jnp.stack([branch, gate.astype(jnp.int32)])
        n_windows = ets_l.shape[0] // window

        def body(s, wdx):
            i0 = wdx * window
            s = _fused_lane(
                s,
                jax.lax.dynamic_slice_in_dim(ets_l, i0, window),
                jax.lax.dynamic_slice_in_dim(vs_l, i0, window),
                jax.lax.dynamic_slice_in_dim(rows_l, i0, window),
                t0 + i0, knobs, flags,
                policy=policies, balance_guard=balance_guard,
                autoscaling=dynamic, dynamic=dynamic,
                interpret=interpret, variant=variant)
            return s, None

        s, _ = jax.lax.scan(body, state,
                            jnp.arange(n_windows, dtype=jnp.int32))
        return s

    ax = None if shared_stream else 0
    if shared_stream:
        lanes = states.assignment.shape[0]
        ets = jnp.broadcast_to(ets, (lanes,) + ets.shape)
        vs = jnp.broadcast_to(vs, (lanes,) + vs.shape)
    return jax.vmap(one_lane, in_axes=(0, 0, 0, 0, 0, 0, ax))(
        states, kns, policy_idx, autoscale, ets, vs, rows)
