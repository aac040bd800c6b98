"""Windowed streaming engine — the beyond-paper TPU optimisation.

The paper assigns strictly one vertex at a time; that serialises the hot
affinity gather and starves the VPU/MXU. This engine processes a *window*
of W arriving events per device step:

  1. committed scores (W, K) — one batched gather+one-hot-histogram against
     the state as of window start (the `partition_affinity` Pallas kernel);
  2. a tiny sequential fixup scan over the W decisions that adds the
     intra-window neighbour contributions and maintains the load /
     cut / scaling counters.

The decomposition is exact: for window vertex i, the faithful engine's
score is (committed neighbours) + (window neighbours whose presence or
label changed before i), which is precisely scores_committed[i] plus the
fixup increment. RNG uses the same fold_in(base_key, global_event_index)
scheme, so the windowed engine is **bit-identical** to repro.core.engine —
verified by tests — while the O(W·max_deg·K) work is batched.

Two window kernels exist:

* ``run_window_adds`` — ADD-only windows, carries just the O(K) counter
  slice through the fixup scan (the fast path for insert-only streams);
* ``run_window_mixed`` — arbitrary interleavings of ADD / DEL_VERTEX /
  DEL_EDGE processed entirely on device, scoring every slot from a dense
  per-vertex label journal (the sessions run the factored form of the
  same window instead, ``repro.runtime.factored_window``, whose slot
  loop carries only K-sized state; this one stays as the sweeps' lane
  body and the oracle the tests hold it to); the transition semantics
  come verbatim from
  ``repro.core.transition`` (the single definition site shared with the
  faithful engine and the sweep runtime). ``sweep_window_mixed`` is the
  same kernel under the *traced* knob (lax.switch policy, per-lane
  autoscale gate), vmapped across sweep lanes — how the ``Sweep``
  builder's ``.windowed()`` mode (repro.api.sweep; ``run_sweep`` is its
  deprecation shim) inherits the window speedup. Under ``use_kernel``
  both kinds swap in their Pallas form: ``partition_affinity`` for the
  batched committed scores here, and ``repro.kernels.fused_chooser`` for
  the entire mixed-window slot loop (plus its lane-batched
  ``sweep_window_mixed_fused`` twin) — same bit-identity contract.

The host driver slices the stream into *fixed* windows — deletion events
no longer split windows, so delete-heavy churn streams (the paper's
real-time regime) keep the batched fast path instead of degenerating into
window-size-1 chunks.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import transition as tx
from repro.core.config import EngineConfig
from repro.core.geometry import Geometry, check_row_width, resolve_geometry
from repro.core.state import PartitionState, init_state
from repro.graph.stream import (
    EVENT_ADD, EVENT_DEL_EDGE, EVENT_DEL_VERTEX, EVENT_PAD, VertexStream,
    normalize_rows,
)


class SmallState(NamedTuple):
    """The O(K)/O(K²) slice of PartitionState carried through the fixup scan."""
    active: jax.Array
    edge_load: jax.Array
    vertex_count: jax.Array
    num_partitions: jax.Array
    total_edges: jax.Array
    cut_edges: jax.Array
    denied_scaleout: jax.Array
    scale_events: jax.Array
    cut_matrix: jax.Array    # (k_max, k_max) pairwise cuts (see transition)


def _small(state: PartitionState) -> SmallState:
    return SmallState(
        state.active, state.edge_load, state.vertex_count, state.num_partitions,
        state.total_edges, state.cut_edges, state.denied_scaleout,
        state.scale_events, state.cut_matrix,
    )


def committed_scores(state: PartitionState, rows: jax.Array):
    """Batched paper-Eq.-1 affinity of W vertices vs the committed state.

    This is the reference (jnp) path; `repro.kernels.partition_affinity`
    provides the Pallas TPU kernel with identical semantics (swap via
    ``use_kernel=True`` in run_stream_windowed). Tolerates committed
    states with deletion holes: absent neighbours (present=False) score
    as empty regardless of their stale assignment entries.
    """
    valid = rows >= 0
    safe = jnp.where(valid, rows, 0)
    nb_present = valid & state.present[safe]
    nb_assign = jnp.where(nb_present, state.assignment[safe], -1)
    k_max = state.edge_load.shape[0]
    onehot = nb_assign[..., None] == jnp.arange(k_max, dtype=jnp.int32)
    scores = jnp.sum(onehot, axis=1, dtype=jnp.int32)   # (W, K)
    deg = jnp.sum(nb_present, axis=1, dtype=jnp.int32)  # (W,)
    return scores, deg


def _run_window_adds(
    state: PartitionState,
    vs: jax.Array,       # (W,) vertex ids (-1 pad allowed)
    rows: jax.Array,     # (W, max_deg)
    t0: jax.Array,       # () global event index of window start
    *,
    policy: str,
    cfg: EngineConfig,
    score_fn=None,
) -> PartitionState:
    """Process one ADD-only window. Bit-identical to the faithful engine.

    Unjitted body — ``run_window_adds`` is the plain jitted binding; the
    session facade (repro.api.partitioner) re-jits it with the carried
    state donated."""
    check_row_width(state, rows)
    n = state.assignment.shape[0]
    w = vs.shape[0]
    k_max = state.edge_load.shape[0]
    base_key = state.key
    kn = tx.make_knobs(cfg, n)
    choose = tx.make_chooser(cfg.balance_guard, policy)
    with jax.named_scope("window.prep"):
        is_add = vs >= 0
        safe_vs = jnp.where(is_add, vs, 0)

        sfn = score_fn or committed_scores
        scores_c, deg_c = sfn(state, rows)                   # (W,K), (W,)
        # window-position lookup for intra-window neighbour fixup (pad
        # slots scatter to sentinel row n so they never clobber a vertex)
        pos_of = jnp.full((n + 1,), -1, jnp.int32).at[
            jnp.where(is_add, vs, n)
        ].set(jnp.arange(w, dtype=jnp.int32))
        valid = rows >= 0
        win_pos = jnp.where(valid, pos_of[jnp.where(valid, rows, 0)],
                            -1)                              # (W,D)

    def fix_step(carry, i):
        small, w_assign = carry
        key = jax.random.fold_in(base_key, t0 + i)
        if policy == "sdp" and cfg.autoscale:
            # faithful engine scales out per ADD event only (pads skip it)
            small = jax.lax.cond(
                is_add[i], lambda s: tx.scale_out(s, kn), lambda s: s, small
            )
        intra = (win_pos[i] >= 0) & (win_pos[i] < i)
        nb_wa = jnp.where(intra, w_assign[jnp.where(intra, win_pos[i], 0)], -1)
        onehot = nb_wa[:, None] == jnp.arange(k_max, dtype=jnp.int32)
        sc = scores_c[i] + jnp.sum(onehot, axis=0, dtype=jnp.int32)
        deg = deg_c[i] + jnp.sum(intra, dtype=jnp.int32)
        p = choose(small, sc, deg, safe_vs[i], key, kn, n)
        do = is_add[i] & ~state.present[safe_vs[i]]
        d = jnp.where(do, deg, 0)
        scm = jnp.where(do, sc, 0)
        small = small._replace(
            vertex_count=small.vertex_count.at[p].add(do.astype(jnp.int32)),
            edge_load=(small.edge_load + scm).at[p].add(d),
            total_edges=small.total_edges + d,
            cut_edges=small.cut_edges + d - scm[p],
            cut_matrix=small.cut_matrix.at[p, :].add(scm).at[:, p].add(scm),
        )
        w_assign = w_assign.at[i].set(jnp.where(do, p, w_assign[i]))
        return (small, w_assign), None

    small0 = _small(state)
    w_assign0 = jnp.full((w,), -1, jnp.int32)
    with jax.named_scope("window.slot_loop"):
        (small, w_assign), _ = jax.lax.scan(
            fix_step, (small0, w_assign0), jnp.arange(w, dtype=jnp.int32)
        )

    with jax.named_scope("window.apply"):
        fresh = is_add & (w_assign >= 0)
        # scatter target: non-fresh slots (pads, duplicate adds) go to the
        # out-of-bounds row n, which jax scatters DROP — they must not
        # write, or a pad could clobber a real vertex's slot (duplicate
        # .set indices have undefined winners).
        tgt = jnp.where(fresh, safe_vs, n)
        assignment = state.assignment.at[tgt].set(
            jnp.where(fresh, w_assign, -1), mode="drop")
        present = state.present.at[tgt].set(True, mode="drop")
        adj = state.adj.at[tgt].set(
            jnp.where(fresh[:, None], rows, -1), mode="drop")
    return state._replace(
        assignment=assignment, present=present, adj=adj,
        active=small.active, edge_load=small.edge_load,
        vertex_count=small.vertex_count, num_partitions=small.num_partitions,
        total_edges=small.total_edges, cut_edges=small.cut_edges,
        denied_scaleout=small.denied_scaleout, scale_events=small.scale_events,
        cut_matrix=small.cut_matrix,
    )


run_window_adds = functools.partial(
    jax.jit, static_argnames=("policy", "cfg", "score_fn"))(_run_window_adds)


def _scale_in_journal(small: SmallState, label_now, kn):
    """transition.scale_in (§4.2.3, Eqs. 6–8) on the window-local journal
    representation (label_now ≡ assignment, label_now >= 0 ≡ present).
    The trigger is shared with the faithful engine so the two cannot
    drift; only the migrate body differs (journal instead of state). The
    merged cut comes from the incremental pairwise matrix — the journal's
    slot step maintains it with the same row scatters as the faithful
    cores, so no adjacency pass (the old per-window recompute_cut) is
    needed here either."""
    src, dst, do = tx.scale_in_trigger(small, kn)

    def migrate(args):
        sm, ln = args
        ln2 = jnp.where(ln == src, dst, ln)
        sm2 = sm._replace(
            edge_load=sm.edge_load.at[dst].add(
                sm.edge_load[src]).at[src].set(0),
            vertex_count=sm.vertex_count.at[dst].add(
                sm.vertex_count[src]).at[src].set(0),
            active=sm.active.at[src].set(False),
            num_partitions=sm.num_partitions - 1,
            cut_edges=sm.cut_edges - sm.cut_matrix[src, dst],
            cut_matrix=tx.merge_cut_matrix(sm.cut_matrix, src, dst),
            scale_events=sm.scale_events + 1,
        )
        return sm2, ln2

    return jax.lax.cond(do, migrate, lambda a: a, (small, label_now))


def _window_mixed_lane(
    state: PartitionState,
    ets: jax.Array,      # (W,) event types (EVENT_* codes)
    vs: jax.Array,       # (W,) subject vertex ids (-1 pad allowed)
    rows: jax.Array,     # (W, max_deg) neighbour rows / deletion operands
    t0: jax.Array,       # () global event index of window start
    kn: tx.Knobs,        # static (python floats) or traced (f32 scalars)
    *,
    choose,              # transition.make_chooser under either knob
    autoscaling: bool,   # trace-level gate: is any scaling code traced?
    do_scale=None,       # traced bool (sweep lanes) or None (static engine)
) -> PartitionState:
    """One mixed window for one lane — the shared body under either knob.

    Because deletions (and earlier adds) inside the window change
    neighbour presence mid-window, scores are read from a dense
    per-vertex label journal ``label_now`` (≡ present ? assignment : -1,
    maintained with one O(1) scatter per slot) rather than from the
    window-start snapshot: the snapshot's batched committed scores would
    cancel exactly against the per-slot correction term, so hoisting them
    here would be pure redundant work (the ADD-only kernel above keeps
    the hoist — there the intra-window fixup is genuinely sparse). Any
    add → delete → re-add chain inside the window is tracked exactly.

    The fixup scan carries only (counters, label_now, adj), and no
    conditional touches the O(n·max_deg) adjacency as a *written*
    operand: one slot holds exactly one event type, so each branch's
    effect (transition.commit_add / del_vertex_core / del_edge_core
    semantics) is computed as a masked O(max_deg·K) contribution to the
    counters plus at most two row-level drop-mode scatters into adj.
    XLA conditionals copy every large operand a branch writes — which is
    what made per-event processing of this state memory-bound in the
    first place. The scale-in cond below no longer touches adj at all:
    the merged cut is read off the incremental O(K²) cut_matrix (no
    per-event recompute pass), and the cond writes only the small
    counters plus the O(n) label journal — same per-delete cost as the
    faithful engine's assignment rewrite, negligible next to adj.

    ``do_scale`` extends the trace-time ``autoscaling`` gate to a
    per-lane runtime gate for the sweep: a runtime-False lane masks the
    scale-out select and scale-in cond to no-ops, bit-identical to a
    statically non-autoscaling trace.
    """
    n = state.assignment.shape[0]
    w = vs.shape[0]
    k_max = state.edge_load.shape[0]
    base_key = state.key

    with jax.named_scope("window.prep"):
        ets = jnp.where(vs >= 0, ets, EVENT_PAD)
        is_add = ets == EVENT_ADD
        is_dv = ets == EVENT_DEL_VERTEX
        is_de = ets == EVENT_DEL_EDGE
        safe_vs = jnp.where(vs >= 0, vs, 0)

        rows_add = jnp.where(is_add[:, None], rows, -1)

        arange_k = jnp.arange(k_max, dtype=jnp.int32)

    def onehot_sum(labels):
        return jnp.sum(labels[:, None] == arange_k, axis=0, dtype=jnp.int32)

    def step(carry, i):
        small, label_now, adj = carry
        key = jax.random.fold_in(base_key, t0 + i)
        v = safe_vs[i]
        row = rows[i]
        add_i, dv_i, de_i = is_add[i], is_dv[i], is_de[i]
        own_row = adj[v]                          # (D,) pre-event adjacency
        u = row[0]
        safe_u = jnp.maximum(u, 0)

        # --- ADD: corrected scores + policy choice (faithful apply_add) ---
        if autoscaling:
            gate = add_i if do_scale is None else add_i & do_scale
            scaled = tx.scale_out(small, kn)
            small = jax.tree_util.tree_map(
                lambda a, b: jnp.where(gate, a, b), scaled, small)
        # one journal gather + histogram serves the whole slot: an ADD
        # scores its event row, a DEL_VERTEX its own adjacency row, and a
        # slot holds exactly one event type, so the sources never overlap.
        # (p is still computed for non-ADD slots but only reaches zero-
        # masked scatters — the values written are exact either way.)
        src_row = jnp.where(add_i, rows_add[i], jnp.where(dv_i, own_row, -1))
        eff = jnp.where(src_row >= 0, label_now[jnp.maximum(src_row, 0)], -1)
        sc_eff = onehot_sum(eff)
        deg_eff = jnp.sum(eff >= 0, dtype=jnp.int32)
        p = choose(small, sc_eff, deg_eff, v, key, kn, n)
        fresh = add_i & (label_now[v] < 0)
        d_add = jnp.where(fresh, deg_eff, 0)
        sc_a = jnp.where(fresh, sc_eff, 0)

        # --- DEL_VERTEX (faithful del_vertex_core over the journal) ---
        was = dv_i & (label_now[v] >= 0)
        p_dv = jnp.maximum(label_now[v], 0)
        d_dv = jnp.where(was, deg_eff, 0)
        sc_d = jnp.where(was, sc_eff, 0)

        # --- DEL_EDGE (faithful _del_edge_core over the journal) ---
        in_adj = jnp.any(own_row == u) & (u >= 0)
        exists = de_i & (label_now[v] >= 0) & (label_now[safe_u] >= 0) & in_adj
        pv = jnp.maximum(label_now[v], 0)
        pu = jnp.maximum(label_now[safe_u], 0)
        e = exists.astype(jnp.int32)
        cutdec = (exists & (pv != pu)).astype(jnp.int32)

        # --- masked counter merge (one event type per slot ⇒ exact) ---
        small = small._replace(
            vertex_count=(small.vertex_count
                          .at[p].add(fresh.astype(jnp.int32))
                          .at[p_dv].add(-was.astype(jnp.int32))),
            edge_load=((small.edge_load + sc_a - sc_d)
                       .at[p].add(d_add).at[p_dv].add(-d_dv)
                       .at[pv].add(-e).at[pu].add(-e)),
            total_edges=small.total_edges + d_add - d_dv - e,
            cut_edges=(small.cut_edges + (d_add - sc_a[p])
                       - (d_dv - sc_d[p_dv]) - cutdec),
            cut_matrix=(small.cut_matrix
                        .at[p, :].add(sc_a).at[:, p].add(sc_a)
                        .at[p_dv, :].add(-sc_d).at[:, p_dv].add(-sc_d)
                        .at[pv, pu].add(-e).at[pu, pv].add(-e)),
        )

        # --- row-level array updates (never a full-array select) ---
        new_lbl = jnp.where(add_i, jnp.where(fresh, p, label_now[v]),
                            jnp.where(dv_i, -1, label_now[v]))
        label_now = label_now.at[jnp.where(vs[i] >= 0, v, n)].set(
            new_lbl, mode="drop")
        row_v_de = jnp.where((own_row == u) & (u >= 0), -1, own_row)
        w1_val = jnp.where(add_i, row, jnp.where(de_i, row_v_de, own_row))
        w1_tgt = jnp.where(fresh | de_i, v, n)
        adj = adj.at[w1_tgt].set(w1_val, mode="drop")
        row_u = adj[safe_u]                       # after write 1 (self-loops)
        row_u_de = jnp.where((row_u == v) & (u >= 0), -1, row_u)
        adj = adj.at[jnp.where(de_i, safe_u, n)].set(row_u_de, mode="drop")

        # --- scale-in after DEL_VERTEX (faithful apply_del_vertex) ---
        if autoscaling:
            gate_dv = dv_i if do_scale is None else dv_i & do_scale
            small, label_now = jax.lax.cond(
                gate_dv,
                lambda sm, ln: _scale_in_journal(sm, ln, kn),
                lambda sm, ln: (sm, ln),
                small, label_now,
            )
        return (small, label_now, adj), None

    small0 = _small(state)
    with jax.named_scope("window.prep"):
        label_now0 = jnp.where(state.present, state.assignment, -1)
    with jax.named_scope("window.slot_loop"):
        (small, label_now, adj), _ = jax.lax.scan(
            step, (small0, label_now0, state.adj),
            jnp.arange(w, dtype=jnp.int32),
        )
    with jax.named_scope("window.apply"):
        present = label_now >= 0
    return state._replace(
        assignment=label_now, present=present, adj=adj,
        active=small.active, edge_load=small.edge_load,
        vertex_count=small.vertex_count, num_partitions=small.num_partitions,
        total_edges=small.total_edges, cut_edges=small.cut_edges,
        denied_scaleout=small.denied_scaleout, scale_events=small.scale_events,
        cut_matrix=small.cut_matrix,
    )


def _run_window_mixed(
    state: PartitionState,
    ets: jax.Array,      # (W,) event types (EVENT_* codes)
    vs: jax.Array,       # (W,) subject vertex ids (-1 pad allowed)
    rows: jax.Array,     # (W, max_deg) neighbour rows / deletion operands
    t0: jax.Array,       # () global event index of window start
    *,
    policy: str,
    cfg: EngineConfig,
) -> PartitionState:
    """Process one window of interleaved ADD / DEL_VERTEX / DEL_EDGE events
    entirely on device, bit-identical to the faithful engine — the
    static-knob entry over ``_window_mixed_lane`` (see its docstring for
    the journal decomposition). Unjitted body — ``run_window_mixed`` is
    the plain jitted binding; repro.api.partitioner re-jits it with the
    carried state donated."""
    check_row_width(state, rows)
    n = state.assignment.shape[0]
    return _window_mixed_lane(
        state, ets, vs, rows, t0, tx.make_knobs(cfg, n),
        choose=tx.make_chooser(cfg.balance_guard, policy),
        autoscaling=policy == "sdp" and cfg.autoscale,
    )


run_window_mixed = functools.partial(
    jax.jit, static_argnames=("policy", "cfg"))(_run_window_mixed)


def sweep_window_mixed(
    states: PartitionState,   # stacked (L, ...) lanes
    kns: tx.Knobs,            # stacked (L,) f32 knobs
    policy_idx: jax.Array,    # (L,) int32 into POLICIES order
    autoscale: jax.Array,     # (L,) bool (cfg.autoscale per lane)
    ets: jax.Array,           # (L, T) per-lane — or (T,) shared — events
    vs: jax.Array,            # (L, T) / (T,)
    rows: jax.Array,          # (L, T, max_deg) / (T, max_deg)
    t0: jax.Array,            # () global event index of the first event
    *,
    balance_guard: str,
    autoscale_mode: str,      # "off" | "dynamic"
    window: int = 256,
    shared_stream: bool = False,
) -> PartitionState:
    """A whole stream of mixed windows across all sweep lanes, in ONE
    device program: per lane, a lax.scan over windows whose body
    dynamic-slices the next ``window`` events and runs
    ``_window_mixed_lane`` under the *traced* knob (policy via
    lax.switch, autoscale via a per-lane runtime gate) — no host loop,
    no per-window re-dispatch. T must be a multiple of ``window``
    (right-pad with EVENT_PAD). Sweeps thereby ride the same window
    kernel as single runs, bit-identical per lane. ``shared_stream``
    takes one (T,)-shaped stream for every lane: the O(T·max_deg)
    neighbour tensor rides vmap in_axes=None unbatched while the O(T)
    etype/vertex columns are broadcast lane-wise on device (see
    repro.runtime.sweep._scan_lanes for why the vertex index must be
    lane-batched). Not jitted here — the sweep runtime wraps it in jit
    or shard_map+jit (repro.runtime.sweep)."""
    check_row_width(states, rows)
    dynamic = autoscale_mode == "dynamic"
    sdp_idx = tx.POLICY_INDEX["sdp"]

    def one_lane(state, kn, pidx, auto, ets_l, vs_l, rows_l):
        do = auto & (pidx == sdp_idx)
        choose = tx.make_chooser(balance_guard, policy_idx=pidx)
        n_windows = ets_l.shape[0] // window

        def body(s, w):
            i0 = w * window
            s = _window_mixed_lane(
                s,
                jax.lax.dynamic_slice_in_dim(ets_l, i0, window),
                jax.lax.dynamic_slice_in_dim(vs_l, i0, window),
                jax.lax.dynamic_slice_in_dim(rows_l, i0, window),
                t0 + i0, kn,
                choose=choose, autoscaling=dynamic,
                do_scale=do if dynamic else None,
            )
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


def _pad_to(arr, length, fill):
    pad = length - arr.shape[0]
    if pad <= 0:
        return jnp.asarray(arr)
    shape = (pad,) + arr.shape[1:]
    return jnp.concatenate([jnp.asarray(arr), jnp.full(shape, fill, arr.dtype)])


def run_stream_windowed(
    stream: VertexStream,
    *,
    policy: str = "sdp",
    cfg: EngineConfig | None = None,
    seed: int = 0,
    window: int = 256,
    use_kernel: bool = False,
    geometry: Geometry | None = None,
) -> PartitionState:
    """Host driver: fixed windows of ``window`` events per device step.

    Pure-ADD windows take the small-carry ``run_window_adds`` kernel;
    windows containing deletions take ``run_window_mixed``, which scores
    from its label journal instead. Both are bit-identical to
    ``run_stream``. (The pre-mixed legacy driver that split windows at
    deletion boundaries lives on only as the fig10 benchmark baseline,
    benchmarks/fig10_time.py.) ``geometry`` overrides the state
    allocation exactly as in ``run_stream`` — growth is a semantics
    no-op (repro.core.geometry).

    ``use_kernel=True`` routes BOTH window kinds through Pallas: pure-ADD
    windows score their batched committed affinities with the
    ``partition_affinity`` kernel, and mixed windows run the whole
    slot loop — gather, score, policy argmax, commit — inside the fused
    chooser kernel (``repro.kernels.fused_chooser``), still bit-identical.
    Interpret mode resolves per backend at ONE site
    (``repro.kernels.common.default_interpret``). The per-event scan
    engine (``repro.core.engine.run_stream``) remains pure XLA — it is
    the faithful reference the kernels are verified against; session
    callers see the split in ``Partitioner.metrics()["windows"]``, a
    count of program calls per path.
    """
    cfg = cfg or EngineConfig()
    geom = resolve_geometry(stream, cfg, geometry)
    state = init_state(geom.n, geom.max_deg, geom.k_max, cfg.k_init, seed)
    if use_kernel:
        from repro.kernels.fused_chooser.ops import run_window_mixed_fused
        from repro.kernels.partition_affinity.ops import scores_for_state
        score_fn = scores_for_state
        mixed_fn = run_window_mixed_fused
    else:
        score_fn = None
        mixed_fn = run_window_mixed

    et = np.asarray(stream.etype)
    vx = jnp.asarray(stream.vertex)
    nb = jnp.asarray(normalize_rows(stream.nbrs, geom.max_deg))

    T = stream.num_events
    for t in range(0, T, window):
        end = min(t + window, T)
        ets_w = _pad_to(et[t:end], window, EVENT_PAD)
        vs_w = _pad_to(vx[t:end], window, -1)
        rows_w = _pad_to(nb[t:end], window, -1)
        if np.all(et[t:end] == EVENT_ADD):
            state = run_window_adds(
                state, vs_w, rows_w, jnp.int32(t),
                policy=policy, cfg=cfg, score_fn=score_fn,
            )
        else:
            state = mixed_fn(
                state, ets_w, vs_w, rows_w, jnp.int32(t),
                policy=policy, cfg=cfg,
            )
    return state
