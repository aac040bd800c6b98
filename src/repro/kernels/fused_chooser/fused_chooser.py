"""Fused Pallas window chooser: gather→score→argmax→commit in ONE kernel.

The mixed-window engine (`repro.core.windowed._window_mixed_lane`) walks a
window of W events with a lax.scan whose carry includes a dense O(n) label
journal: every slot re-gathers neighbour labels through HBM, scores them,
runs the policy chooser, and scatters the decision back into the journal.
That per-slot HBM round-trip is the remaining hot-path cost (ROADMAP "fuse
the chooser").

This kernel keeps the whole window resident in VMEM instead. The insight
making that possible: *which* labels a slot can observe is choice-
independent — presence, adjacency, freshness, and "which earlier slot last
touched this vertex" depend only on the event structure, never on the
partition decisions. So a cheap choice-independent prep pass
(`ops._prepare_window`, batched XLA outside the kernel) reduces the O(n)
journal to three window-local **touch tables**:

* ``src_lbl[i, d]`` — the *committed* label of slot i's d-th score-source
  vertex (−1 if absent/padded), gathered once;
* ``touch[i, d]`` — the index of the last earlier slot that re-labelled
  that vertex (−1 if none): the in-window label is then
  ``w_label[touch[i, d]]``, a one-hot (W, D) select in VMEM;
* a per-slot scalar row (event code, subject vertex, fresh/was/exists
  flags, the subject's and deletion-peer's committed label + touch index).

Inside the kernel a ``fori_loop`` carries only O(K) counters plus the
(W, 1) ``w_label`` decision column and a (K, 1) ``remap`` composing
scale-in merges over committed labels — the score tile never leaves VMEM,
and the policy chooser is the *same table* as the engines
(``transition.make_table_chooser``: the ``make_chooser`` bodies with the
single random draw precomputed by ``transition.rand_index_table``). Both
knob bindings exist: static policy string (single runs) and a traced
index via lax.switch on a kernel scalar over the lanes' policies (sweep
lanes, vmapped over the pallas_call). Every index is in the mask form
Mosaic lowers (see ``make_slot_step``); the kernel compiles for a TPU for
every policy but fennel, whose float power Mosaic cannot lower, and for
a switch over any set of them without fennel (tests/test_v5e_compile.py).

Bit-identity with `run_stream` (all policies, autoscale on, interleaved
churn) is the contract — tests/test_fused_chooser.py; `ref.py` is the
same slot step driven by lax.scan for kernel-vs-oracle triangulation.
Interpret-mode policy and histogram masking come from
`repro.kernels.common` (shared with `partition_affinity`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import transition as tx
from repro.core.windowed import SmallState
from repro.graph.stream import EVENT_ADD, EVENT_DEL_VERTEX
from repro.kernels.common import resolve_interpret, row_histogram

# per-slot scalar row layout (ops._prepare_window packs, the kernel unpacks)
EV_ET, EV_V, EV_FRESH, EV_WAS, EV_EXISTS = 0, 1, 2, 3, 4
EV_VLBL, EV_VTOUCH, EV_ULBL, EV_UTOUCH = 5, 6, 7, 8
EV_COLS = 9

# scalar-counter vector layout (window in/out)
SCAL_NP, SCAL_TOTAL, SCAL_CUT, SCAL_DENIED, SCAL_SCALE = 0, 1, 2, 3, 4
SCAL_N = 5


def _scale_in_touch(small: SmallState, w_label, remap, kn):
    """transition.scale_in on the touch-table representation: the trigger
    and counter merges are shared with the faithful engine
    (`windowed._scale_in_journal`); only the relabel target differs — the
    (W, 1) in-window decisions and the (K, 1) committed-label remap instead
    of the O(n) journal. Future slots' w_label entries are −1 and src is
    always a valid partition id, so the select cannot corrupt them."""
    src, dst, do = tx.scale_in_trigger(small, kn)

    def merge(x):
        return tx.set_at(tx.add_at(x, dst, tx.take_at(x, src)), src, 0)

    def migrate(args):
        sm, wl, rm = args
        sm2 = sm._replace(
            edge_load=merge(sm.edge_load),
            vertex_count=merge(sm.vertex_count),
            active=sm.active & (tx.lane_iota(sm.active) != src),
            num_partitions=sm.num_partitions - 1,
            cut_edges=sm.cut_edges - tx.matrix_at(sm.cut_matrix, src, dst),
            cut_matrix=tx.merge_cut_matrix(sm.cut_matrix, src, dst),
            scale_events=sm.scale_events + 1,
        )
        return sm2, jnp.where(wl == src, dst, wl), jnp.where(rm == src, dst, rm)

    return _cond(do, migrate, (small, w_label, remap))


def _select(pred, a, b):
    """``jnp.where(pred, a, b)`` for a scalar ``pred``; bool vectors go
    through int32, since Mosaic selects no i1 vector on a scalar."""
    if a.dtype == jnp.bool_ and a.ndim:
        return jnp.where(pred, a.astype(jnp.int32), b.astype(jnp.int32)) != 0
    return jnp.where(pred, a, b)


def _cond(pred, f, operand):
    """``lax.cond(pred, f, identity, operand)`` with bool vector leaves
    carried as int32 across the branch (see ``_select``)."""
    def to_i32(x):
        return x.astype(jnp.int32) if x.dtype == jnp.bool_ and x.ndim else x

    bools = jax.tree_util.tree_map(
        lambda x: x.dtype == jnp.bool_ and x.ndim > 0, operand)

    def from_i32(tree):
        return jax.tree_util.tree_map(
            lambda x, b: x != 0 if b else x, tree, bools)

    out = jax.lax.cond(
        pred,
        lambda t: jax.tree_util.tree_map(to_i32, f(from_i32(t))),
        lambda t: t,
        jax.tree_util.tree_map(to_i32, operand))
    return from_i32(out)


def gather_col_one_hot(col, idx):
    """``col[idx, 0]`` for a (M, 1) column and a (1, D) row of in-range
    indices, as a (1, D) row: a one-hot (M, D) compare and a sum over M —
    the form Mosaic lowers, O(M·D) per call."""
    hit = jax.lax.broadcasted_iota(jnp.int32, (col.shape[0],) + idx.shape[1:],
                                   0) == idx
    return jnp.sum(jnp.where(hit, col, 0), axis=0, keepdims=True)


def gather_col_take(col, idx):
    """``gather_col_one_hot`` as a plain O(D) XLA gather; equal to it on
    in-range indices, which are the only ones the slot step passes."""
    return jnp.take(col[:, 0], idx)


def _col_at(col, i):
    """``col[i, 0]`` of a (M, 1) column as a scalar."""
    hit = jax.lax.broadcasted_iota(jnp.int32, col.shape, 0) == i
    return jnp.sum(jnp.where(hit, col, 0))


def make_slot_step(*, k_max: int, n: int, choose, autoscaling: bool,
                   dynamic: bool, gather_col):
    """One window slot on the touch-table representation.

    ``choose`` is a ``transition.make_table_chooser`` chooser. The body
    mirrors ``windowed._window_mixed_lane``'s scan step op-for-op (same
    cores, same masked counter merge, same scale gates) with the journal
    gathers replaced by touch-table lookups — the seam both the Pallas
    kernel and the `ref.py` lax.scan oracle drive, so they cannot drift.

    Shapes are the ones Mosaic lowers: K-vectors of ``small`` are (1, K)
    rows, ``w_label`` is a (W, 1) column and ``remap`` a (K, 1) column;
    ``ev`` is the slot's EV_COLS scalars, ``src_lbl``/``touch`` are (1, D)
    rows and ``rand_row`` a (1, K) row. Every index is a mask compare but
    the (1, D) neighbour-label lookups, which go through ``gather_col``:
    the kernel passes ``gather_col_one_hot``, the XLA oracle (which the
    sharded session runs every window) ``gather_col_take``.
    """

    def slot_step(small: SmallState, w_label, remap, kn, do_scale, i,
                  ev, src_lbl, touch, rand_row):
        et = ev[EV_ET]
        v = ev[EV_V]
        fresh = ev[EV_FRESH] != 0
        was = ev[EV_WAS] != 0
        exists = ev[EV_EXISTS] != 0
        add_i = et == EVENT_ADD
        dv_i = et == EVENT_DEL_VERTEX

        # --- scale-out before the ADD decision (faithful apply_add) ---
        if autoscaling:
            gate = add_i if not dynamic else add_i & do_scale
            scaled = tx.scale_out(small, kn)
            small = jax.tree_util.tree_map(
                lambda a, b: _select(gate, a, b), scaled, small)

        # --- effective neighbour labels + affinity (paper Eq. 1): the
        # last in-window decision if touched, else the committed label
        # pushed through the scale-in remap ---
        in_win = gather_col(w_label, jnp.maximum(touch, 0))
        committed = jnp.where(src_lbl >= 0,
                              gather_col(remap, jnp.maximum(src_lbl, 0)), -1)
        eff = jnp.where(touch >= 0, in_win, committed)        # (1, D)
        sc_eff, deg_eff = row_histogram(eff, k_max)
        ridx = tx.take_at(rand_row, jnp.maximum(small.num_partitions, 1) - 1)
        p = choose(small, sc_eff, deg_eff, v, ridx, kn, n)
        d_add = jnp.where(fresh, deg_eff, 0)
        sc_a = jnp.where(fresh, sc_eff, 0)

        def label_at(lbl_c, touch_i):
            committed = jnp.where(lbl_c >= 0,
                                  _col_at(remap, jnp.maximum(lbl_c, 0)), -1)
            return jnp.where(touch_i >= 0,
                             _col_at(w_label, jnp.maximum(touch_i, 0)),
                             committed)

        # --- DEL_VERTEX / DEL_EDGE quantities (faithful cores) ---
        vl = label_at(ev[EV_VLBL], ev[EV_VTOUCH])
        ul = label_at(ev[EV_ULBL], ev[EV_UTOUCH])
        p_dv = jnp.maximum(vl, 0)
        d_dv = jnp.where(was, deg_eff, 0)
        sc_d = jnp.where(was, sc_eff, 0)
        pu = jnp.maximum(ul, 0)
        e = exists.astype(jnp.int32)
        cutdec = (exists & (p_dv != pu)).astype(jnp.int32)

        # --- masked counter merge (one event type per slot ⇒ exact) ---
        el = tx.add_at(tx.add_at(small.edge_load + sc_a - sc_d, p, d_add),
                       p_dv, -d_dv)
        cm = tx.matrix_add_cross(
            tx.matrix_add_cross(small.cut_matrix, p, sc_a), p_dv, -sc_d)
        small = small._replace(
            vertex_count=tx.add_at(
                tx.add_at(small.vertex_count, p, fresh.astype(jnp.int32)),
                p_dv, -was.astype(jnp.int32)),
            edge_load=tx.add_at(tx.add_at(el, p_dv, -e), pu, -e),
            total_edges=small.total_edges + d_add - d_dv - e,
            cut_edges=(small.cut_edges + (d_add - tx.take_at(sc_a, p))
                       - (d_dv - tx.take_at(sc_d, p_dv)) - cutdec),
            cut_matrix=tx.matrix_add_at(tx.matrix_add_at(cm, p_dv, pu, -e),
                                        pu, p_dv, -e),
        )

        # --- record the slot's label decision (add/dv touch the subject;
        # del_edge leaves labels unchanged, so its slot stays -1 and no
        # later touch index ever points at it) ---
        new_lbl = jnp.where(add_i, jnp.where(fresh, p, vl),
                            jnp.where(dv_i, -1, vl))
        w_iota = jax.lax.broadcasted_iota(jnp.int32, w_label.shape, 0)
        w_label = jnp.where(w_iota == i,
                            jnp.where(add_i | dv_i, new_lbl, -1), w_label)

        # --- scale-in after DEL_VERTEX (faithful apply_del_vertex) ---
        if autoscaling:
            gate_dv = dv_i if not dynamic else dv_i & do_scale
            small, w_label, remap = _cond(
                gate_dv, lambda args: _scale_in_touch(*args, kn),
                (small, w_label, remap))
        return small, w_label, remap, p

    return slot_step


def _read_small(active_ref, loads_ref, cutmat_ref, scal_ref) -> SmallState:
    return SmallState(
        active=active_ref[...] != 0,
        edge_load=loads_ref[0:1, :],
        vertex_count=loads_ref[1:2, :],
        num_partitions=scal_ref[0, SCAL_NP],
        total_edges=scal_ref[0, SCAL_TOTAL],
        cut_edges=scal_ref[0, SCAL_CUT],
        denied_scaleout=scal_ref[0, SCAL_DENIED],
        scale_events=scal_ref[0, SCAL_SCALE],
        cut_matrix=cutmat_ref[...],
    )


def _fused_kernel(ev_ref, scal_ref, knobs_ref, flags_ref, srclbl_ref,
                  touch_ref, rand_ref, active_ref, loads_ref, cutmat_ref,
                  wlabel_ref, psel_ref, remap_ref, active_o_ref, loads_o_ref,
                  cutmat_o_ref, scal_o_ref, *, w: int, k_max: int, n: int,
                  policy: str | tuple[str, ...], balance_guard: str,
                  autoscaling: bool, dynamic: bool):
    """Single-program kernel: the per-slot scalars, counters, knobs and
    flags live in SMEM as (1, X) rows, the (W, D) tables and O(K²)
    counters in VMEM; a fori_loop walks the W slots carrying only
    O(K)+O(W) values. Policy dispatch is static (trace-time table pick)
    when ``policy`` is a string, else a lax.switch over those policies on
    the ``flags`` scalar."""
    kn = tx.Knobs(*(knobs_ref[0, j] for j in range(7)))
    choose = tx.make_table_chooser(balance_guard, policy, flags_ref[0, 0])
    do_scale = flags_ref[0, 1] != 0
    slot_step = make_slot_step(k_max=k_max, n=n, choose=choose,
                               autoscaling=autoscaling, dynamic=dynamic,
                               gather_col=gather_col_one_hot)

    # the loop carries ``active`` as int32: Mosaic carries no i1 vector
    small0 = _read_small(active_ref, loads_ref, cutmat_ref, scal_ref)
    small0 = small0._replace(active=small0.active.astype(jnp.int32))
    w_label0 = jnp.full((w, 1), -1, jnp.int32)
    remap0 = jax.lax.broadcasted_iota(jnp.int32, (k_max, 1), 0)

    def body(i, carry):
        small, w_label, remap = carry
        ev = [ev_ref[0, i * EV_COLS + c] for c in range(EV_COLS)]
        row = pl.ds(i, 1)
        small, w_label, remap, p = slot_step(
            small._replace(active=small.active != 0), w_label, remap, kn,
            do_scale, i, ev,
            srclbl_ref[row, :], touch_ref[row, :], rand_ref[row, :])
        psel_ref[0, i] = p
        return small._replace(active=small.active.astype(jnp.int32)), \
            w_label, remap

    small, w_label, remap = jax.lax.fori_loop(
        0, w, body, (small0, w_label0, remap0))

    wlabel_ref[...] = w_label
    remap_ref[...] = remap
    active_o_ref[...] = small.active
    loads_o_ref[0:1, :] = small.edge_load
    loads_o_ref[1:2, :] = small.vertex_count
    cutmat_o_ref[...] = small.cut_matrix
    for c, x in enumerate((small.num_partitions, small.total_edges,
                           small.cut_edges, small.denied_scaleout,
                           small.scale_events)):
        scal_o_ref[0, c] = x


def _batch_axis_first(call):
    """``call`` with a vmap rule that hands it its batched operands with
    the batch axis first. A batched Mosaic block must keep the array's
    last two dims, and plain vmap leaves the batch axis where the producer
    put it: a lax.scan's stacked output carries it second."""
    f = jax.custom_batching.custom_vmap(call)

    @f.def_vmap
    def rule(axis_size, in_batched, *args):
        out = jax.vmap(call, in_axes=[0 if b else None for b in in_batched])(
            *args)
        return out, jax.tree_util.tree_map(lambda _: True, out)
    return f


def fused_window_choose(ev, src_lbl, touch, rand_tab, active, edge_load,
                        vertex_count, cut_matrix, scalars, knobs, flags, *,
                        n: int, policy: str | tuple[str, ...],
                        balance_guard: str, autoscaling: bool, dynamic: bool,
                        interpret: bool | None = None):
    """One pallas_call for one whole window.

    Inputs are the prep tables (`ops._prepare_window`), the per-slot random
    table (`transition.rand_index_table`), and the O(K) counter slice;
    outputs are (w_label, p_sel, remap, active, loads, cut_matrix,
    scalars), 1-D where the slice is. ``policy`` is one policy, or a tuple
    of them that ``flags[0]`` indexes (`transition.make_table_chooser`).
    ``interpret=None`` defers to ``repro.kernels.common.default_interpret``.
    vmap over this call is the sweep's lane batching (pallas_call lifts the
    batch to a grid axis). Every operand is at least 2-D, so a lane's
    block keeps the array's last two dims, as Mosaic requires of a
    batched block.
    """
    interpret = resolve_interpret(interpret)
    policies = (policy,) if isinstance(policy, str) else policy
    if not interpret and "fennel" in policies:
        raise NotImplementedError(
            "the fused chooser cannot compile fennel for a TPU (Mosaic "
            "lowers no float power); run fennel without the kernel")
    w = ev.shape[0]
    k_max = int(rand_tab.shape[-1])
    kernel = functools.partial(
        _fused_kernel, w=w, k_max=k_max, n=n, policy=policy,
        balance_guard=balance_guard, autoscaling=autoscaling, dynamic=dynamic)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    i32 = jnp.int32
    call = pl.pallas_call(
        kernel,
        in_specs=[smem] * 4 + [vmem] * 6,
        out_specs=[vmem, smem] + [vmem] * 4 + [smem],
        out_shape=[
            jax.ShapeDtypeStruct((w, 1), i32),              # w_label
            jax.ShapeDtypeStruct((1, w), i32),              # p_sel
            jax.ShapeDtypeStruct((k_max, 1), i32),          # remap
            jax.ShapeDtypeStruct((1, k_max), i32),          # active
            jax.ShapeDtypeStruct((2, k_max), i32),          # loads
            jax.ShapeDtypeStruct((k_max, k_max), i32),      # cut_matrix
            jax.ShapeDtypeStruct((1, SCAL_N), i32),         # scalars
        ],
        interpret=interpret,
    )
    w_label, psel, remap, active, loads, cut_matrix, scal = \
        _batch_axis_first(call)(
            ev.reshape(1, -1), scalars[None], knobs[None], flags[None],
            src_lbl, touch, rand_tab, active.astype(i32).reshape(1, k_max),
            jnp.stack([edge_load, vertex_count]), cut_matrix)
    return (w_label[:, 0], psel[0], remap[:, 0], active[0], loads,
            cut_matrix, scal[0])
