"""Oracle for the fused window chooser: the SAME slot step
(`fused_chooser.make_slot_step`) driven by a plain lax.scan instead of the
Pallas fori_loop, with no pallas_call anywhere. It is the slot loop both
sessions run (`repro.runtime.factored_window`), and it triangulates
failures — kernel vs ref isolates Pallas lowering issues, ref vs the
faithful `_window_mixed_lane` isolates touch-table prep issues."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import transition as tx
from repro.core.windowed import SmallState
from repro.kernels.fused_chooser.fused_chooser import (
    SCAL_CUT, SCAL_DENIED, SCAL_NP, SCAL_SCALE, SCAL_TOTAL, gather_col_take,
    make_slot_step,
)


def fused_window_choose_ref(ev, src_lbl, touch, rand_tab, active, edge_load,
                            vertex_count, cut_matrix, scalars, knobs, flags,
                            *, n: int, policy: str | tuple[str, ...],
                            balance_guard: str, autoscaling: bool,
                            dynamic: bool):
    """Same signature and outputs as `fused_chooser.fused_window_choose`
    (minus ``interpret``), pure XLA: neighbour labels are gathered with
    a plain O(D) take where the kernel needs an O(W·D) one-hot."""
    w = ev.shape[0]
    k_max = int(rand_tab.shape[-1])
    kn = tx.Knobs(*(knobs[j] for j in range(7)))
    choose = tx.make_table_chooser(balance_guard, policy, flags[0])
    do_scale = flags[1] != 0
    slot_step = make_slot_step(k_max=k_max, n=n, choose=choose,
                               autoscaling=autoscaling, dynamic=dynamic,
                               gather_col=gather_col_take)

    def row(x):
        return x.reshape(1, k_max)

    small0 = SmallState(
        active=row(active != 0), edge_load=row(edge_load),
        vertex_count=row(vertex_count),
        num_partitions=scalars[SCAL_NP], total_edges=scalars[SCAL_TOTAL],
        cut_edges=scalars[SCAL_CUT], denied_scaleout=scalars[SCAL_DENIED],
        scale_events=scalars[SCAL_SCALE], cut_matrix=cut_matrix)
    w_label0 = jnp.full((w, 1), -1, jnp.int32)
    remap0 = jnp.arange(k_max, dtype=jnp.int32)[:, None]

    def body(carry, xs):
        small, w_label, remap = carry
        i, ev_i, src_i, touch_i, rand_i = xs
        small, w_label, remap, p = slot_step(
            small, w_label, remap, kn, do_scale, i,
            [ev_i[c] for c in range(ev_i.shape[0])],
            src_i[None, :], touch_i[None, :], rand_i[None, :])
        return (small, w_label, remap), p

    (small, w_label, remap), psel = jax.lax.scan(
        body, (small0, w_label0, remap0),
        (jnp.arange(w, dtype=jnp.int32), ev, src_lbl, touch, rand_tab))
    return (w_label[:, 0], psel, remap[:, 0],
            small.active[0].astype(jnp.int32),
            jnp.concatenate([small.edge_load, small.vertex_count]),
            small.cut_matrix,
            jnp.stack([small.num_partitions, small.total_edges,
                       small.cut_edges, small.denied_scaleout,
                       small.scale_events]))
