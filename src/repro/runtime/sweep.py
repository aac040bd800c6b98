"""Device-sharded sweep runtime: many (policy × seed × config × stream)
lanes in ONE device program, lanes sharded across devices.

The figure benchmarks previously looped over policies/configs on the host,
re-dispatching the whole stream scan per run. Here every run becomes a
*lane*: `PartitionState` is stacked along a leading axis, each lane
carries its OWN (T,)-padded event stream (per-seed stream permutations
and per-lane churn mixes), and the lane axis is sharded across local
devices with ``shard_map`` over the 1-D "lanes" mesh
(repro.launch.mesh.make_lane_mesh) — vmap inside each shard, the lane
axis padded to a multiple of the device count, with a plain vmapped
host-fallback when only one device exists (or ``shard=False``).

Static-vs-traced knob parameterization
--------------------------------------
Both sweep kernels are the *traced-knob* instantiation of the unified
transition layer (repro.core.transition): the numeric knobs
(`transition.Knobs`) enter as stacked f32 scalars, the policy as a
traced int32 dispatched with ``lax.switch`` over the full policy table,
and per-lane autoscale as a traced bool gating the scale hooks. The
single-run engines bind the same functions with *static* knobs (Python
string/bool), so XLA specializes one program per config there and one
program for ALL lanes here. Because ``transition.make_knobs`` performs
every host-side arithmetic step before values enter the graph, the two
bindings execute bit-identical f32 ops.

The bit-identity contract: every lane — vmapped or sharded, per-event
(``engine="scan"``) or mixed-window (``engine="windowed"``), whole-stream
or chunked — produces exactly the same `PartitionState` (and, for the
scan engine, `EventTrace`) as ``repro.core.engine.run_stream`` on that
lane's stream with that lane's (policy, cfg, seed). Enforced by
tests/test_sweep.py and tests/test_sweep_sharded.py (the latter also
under ``XLA_FLAGS=--xla_force_host_platform_device_count=4`` in CI).

Static requirements across lanes: identical ``k_max`` (array shapes)
and ``balance_guard`` (trace-time branch). ``k_init``, ``seed``,
``autoscale``, the stream, and all numeric knobs vary freely per lane —
including the stream *geometry*: per-lane streams of unequal ``n`` /
``max_deg`` are padded to the union geometry (componentwise max) before
stacking, and since absent-padded rows are inert in every transition
core (repro.core.geometry), each lane stays bit-identical to
``run_stream(stream, geometry=union)`` — which equals the lane's
own-geometry ``run_stream`` for every policy except LDG (whose capacity
knob reads the live ``n``).
"""
from __future__ import annotations

import functools
import warnings
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import transition as tx
from repro.core.config import EngineConfig
from repro.core.geometry import Geometry, check_row_width
from repro.core.state import PartitionState, init_state
from repro.core.windowed import sweep_window_mixed
from repro.graph.stream import EVENT_PAD, VertexStream, normalize_rows
from repro.kernels.fused_chooser.ops import sweep_window_mixed_fused
from repro.launch.mesh import make_lane_mesh


class SweepRun(NamedTuple):
    """One lane of a sweep: a policy/config/seed triple over its stream."""
    policy: str = "sdp"
    cfg: EngineConfig = EngineConfig()
    seed: int = 0


class SweepResult(NamedTuple):
    policy: str
    cfg: EngineConfig
    seed: int
    state: PartitionState
    trace: tx.EventTrace | None   # None for engine="windowed"


def _scan_lanes(
    states: PartitionState,   # stacked (L, ...) lanes
    kns: tx.Knobs,            # stacked (L,) f32 knobs
    policy_idx: jax.Array,    # (L,) int32 into POLICIES order
    autoscale: jax.Array,     # (L,) bool (cfg.autoscale per lane)
    etype: jax.Array,         # (L, T) per-lane — or (T,) shared — streams
    vertex: jax.Array,        # (L, T) / (T,)
    nbrs: jax.Array,          # (L, T, max_deg) / (T, max_deg)
    t0: jax.Array,            # () global index of first event
    *,
    balance_guard: str,
    autoscale_mode: str,      # "off" | "dynamic"
    shared_stream: bool = False,
    cut_fn=None,              # scale-in cut override (fig12 baseline only)
):
    """One chunk of every lane's stream through the per-event scan
    (transition.scan_events under the traced knob); resumable. Lanes use
    the fused masked step: under vmap a branch switch would compute every
    branch and select over the full state per event (see
    transition.make_masked_step). ``shared_stream`` takes one (T,)-shaped
    stream for every lane: the O(T·max_deg) neighbour tensor — the bulk
    of the stream — rides vmap in_axes=None unbatched, while the O(T)
    etype/vertex columns are broadcast lane-wise on device (an unbatched
    *vertex* index against lane-batched state lowers to a pathologically
    slow batched gather/scatter on CPU; unbatched neighbour *rows* are
    fine and they are where the memory is)."""
    check_row_width(states, nbrs)
    n = states.assignment.shape[1]
    sdp_idx = tx.POLICY_INDEX["sdp"]
    dynamic = autoscale_mode == "dynamic"

    def one_lane(state, kn, pidx, auto, et, vx, nb):
        do_scale = auto & (pidx == sdp_idx)
        step = tx.make_masked_step(
            kn, n, balance_guard=balance_guard, policy_idx=pidx,
            autoscale=do_scale if dynamic else False, cut_fn=cut_fn,
        )
        return tx.scan_events(step, state, et, vx, nb, t0)

    ax = None if shared_stream else 0
    if shared_stream:
        L = states.assignment.shape[0]
        etype = jnp.broadcast_to(etype, (L,) + etype.shape)
        vertex = jnp.broadcast_to(vertex, (L,) + vertex.shape)
    return jax.vmap(one_lane, in_axes=(0, 0, 0, 0, 0, 0, ax))(
        states, kns, policy_idx, autoscale, etype, vertex, nbrs)


_STATICS = ("balance_guard", "autoscale_mode", "shared_stream")

# public resumable kernel (no donation — callers may keep their states).
# ``cut_fn`` is static (a trace-time function: None = incremental
# cut_matrix scale-in; benchmarks/fig12 passes the from-scratch baseline)
sweep_events = jax.jit(_scan_lanes, static_argnames=_STATICS + ("cut_fn",))

# run_sweep's private kernels donate the stacked states: the chunk driver
# immediately rebinds them, and donation lets XLA reuse the
# (L, n, max_deg) adjacency buffers (incl. the stacked (L, K, K)
# cut_matrix) instead of copying per re-dispatch
_JITTED = {
    "scan": jax.jit(_scan_lanes, static_argnames=_STATICS + ("cut_fn",),
                    donate_argnums=(0,)),
    "windowed": jax.jit(sweep_window_mixed,
                        static_argnames=_STATICS + ("window",),
                        donate_argnums=(0,)),
    # the fused Pallas chooser lane-batched across lanes (vmap over
    # pallas_call); bit-identical to "windowed", selected by use_kernel
    "windowed_fused": jax.jit(
        sweep_window_mixed_fused,
        static_argnames=_STATICS + ("policies", "window", "interpret",
                                    "variant"),
        donate_argnums=(0,)),
}
_KERNELS = {
    "scan": _scan_lanes,
    "windowed": sweep_window_mixed,
    "windowed_fused": sweep_window_mixed_fused,
}


@functools.lru_cache(maxsize=None)
def _sharded_kernel(kind: str, n_devices: int, balance_guard: str,
                    autoscale_mode: str, shared_stream: bool, window: int,
                    policies: tuple[str, ...]):
    """jit(shard_map(vmapped kernel)) over the "lanes" mesh. Lanes are
    embarrassingly parallel: every lane-stacked operand shards on axis 0,
    the scalar t0 (and the stream, when shared) is replicated, and no
    collective is emitted."""
    mesh = make_lane_mesh(n_devices)
    lanes = P("lanes")
    stream_spec = P() if shared_stream else lanes
    kw = {"balance_guard": balance_guard, "autoscale_mode": autoscale_mode,
          "shared_stream": shared_stream}
    if kind in ("windowed", "windowed_fused"):
        kw["window"] = window
    if kind == "windowed_fused":
        kw["policies"] = policies
    base = functools.partial(_KERNELS[kind], **kw)
    return jax.jit(jax.shard_map(
        base, mesh=mesh,
        in_specs=(lanes,) * 4 + (stream_spec,) * 3 + (P(),),
        out_specs=lanes,
        # pallas_call has no varying-axes rule; lanes emit no collectives,
        # so the checker is vacuous for every kind
        check_vma=kind != "windowed_fused"),
        donate_argnums=(0,))


def _stack(trees):
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)


def _unstack(tree, i):
    return jax.tree_util.tree_map(lambda x: x[i], tree)


def _pad_lanes(tree, pad: int):
    """Pad the leading lane axis by replicating lane 0 (sliced off after)."""
    if pad == 0:
        return tree
    return jax.tree_util.tree_map(
        lambda x: jnp.concatenate(
            [x, jnp.broadcast_to(x[:1], (pad,) + x.shape[1:])]), tree)


def _stack_streams(streams: Sequence[VertexStream], length: int):
    """Per-lane streams → dense (L, T[, D]) event tensors, EVENT_PAD-padded
    on the right so shorter lanes no-op through the shared scan. Lanes of
    heterogeneous geometry (unequal ``n`` / ``max_deg``) are padded to
    the union geometry before stacking — absent-padded rows are inert,
    so each lane stays bit-identical to ``run_stream`` at the union
    geometry (see repro.core.geometry; the per-lane union is returned as
    the (n, max_deg) the caller sizes the stacked states at)."""
    geom = functools.reduce(
        Geometry.union, (Geometry(s.n, s.max_deg) for s in streams))
    L = len(streams)
    et = np.full((L, length), EVENT_PAD, np.int32)
    vx = np.full((L, length), -1, np.int32)
    nb = np.full((L, length, geom.max_deg), -1, np.int32)
    for i, s in enumerate(streams):
        t = s.num_events
        et[i, :t] = s.etype
        vx[i, :t] = s.vertex
        nb[i, :t] = normalize_rows(s.nbrs, geom.max_deg)
    return jnp.asarray(et), jnp.asarray(vx), jnp.asarray(nb), geom.n, \
        geom.max_deg


def _shared_stream_arrays(s: VertexStream, length: int):
    """One shared stream → dense (T[, D]) tensors broadcast to every lane
    at trace time (no L-fold materialization)."""
    et = np.full(length, EVENT_PAD, np.int32)
    vx = np.full(length, -1, np.int32)
    nb = np.full((length, s.max_deg), -1, np.int32)
    t = s.num_events
    et[:t] = s.etype
    vx[:t] = s.vertex
    nb[:t] = s.nbrs
    return jnp.asarray(et), jnp.asarray(vx), jnp.asarray(nb)


def _execute_sweep(
    stream: VertexStream | Sequence[VertexStream],
    runs: Sequence[SweepRun | tuple],
    *,
    chunk: int | None = None,
    engine: str = "scan",
    window: int = 256,
    shard: bool | None = None,
    use_kernel: bool = False,
    rebalance: dict | None = None,
    shard_vertices: bool = False,
) -> list[SweepResult]:
    """Executor behind ``repro.api.Sweep`` (and the deprecated
    ``run_sweep`` shim): every (policy, cfg, seed) lane in one device
    program, each lane's result bit-identical to ``run_stream`` with the
    same arguments on that lane's stream. Lane-compatibility validation
    (shared k_max/balance_guard, chunk×engine rules, stream pairing)
    happens in ``Sweep._validate`` — go through the builder.

    stream: one shared ``VertexStream`` (broadcast to every lane at trace
      time — never materialized L-fold), or a sequence of per-lane
      streams (one per run; may differ in length, order, churn mix, and
      geometry — they are right-padded with no-op events to a common T
      and padded to the union (n, max_deg) geometry, see
      ``_stack_streams``).
    chunk: re-dispatch the scan engine every ``chunk`` events (resumable,
      bounds step count per program); traces are concatenated along the
      event axis.
    engine: "scan" — faithful per-event scan, returns per-event traces;
      "windowed" — the mixed-event window kernel vmapped across lanes
      (PR 1's batched-window speedup), returns ``trace=None``.
    shard: shard the lane axis across local devices with shard_map
      (padding lanes to a multiple of the device count). ``None`` = auto:
      shard iff more than one device exists; ``False`` forces the
      single-device vmapped path; ``True`` forces shard_map even on one
      device (exercises the padding path).
    use_kernel: with ``engine="windowed"``, run the lanes through the
      fused Pallas chooser (repro.kernels.fused_chooser) instead of the
      XLA window kernel — bit-identical by contract, interpret mode off
      TPU. Ignored for ``engine="scan"`` (the scan is the semantic
      reference and stays XLA; ``Sweep._validate`` rejects the combo).
    rebalance: ``{"m", "every", "passes", "slack", "lanes"}`` from
      ``Sweep.rebalance()`` — after every full ``every`` processed
      events the stream is segmented and one vmapped
      ``repro.rebalance.rebalance_state`` runs over the stacked lanes
      (per-lane ``max_cap``, shared slack, ``lanes`` as a traced
      enabled mask — excluded lanes pass through bit-identically).
    """
    runs = [r if isinstance(r, SweepRun) else SweepRun(*r) for r in runs]
    if not runs:
        return []
    shared = not isinstance(stream, (list, tuple))
    streams = [stream] * len(runs) if shared else list(stream)
    if shard_vertices:
        # vertex-parallel lanes: each lane is one vertex-sharded session
        # over the WHOLE local mesh (repro.runtime.shard_session), so
        # lanes run sequentially — the device budget is spent on n, not
        # L. No union-geometry stacking: every lane runs (and is checked)
        # at its own stream's geometry, bit-identical to run_stream.
        from repro.runtime.shard_session import run_stream_sharded
        return [
            SweepResult(r.policy, r.cfg, r.seed,
                        run_stream_sharded(s, policy=r.policy, cfg=r.cfg,
                                           seed=r.seed, window=window),
                        None)
            for r, s in zip(runs, streams)
        ]
    cfg0 = runs[0].cfg
    autoscale_mode = (
        "dynamic"
        if any(r.cfg.autoscale and r.policy == "sdp" for r in runs)
        else "off"
    )

    kind = ("windowed_fused" if engine == "windowed" and use_kernel
            else engine)
    # the fused kernel switches over these alone (POLICIES order), so a
    # policy no lane runs is never compiled — on a TPU, fennel only
    # refuses when a lane runs it
    policies = tuple(p for p in tx.POLICIES if any(r.policy == p
                                                   for r in runs))

    L = len(runs)
    lens = [s.num_events for s in streams]
    T_ev = max(lens)   # real events: the rebalance cadence counts these
    T = T_ev
    if engine == "windowed":
        T = ((T + window - 1) // window) * window
    if shared:
        et, vx, nb = _shared_stream_arrays(streams[0], T)
        n, max_deg = streams[0].n, streams[0].max_deg
    else:
        et, vx, nb, n, max_deg = _stack_streams(streams, T)
    states = _stack([
        init_state(n, max_deg, cfg0.k_max, r.cfg.k_init, r.seed) for r in runs
    ])
    kns = _stack([tx.knobs_arrays(r.cfg, n) for r in runs])
    pidx = jnp.asarray([tx.POLICY_INDEX[r.policy] for r in runs], jnp.int32)
    auto = jnp.asarray([r.cfg.autoscale for r in runs], bool)

    ndev = jax.device_count()
    use_shard = (ndev > 1) if shard is None else bool(shard)
    if use_shard:
        lane_pad = (-L) % ndev
        states, kns, pidx, auto = (
            _pad_lanes(x, lane_pad) for x in (states, kns, pidx, auto))
        if not shared:
            et, vx, nb = (_pad_lanes(x, lane_pad) for x in (et, vx, nb))
        call = _sharded_kernel(kind, ndev, cfg0.balance_guard,
                               autoscale_mode, shared, window, policies)
    else:
        kw = {"balance_guard": cfg0.balance_guard,
              "autoscale_mode": autoscale_mode, "shared_stream": shared}
        if engine == "windowed":
            kw["window"] = window
        if kind == "windowed_fused":
            kw["policies"] = policies
        call = functools.partial(_JITTED[kind], **kw)

    def ev_slice(a, sl):
        return a[sl] if shared else a[:, sl]

    reb_apply = None
    if rebalance is not None:
        from repro.rebalance import lane_rebalance
        reb_every = int(rebalance["every"])
        Lp = int(states.assignment.shape[0])  # incl. shard padding
        en = np.zeros(Lp, bool)
        if rebalance["lanes"] is None:
            en[:L] = True   # pad lanes stay gated off (sliced away after)
        else:
            en[np.asarray(rebalance["lanes"], int)] = True
        enabled = jnp.asarray(en)
        caps = np.asarray([float(r.cfg.max_cap) for r in runs], np.float32)
        caps = np.concatenate(
            [caps, np.full(Lp - L, caps[0] if L else 1.0, np.float32)])
        maxcap, slack = jnp.asarray(caps), jnp.float32(rebalance["slack"])
        reb_call = lane_rebalance(min(int(rebalance["m"]), n),
                                  int(rebalance["passes"]))

        def reb_apply(states, t):
            states, _ = reb_call(states, jnp.int32(t), slack, maxcap,
                                 enabled)
            return states

    if engine == "windowed":
        if reb_apply is None:
            # the window loop runs on device (lax.scan over windows
            # inside the kernel) — one dispatch for the whole stream,
            # like "scan"
            states = call(states, kns, pidx, auto, et, vx, nb,
                          jnp.int32(0))
        else:
            # segment the stream at the rebalance cadence (a window
            # multiple, validated) and rebalance after each full segment
            t = 0
            while t < T:
                end = min(t + reb_every, T)
                sl = slice(t, end)
                states = call(states, kns, pidx, auto, ev_slice(et, sl),
                              ev_slice(vx, sl), ev_slice(nb, sl),
                              jnp.int32(t))
                # a segment padded past the real stream end is not a full
                # cadence interval (T is window-rounded; the scan engine
                # never sees the padding, and the engines must agree)
                if end - t == reb_every and end <= T_ev:
                    states = reb_apply(states, end)
                t = end
        trace = None
    elif chunk is None and rebalance is None:
        states, trace = call(states, kns, pidx, auto, et, vx, nb,
                             jnp.int32(0))
    else:
        step = chunk if chunk is not None else T
        traces = []
        t = 0
        while t < T:
            end = min(t + step, T)
            if rebalance is not None:
                # dispatch boundaries never cross a cadence boundary, so
                # the pass lands exactly between the right two events
                end = min(end, (t // reb_every + 1) * reb_every)
            sl = slice(t, end)
            states, tr = call(states, kns, pidx, auto, ev_slice(et, sl),
                              ev_slice(vx, sl), ev_slice(nb, sl),
                              jnp.int32(t))
            traces.append(tr)
            t = end
            if rebalance is not None and t % reb_every == 0:
                states = reb_apply(states, t)
        trace = tx.EventTrace(*(
            jnp.concatenate([getattr(tr, f) for tr in traces], axis=1)
            for f in tx.EventTrace._fields
        ))

    return [
        SweepResult(
            r.policy, r.cfg, r.seed, _unstack(states, i),
            None if trace is None else jax.tree_util.tree_map(
                lambda x: x[:lens[i]], _unstack(trace, i)),
        )
        for i, r in enumerate(runs)
    ]


def run_sweep(
    stream: VertexStream | Sequence[VertexStream],
    runs: Sequence[SweepRun | tuple],
    *,
    chunk: int | None = None,
    engine: str = "scan",
    window: int = 256,
    shard: bool | None = None,
) -> list[SweepResult]:
    """Deprecated batch entry — use the fluent builder::

        from repro.api import Sweep
        Sweep(stream).lanes(runs).windowed(256).sharded().run()

    This shim builds the equivalent ``Sweep`` (so the builder's lane
    validation applies — e.g. ``engine="windowed"`` with ``chunk`` now
    raises instead of silently ignoring the chunk) and runs it.
    """
    warnings.warn(
        "run_sweep is deprecated: use repro.api.Sweep — e.g. "
        "Sweep(stream).lanes(runs).windowed().sharded().run()",
        DeprecationWarning, stacklevel=2)
    from repro.api.sweep import Sweep
    sw = Sweep(stream).lanes(runs)
    if engine == "windowed":
        sw.windowed(window)
    elif engine != "scan":
        raise ValueError(
            f"unknown engine {engine!r} (expected 'scan' or 'windowed')")
    if chunk is not None:
        sw.chunked(chunk)
    if shard is not None:
        sw.sharded(shard)
    return sw.run()
