"""Unified event-transition kernel — the ONE definition site for SDP's
add/delete transitions, policy dispatch, and autoscale hooks.

Three engine paths consume these functions:

  (a) the faithful per-event scan (``repro.core.engine.run_events``),
  (b) the mixed-window journal kernel (``repro.core.windowed``), and
  (c) the vmapped/sharded sweep lanes (``repro.runtime.sweep``).

They differ only in *how the knobs enter the graph*, which is the
static-vs-traced parameterization this module provides:

* **static knob** (``make_transition`` → ``EventTransition.step``) —
  ``policy`` is a Python string and ``autoscale`` a Python bool. The
  chooser is picked at trace time, the scale hooks are traced
  unconditionally (``scale_out``/``scale_in`` are internally
  data-dependent no-ops when their trigger is false), and the event
  branches dispatch through ``lax.switch`` — right for a *scalar* event
  type, which executes exactly one branch. This is the single-run
  engine path: one compiled program per (policy, cfg).

* **traced knob** (``make_masked_step``) — ``policy_idx`` is a traced
  int32 dispatched with ``lax.switch`` over the full policy table
  (``make_chooser``), and ``autoscale`` a traced bool gating the scale
  effects per lane. The event branches are fused into one branch-free
  masked step, because under ``vmap`` a *batched* switch/cond computes
  every branch and selects. This is the sweep path: one compiled
  program for ALL (policy × seed × config) lanes.

The bit-identity contract: because ``make_knobs`` performs every
host-side arithmetic step (products, percentages) before values enter
the graph, a traced f32 knob executes exactly the same f32 ops as the
trace-time-constant knob, and ``lax.cond(pred, f, identity)`` evaluates
``f`` with the same operands as an unconditional ``f`` when ``pred`` is
true. Every lane of every path is therefore bit-identical to the
faithful engine — enforced by tests/test_sdp_engine.py,
tests/test_mixed_window.py, tests/test_sweep.py and
tests/test_sweep_sharded.py.

The pairwise cut-matrix invariant
---------------------------------
``PartitionState.cut_matrix`` is a (k_max, k_max) int32 symmetric matrix of
pairwise cut counts, maintained incrementally by every transition core:

* ``cut_matrix[p, q]`` (p != q) = number of *present* edges between
  partitions p and q; ``cut_matrix[p, p]`` counts each internal edge of p
  twice (once per endpoint);
* row sums equal ``edge_load`` and the off-diagonal half-sum equals
  ``cut_edges`` (``metrics.recompute_counters`` recounts all of it from
  scratch; the property tests assert agreement).

``commit_add`` scatter-adds the chooser's already-computed ``scores``
vector into row/col p, ``del_vertex_core`` subtracts it, ``del_edge_core``
touches one (pv, pu) pair, and ``make_masked_step`` merges the three
effects with masks exactly like the other counters. ``scale_in``'s merged
cut is then just ``cut_edges - cut_matrix[src, dst]`` and the migrate
folds row/col src into dst in O(K²) — the per-event O(n·max_deg)
``recompute_cut`` adjacency pass is gone from every engine path (it
survives only as the from-scratch reference for tests and benchmarks).
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.config import EngineConfig, POLICIES
from repro.core.state import PartitionState
from repro.graph.stream import EVENT_ADD, EVENT_DEL_EDGE, EVENT_DEL_VERTEX

# a Python int, not a jnp constant: masked_argmin runs inside the fused
# Pallas kernel body, where captured device constants are not allowed —
# a weak-typed literal traces to the same int32 ops either way
_BIG = 2**30


class EventTrace(NamedTuple):
    """Per-event metric trace (paper captures these at interval boundaries)."""
    total_edges: jax.Array
    cut_edges: jax.Array
    num_partitions: jax.Array
    load_std: jax.Array


# ---------------------------------------------------------------------------
# engine knobs
# ---------------------------------------------------------------------------

class Knobs(NamedTuple):
    """Numeric policy/scaling knobs derived from EngineConfig on the host.

    All Python-level arithmetic (products, percentages) happens in
    ``make_knobs`` so that the static path (fields are weak Python scalars,
    embedded as f32 constants at trace time) and the dynamic sweep path
    (fields are traced f32 scalars, see repro.runtime.sweep) perform
    bit-identical f32 operations.
    """
    max_cap: jax.Array | float            # Eq. 5 MAXCAP
    scale_in_l: jax.Array | float         # Eq. 6 l = tolerance*MAXCAP/100
    scale_in_dest: jax.Array | float      # Eq. 7 destinationThreshold
    ldg_cap_num: jax.Array | float        # ldg_slack * n (cap = this / k)
    fennel_gamma: jax.Array | float
    fennel_gm1: jax.Array | float         # gamma - 1
    fennel_alpha_scale: jax.Array | float


def make_knobs(cfg: EngineConfig, n: int) -> Knobs:
    """Host-side knob derivation shared by every engine path."""
    return Knobs(
        max_cap=cfg.max_cap,
        scale_in_l=cfg.tolerance_param * cfg.max_cap / 100.0,
        scale_in_dest=cfg.max_cap - cfg.dest_param * cfg.max_cap / 100.0,
        ldg_cap_num=cfg.ldg_slack * n,
        fennel_gamma=cfg.fennel_gamma,
        fennel_gm1=cfg.fennel_gamma - 1.0,
        fennel_alpha_scale=cfg.fennel_alpha_scale,
    )


def knobs_arrays(cfg: EngineConfig, n: int) -> Knobs:
    """Knobs as f32 scalars — the traced/vmapped form for the sweep runtime."""
    kn = make_knobs(cfg, n)
    return Knobs(*(jnp.float32(x) for x in kn))


# ---------------------------------------------------------------------------
# shared helpers
# ---------------------------------------------------------------------------

def neighbor_stats(state: PartitionState, row: jax.Array):
    """(scores[k], deg, nb_present, safe_row): affinity of one vertex row.

    scores[k] = |E(v) ∩ P_k| over *present* neighbours (paper Eq. 1).
    """
    valid = row >= 0
    safe_row = jnp.where(valid, row, 0)
    nb_present = valid & state.present[safe_row]
    nb_assign = jnp.where(nb_present, state.assignment[safe_row], -1)
    k_max = state.edge_load.shape[0]
    onehot = (nb_assign[:, None] == jnp.arange(k_max, dtype=jnp.int32)[None, :])
    scores = jnp.sum(onehot, axis=0, dtype=jnp.int32)
    deg = jnp.sum(nb_present, dtype=jnp.int32)
    return scores, deg, nb_present, safe_row


# ---------------------------------------------------------------------------
# index primitives in mask form
# ---------------------------------------------------------------------------
#
# The fused Pallas chooser runs the policy and scaling bodies below inside
# a Mosaic kernel, which lowers neither argmax/argmin over integers nor a
# dynamic index, gather or scatter on a vector value. Every such access is
# therefore written as a compare against an iota followed by a select and
# an integer reduction. On integers this is exact, so the XLA engines that
# share these bodies compute the same values as before. K-vectors may be
# 1-D ``(K,)`` (the XLA engines) or ``(1, K)`` rows (the kernel): every
# helper works along the last axis.

def lane_iota(x: jax.Array) -> jax.Array:
    """int32 index along the last axis of ``x``, in ``x``'s shape."""
    return jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)


def take_at(x: jax.Array, i) -> jax.Array:
    """``x[..., i]`` of a K-vector as a scalar (``i`` in range)."""
    if x.dtype == jnp.bool_:
        return take_at(x.astype(jnp.int32), i) != 0
    return jnp.sum(jnp.where(lane_iota(x) == i, x, jnp.zeros((), x.dtype)))


def add_at(x: jax.Array, i, val) -> jax.Array:
    """``x.at[..., i].add(val)`` of a K-vector."""
    return x + jnp.where(lane_iota(x) == i, val, jnp.zeros((), x.dtype))


def set_at(x: jax.Array, i, val) -> jax.Array:
    """``x.at[..., i].set(val)`` of a K-vector."""
    return jnp.where(lane_iota(x) == i, val, x)


def first_index(mask: jax.Array) -> jax.Array:
    """Index of the first True along the last axis; the axis size if none."""
    k = mask.shape[-1]
    return jnp.min(jnp.where(mask, lane_iota(mask), k)).astype(jnp.int32)


def _square_iotas(k: int):
    return (jax.lax.broadcasted_iota(jnp.int32, (k, k), 0),
            jax.lax.broadcasted_iota(jnp.int32, (k, k), 1))


def row_to_col(row: jax.Array) -> jax.Array:
    """A K-vector (``(K,)`` or ``(1, K)``) as a ``(K, 1)`` column, through
    the diagonal of a ``(K, K)`` select — no transpose or reshape."""
    ri, ci = _square_iotas(row.shape[-1])
    return jnp.sum(jnp.where(ri == ci, row, jnp.zeros((), row.dtype)),
                   axis=1, keepdims=True)


def matrix_at(m: jax.Array, i, j) -> jax.Array:
    """``m[i, j]`` of a ``(K, K)`` matrix as a scalar."""
    ri, ci = _square_iotas(m.shape[0])
    return jnp.sum(jnp.where((ri == i) & (ci == j), m, 0))


def matrix_add_at(m: jax.Array, i, j, val) -> jax.Array:
    """``m.at[i, j].add(val)`` of a ``(K, K)`` matrix."""
    ri, ci = _square_iotas(m.shape[0])
    return m + jnp.where((ri == i) & (ci == j), val, 0)


def matrix_add_cross(m: jax.Array, p, vec) -> jax.Array:
    """``m.at[p, :].add(vec).at[:, p].add(vec)`` of a ``(K, K)`` matrix —
    the cut-matrix update every transition core makes."""
    ri, ci = _square_iotas(m.shape[0])
    return (m + jnp.where(ri == p, vec, 0)
            + jnp.where(ci == p, row_to_col(vec), 0))


def nth_active(active: jax.Array, i: jax.Array) -> jax.Array:
    """Index of the i-th active partition, with i taken modulo the active
    count. Callers draw i in [0, num_partitions); clamping keeps the result
    an *active* index even if num_partitions ever drifts from
    popcount(active). All-inactive yields 0 — there is no valid answer in
    that state.

    The (i+1)-th active index equals the number of positions whose
    inclusive active prefix count is at most i; the prefix counts come from
    a triangular masked sum."""
    a = active.astype(jnp.int32)
    cnt = jnp.sum(a)
    i = jnp.mod(i, jnp.maximum(cnt, 1))
    ri, ci = _square_iotas(a.shape[-1])
    incl = jnp.sum(jnp.where(ci <= ri, a, 0), axis=1, keepdims=True)
    p = jnp.sum((incl <= i).astype(jnp.int32))
    return jnp.where(cnt > 0, p, 0)


def masked_argmin(x: jax.Array, mask: jax.Array) -> jax.Array:
    """First index of the least ``x`` among ``mask`` (0 if none), as
    ``argmin`` would return it."""
    v = jnp.where(mask, x, _BIG)
    return first_index(v == jnp.min(v))


def load_stats(state):
    """(avg_d, load_dev) over active partitions — Eqs. 2 & 10.

    ``state`` is any carrier of active/edge_load/num_partitions
    (PartitionState or the windowed engine's SmallState).
    """
    act = state.active
    load = state.edge_load.astype(jnp.float32)
    # normalise by popcount(active), the same denominator as the host-side
    # metrics.load_imbalance / state_metrics (num_partitions is kept equal
    # to it by the scale hooks, but the two definitions must not drift)
    p = jnp.maximum(jnp.sum(act, dtype=jnp.int32).astype(jnp.float32), 1.0)
    maxl = jnp.max(jnp.where(act, load, -jnp.inf))
    minl = jnp.min(jnp.where(act, load, jnp.inf))
    avg_d = (maxl - minl) / p
    mean = jnp.sum(jnp.where(act, load, 0.0)) / p
    var = jnp.sum(jnp.where(act, (load - mean) ** 2, 0.0)) / p
    return avg_d, jnp.sqrt(var)


# ---------------------------------------------------------------------------
# policies: choose a partition for an arriving vertex
# ---------------------------------------------------------------------------

def _affinity_choice_at(state, scores: jax.Array, ridx: jax.Array):
    """Paper Alg. 3 with the random draw precomputed: argmax affinity; tie →
    min load; no overlap → the ``ridx``-th active partition. The key-driven
    ``_affinity_choice`` below and the fused Pallas chooser (which consumes
    a per-slot ``rand_index_table``) share this body, so the two cannot
    drift."""
    act = state.active
    s = jnp.where(act, scores, -1)
    best = jnp.max(s)
    tied = act & (s == best)
    p_tie = masked_argmin(state.edge_load, tied)          # tie → min load
    p_rand = nth_active(act, ridx)                        # no overlap → random
    return jnp.where(best > 0, p_tie, p_rand)


def _rand_index(state, key: jax.Array) -> jax.Array:
    """The ONE random draw any policy makes: an index in
    [0, num_partitions). ``rand_index_table`` precomputes it per possible
    ``num_partitions`` so the fused kernel can look it up instead."""
    return jax.random.randint(key, (), 0, jnp.maximum(state.num_partitions, 1))


def _affinity_choice(state, scores: jax.Array, key: jax.Array):
    """Paper Alg. 3: argmax affinity; tie → min load; no overlap → random."""
    return _affinity_choice_at(state, scores, _rand_index(state, key))


def _sdp_guard_inputs(state):
    avg_d, load_dev = load_stats(state)
    cut = jnp.maximum(state.cut_edges.astype(jnp.float32), 1.0)
    w_dev = (state.total_edges.astype(jnp.float32) / cut) * load_dev  # Eq. 4
    th = w_dev - load_dev                                             # Eq. 3
    return avg_d, load_dev, th


def _sdp_text_pick(state, p_aff):
    """§4.2.2 guard around an already-made affinity choice."""
    avg_d, _, th = _sdp_guard_inputs(state)
    p_min = masked_argmin(state.edge_load, state.active)
    guard = (state.num_partitions > 1) & (avg_d > th)
    return jnp.where(guard, p_min, p_aff)


def _sdp_alg1_pick(state, p_aff):
    """Alg. 1 listing guard around an already-made affinity choice."""
    _, load_dev, th = _sdp_guard_inputs(state)
    p_min = masked_argmin(state.edge_load, state.active)
    guard = (state.num_partitions > 1) & (load_dev > th)
    return jnp.where(guard, p_aff, p_min)


def _choose_sdp_text(state, scores, deg, v, key, kn: Knobs, n: int):
    """§4.2.2 text semantics: imbalance (AVG_d > TH) ⇒ least-loaded."""
    return _sdp_text_pick(state, _affinity_choice(state, scores, key))


def _choose_sdp_alg1(state, scores, deg, v, key, kn: Knobs, n: int):
    """Alg. 1 listing semantics: σ > TH ⇒ affinity path, else least-loaded."""
    return _sdp_alg1_pick(state, _affinity_choice(state, scores, key))


def _choose_ldg(state, scores, deg, v, key, kn: Knobs, n: int):
    k = jnp.maximum(state.num_partitions.astype(jnp.float32), 1.0)
    cap = kn.ldg_cap_num / k
    w = 1.0 - state.vertex_count.astype(jnp.float32) / cap
    h = scores.astype(jnp.float32) * jnp.maximum(w, 0.0)
    h = jnp.where(state.active, h, -jnp.inf)
    best = jnp.max(h)
    tied = state.active & (h >= best - 1e-6)
    return masked_argmin(state.vertex_count, tied)


def _choose_fennel(state, scores, deg, v, key, kn: Knobs, n: int):
    m = state.total_edges.astype(jnp.float32) + deg.astype(jnp.float32)
    nt = jnp.maximum(jnp.sum(state.vertex_count).astype(jnp.float32), 1.0)
    k = jnp.maximum(state.num_partitions.astype(jnp.float32), 1.0)
    alpha = kn.fennel_alpha_scale * jnp.sqrt(k) * m / (nt**1.5)
    cost = alpha * kn.fennel_gamma * \
        state.vertex_count.astype(jnp.float32) ** kn.fennel_gm1
    h = jnp.where(state.active, scores.astype(jnp.float32) - cost, -jnp.inf)
    best = jnp.max(h)
    tied = state.active & (h >= best - 1e-6)
    return masked_argmin(state.vertex_count, tied)


def _choose_hash(state, scores, deg, v, key, kn: Knobs, n: int):
    idx = jnp.mod(v, jnp.maximum(state.num_partitions, 1))
    return nth_active(state.active, idx)


def _choose_random(state, scores, deg, v, key, kn: Knobs, n: int):
    idx = jax.random.randint(key, (), 0, jnp.maximum(state.num_partitions, 1))
    return nth_active(state.active, idx)


def _choose_greedy(state, scores, deg, v, key, kn: Knobs, n: int):
    return _affinity_choice(state, scores, key)


POLICY_INDEX = {p: i for i, p in enumerate(POLICIES)}


def policy_fns(balance_guard: str):
    """Policy table in POLICIES order — indexable by POLICY_INDEX for the
    static engines or by a traced lax.switch index in the sweep runtime."""
    sdp = _choose_sdp_text if balance_guard == "text" else _choose_sdp_alg1
    return (sdp, _choose_ldg, _choose_fennel, _choose_hash, _choose_random,
            _choose_greedy)


def make_chooser(balance_guard: str, policy: str | None = None,
                 policy_idx: jax.Array | None = None) -> Callable:
    """``choose(state, scores, deg, v, key, kn, n) -> p`` under either knob:
    static-string (trace-time table pick) or traced-index (lax.switch)."""
    table = policy_fns(balance_guard)
    if (policy is None) == (policy_idx is None):
        raise ValueError("pass exactly one of policy / policy_idx")
    if policy is not None:
        return table[POLICY_INDEX[policy]]

    def choose(state, scores, deg, v, key, kn, n):
        return jax.lax.switch(
            policy_idx, list(table), state, scores, deg, v, key, kn, n)
    return choose


# ---------------------------------------------------------------------------
# table-driven choosers (the fused Pallas kernel's policy seam)
# ---------------------------------------------------------------------------
#
# Identical policy bodies with the single random draw hoisted out: every
# key-consuming policy draws exactly ``_rand_index`` (randint in
# [0, num_partitions)), so a chooser parameterized on that *index* instead
# of the key needs no RNG inside the kernel. ``rand_index_table``
# precomputes the draw for every possible num_partitions per window slot —
# the kernel looks up ``rand_tab[slot, num_partitions - 1]`` and feeds it
# to ``make_table_chooser``'s table, which reuses the exact ``_choose_*``
# bodies above. Bit-identity with ``make_chooser`` is a theorem of
# ``randint(key, (), 0, m)`` being reproducible per (key, m), asserted by
# tests/test_fused_chooser.py property tests.

def _choose_sdp_text_at(state, scores, deg, v, ridx, kn: Knobs, n: int):
    return _sdp_text_pick(state, _affinity_choice_at(state, scores, ridx))


def _choose_sdp_alg1_at(state, scores, deg, v, ridx, kn: Knobs, n: int):
    return _sdp_alg1_pick(state, _affinity_choice_at(state, scores, ridx))


def _choose_random_at(state, scores, deg, v, ridx, kn: Knobs, n: int):
    return nth_active(state.active, ridx)


def _choose_greedy_at(state, scores, deg, v, ridx, kn: Knobs, n: int):
    return _affinity_choice_at(state, scores, ridx)


def policy_fns_at(balance_guard: str):
    """Table-driven policy table in POLICIES order: each entry takes the
    precomputed random index where ``policy_fns`` takes a PRNG key. The
    ldg/fennel/hash entries never consume randomness, so the key-position
    argument is simply ignored and the functions are shared verbatim."""
    sdp = _choose_sdp_text_at if balance_guard == "text" else _choose_sdp_alg1_at
    return (sdp, _choose_ldg, _choose_fennel, _choose_hash, _choose_random_at,
            _choose_greedy_at)


def make_table_chooser(balance_guard: str, policy: str | tuple[str, ...],
                       policy_idx: jax.Array | None = None) -> Callable:
    """``choose(state, scores, deg, v, ridx, kn, n) -> p`` — the
    ``make_chooser`` contract with the PRNG key replaced by the precomputed
    random index ``ridx`` (see ``rand_index_table``). A string ``policy``
    is a trace-time table pick; a tuple is the traced knob, a lax.switch
    over just those policies with ``policy_idx`` indexing the tuple (the
    sweep passes its lanes' policies, so a branch no lane takes is never
    compiled). The traced form is built *inside* the fused kernel body so
    the switch runs on the kernel's scalars."""
    table = policy_fns_at(balance_guard)
    if isinstance(policy, str):
        return table[POLICY_INDEX[policy]]
    branches = [table[POLICY_INDEX[p]] for p in policy]

    def choose(state, scores, deg, v, ridx, kn, n):
        return jax.lax.switch(
            policy_idx, branches, state, scores, deg, v, ridx, kn, n)
    return choose


def rand_index_table(base_key: jax.Array, t0, w: int, k_max: int) -> jax.Array:
    """(w, k_max) int32 table of the per-slot random draw for every possible
    partition count: ``tab[i, m-1] = randint(fold_in(base_key, t0+i), (),
    0, m)``. ``fold_in(base_key, t0+i)`` is exactly the per-event key of
    ``scan_events``, and ``randint`` with a static maxval m draws the same
    bits as the traced-maxval draw inside ``_rand_index`` — so a chooser
    reading ``tab[i, num_partitions-1]`` reproduces the key-driven engines
    bit-for-bit without tracing threefry inside the Pallas kernel."""
    idx = t0 + jnp.arange(w, dtype=jnp.int32)
    keys = jax.vmap(lambda i: jax.random.fold_in(base_key, i))(idx)

    def per_key(k):
        return jnp.stack([jax.random.randint(k, (), 0, m)
                          for m in range(1, k_max + 1)])

    return jax.vmap(per_key)(keys).astype(jnp.int32)


# ---------------------------------------------------------------------------
# scaling (§4.2.3)
# ---------------------------------------------------------------------------

def scale_out(state, kn: Knobs):
    """Eq. 5: if MAXCAP ≤ |E|/|P|, activate one more partition.

    ``state`` is any carrier of active/num_partitions/total_edges/
    scale_events/denied_scaleout (PartitionState or SmallState)."""
    p = jnp.maximum(state.num_partitions.astype(jnp.float32), 1.0)
    adding_threshold = state.total_edges.astype(jnp.float32) / p
    want = kn.max_cap <= adding_threshold
    inactive = ~state.active
    slot_free = jnp.sum(inactive.astype(jnp.int32)) > 0
    do = want & slot_free
    slot = first_index(inactive)                          # first inactive slot
    return state._replace(
        active=state.active | ((lane_iota(inactive) == slot) & do),
        num_partitions=state.num_partitions + do.astype(jnp.int32),
        scale_events=state.scale_events + do.astype(jnp.int32),
        denied_scaleout=state.denied_scaleout + (want & ~slot_free).astype(jnp.int32),
    )


def recompute_cut(assignment, present, adj) -> jax.Array:
    """Exact cut count (each undirected edge stored twice in adj).

    A full O(n·max_deg) adjacency pass — NOT used on any engine path
    anymore (scale-in reads the incremental ``cut_matrix`` instead); kept
    as the from-scratch reference for tests. The fig12 recompute baseline
    deliberately carries its own copy (benchmarks stay grep-clean of
    engine-layer recompute call sites); keep the two in sync."""
    valid = adj >= 0
    safe = jnp.where(valid, adj, 0)
    nb_present = valid & present[safe]
    both = nb_present & present[:, None]
    diff = assignment[:, None] != assignment[safe]
    return (jnp.sum(both & diff, dtype=jnp.int32) // 2).astype(jnp.int32)


def merge_cut_matrix(cut_matrix: jax.Array, src, dst) -> jax.Array:
    """Fold row/col ``src`` into ``dst`` in O(K²): relabelling every
    src-vertex as dst sends M'[a, b] = Σ M[p, q] over p→a, q→b under the
    map src→dst. Preserves symmetry, row sums (= merged edge_load), and
    the off-diagonal half-sum dropping by exactly M[src, dst] (= the
    merged cut delta)."""
    ri, ci = _square_iotas(cut_matrix.shape[0])
    row = jnp.sum(jnp.where(ri == src, cut_matrix, 0), axis=0, keepdims=True)
    cm = matrix_add_cross(cut_matrix, dst, row)
    cm = matrix_add_at(cm, dst, dst, matrix_at(cut_matrix, src, src))
    return jnp.where((ri == src) | (ci == src), 0, cm)


def scale_in_trigger(small, kn: Knobs):
    """Eqs. 6–8 trigger: (src, dst, do). `small` is any state carrying
    active/edge_load/num_partitions — shared with the windowed journal."""
    under = small.active & (small.edge_load.astype(jnp.float32) < kn.scale_in_l)
    n_under = jnp.sum(under.astype(jnp.int32))
    src = masked_argmin(small.edge_load, small.active)
    mask2 = small.active & (lane_iota(small.active) != src)
    dst = masked_argmin(small.edge_load, mask2)
    fits = (take_at(small.edge_load, src) + take_at(small.edge_load, dst)
            ).astype(jnp.float32) <= kn.scale_in_dest
    do = (small.num_partitions > 1) & (n_under >= 2) & fits
    return src, dst, do


def scale_in(state: PartitionState, kn: Knobs,
             gate=True, *, cut_fn=None) -> PartitionState:
    """Eqs. 6–8: if ≥2 machines under l, migrate min-load machine into the
    next-least-loaded one (if it fits under destinationThreshold).
    ``gate`` AND-composes an outer condition (e.g. "this event was a
    DEL_VERTEX" in the fused masked step) into the migrate trigger.

    The merged cut comes from the incremental pairwise matrix:
    ``cut_edges - cut_matrix[src, dst]`` plus an O(K²) row/col fold — no
    adjacency pass. ``cut_fn`` (assignment, present, adj) -> cut swaps in a
    from-scratch recompute instead; only the fig12 benchmark baseline uses
    it (the counters are exact, so both produce identical states)."""
    src, dst, do = scale_in_trigger(state, kn)
    do = do & gate

    def migrate(s: PartitionState) -> PartitionState:
        assignment = jnp.where(s.assignment == src, dst, s.assignment)
        edge_load = s.edge_load.at[dst].add(s.edge_load[src]).at[src].set(0)
        vertex_count = s.vertex_count.at[dst].add(s.vertex_count[src]).at[src].set(0)
        if cut_fn is None:
            cut = s.cut_edges - s.cut_matrix[src, dst]
        else:
            cut = cut_fn(assignment, s.present, s.adj)
        return s._replace(
            assignment=assignment, edge_load=edge_load, vertex_count=vertex_count,
            active=s.active.at[src].set(False),
            num_partitions=s.num_partitions - 1,
            cut_edges=cut,
            cut_matrix=merge_cut_matrix(s.cut_matrix, src, dst),
            scale_events=s.scale_events + 1,
        )

    return jax.lax.cond(do, migrate, lambda s: s, state)


# ---------------------------------------------------------------------------
# event transition cores (shared by every engine path)
# ---------------------------------------------------------------------------

def commit_add(state: PartitionState, v, row, p, scores, deg):
    """Apply an ADD decision (partition p, scores vs current presence).

    Non-fresh (duplicate) adds scatter to the out-of-bounds row n, which
    drop-mode scatters skip — cheaper inside a scan than a full-array
    select, and identical values."""
    n = state.assignment.shape[0]
    fresh = ~state.present[v]  # ignore duplicate adds
    tgt = jnp.where(fresh, v, n)
    d = jnp.where(fresh, deg, 0)
    sc = jnp.where(fresh, scores, 0)
    return state._replace(
        assignment=state.assignment.at[tgt].set(p, mode="drop"),
        present=state.present.at[v].set(True),
        adj=state.adj.at[tgt].set(row, mode="drop"),
        vertex_count=state.vertex_count.at[p].add(fresh.astype(jnp.int32)),
        edge_load=(state.edge_load + sc).at[p].add(d),
        total_edges=state.total_edges + d,
        cut_edges=state.cut_edges + d - sc[p],
        cut_matrix=state.cut_matrix.at[p, :].add(sc).at[:, p].add(sc),
    )


def del_vertex_core(state: PartitionState, v):
    """Remove vertex v and its incident edges (no scale-in)."""
    n = state.assignment.shape[0]
    was = state.present[v]
    own_row = state.adj[v]
    scores, deg, _, _ = neighbor_stats(state, own_row)
    p = jnp.maximum(state.assignment[v], 0)
    d = jnp.where(was, deg, 0)
    sc = jnp.where(was, scores, 0)
    return state._replace(
        assignment=state.assignment.at[jnp.where(was, v, n)].set(
            -1, mode="drop"),
        present=state.present.at[v].set(False),
        vertex_count=state.vertex_count.at[p].add(-was.astype(jnp.int32)),
        edge_load=(state.edge_load - sc).at[p].add(-d),
        total_edges=state.total_edges - d,
        cut_edges=state.cut_edges - (d - sc[p]),
        cut_matrix=state.cut_matrix.at[p, :].add(-sc).at[:, p].add(-sc),
    )


def del_edge_core(state: PartitionState, v, row):
    """Remove edge (v, row[0]) if it exists (no config dependence)."""
    u = row[0]
    safe_u = jnp.maximum(u, 0)
    in_adj = jnp.any(state.adj[v] == u) & (u >= 0)
    exists = state.present[v] & state.present[safe_u] & in_adj
    pv = jnp.maximum(state.assignment[v], 0)
    pu = jnp.maximum(state.assignment[safe_u], 0)
    e = exists.astype(jnp.int32)
    cutdec = (exists & (pv != pu)).astype(jnp.int32)
    # row-wise edits only (u < 0 rewrites the rows with themselves) — a
    # full-array select here is a per-event O(n·max_deg) copy in the scan
    row_v = jnp.where((state.adj[v] == u) & (u >= 0), -1, state.adj[v])
    adj = state.adj.at[v].set(row_v)
    row_u = jnp.where((adj[safe_u] == v) & (u >= 0), -1, adj[safe_u])
    adj = adj.at[safe_u].set(row_u)
    return state._replace(
        adj=adj,
        edge_load=state.edge_load.at[pv].add(-e).at[pu].add(-e),
        total_edges=state.total_edges - e,
        cut_edges=state.cut_edges - cutdec,
        cut_matrix=state.cut_matrix.at[pv, pu].add(-e).at[pu, pv].add(-e),
    )


def migrate_core(state: PartitionState, v, dst, gate=True):
    """Move present vertex v to partition ``dst`` (the rebalance
    transition, see ``repro.rebalance``). Returns ``(state, did)``.

    Algebraically ``del_vertex_core(v)`` followed by ``commit_add(v)``
    at ``dst`` with the same neighbour scores — legal because a move
    never changes the adjacency, so every neighbour's label histogram
    is the same before and after. The deltas net out: neighbours'
    edge_load terms cancel, ``total_edges`` is untouched, and only the
    src/dst rows+columns of ``cut_matrix`` move. Gated-off calls (or
    moves to the current / an inactive partition) return the state
    bit-identically via the same drop-mode scatter trick as
    ``commit_add``."""
    n = state.assignment.shape[0]
    scores, deg, _, _ = neighbor_stats(state, state.adj[v])
    src = jnp.maximum(state.assignment[v], 0)
    dst = jnp.clip(dst, 0, state.edge_load.shape[0] - 1)
    do = (gate & state.present[v] & (state.assignment[v] >= 0)
          & state.active[dst] & (dst != src))
    e = do.astype(jnp.int32)
    d = jnp.where(do, deg, 0)
    sc = jnp.where(do, scores, 0)
    moved = state._replace(
        assignment=state.assignment.at[jnp.where(do, v, n)].set(
            dst, mode="drop"),
        vertex_count=state.vertex_count.at[src].add(-e).at[dst].add(e),
        edge_load=state.edge_load.at[src].add(-d).at[dst].add(d),
        cut_edges=state.cut_edges + sc[src] - sc[dst],
        cut_matrix=(state.cut_matrix
                    .at[src, :].add(-sc).at[:, src].add(-sc)
                    .at[dst, :].add(sc).at[:, dst].add(sc)),
    )
    return moved, do


# ---------------------------------------------------------------------------
# the parameterized transition kernel
# ---------------------------------------------------------------------------

class EventTransition(NamedTuple):
    """Event branches in EVENT_* code order — ``list(trn)`` is directly the
    branch table for ``lax.switch`` over the event type."""
    apply_add: Callable       # (state, v, row, key) -> state
    apply_del_vertex: Callable
    apply_del_edge: Callable
    apply_pad: Callable

    def step(self, state, et, v, row, key):
        """One event through the branch switch (scalar ``et`` executes
        exactly one branch — right for the single-lane reference engine;
        batched lanes use ``make_masked_step`` instead, see its docstring)."""
        return jax.lax.switch(jnp.clip(et, 0, 3), list(self),
                              state, v, row, key)


def make_scale_hooks(kn: Knobs, autoscale: bool):
    """(scale_out_hook, scale_in_hook) under the static knob: False hooks
    are identity and trace nothing; True hooks trace the — internally
    data-dependent — scale ops unconditionally. Traced per-lane autoscale
    belongs to ``make_masked_step`` (its gates mask the scale effects)."""
    if not autoscale:
        return (lambda s: s), (lambda s: s)
    return (lambda s: scale_out(s, kn)), (lambda s: scale_in(s, kn))


def make_transition(
    kn: Knobs,
    n: int,
    *,
    balance_guard: str,
    policy: str,
    autoscale: bool = False,
) -> EventTransition:
    """Build the four event branches for one engine lane — the
    *static-knob* binding: ``policy`` is a Python string and ``autoscale``
    a Python bool (the caller resolves ``cfg.autoscale and policy ==
    "sdp"``). The branch switch is right when the event type is a scalar;
    batched lanes (the sweep's traced knob) use ``make_masked_step``.
    """
    choose = make_chooser(balance_guard, policy)
    so_hook, si_hook = make_scale_hooks(kn, autoscale)

    def apply_add(state, v, row, key):
        state = so_hook(state)
        scores, deg, _, _ = neighbor_stats(state, row)
        p = choose(state, scores, deg, v, key, kn, n)
        return commit_add(state, v, row, p, scores, deg)

    def apply_del_vertex(state, v, row, key):
        state = del_vertex_core(state, v)
        return si_hook(state)

    def apply_del_edge(state, v, row, key):
        return del_edge_core(state, v, row)

    def apply_pad(state, v, row, key):
        return state

    return EventTransition(apply_add, apply_del_vertex, apply_del_edge,
                           apply_pad)


def make_masked_step(
    kn: Knobs,
    n: int,
    *,
    balance_guard: str,
    policy: str | None = None,
    policy_idx: jax.Array | None = None,
    autoscale=False,
    cut_fn=None,
) -> Callable:
    """Fused, branch-free event step: ``step(state, et, v, row, key)``.

    Bit-identical to ``EventTransition.step`` (same cores, same op order)
    but merges the three event effects with masks and row-level drop-mode
    scatters instead of a ``lax.switch``. Under ``vmap`` — the sweep's
    traced path — a switch/cond with a *batched* predicate computes every
    branch and selects, so the reference step pays all four branches plus
    a full-state (incl. (n, max_deg) adjacency) select per event per
    lane; here only one masked neighbour-gather per effect remains and
    every large-array write is an unconditional drop-mode scatter (the
    same design that makes the mixed-window kernel fast). Knob
    parameterization matches ``make_transition``. ``cut_fn`` is forwarded
    to ``scale_in`` (fig12 recompute baseline only).
    """
    choose = make_chooser(balance_guard, policy, policy_idx)
    static_auto = isinstance(autoscale, bool)
    scaling = autoscale is not False   # trace-level: any scaling code?

    def step(state: PartitionState, et, v, row, key) -> PartitionState:
        is_add = et == EVENT_ADD
        is_dv = et == EVENT_DEL_VERTEX
        is_de = et == EVENT_DEL_EDGE

        # --- scale-out before the ADD decision (§4.2.3, add events only);
        # touches only the O(K) fields, so the masked merge is cheap ---
        if scaling:
            gate = is_add if static_auto else is_add & autoscale
            so = scale_out(state, kn)
            state = state._replace(
                active=jnp.where(gate, so.active, state.active),
                num_partitions=jnp.where(gate, so.num_partitions,
                                         state.num_partitions),
                scale_events=jnp.where(gate, so.scale_events,
                                       state.scale_events),
                denied_scaleout=jnp.where(gate, so.denied_scaleout,
                                          state.denied_scaleout),
            )

        # --- ADD effect (commit_add quantities; faithful apply_add) ---
        row_add = jnp.where(is_add, row, -1)
        sc_add, deg_add, _, _ = neighbor_stats(state, row_add)
        p_add = choose(state, sc_add, deg_add, v, key, kn, n)
        fresh = is_add & ~state.present[v]
        d_add = jnp.where(fresh, deg_add, 0)
        sc_a = jnp.where(fresh, sc_add, 0)

        # --- DEL_VERTEX effect (del_vertex_core quantities) ---
        own_row = state.adj[v]
        row_dv = jnp.where(is_dv, own_row, -1)
        sc_dvs, deg_dv, _, _ = neighbor_stats(state, row_dv)
        was = is_dv & state.present[v]
        p_dv = jnp.maximum(state.assignment[v], 0)
        d_dv = jnp.where(was, deg_dv, 0)
        sc_d = jnp.where(was, sc_dvs, 0)

        # --- DEL_EDGE effect (del_edge_core quantities) ---
        u = row[0]
        safe_u = jnp.maximum(u, 0)
        in_adj = jnp.any(own_row == u) & (u >= 0)
        exists = is_de & state.present[v] & state.present[safe_u] & in_adj
        pu = jnp.maximum(state.assignment[safe_u], 0)
        e = exists.astype(jnp.int32)
        cutdec = (exists & (p_dv != pu)).astype(jnp.int32)

        # --- masked counter merge (one event type per step ⇒ exact) ---
        vertex_count = (state.vertex_count
                        .at[p_add].add(fresh.astype(jnp.int32))
                        .at[p_dv].add(-was.astype(jnp.int32)))
        edge_load = ((state.edge_load + sc_a - sc_d)
                     .at[p_add].add(d_add).at[p_dv].add(-d_dv)
                     .at[p_dv].add(-e).at[pu].add(-e))
        total_edges = state.total_edges + d_add - d_dv - e
        cut_edges = (state.cut_edges + (d_add - sc_a[p_add])
                     - (d_dv - sc_d[p_dv]) - cutdec)
        cut_matrix = (state.cut_matrix
                      .at[p_add, :].add(sc_a).at[:, p_add].add(sc_a)
                      .at[p_dv, :].add(-sc_d).at[:, p_dv].add(-sc_d)
                      .at[p_dv, pu].add(-e).at[pu, p_dv].add(-e))

        # --- row-level array updates (never a full-array select) ---
        assignment = (state.assignment
                      .at[jnp.where(fresh, v, n)].set(p_add, mode="drop")
                      .at[jnp.where(was, v, n)].set(-1, mode="drop"))
        present = (state.present
                   .at[jnp.where(is_add, v, n)].set(True, mode="drop")
                   .at[jnp.where(is_dv, v, n)].set(False, mode="drop"))
        row_v_de = jnp.where((own_row == u) & (u >= 0), -1, own_row)
        w1_val = jnp.where(is_add, row, jnp.where(is_de, row_v_de, own_row))
        w1_tgt = jnp.where(fresh | is_de, v, n)
        adj = state.adj.at[w1_tgt].set(w1_val, mode="drop")
        row_u = adj[safe_u]                   # after write 1 (self-loops)
        row_u_de = jnp.where((row_u == v) & (u >= 0), -1, row_u)
        adj = adj.at[jnp.where(is_de, safe_u, n)].set(row_u_de, mode="drop")

        state = state._replace(
            assignment=assignment, present=present, adj=adj,
            vertex_count=vertex_count, edge_load=edge_load,
            total_edges=total_edges, cut_edges=cut_edges,
            cut_matrix=cut_matrix,
        )

        # --- scale-in after DEL_VERTEX (faithful apply_del_vertex) ---
        if scaling:
            gate_dv = is_dv if static_auto else is_dv & autoscale
            state = scale_in(state, kn, gate=gate_dv, cut_fn=cut_fn)
        return state

    return step


def scan_events(
    step_fn: Callable,    # (state, et, v, row, key) -> state
    state: PartitionState,
    etype: jax.Array,     # (T,)
    vertex: jax.Array,    # (T,)
    nbrs: jax.Array,      # (T, max_deg)
    t0: jax.Array,        # () global index of first event (RNG alignment)
) -> tuple[PartitionState, EventTrace]:
    """Per-event lax.scan over one lane — the faithful event loop shared by
    the reference engine (``EventTransition.step``) and every sweep lane
    (``make_masked_step``)."""
    base_key = state.key

    def step(s: PartitionState, ev):
        et, v, row, i = ev
        key = jax.random.fold_in(base_key, i)
        sv = jnp.maximum(v, 0)
        s = step_fn(s, et, sv, row, key)
        _, load_dev = load_stats(s)
        tr = EventTrace(s.total_edges, s.cut_edges, s.num_partitions, load_dev)
        return s, tr

    idx = t0 + jnp.arange(etype.shape[0], dtype=jnp.int32)
    return jax.lax.scan(step, state, (etype, vertex, nbrs, idx))
