"""Plain reference of the partitioner: Algorithm 1 of SDP over dicts and sets.

A copy of the program's pure-Python oracle (``core/ref.py``), kept here so
that the yardstick does not move when the program changes. It imports
nothing of the program. Three changes make it fast enough for a run's
whole stream, and none changes an answer
(``bench/tests/test_copies.py`` holds it equal to the program's oracle):

- the random draw an event may need, ``randint(fold_in(PRNGKey(seed), i),
  (), 0, m)`` for each partition count ``m``, is taken for a block of
  events in one vectorised JAX call on the host's CPU;
- each partition keeps its member set, so a scale-in relabels the merged
  partition's vertices only, and the merged cut drops by the edges found
  between the two partitions' members (the oracle recounts every edge);
- neighbour rows are read as Python lists once.

``stale_window`` turns the reference into the benchmark's control: each
arriving vertex is placed by scores that do not see the vertices added
earlier in the same window of that many events (counters still count
them). That is the placement a window-parallel chooser would make; the
configurations state that every event sees every earlier one.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

EVENT_ADD, EVENT_DEL_VERTEX, EVENT_DEL_EDGE = 0, 1, 2
_RNG_BLOCK = 1 << 16


@dataclasses.dataclass(frozen=True)
class Knobs:
    """Algorithm 1's knobs (SDP paper section 4.2), as a configuration
    states them."""

    k_max: int = 16
    k_init: int = 1
    max_cap: int = 1 << 30
    tolerance_param: float = 25.0
    dest_param: float = 5.0
    balance_guard: str = "text"
    autoscale: bool = True
    fennel_gamma: float = 1.5
    fennel_alpha_scale: float = 1.0
    ldg_slack: float = 1.1


def host_device():
    """The host's CPU device where JAX has one, else the default device:
    the reference's few JAX calls stay off the chip when they can."""
    import jax
    try:
        return jax.devices("cpu")[0]
    except RuntimeError:
        return jax.devices()[0]


class _Draws:
    """``randint(fold_in(PRNGKey(seed), i), (), 0, m)`` for every event
    ``i`` and partition count ``m`` in ``1..k_max``, a block at a time."""

    def __init__(self, seed: int, k_max: int):
        import jax
        self._jax = jax
        self._cpu = host_device()
        self._k_max = k_max
        with jax.default_device(self._cpu):
            self._key = jax.random.PRNGKey(seed)
        self._fn = jax.jit(self._table)
        self._lo, self._tab = None, None

    def _table(self, key, idx):
        jax = self._jax
        keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(idx)
        per_key = lambda k: jax.numpy.stack(  # noqa: E731
            [jax.random.randint(k, (), 0, m) for m in range(1, self._k_max + 1)])
        return jax.vmap(per_key)(keys)

    def __call__(self, i: int, m: int) -> int:
        lo = i - i % _RNG_BLOCK
        if lo != self._lo:
            idx = np.arange(lo, lo + _RNG_BLOCK, dtype=np.int32)
            with self._jax.default_device(self._cpu):
                self._tab = np.asarray(self._fn(self._key, idx)).tolist()
            self._lo = lo
        return self._tab[i - lo][m - 1]


class RefState:
    """The reference's state after a stream: per-vertex labels, neighbour
    sets, and Algorithm 1's per-partition counters."""

    def __init__(self, n: int, knobs: Knobs):
        k = knobs.k_max
        self.n = n
        self.k_max = k
        self.assignment: dict[int, int] = {}     # present vertex -> partition
        self.adj: dict[int, set] = {}            # vertex -> neighbour set
        self.members = [set() for _ in range(k)]
        self.active = [i < knobs.k_init for i in range(k)]
        self.edge_load = [0] * k
        self.vertex_count = [0] * k
        self.total_edges = 0
        self.cut_edges = 0
        self.denied = 0
        self.scale_events = 0
        # [p][q] (p != q): present edges between p and q; [p][p]: twice the
        # edges inside p
        self.cut_matrix = np.zeros((k, k), np.int64)

    @property
    def num_partitions(self) -> int:
        return sum(self.active)


def _load_stats(s: RefState):
    loads = [l for l, a in zip(s.edge_load, s.active) if a]
    if not loads:
        return 0.0, 0.0
    p = len(loads)
    mean = sum(loads) / p
    var = sum([(l - mean) ** 2 for l in loads]) / p
    return (max(loads) - min(loads)) / p, math.sqrt(var)


def _argmin_load(s: RefState, mask=None) -> int | None:
    best, bk = None, None
    for k in range(s.k_max):
        ok = s.active[k] if mask is None else mask[k]
        if ok and (best is None or s.edge_load[k] < best):
            best, bk = s.edge_load[k], k
    return bk


def _nth_active(s: RefState, i: int) -> int:
    c = -1
    for k in range(s.k_max):
        if s.active[k]:
            c += 1
            if c == i:
                return k
    raise AssertionError("no active partition")


def _affinity(s: RefState, sc, draw) -> int:
    best = max([sc[k] if s.active[k] else -1 for k in range(s.k_max)])
    if best > 0:
        tied = [s.active[k] and sc[k] == best for k in range(s.k_max)]
        return _argmin_load(s, tied)
    return _nth_active(s, draw(max(s.num_partitions, 1)))


def _choose(s: RefState, policy: str, kn: Knobs, sc, deg, v, draw) -> int:
    if policy == "greedy":
        return _affinity(s, sc, draw)
    if policy == "sdp":
        avg_d, load_dev = _load_stats(s)
        w_dev = (s.total_edges / max(s.cut_edges, 1)) * load_dev
        th = w_dev - load_dev
        if kn.balance_guard == "text":
            guard = s.num_partitions > 1 and avg_d > th
            return _argmin_load(s) if guard else _affinity(s, sc, draw)
        guard = s.num_partitions > 1 and load_dev > th
        return _affinity(s, sc, draw) if guard else _argmin_load(s)
    if policy in ("ldg", "fennel"):
        if policy == "ldg":
            k_act = max(s.num_partitions, 1)
            cap = kn.ldg_slack * s.n / k_act
            h = [sc[k] * max(1.0 - s.vertex_count[k] / cap, 0.0)
                 if s.active[k] else -np.inf for k in range(s.k_max)]
        else:
            g = kn.fennel_gamma
            m = s.total_edges + deg
            nt = max(sum(s.vertex_count), 1)
            alpha = (kn.fennel_alpha_scale * np.sqrt(max(s.num_partitions, 1))
                     * m / nt ** 1.5)
            h = [sc[k] - alpha * g * s.vertex_count[k] ** (g - 1.0)
                 if s.active[k] else -np.inf for k in range(s.k_max)]
        best = max(h)
        cand = [(s.vertex_count[k], k) for k in range(s.k_max)
                if s.active[k] and h[k] >= best - 1e-6]
        return min(cand)[1]
    if policy == "hash":
        return _nth_active(s, int(v) % max(s.num_partitions, 1))
    if policy == "random":
        return _nth_active(s, draw(max(s.num_partitions, 1)))
    raise ValueError(f"no reference for policy {policy!r}")


def _scale_out(s: RefState, kn: Knobs) -> None:
    p = max(s.num_partitions, 1)
    if kn.max_cap <= s.total_edges / p:
        if all(s.active):
            s.denied += 1
        else:
            s.active[s.active.index(False)] = True
            s.scale_events += 1


def _scale_in(s: RefState, kn: Knobs) -> None:
    low = kn.tolerance_param * kn.max_cap / 100.0
    dest_threshold = kn.max_cap - kn.dest_param * kn.max_cap / 100.0
    under = sum(1 for l, a in zip(s.edge_load, s.active) if a and l < low)
    if s.num_partitions <= 1 or under < 2:
        return
    src = _argmin_load(s)
    mask = list(s.active)
    mask[src] = False
    dst = _argmin_load(s, mask)
    if s.edge_load[src] + s.edge_load[dst] > dest_threshold:
        return
    # edges between the two partitions' members stop being cut
    between = sum(1 for v in s.members[src] for u in s.adj[v]
                  if s.assignment.get(u) == dst)
    for v in s.members[src]:
        s.assignment[v] = dst
    s.members[dst] |= s.members[src]
    s.members[src] = set()
    s.edge_load[dst] += s.edge_load[src]
    s.edge_load[src] = 0
    s.vertex_count[dst] += s.vertex_count[src]
    s.vertex_count[src] = 0
    s.active[src] = False
    s.scale_events += 1
    s.cut_edges -= between
    cm = s.cut_matrix
    row = cm[src, :].copy()
    cm[dst, :] += row
    cm[:, dst] += row
    cm[dst, dst] += cm[src, src]
    cm[src, :] = 0
    cm[:, src] = 0


def _rows(etype: np.ndarray, nbrs: np.ndarray) -> list:
    """Each event's neighbour row as a list of its real ids (adds), or its
    partner id (edge deletes)."""
    out = [None] * etype.shape[0]
    adds = np.flatnonzero(etype == EVENT_ADD)
    for lo in range(0, adds.size, _RNG_BLOCK):
        block = adds[lo:lo + _RNG_BLOCK]
        rows = nbrs[block]
        real = rows >= 0
        ids = rows[real].tolist()
        ends = np.cumsum(real.sum(axis=1)).tolist()
        start = 0
        for i, end in zip(block.tolist(), ends):
            out[i] = ids[start:end]
            start = end
    des = np.flatnonzero(etype == EVENT_DEL_EDGE)
    for i, u in zip(des.tolist(), nbrs[des, 0].tolist()):
        out[i] = u
    return out


def run_reference(etype, vertex, nbrs, n: int, *, policy: str = "sdp",
                  knobs: Knobs = Knobs(), seed: int = 0,
                  stale_window: int | None = None) -> RefState:
    """Algorithm 1 over events ``(etype[i], vertex[i], nbrs[i])`` in order;
    returns the final :class:`RefState`. With ``stale_window`` it is the
    control described in the module docstring."""
    s = RefState(n, knobs)
    draws = _Draws(seed, knobs.k_max)
    rows = _rows(np.asarray(etype), np.asarray(nbrs))
    added_at: dict[int, int] = {}
    scale = policy == "sdp" and knobs.autoscale
    k_range = range(s.k_max)
    assignment = s.assignment
    for i, (et, v) in enumerate(zip(np.asarray(etype).tolist(),
                                    np.asarray(vertex).tolist())):
        if et == EVENT_ADD:
            if scale:
                _scale_out(s, knobs)
            nb = rows[i]
            sc = [0] * s.k_max
            deg = 0
            for u in nb:
                p = assignment.get(u)
                if p is not None:
                    sc[p] += 1
                    deg += 1
            seen, seen_deg = sc, deg
            if stale_window:
                w0 = i - i % stale_window
                seen = [0] * s.k_max
                seen_deg = 0
                for u in nb:
                    p = assignment.get(u)
                    if p is not None and added_at[u] < w0:
                        seen[p] += 1
                        seen_deg += 1
            p = _choose(s, policy, knobs, seen, seen_deg, v,
                        lambda m, i=i: draws(i, m))
            if v not in assignment:
                assignment[v] = p
                added_at[v] = i
                s.members[p].add(v)
                s.adj[v] = set(nb)
                s.vertex_count[p] += 1
                for k in k_range:
                    s.edge_load[k] += sc[k]
                s.edge_load[p] += deg
                s.total_edges += deg
                s.cut_edges += deg - sc[p]
                sc_a = np.asarray(sc)
                s.cut_matrix[p, :] += sc_a
                s.cut_matrix[:, p] += sc_a
        elif et == EVENT_DEL_VERTEX:
            if v in assignment:
                sc = [0] * s.k_max
                deg = 0
                for u in s.adj.get(v, ()):
                    q = assignment.get(u)
                    if q is not None:
                        sc[q] += 1
                        deg += 1
                p = assignment.pop(v)
                s.members[p].discard(v)
                for k in k_range:
                    s.edge_load[k] -= sc[k]
                s.edge_load[p] -= deg
                s.vertex_count[p] -= 1
                s.total_edges -= deg
                s.cut_edges -= deg - sc[p]
                sc_a = np.asarray(sc)
                s.cut_matrix[p, :] -= sc_a
                s.cut_matrix[:, p] -= sc_a
            if scale:
                _scale_in(s, knobs)
        elif et == EVENT_DEL_EDGE:
            u = rows[i]
            if (v in assignment and u in assignment
                    and u in s.adj.get(v, ())):
                pv, pu = assignment[v], assignment[u]
                s.edge_load[pv] -= 1
                s.edge_load[pu] -= 1
                s.total_edges -= 1
                s.cut_edges -= int(pv != pu)
                s.cut_matrix[pv, pu] -= 1
                s.cut_matrix[pu, pv] -= 1
            if u >= 0:
                s.adj.get(v, set()).discard(u)
                s.adj.get(u, set()).discard(v)
    return s
