"""Traffic for the benchmark: the churn stream and its arrival schedule.

``powerlaw_churn`` and ``poisson_arrivals`` are copies of the program's
generators (``repro.graph.stream``), kept here so that the yardstick does
not move when the program's own generators change. ``powerlaw_churn``
takes its churn mix as parameters (the program fixes it in constants) and
builds the same stream for the same arguments, faster: the graph is
drawn in row blocks and the neighbour rows are written after the event
loop. ``bench/tests/test_copies.py`` holds both copies equal to the
program's versions.

A run generates its stream in a separate process (``StreamProcess``),
started before the parent touches JAX, so that drawing the stream overlaps
the chip's start-up. The process imports numpy only.
"""
from __future__ import annotations

import dataclasses
import multiprocessing

import numpy as np

EVENT_ADD = 0          # add vertex `vertex` with neighbour row `nbrs`
EVENT_DEL_VERTEX = 1   # delete vertex `vertex` and its edges
EVENT_DEL_EDGE = 2     # delete edge (vertex, nbrs[0])

_PIPE_PIECE = 1 << 26  # bytes per pipe message (large messages are slow)


@dataclasses.dataclass(frozen=True)
class ChurnMix:
    """The shape of a churn stream: its degree law and its event mix."""

    vertex_delete_share: float = 0.15   # share of events deleting a vertex
    edge_delete_share: float = 0.15     # share of events deleting an edge
    degree_exponent: float = 2.3        # Pareto tail of links per arrival
    min_degree: int = 2                 # links an arrival asks for, at least
    lead_in_adds: int = 512             # leading events that are all adds


@dataclasses.dataclass(frozen=True)
class Stream:
    """A padded event tensor: (T,) codes, (T,) vertices, (T, D) rows."""

    etype: np.ndarray
    vertex: np.ndarray
    nbrs: np.ndarray
    n: int
    truncated_nbrs: int = 0

    @property
    def num_events(self) -> int:
        return int(self.etype.shape[0])

    def chunk(self, lo: int, hi: int):
        """Events ``lo:hi`` as the ``(etype, vertex, nbrs)`` triple that
        ``Partitioner.feed`` and ``PartitionService.submit`` take."""
        return self.etype[lo:hi], self.vertex[lo:hi], self.nbrs[lo:hi]


def _ranks(src: np.ndarray, dst: np.ndarray) -> np.ndarray:
    """Position of each (src, dst) entry within src's row, rows ordered by
    partner arrival. The pairs are distinct, so any sort gives one order."""
    order = np.argsort(src * (int(dst.max(initial=0)) + 1) + dst)
    first = np.searchsorted(src[order], src[order], side="left")
    r = np.empty_like(src)
    r[order] = np.arange(src.size) - first
    return r


def powerlaw_churn(n: int, num_events: int, *, max_deg: int, seed: int,
                   mix: ChurnMix = ChurnMix(),
                   block_rows: int = 1 << 16) -> Stream:
    """Seeded power-law churn stream over the vertex ids ``[0, n)``.

    The first ``mix.lead_in_adds`` events are adds; after that each event
    is an add, a vertex delete or an edge delete with the mix's shares.
    Added vertices take distinct ids drawn uniformly from ``[0, n)``.
    Arrival ``j`` links to ``d_j`` earlier arrivals, ``d_j`` from a Pareto
    tail of exponent ``mix.degree_exponent`` starting at
    ``mix.min_degree``; the partner index is ``floor(j * u**2)`` for
    uniform ``u``, so early arrivals collect links and become hubs. The
    undirected graph is fixed up front and both endpoints list every edge;
    an edge that would overflow ``max_deg`` at either endpoint is dropped
    from both, counted in ``truncated_nbrs``. Deletes name only present
    vertices and live edges between present vertices; nothing is re-added.
    """
    add_frac = 1.0 - mix.vertex_delete_share - mix.edge_delete_share
    rng = np.random.default_rng(seed)
    kinds = rng.choice(
        np.asarray([EVENT_ADD, EVENT_DEL_VERTEX, EVENT_DEL_EDGE], np.int32),
        size=num_events,
        p=[add_frac, mix.vertex_delete_share, mix.edge_delete_share])
    kinds[:mix.lead_in_adds] = EVENT_ADD
    n_add = int(np.sum(kinds == EVENT_ADD))
    if n_add > n:
        raise ValueError(f"{n_add} adds need n >= {n_add} distinct ids, "
                         f"got n={n}")
    ids = rng.choice(n, size=n_add, replace=False).astype(np.int32)

    # --- the static graph, in arrival-index space -----------------------
    # The partner draws come in row blocks: one (rows, max_deg) draw after
    # another takes the same numbers as a single (n_add, max_deg) draw.
    deg = np.minimum(mix.min_degree * (1.0 - rng.random(n_add))
                     ** (-1.0 / (mix.degree_exponent - 1.0)),
                     max_deg).astype(np.int64)
    cols = np.arange(max_deg)[None, :]
    keys = []
    for lo in range(0, n_add, block_rows):
        hi = min(lo + block_rows, n_add)
        u = rng.random((hi - lo, max_deg))
        j = np.arange(lo, hi, dtype=np.int64)[:, None]
        links = (cols < deg[lo:hi, None]) & (j > 0)
        jl = np.broadcast_to(j, links.shape)[links]
        keys.append(jl * n_add + (jl * u[links] ** 2).astype(np.int64))
    key = np.unique(np.concatenate(keys)) if keys else np.zeros(0, np.int64)
    a, b = np.divmod(key, n_add)                      # a > b, unique pairs
    e = a.size
    r = _ranks(np.concatenate([a, b]), np.concatenate([b, a]))
    keep = (r[:e] < max_deg) & (r[e:] < max_deg)
    truncated = 2 * int(e - np.sum(keep))
    a, b = a[keep], b[keep]
    src, dst = np.concatenate([a, b]), np.concatenate([b, a])
    rows = np.full((n_add, max_deg), -1, np.int32)     # arrival indices
    rows[src, _ranks(src, dst)] = dst

    # --- the event sequence ----------------------------------------------
    etype = np.empty(num_events, np.int32)
    vertex = np.empty(num_events, np.int32)
    partner = np.full(num_events, -1, np.int32)      # edge deletes' u
    present = np.zeros(n_add, bool)
    live: list[int] = []                 # present arrival indices
    slot = [-1] * n_add                  # position in `live`
    dead: set[tuple[int, int]] = set()
    nxt = 0
    ids_l = ids.tolist()
    integers = rng.integers

    for t, kind in enumerate(kinds.tolist()):
        if kind == EVENT_DEL_EDGE:
            v = live[int(integers(len(live)))]
            row = rows[v]
            cand = row[row >= 0]
            cand = [u for u in cand[present[cand]].tolist()
                    if (min(v, u), max(v, u)) not in dead]
            if cand:
                u = cand[int(integers(len(cand)))]
                dead.add((min(v, u), max(v, u)))
                etype[t], vertex[t], partner[t] = (EVENT_DEL_EDGE, ids_l[v],
                                                   ids_l[u])
                continue
            kind = EVENT_DEL_VERTEX          # v has no live edge left
        if kind == EVENT_DEL_VERTEX and live:
            v = live[int(integers(len(live)))]
            i, last = slot[v], live[-1]
            live[i], slot[last] = last, i
            live.pop()
            slot[v] = -1
            present[v] = False
            etype[t], vertex[t] = EVENT_DEL_VERTEX, ids_l[v]
            continue
        etype[t], vertex[t] = EVENT_ADD, ids_l[nxt]
        present[nxt] = True
        slot[nxt] = len(live)
        live.append(nxt)
        nxt += 1

    nbrs = np.full((num_events, max_deg), -1, np.int32)
    adds = np.flatnonzero(etype == EVENT_ADD)
    for lo in range(0, adds.size, block_rows):
        hi = min(lo + block_rows, adds.size)
        row = rows[lo:hi]
        nbrs[adds[lo:hi]] = np.where(row >= 0, ids[np.maximum(row, 0)], -1)
    dels = partner >= 0
    nbrs[dels, 0] = partner[dels]
    return Stream(etype=etype, vertex=vertex, nbrs=nbrs, n=n,
                  truncated_nbrs=truncated)


def poisson_arrivals(num_events: int, *, rate: float, mean_batch: float,
                     seed: int) -> tuple[np.ndarray, np.ndarray]:
    """Chop ``num_events`` events into arrival chunks with Poisson-process
    due times: chunk sizes are Poisson around ``mean_batch`` (at least 1,
    cut at the end), and the gap before each chunk is exponential with
    mean ``size / rate``, so the long-run event rate is ``rate``.

    Returns ``(bounds, due)``: chunk ``i`` is events
    ``bounds[i]:bounds[i+1]``, due ``due[i]`` seconds after the start."""
    if rate <= 0:
        raise ValueError(f"rate={rate} must be > 0 events/second")
    if mean_batch <= 0:
        raise ValueError(f"mean_batch={mean_batch} must be > 0 events")
    rng = np.random.default_rng(seed)
    sizes: list[int] = []
    total = 0
    while total < num_events:
        b = max(int(rng.poisson(mean_batch)), 1)
        b = min(b, num_events - total)
        sizes.append(b)
        total += b
    bounds = np.concatenate([[0], np.cumsum(sizes, dtype=np.int64)])
    gaps = rng.exponential(np.asarray(sizes, np.float64) / rate)
    return bounds, np.cumsum(gaps)


# --- the generator process ---------------------------------------------------

def _send_array(conn, arr: np.ndarray) -> None:
    view = memoryview(np.ascontiguousarray(arr)).cast("B")
    for lo in range(0, len(view), _PIPE_PIECE):
        conn.send_bytes(view[lo:lo + _PIPE_PIECE])


def _recv_array(conn, shape, dtype) -> np.ndarray:
    arr = np.empty(shape, dtype)
    view = memoryview(arr).cast("B")
    got = 0
    while got < len(view):
        got += conn.recv_bytes_into(view[got:])
    return arr


def _produce(conn, n: int, num_events: int, max_deg: int, seed: int,
             mix: dict) -> None:
    try:
        s = powerlaw_churn(n, num_events, max_deg=max_deg, seed=seed,
                           mix=ChurnMix(**mix))
    except Exception as e:  # noqa: BLE001 — reported to the parent
        conn.send(("error", repr(e)))
        return
    conn.send(("ok", s.truncated_nbrs))
    for arr in (s.etype, s.vertex, s.nbrs):
        _send_array(conn, arr)
    conn.close()


class StreamProcess:
    """Draws one churn stream in a child process; ``result()`` waits for it.

    Use as a context manager: leaving the block ends the child, whether or
    not its stream was taken."""

    def __init__(self, n: int, num_events: int, *, max_deg: int, seed: int,
                 mix: ChurnMix):
        self._args = (n, num_events, max_deg, seed)
        ctx = multiprocessing.get_context("spawn")
        self._conn, child = ctx.Pipe(duplex=False)
        self._proc = ctx.Process(
            target=_produce, name="bench-stream",
            args=(child, n, num_events, max_deg, seed,
                  dataclasses.asdict(mix)))
        self._proc.start()
        child.close()

    def result(self) -> Stream:
        n, num_events, max_deg, _ = self._args
        status, info = self._conn.recv()
        if status != "ok":
            raise RuntimeError(f"stream generation failed: {info}")
        etype = _recv_array(self._conn, (num_events,), np.int32)
        vertex = _recv_array(self._conn, (num_events,), np.int32)
        nbrs = _recv_array(self._conn, (num_events, max_deg), np.int32)
        self._proc.join(timeout=60)
        return Stream(etype, vertex, nbrs, n, int(info))

    def close(self) -> None:
        self._conn.close()
        if self._proc.is_alive():
            self._proc.kill()
        self._proc.join(timeout=60)

    def __enter__(self) -> "StreamProcess":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
