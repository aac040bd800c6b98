"""Stateful streaming session over the SDP engines — THE public surface.

The paper's headline is *real-time* dynamic partitioning, but the engine
entry points (``run_stream``/``run_stream_windowed``) are batch shaped:
whole stream in, final state out. ``Partitioner`` is the serving shape —
a long-lived session that owns a device-resident :class:`PartitionState`
and a global event cursor, and ingests events **as they arrive**:

    part = Partitioner.from_stream(stream, cfg, policy="sdp")
    for chunk in arriving_chunks:
        part.feed(chunk)            # any number of events per call
        print(part.metrics())       # observable mid-stream
    part.snapshot("ckpts/session")  # resumable later via .restore()

Guarantees:

* **Bit-identity under any chopping.** ``feed()`` RNG-aligns every event
  via the engines' existing ``t0`` plumbing (``fold_in(key, global_index)``),
  so feeding in chunks of 1, 7, or anything else produces exactly the
  state one whole-stream ``run_stream`` produces — enforced by
  tests/test_api_partitioner.py.
* **Donated carry.** The session's state is donated to each feed call's
  jitted kernel, so XLA reuses the O(n·max_deg) adjacency buffers
  between calls instead of copying them. A dense session keeps the
  adjacency in the row-major device layout its programs take
  (``adj_format``), so no call moves it between layouts either.
  Corollary: a reference you took from ``part.state`` is invalidated by
  the *next* ``feed()`` — copy (``np.asarray``) anything you want to
  keep, or use ``snapshot()``.
* **Auto engine selection.** Per call, full windows of ``window`` events
  ride the batched mixed-window program (the factored window of
  ``repro.runtime.factored_window``, the body the vertex-sharded session
  runs too, or the small-carry ``run_window_adds`` for pure-ADD windows)
  and small tails ride the faithful per-event scan; both are
  bit-identical, so the choice is pure throughput.
  ``engine="scan"``/``"windowed"`` pin one backend
  (``collect_trace=True`` implies the scan, the only backend that
  produces per-event traces).
* **Resumability.** ``snapshot()``/``Partitioner.restore()`` wrap
  ``repro.checkpoint`` (atomic renames, retention); checkpoints record
  their geometry in metadata, and checkpoints that predate
  ``PartitionState.cut_matrix`` restore via ``fill_missing`` and are
  healed with ``recount_cut_matrix``.
* **Elastic geometry — both directions.** The session's ``(n, max_deg)``
  allocation is a starting point, not a contract: ``feed()`` grows the
  state (``repro.core.state.grow_state``) along power-of-two tiers
  whenever an event references a vertex id or neighbour-row width beyond
  the current geometry — a semantics no-op, so a session started tiny
  and grown on demand stays bit-identical to one presized at the final
  geometry (see repro.core.geometry; LDG is the one knob-level
  exception). Each tier change re-jits the kernels (donation keeps
  reusing buffers within a tier); ``grow_to()`` pre-sizes explicitly to
  pay one re-jit instead of log-many. Sessions also shrink:
  ``compact()`` densely re-packs the live vertices to the smallest tier,
  ``shrink_to()`` targets an exact geometry, and ``maybe_shrink()`` (or
  ``auto_shrink=True``) applies the hysteretic ``shrink_tier`` policy so
  a session that bulk-deleted most of its graph stops paying peak-tier
  memory and compute. Every change is recorded in ``geometry_events``.

External vs internal vertex ids
-------------------------------
A compaction may *relabel* vertices (dense re-pack via a permutation).
The session hides that: callers keep using the original ("external") ids
in events and queries, and the session maintains the external→internal
map (persisted by ``snapshot()``/``restore()``), exposed as
``to_internal``/``to_external``. Until the first relabeling compaction
the map is the identity and costs nothing — pure truncation shrinks
(``shrink_state``) preserve ids and never create a map. Relabeling is a
semantics no-op for every policy except ``hash`` (which assigns by raw
vertex id — relabel-compaction refuses it) and LDG's allocated-``n``
capacity knob (the PR-5 caveat, which any geometry change already
carries).
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax._src import config as jax_config
from jax.experimental.layout import Format, Layout
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.checkpoint.manager import CheckpointManager
from repro.core import engine as eng
from repro.core import windowed as wnd
from repro.core.config import EngineConfig, POLICIES
from repro.core.geometry import (
    Geometry, geometry_of, grow_tier, next_pow2, shrink_tier,
)
from repro.core.state import (
    PartitionState, compact_state, grow_state, init_state, live_extent,
    recount_cut_matrix, shrink_state, state_bytes, state_metrics,
)
from repro.core.sharded_state import (
    gather_state, init_sharded_state, pad_rows, per_device_state_bytes,
    shard_state, state_shardings, unshard_state,
)
from repro.core.transition import EventTrace
from repro.core.metrics import load_imbalance, normalized_load_imbalance
from repro.graph.stream import (
    EVENT_ADD, EVENT_PAD, VertexStream, normalize_rows, required_geometry_of,
)
from repro.rebalance import rebalance_jit
from repro.runtime import telemetry
from repro.runtime.factored_window import run_window_factored

_ENGINES = ("auto", "scan", "windowed")
# the programs a feed dispatches, as ``metrics()["windows"]`` counts them:
# the per-event scan, the dense ADD-only and mixed window programs (and
# their Pallas forms under use_kernel), and the vertex-sharded window
_PATHS = ("scan", "adds", "mixed", "adds_kernel", "mixed_kernel", "sharded")

# The dense session keeps ``state.adj`` in ONE device format, row-major,
# the layout its programs read and write whole rows in. A TPU's default
# for an (n, max_deg) int32 array is column-major (a row-major 192-lane row
# pads to 256), and a program bound with the default would move the whole
# adjacency to row-major on entry and back on exit: a copy each way, and
# a second adjacency as temp. So every session program pins ``adj`` to
# ``adj_format`` on input and output, ``init_state`` builds it there, and
# ``Partitioner._pin`` re-places a state any other step produced. A
# vertex-sharded session keeps each chip's row block in the same layout:
# its window needs none, but a row gather from a column-major block (the
# benchmark's state check reads the touched rows) copies the whole block
# to row-major first. On a CPU row-major is the default and the pin
# changes nothing.
ADJ_LAYOUT = Layout(major_to_minor=(0, 1))   # tiling left to the compiler


def adj_format(sharding) -> Format:
    """The device format of a session's ``adj`` on ``sharding``: one
    device's whole block, or each chip's row block on a vertices mesh."""
    return Format(ADJ_LAYOUT, sharding)


def _state_formats(fmt: Format) -> PartitionState:
    """Placements of a dense state whose ``adj`` is in ``fmt``: every
    other leaf on the same device in its default layout."""
    s = fmt.sharding
    return PartitionState(*[s] * len(PartitionState._fields))._replace(
        adj=fmt)


def _donated(body, fmt: Format, n_inputs: int, *, trace: bool = False,
             **statics):
    """``body(state, *inputs, **statics)`` jitted with the state donated
    and ``adj`` in ``fmt`` on both sides, so donation aliases it in place
    from window to window."""
    st, s = _state_formats(fmt), fmt.sharding
    return jax.jit(functools.partial(body, **statics),
                   in_shardings=(st,) + (s,) * n_inputs,
                   out_shardings=(st, s) if trace else st,
                   donate_argnums=(0,))


# The session's programs, one per (adj format, statics). The session
# rebinds its carried state to each call's result, so donation lets XLA
# reuse the (n, max_deg) adjacency (and (k_max, k_max) cut_matrix)
# buffers between feed() calls instead of copying them per call.
@functools.lru_cache(maxsize=None)
def _scan_donated(fmt: Format, policy: str, cfg: EngineConfig):
    return _donated(eng._run_events, fmt, 4, trace=True, policy=policy,
                    cfg=cfg)


@functools.lru_cache(maxsize=None)
def _adds_donated(fmt: Format, policy: str, cfg: EngineConfig,
                  score_fn=None):
    return _donated(wnd._run_window_adds, fmt, 3, policy=policy, cfg=cfg,
                    score_fn=score_fn)


@functools.lru_cache(maxsize=None)
def _mixed_donated(fmt: Format, policy: str, cfg: EngineConfig):
    """The mixed-window program: the factored body the vertex-sharded
    step runs too, with the whole state as one row block."""
    return _donated(run_window_factored, fmt, 4, policy=policy, cfg=cfg)


@functools.lru_cache(maxsize=None)
def _mixed_fused_donated(fmt: Format, policy: str, cfg: EngineConfig,
                         interpret: bool | None = None,
                         variant: str = "pallas"):
    """The fused Pallas mixed-window kernel — imported lazily so sessions
    that never set ``use_kernel=True`` do not import the kernels layer at
    all."""
    from repro.kernels.fused_chooser.ops import _run_window_mixed_fused
    return _donated(_run_window_mixed_fused, fmt, 4, policy=policy, cfg=cfg,
                    interpret=interpret, variant=variant)


def _uncached(fn):
    """``fn`` with the programs it compiles kept out of JAX's persistent
    compilation cache. JAX 0.9 restores a cached executable without the
    entry layouts it was compiled for: its outputs come back in the
    default layout. So each process compiles the programs that pin
    ``adj`` itself (a few seconds in all); no process writes them, so no
    read finds them."""
    @functools.wraps(fn)
    def call(*args, **kwargs):
        with jax_config.persistent_cache_min_compile_time_secs(math.inf):
            return fn(*args, **kwargs)
    return call


@functools.lru_cache(maxsize=None)
def _init_pinned(n: int, max_deg: int, k_max: int, k_init: int,
                 fmt: Format):
    """``init_state`` built straight into ``fmt``'s layout (the PRNG key,
    drawn from the seed on the host, is its argument), so the adjacency
    never exists in two layouts at once."""
    return jax.jit(
        lambda key: init_state(n, max_deg, k_max, k_init)._replace(key=key),
        out_shardings=_state_formats(fmt))


def _resolve_vertices_mesh(devices):
    """Constructor/``reshard`` device selection: None = every local
    device, int = the first N, sequence = exactly those."""
    from repro.launch.mesh import make_vertices_mesh
    if devices is None:
        return make_vertices_mesh()
    if isinstance(devices, int):
        return make_vertices_mesh(devices)
    return make_vertices_mesh(devices=list(devices))


_TRACE_DTYPES = (jnp.int32, jnp.int32, jnp.int32, jnp.float32)


class PreparedChunk(NamedTuple):
    """The host-side half of a ``feed()``: validated, dtype-coerced event
    arrays plus their ingestion requirement. Produced by
    ``Partitioner.prepare`` — which touches no session state, so a
    serving thread may prepare chunk *t+1* while the device executes
    chunk *t* (repro.api.serve) — and consumed by ``feed_prepared``.
    Chunks over the same session concatenate associatively: feeding two
    merged chunks is bit-identical to feeding them back to back."""

    etype: np.ndarray    # (T,) int32 event codes
    vertex: np.ndarray   # (T,) int32 subject vertices
    nbrs: np.ndarray     # (T, width) int32 neighbour rows, -1 padded
    required: Geometry   # minimal geometry able to ingest these events

    @property
    def num_events(self) -> int:
        return int(self.etype.shape[0])


class Partitioner:
    """A stateful streaming partitioning session (see module docstring).

    Args:
      cfg: engine knobs (validated in ``EngineConfig.__post_init__``).
      n: starting vertex-universe size. Optional — the session grows its
        geometry on demand (tier-doubling, see module docstring), so a
        serving session whose stream size nobody knows can start with no
        pre-sizing at all; declare it (or use ``from_stream`` /
        ``grow_to``) to avoid the growth re-jits when the size IS known.
      max_deg: starting neighbour-row width of the padded adjacency
        (optional, grows like ``n``).
      policy: one of ``repro.core.config.POLICIES``.
      seed: PRNG seed for tie-breaking (folds with the global event index).
      engine: ``"auto"`` (default — windows when a call has them, scan for
        the tails), ``"scan"``, or ``"windowed"`` (tails are padded into a
        full window of no-op events).
      window: events per device step for the windowed backend.
      collect_trace: record the per-event :class:`EventTrace`; forces the
        scan backend (the window kernels return no trace).
      use_kernel: route full windows through the Pallas kernels —
        pure-ADD windows score with ``partition_affinity``, mixed windows
        run the whole slot loop in the fused chooser
        (``repro.kernels.fused_chooser``); both bit-identical to the XLA
        paths, interpret mode resolved per backend at one site
        (``repro.kernels.common.default_interpret``). Coverage is NOT
        total: the per-event scan backend — ``engine="scan"``,
        ``collect_trace``, and ``engine="auto"``'s small tails — always
        runs pure XLA (it is the faithful reference the kernels are
        verified against). ``metrics()["windows"]`` counts the
        dispatches of each path, so a session can tell how much of its
        stream actually rode the kernels.
      auto_shrink: run the hysteretic ``maybe_shrink()`` check every
        ``shrink_every`` ingested events, so a long-lived session whose
        graph bulk-deleted drops back down the tiers without anyone
        calling ``compact()``. Off by default — serving tiers usually
        prefer the idle-window drain-compact (repro.api.serve).
      shrink_every: event spacing of the ``auto_shrink`` checks (the
        check itself syncs the device, so it is not free).
      auto_rebalance: run ``rebalance()`` every ``rebalance_every``
        ingested events (checked at feed boundaries, *before* the
        auto-shrink check), so a session on a drifting stream repairs
        its cut and balance without anyone calling ``rebalance()``.
        Off by default; see ``repro.rebalance`` for the passes.
      rebalance_every: event spacing of the ``auto_rebalance`` checks
        (the pass itself syncs the device, so it is not free).
      rebalance_m: default migration budget per ``rebalance()`` — the
        top-m worst-gain boundary vertices are moved greedily.
      rebalance_passes: default LPA refinement iterations per
        ``rebalance()`` (0 = greedy migration only).
      rebalance_slack: Eq. 10 capacity slack — no rebalance move may
        push a destination beyond mean active load × (1 + slack).
      rebalance_drift: adaptive rebalance cadence — after each feed,
        fire ``rebalance()`` when the observed cut ratio OR the load
        imbalance has drifted more than this much above its value at the
        last pass (both read from counters the engines already maintain;
        no extra device work). Independent of ``auto_rebalance``'s fixed
        event spacing; the two compose (fixed cadence is checked first).
        The drift baseline re-bases after every executed pass and rides
        checkpoint ``extras``.
      sharded: shard THIS session's vertex axis across the device mesh
        (repro.runtime.shard_session): adjacency rows, label journal and
        presence live as per-device row blocks on a "vertices" mesh,
        K-sized loads and the cut matrix stay replicated and are
        psum-combined once per window. Bit-identical to a dense session
        for any device count. Implies the windowed backend for every
        slice (tails are padded into no-op slots); incompatible with
        ``use_kernel``, ``collect_trace`` and ``engine="scan"``.
      shard_devices: device selection for ``sharded=True`` — an int
        (first N local devices), an explicit device sequence, or None
        for every local device.
    """

    def __init__(self, cfg: EngineConfig | None = None, *,
                 n: int | None = None, max_deg: int | None = None,
                 policy: str = "sdp", seed: int = 0,
                 engine: str = "auto", window: int = 256,
                 collect_trace: bool = False, use_kernel: bool = False,
                 auto_shrink: bool = False, shrink_every: int = 4096,
                 auto_rebalance: bool = False, rebalance_every: int = 2048,
                 rebalance_m: int = 32, rebalance_passes: int = 0,
                 rebalance_slack: float = 0.25,
                 rebalance_drift: float | None = None,
                 sharded: bool = False, shard_devices=None):
        cfg = cfg or EngineConfig()
        if policy not in POLICIES:
            raise ValueError(
                f"policy={policy!r} is unknown: expected one of {POLICIES}")
        if engine not in _ENGINES:
            raise ValueError(
                f"engine={engine!r} is unknown: expected one of {_ENGINES} "
                "('auto' picks windows for full windows and the per-event "
                "scan for small tails)")
        if window <= 0:
            raise ValueError(
                f"window={window} must be > 0: it is the number of events "
                "the windowed backend batches per device step")
        if (n is not None and n <= 0) or (max_deg is not None
                                          and max_deg <= 0):
            raise ValueError(
                f"n={n} and max_deg={max_deg} must be > 0 (or omitted to "
                "grow on demand): they size the dense (n, max_deg) "
                "adjacency")
        if collect_trace and engine == "windowed":
            raise ValueError(
                "collect_trace=True needs the per-event scan (the window "
                "kernels do not produce traces) — use engine='scan' or "
                "'auto'")
        if sharded:
            if use_kernel:
                raise ValueError(
                    "sharded=True routes windows through the shard_map'd "
                    "window step, which runs the chooser oracle replicated "
                    "— it cannot also run the Pallas fused kernel; drop "
                    "use_kernel")
            if collect_trace or engine == "scan":
                raise ValueError(
                    "sharded=True processes every slice as (padded) "
                    "windows on the vertices mesh — the per-event scan "
                    "backend (engine='scan' / collect_trace=True) has no "
                    "sharded counterpart")
        self.cfg = cfg
        self.policy = policy
        self.engine = engine
        self.window = int(window)
        self.collect_trace = bool(collect_trace)
        self.use_kernel = bool(use_kernel)
        if use_kernel:
            from repro.kernels.partition_affinity.ops import scores_for_state
            self._score_fn = scores_for_state
        else:
            self._score_fn = None
        if shrink_every <= 0:
            raise ValueError(
                f"shrink_every={shrink_every} must be > 0: it is the "
                "event spacing of the auto_shrink checks")
        self.auto_shrink = bool(auto_shrink)
        self.shrink_every = int(shrink_every)
        if rebalance_every <= 0:
            raise ValueError(
                f"rebalance_every={rebalance_every} must be > 0: it is "
                "the event spacing of the auto_rebalance checks")
        if rebalance_m < 0 or rebalance_passes < 0 or rebalance_slack < 0:
            raise ValueError(
                f"rebalance_m={rebalance_m}, rebalance_passes="
                f"{rebalance_passes} and rebalance_slack={rebalance_slack} "
                "must all be >= 0 (m is a move budget, passes an iteration "
                "count, slack a capacity fraction)")
        if auto_rebalance and rebalance_m == 0 and rebalance_passes == 0:
            raise ValueError(
                "auto_rebalance=True with rebalance_m=0 and "
                "rebalance_passes=0 would run empty passes forever — give "
                "it a migration budget (rebalance_m) and/or LPA "
                "iterations (rebalance_passes)")
        self.auto_rebalance = bool(auto_rebalance)
        self.rebalance_every = int(rebalance_every)
        self.rebalance_m = int(rebalance_m)
        self.rebalance_passes = int(rebalance_passes)
        self.rebalance_slack = float(rebalance_slack)
        if rebalance_drift is not None:
            if rebalance_drift <= 0:
                raise ValueError(
                    f"rebalance_drift={rebalance_drift} must be > 0: it "
                    "is the cut-ratio / imbalance increase (since the "
                    "last pass) that triggers an adaptive rebalance")
            if rebalance_m == 0 and rebalance_passes == 0:
                raise ValueError(
                    "rebalance_drift with rebalance_m=0 and "
                    "rebalance_passes=0 would fire empty passes — give "
                    "it a migration budget and/or LPA iterations")
        self.rebalance_drift = (None if rebalance_drift is None
                                else float(rebalance_drift))
        self._drift_base: tuple[float, float] | None = None
        self._drift_fires = 0
        self._last_rebalance = 0
        self._rebalances = 0
        self._rebalance_moves = 0
        self._rebalance_events: list[dict] = []
        self._windows = dict.fromkeys(_PATHS, 0)
        self._pad_slots = 0
        self._relayouts = 0
        self._sharded = bool(sharded)
        self._mesh = None
        self._fmt = None
        if self._sharded:
            self._mesh = _resolve_vertices_mesh(shard_devices)
            self._fmt = adj_format(state_shardings(self._mesh).adj)
            # semantic geometry: the tier a dense session would sit at —
            # what the knobs (LDG capacity) and checkpoints see; the
            # physical row count is padded to a multiple of the mesh
            self._sem_geom = Geometry(int(n or 1), int(max_deg or 1),
                                      cfg.k_max)
            self._state = _uncached(init_sharded_state)(
                *self._sem_geom, cfg.k_init, seed, self._mesh, ADJ_LAYOUT)
        else:
            self._bind(SingleDeviceSharding(jax.devices()[0]))
            self._state = _uncached(_init_pinned(
                int(n or 1), int(max_deg or 1), cfg.k_max, cfg.k_init,
                self._fmt))(jax.random.PRNGKey(seed))
        self._regeometries = 0
        self._shrinks = 0
        self._compactions = 0
        self._last_shrink_check = 0
        self._cursor = 0
        # external→internal vertex-id map (None = identity: no relabeling
        # compaction has happened) and its dense inverse — see the module
        # docstring's "External vs internal vertex ids"
        self._ext2int: np.ndarray | None = None
        self._int2ext: np.ndarray | None = None
        self._geometry_events: list[dict] = []
        self._traces: list[EventTrace] = []
        self._managers: dict[str, CheckpointManager] = {}

    @classmethod
    def from_stream(cls, stream: VertexStream,
                    cfg: EngineConfig | None = None, **kw) -> "Partitioner":
        """Size a session for ``stream``'s vertex universe and degree cap
        — its declared geometry unioned with ``required_geometry()``, the
        same definition the feed-time auto-grow check uses (the stream
        itself is NOT ingested — call ``feed``)."""
        geom = Geometry(stream.n, stream.max_deg).union(
            stream.required_geometry())
        return cls(cfg, n=geom.n, max_deg=geom.max_deg, **kw)

    # -- properties ---------------------------------------------------------

    @property
    def state(self) -> PartitionState:
        """The live device-resident state. Invalidated (donated) by the
        next ``feed()`` — copy what you want to keep."""
        return self._state

    @property
    def n(self) -> int:
        """Current vertex-universe allocation (grows on demand, shrinks
        via ``compact``/``shrink_to``/``maybe_shrink``). Internal slots —
        after a relabeling compaction this is smaller than the external
        id space (see ``to_internal``)."""
        return int(self._state.assignment.shape[0])

    @property
    def max_deg(self) -> int:
        """Current neighbour-row width (grows on demand, shrinks via
        ``compact``/``shrink_to``/``maybe_shrink``)."""
        return int(self._state.adj.shape[1])

    @property
    def geometry(self) -> Geometry:
        """The session's current :class:`Geometry` (n, max_deg, k_max)."""
        return geometry_of(self._state)

    @property
    def regeometries(self) -> int:
        """How many times the state geometry changed (grow, shrink or
        tier-changing compact) — each one re-jits the engine kernels for
        the new tier."""
        return self._regeometries

    @property
    def geometry_events(self) -> list[dict]:
        """The session's geometry lifecycle trace: one
        ``{"cursor", "kind", "from", "to"}`` dict per change, ``kind`` in
        ``{"grow", "shrink", "compact", "restore"}`` and ``from``/``to``
        the :class:`Geometry` before/after. ``compact`` entries are
        same-tier re-packs; tier-dropping re-packs record ``shrink``."""
        return list(self._geometry_events)

    @property
    def rebalance_events(self) -> list[dict]:
        """The session's rebalance lifecycle trace (mirrors
        ``geometry_events``): one ``{"cursor", "m", "passes", "moved",
        "cut_before", "cut_after", "imbalance_before",
        "imbalance_after"}`` dict per executed ``rebalance()``."""
        return list(self._rebalance_events)

    @property
    def cursor(self) -> int:
        """Global index of the next event (== events ingested so far)."""
        return self._cursor

    def __repr__(self) -> str:
        return (f"Partitioner(policy={self.policy!r}, engine={self.engine!r},"
                f" n={self.n}, max_deg={self.max_deg}, events={self._cursor},"
                f" partitions={int(self._state.num_partitions)})")

    # -- geometry -----------------------------------------------------------

    def _record_geometry(self, kind: str, before: Geometry,
                         after: Geometry) -> None:
        self._geometry_events.append(
            {"cursor": self._cursor, "kind": kind,
             "from": before, "to": after})

    def grow_to(self, n: int | None = None,
                max_deg: int | None = None) -> "Partitioner":
        """Explicitly pre-size the session geometry (exact — no tier
        rounding: the caller knows the size). Grows the state to cover
        ``(n, max_deg)``; dimensions already covered are untouched, and
        shrinking is never performed (that is ``shrink_to``). Use before
        a large ``feed`` to pay one re-jit instead of log-many tier
        doublings."""
        cur = self._sem_geometry()
        target = cur.union(Geometry(int(n or 1), int(max_deg or 1)))
        if target != cur:
            self._grow(cur, target)
        return self

    def _sem_geometry(self) -> Geometry:
        """The session's *semantic* geometry: what a dense session would
        allocate. For a sharded session the physical row count is this,
        padded up to a multiple of the mesh; the semantic n is what the
        knobs (LDG capacity) and checkpoint metadata see, so sharded and
        dense sessions stay bit-identical and round-trip."""
        return self._sem_geom if self._sharded else geometry_of(self._state)

    def _grow(self, cur: Geometry, target: Geometry) -> None:
        if self._sharded:
            phys = Geometry(pad_rows(target.n, self._mesh.shape["vertices"]),
                            target.max_deg, target.k_max)
            self._state = self._pin(
                grow_state(unshard_state(self._state), phys))
            self._sem_geom = target
        else:
            self._state = self._pin(grow_state(self._state, target))
        self._regeometries += 1
        self._record_geometry("grow", cur, target)

    def _ensure_geometry(self, required: Geometry) -> None:
        """Grow the state along power-of-two tiers until it covers
        ``required`` (no-op when it already does) — the feed-time
        auto-grow. Growth is a semantics no-op (repro.core.geometry), so
        donation simply resumes at the new tier after one re-jit. The
        tier trigger compares the SEMANTIC geometry, so a sharded
        session grows at exactly the cursors its dense twin would."""
        cur = self._sem_geometry()
        if not cur.covers(required):
            self._grow(cur, grow_tier(cur, required))

    def _repack_to(self, target: Geometry, kind: str) -> None:
        """Move the (synced) state to ``target``, preferring the
        id-preserving truncation (``shrink_state`` — no permutation, no
        translation overhead afterwards) and falling back to the
        relabeling dense re-pack (``compact_state``) when live content
        sits above ``target.n``. Updates the id maps and the lifecycle
        trace; callers guarantee ``target`` covers the packed extent."""
        cur = geometry_of(self._state)
        if target == cur or (self._sharded and target == self._sem_geom):
            return
        _, prefix = live_extent(self._state)
        if prefix.n <= target.n and prefix.max_deg <= target.max_deg:
            state = shrink_state(self._state, target)
        else:
            if self.policy == "hash":
                raise ValueError(
                    "the 'hash' policy assigns by raw vertex id, so a "
                    "relabeling compaction would change every future "
                    "decision — only id-preserving shrinks are legal "
                    "(shrink_to a geometry the current slot ids fit, or "
                    "accept the current tier)")
            state, perm = compact_state(self._state, target)
            self._apply_perm(perm)
        if self._sharded:
            # repacks land dense at the semantic target; _pin pads the
            # rows back to a mesh multiple
            self._sem_geom = target
        self._state = self._pin(state)
        self._regeometries += 1
        if kind == "shrink":
            self._shrinks += 1
        self._record_geometry(kind, cur, target)

    def _apply_perm(self, perm: np.ndarray) -> None:
        """Fold a relabeling permutation (old slot → new slot, -1 =
        dropped) into the external→internal id maps. First relabel:
        external ids ARE the old slots, so the map starts as ``perm``
        itself."""
        n_old = len(perm)
        keep_idx = np.flatnonzero(perm >= 0).astype(np.int32)
        if len(keep_idx) == n_old:
            return  # nothing moved or dropped — still the identity
        if self._ext2int is None:
            self._ext2int = perm.astype(np.int32).copy()
            self._int2ext = keep_idx
        else:
            self._int2ext = self._int2ext[keep_idx]
            m = self._ext2int
            valid = m >= 0
            m[valid] = perm[m[valid]]
            self._ext2int = m

    def compact(self) -> "Partitioner":
        """Densely re-pack the live vertices and drop to the smallest
        power-of-two tier that holds them — the explicit "reclaim now"
        seam (no hysteresis: the caller has decided). Prefers the
        id-preserving truncation; otherwise relabels and maintains the
        external-id map so ``feed``/``where``/``route`` keep speaking
        original ids (see the module docstring). A semantics no-op
        modulo that relabeling; counters are untouched. Syncs (it must
        read the live content). Returns ``self``."""
        self.sync()
        cur = geometry_of(self._state)
        packed, _ = live_extent(self._state)
        target = Geometry(min(next_pow2(packed.n), cur.n),
                          min(next_pow2(packed.max_deg), cur.max_deg),
                          cur.k_max)
        self._compactions += 1
        self._repack_to(target, "shrink" if (target.n < cur.n
                        or target.max_deg < cur.max_deg) else "compact")
        return self

    def shrink_to(self, n: int | None = None,
                  max_deg: int | None = None) -> "Partitioner":
        """Shrink the session geometry to exactly ``(n, max_deg)``
        (omitted dimensions keep their current size) — the precise
        counterpart of ``grow_to``. Truncates when the live slot ids
        already fit, otherwise densely re-packs (relabeling, see
        ``compact``). Raises if the live content cannot fit the target
        even packed, or if a dimension would grow (use ``grow_to``)."""
        self.sync()
        cur = geometry_of(self._state)
        target = Geometry(int(n if n is not None else cur.n),
                          int(max_deg if max_deg is not None
                              else cur.max_deg), cur.k_max)
        if target.n > cur.n or target.max_deg > cur.max_deg:
            raise ValueError(
                f"shrink_to target (n={target.n}, max_deg={target.max_deg})"
                f" exceeds the current geometry (n={cur.n}, "
                f"max_deg={cur.max_deg}) — growing is grow_to's job")
        packed, _ = live_extent(self._state)
        if not Geometry(target.n, target.max_deg).covers(
                Geometry(packed.n, packed.max_deg)):
            raise ValueError(
                f"live content needs (n={packed.n}, "
                f"max_deg={packed.max_deg}) even densely packed — "
                f"(n={target.n}, max_deg={target.max_deg}) cannot hold "
                "this session")
        self._repack_to(target, "shrink")
        return self

    def maybe_shrink(self, *, hysteresis: int = 4) -> bool:
        """The auto-shrink check: apply ``repro.core.geometry.shrink_tier``
        — shrink only when live content occupies at most
        ``1/(2*hysteresis)`` of a dimension, landing at most half-full —
        and re-pack if any dimension qualifies. Returns True iff the
        geometry changed. Cheap when there is nothing to do: a one-scalar
        device read gates the O(n·max_deg) host scan. This is what
        ``auto_shrink=True`` runs every ``shrink_every`` events, and what
        the serving tier runs in idle windows (repro.api.serve)."""
        cur = geometry_of(self._state)
        # gate on the present-count alone (an underestimate of the packed
        # extent, so it can only produce false positives for the scan
        # below, never a missed shrink of n; a max_deg-only shrink is
        # deliberately not gated in — it rides along when n qualifies or
        # when compact() is called explicitly)
        n_present = int(jnp.sum(self._state.present))
        if (n_present + 1) * 2 * hysteresis > cur.n:
            return False
        self.sync()
        packed, _ = live_extent(self._state)
        target = shrink_tier(cur, packed, hysteresis=hysteresis)
        if target == cur:
            return False
        self._repack_to(target, "shrink")
        return True

    def place(self, device) -> "Partitioner":
        """Move the session state onto ``device`` (a ``jax.Device``) via
        a host round-trip — the single-session re-mesh path: after a
        (simulated) device loss, a recovered or surviving session
        continues on the replacement device bit-identically (placement
        is not semantics). Syncs. Returns ``self``."""
        if self._sharded:
            raise ValueError(
                "this session is vertex-sharded across a device mesh — "
                "single-device place() does not apply; use "
                "reshard(devices=...) to move it onto a different mesh")
        self.sync()
        host = jax.tree_util.tree_map(np.asarray, self._state)
        self._bind(SingleDeviceSharding(device))
        self._state = self._pin(jax.device_put(host, device))
        return self

    def reshard(self, devices=None) -> "Partitioner":
        """Re-shard a ``sharded=True`` session onto a different vertices
        mesh (``devices``: int, device sequence, or None for every local
        device) — the sharded re-mesh path after a device-count change:
        gather to the canonical dense layout, rebuild the mesh, re-pad
        the rows to the new shard count, and re-place. Placement is not
        semantics, so the session continues bit-identically. Syncs.
        Returns ``self``."""
        if not self._sharded:
            raise ValueError(
                "reshard() applies to sharded=True sessions only — a "
                "dense session moves with place(device)")
        self.sync()
        dense = unshard_state(self._state, n=self._sem_geom.n)
        self._mesh = _resolve_vertices_mesh(devices)
        self._fmt = adj_format(state_shardings(self._mesh).adj)
        self._state = self._pin(dense)
        return self

    # -- external ids -------------------------------------------------------

    def to_internal(self, ids) -> np.ndarray:
        """Map external (caller-facing, original) vertex ids to the
        session's internal slot ids — the identity until a relabeling
        compaction happens. Unknown or negative ids map to -1. Queries
        against ``state.assignment`` must go through this (the serving
        tier does: repro.api.serve.where_many)."""
        ids = np.atleast_1d(np.asarray(ids, np.int64))
        if self._ext2int is None:
            return ids.astype(np.int32)
        m = self._ext2int
        out = np.full(ids.shape, -1, np.int32)
        ok = (ids >= 0) & (ids < len(m))
        out[ok] = m[ids[ok]]
        return out

    def to_external(self, ids) -> np.ndarray:
        """Inverse of ``to_internal``: internal slot ids back to the
        external ids callers speak (identity until a relabeling
        compaction). Out-of-range slots map to -1."""
        ids = np.atleast_1d(np.asarray(ids, np.int64))
        if self._int2ext is None:
            return ids.astype(np.int32)
        m = self._int2ext
        out = np.full(ids.shape, -1, np.int32)
        ok = (ids >= 0) & (ids < len(m))
        out[ok] = m[ids[ok]]
        return out

    def _translate(self, chunk: PreparedChunk) -> PreparedChunk:
        """Rewrite a prepared chunk's external ids to internal slots,
        allocating fresh slots (in first-appearance order — the property
        that makes a journal replay allocate identically) for ids never
        seen since the last relabeling. No-op while the map is the
        identity."""
        if self._ext2int is None:
            return chunk
        vx, nb = chunk.vertex, chunk.nbrs
        # event-order first-appearance sequence: vertex before its row
        seq = np.concatenate([vx[:, None], nb], axis=1).ravel()
        seq = seq[seq >= 0].astype(np.int64)
        m = self._ext2int
        if seq.size:
            mx = int(seq.max())
            if mx >= len(m):
                m = np.concatenate(
                    [m, np.full(mx + 1 - len(m), -1, np.int32)])
            unmapped = seq[m[seq] < 0]
            if unmapped.size:
                uniq, first = np.unique(unmapped, return_index=True)
                order = uniq[np.argsort(first)].astype(np.int32)
                base = len(self._int2ext)
                m[order] = np.arange(base, base + len(order),
                                     dtype=np.int32)
                self._int2ext = np.concatenate([self._int2ext, order])
            self._ext2int = m
        vx_t = np.where(vx >= 0, m[np.clip(vx, 0, None)], -1).astype(np.int32)
        nb_t = np.where(nb >= 0, m[np.clip(nb, 0, None)], -1).astype(np.int32)
        return PreparedChunk(chunk.etype, vx_t, nb_t,
                             required_geometry_of(vx_t, nb_t))

    # -- ingestion ----------------------------------------------------------

    def feed(self, events) -> "Partitioner":
        """Ingest any number of events; returns ``self`` for chaining.

        ``events`` is a :class:`VertexStream` (over the same vertex
        universe) or an ``(etype, vertex, nbrs)`` triple of arrays.
        Bit-identical to one whole-stream run regardless of how the
        stream is chopped across calls. Equivalent to
        ``feed_prepared(prepare(events))``; dispatch is asynchronous
        (JAX async dispatch) — call ``sync()`` to block on completion.
        """
        with telemetry.span("session.feed"):
            return self._feed_prepared(self.prepare(events))

    def prepare(self, events) -> PreparedChunk:
        """Host-only coercion: validate ``events`` (a
        :class:`VertexStream` or ``(etype, vertex, nbrs)`` triple),
        coerce dtypes, and compute the required ingestion geometry —
        WITHOUT touching session state. The expensive O(T·max_deg) host
        work of a ``feed`` lives here, so a serving loop
        (repro.api.serve) can run it on chunk *t+1* while the device
        executes chunk *t*. Thread-safe with respect to the session."""
        with telemetry.span("session.prepare"):
            if isinstance(events, VertexStream):
                et = np.asarray(events.etype, np.int32)
                vx = np.asarray(events.vertex, np.int32)
                nb = np.asarray(events.nbrs, np.int32)
                required = events.required_geometry()
            else:
                try:
                    et, vx, nb = events
                except (TypeError, ValueError):
                    raise TypeError(
                        "feed() takes a VertexStream or an (etype, vertex, "
                        f"nbrs) triple, got {type(events).__name__}") \
                        from None
                et = np.atleast_1d(np.asarray(et, np.int32))
                vx = np.atleast_1d(np.asarray(vx, np.int32))
                nb = np.asarray(nb, np.int32)
                if nb.ndim != 2 or et.shape != vx.shape \
                        or nb.shape[0] != et.shape[0]:
                    raise ValueError(
                        f"event triple shapes disagree: etype{et.shape}, "
                        f"vertex{vx.shape}, nbrs{nb.shape} — want (T,), "
                        "(T,), (T, max_deg)")
                required = required_geometry_of(vx, nb)
            return PreparedChunk(et, vx, nb, required)

    def feed_prepared(self, chunk: PreparedChunk) -> "Partitioner":
        """Ingest a :class:`PreparedChunk` (see ``prepare``): grow the
        geometry if the chunk requires it, re-width the neighbour rows
        to the session, and dispatch the engine kernels. Dispatch is
        asynchronous — the call returns once the work is enqueued, and
        the carried state is a future until ``sync()`` (or any host
        read) blocks on it."""
        with telemetry.span("session.feed"):
            return self._feed_prepared(chunk)

    def _feed_prepared(self, chunk: PreparedChunk) -> "Partitioner":
        with telemetry.span("session.ingest"):
            # external ids → internal slots (identity until a relabeling
            # compaction; allocates slots for first-seen ids)
            chunk = self._translate(chunk)
            # elastic: events beyond the current geometry grow the state
            # (tier-doubled) instead of raising — the session's shapes
            # are a starting point, not a contract
            self._ensure_geometry(chunk.required)
            et, vx = chunk.etype, chunk.vertex
            nb = normalize_rows(chunk.nbrs, self.max_deg)
        T = chunk.num_events
        if T == 0:
            return self
        use_scan = self.collect_trace or self.engine == "scan"
        t = 0
        while t < T:
            if use_scan:
                end = T
                self._feed_scan(et[t:], vx[t:], nb[t:])
            else:
                end = min(t + self.window, T)
                if end - t < self.window and self.engine == "auto" \
                        and not self._sharded:
                    # small/mixed tail: the per-event scan beats padding a
                    # nearly-empty window through the batched kernel
                    end = T
                    self._feed_scan(et[t:], vx[t:], nb[t:])
                else:
                    self._feed_window(et[t:end], vx[t:end], nb[t:end])
            # advance per processed slice, not per call: if a later slice
            # dies (interrupt, OOM) the cursor still matches the mutated
            # state, so re-feeding the unprocessed remainder resumes
            # exactly instead of double-applying the finished slices
            self._cursor += end - t
            t = end
        # rebalance before the shrink check: migration changes loads and
        # therefore what maybe_shrink sees — the order is part of the
        # replay contract (both cadence marks ride checkpoint extras)
        if self.auto_rebalance and (self._cursor - self._last_rebalance
                                    >= self.rebalance_every):
            self._last_rebalance = self._cursor
            self.rebalance()
        if self.rebalance_drift is not None:
            self._check_drift()
        if self.auto_shrink and (self._cursor - self._last_shrink_check
                                 >= self.shrink_every):
            self._last_shrink_check = self._cursor
            self.maybe_shrink()
        return self

    def _dispatch(self, path: str, events: int, slots: int):
        """Count one program call of ``path`` carrying ``events`` true
        events in ``slots`` slots, and time its dispatch."""
        self._windows[path] += 1
        self._pad_slots += slots - events
        return telemetry.span("session.dispatch", path=path, events=events,
                              slots=slots)

    def _feed_scan(self, et, vx, nb):
        # the scan backend is outside the kernel surface (it is the
        # faithful reference): its one program call carries no padding
        with self._dispatch("scan", len(et), len(et)):
            self._state, tr = self._scan_fn(
                self._state, jnp.asarray(et), jnp.asarray(vx),
                jnp.asarray(nb), jnp.int32(self._cursor))
        if self.collect_trace:
            self._traces.append(tr)

    def _feed_window(self, et, vx, nb):
        """One (possibly right-padded) window through the batched kernels.
        Pad slots are no-ops that still occupy RNG indices past the true
        events — the cursor advances by the true count only, so the next
        call's fold_in indices line up with an unchopped run."""
        w = self.window
        if self._sharded:
            from repro.runtime.shard_session import sharded_stream_fn
            fn = _uncached(sharded_stream_fn(
                self._mesh, n_sem=self._sem_geom.n, policy=self.policy,
                cfg=self.cfg, window=w, n_events=w, adj_layout=ADJ_LAYOUT))
            with self._dispatch("sharded", len(et), w):
                # the window's event tensors, padded on the host, go to
                # every device of the mesh in one transfer
                pad = w - len(et)
                events = (np.pad(et, (0, pad), constant_values=EVENT_PAD),
                          np.pad(vx, (0, pad), constant_values=-1),
                          np.pad(nb, ((0, pad), (0, 0)), constant_values=-1),
                          np.int32(self._cursor))
                with telemetry.span("session.shard_put",
                                    bytes=sum(a.nbytes for a in events)):
                    events = jax.device_put(
                        events, NamedSharding(self._mesh, PartitionSpec()))
                self._state = fn(self._state, *events)
            return
        adds = bool(np.all(et == EVENT_ADD))
        path = ("adds" if adds else "mixed") \
            + ("_kernel" if self.use_kernel else "")
        with self._dispatch(path, len(et), w):
            vs_w = wnd._pad_to(vx, w, -1)
            rows_w = wnd._pad_to(nb, w, -1)
            t0 = jnp.int32(self._cursor)
            if adds:
                self._state = self._adds_fn(self._state, vs_w, rows_w, t0)
            else:
                self._state = self._mixed_fn(
                    self._state, wnd._pad_to(et, w, EVENT_PAD),
                    vs_w, rows_w, t0)

    def _bind(self, sharding) -> None:
        """Bind the dense session's programs to ``sharding``'s device,
        with ``adj`` in ``adj_format`` there."""
        self._fmt = adj_format(sharding)
        statics = (self._fmt, self.policy, self.cfg)
        self._scan_fn = _uncached(_scan_donated(*statics))
        self._adds_fn = _uncached(_adds_donated(*statics, self._score_fn))
        self._mixed_fn = _uncached((_mixed_fused_donated if self.use_kernel
                                    else _mixed_donated)(*statics))

    def _pin(self, state: PartitionState) -> PartitionState:
        """Place a state that a step outside the session's programs made
        (a regeometry, a repack, a rebalance, a restore, a ``place``, a
        ``reshard``) where those programs take it: ``adj`` in the format
        ``self._fmt`` (row-major; on one device, or in row blocks on the
        vertices mesh), moved only where its format differs. Each such
        move is a ``session.relayout`` span and counts in
        ``metrics()["relayouts"]``. A sharded state's other leaves go to
        their mesh placements either way."""
        adj = state.adj
        if (adj.format.layout.major_to_minor
                == self._fmt.layout.major_to_minor
                and adj.sharding == self._fmt.sharding):
            return self._place(state) if self._sharded else state
        with telemetry.span("session.relayout", bytes=adj.nbytes):
            if self._sharded:
                state = self._place(state)
            else:
                state = state._replace(adj=_uncached(jax.device_put)(
                    adj, self._fmt, donate=True))
        self._relayouts += 1
        return state

    def _place(self, state: PartitionState) -> PartitionState:
        """``state`` on the vertices mesh, rows padded to a mesh multiple
        and ``adj``'s blocks in ``ADJ_LAYOUT``."""
        return _uncached(shard_state)(state, self._mesh, ADJ_LAYOUT)

    def sync(self) -> "Partitioner":
        """Block until every dispatched feed has executed (feeds are
        asynchronous — JAX async dispatch). THE explicit query point:
        after ``sync()`` the carried state is materialized and host
        reads of it are free. Returns ``self`` for chaining."""
        jax.block_until_ready(self._state)
        return self

    # -- rebalancing --------------------------------------------------------

    def _drift_signals(self) -> tuple[float, float]:
        """(cut ratio, normalized load imbalance) from counters the
        engines already maintain — a host read of the live state, no new
        device work. Both are scale-free ratios (the imbalance is the
        mean-normalized Eq. 10 std), so ONE drift threshold compares
        meaningfully against either and does not loosen as the stream
        grows."""
        tot = int(self._state.total_edges)
        ratio = int(self._state.cut_edges) / tot if tot else 0.0
        imb = normalized_load_imbalance(np.asarray(self._state.edge_load),
                                        np.asarray(self._state.active))
        return ratio, float(imb)

    def _check_drift(self) -> bool:
        """The ``rebalance_drift`` cadence check (each feed boundary):
        fire a pass when either signal rose more than the threshold
        since the last pass (or since the first check — the baseline).
        Drops in either signal re-base nothing: only an executed pass
        (which re-reads both signals afterwards) moves the baseline, so
        slow monotone drift cannot creep under the threshold."""
        ratio, imb = self._drift_signals()
        if self._drift_base is None:
            self._drift_base = (ratio, imb)
            return False
        r0, i0 = self._drift_base
        if (ratio - r0) < self.rebalance_drift \
                and (imb - i0) < self.rebalance_drift:
            return False
        self._drift_fires += 1
        self._last_rebalance = self._cursor
        self.rebalance()
        return True

    def rebalance(self, m: int | None = None, passes: int | None = None,
                  slack: float | None = None) -> dict:
        """Run one between-windows rebalance over the live state: greedy
        migration of the top-``m`` worst-gain boundary vertices, then
        ``passes`` Spinner-style LPA iterations (see ``repro.rebalance``
        for both). Defaults come from the constructor knobs. Never
        touches the event RNG (``state.key``) or the cursor, so the
        session's *event* decisions stay bit-identical to an
        unrebalanced run; with ``m=0`` and ``passes=0`` the device state
        is not touched at all. Returns the recorded rebalance event
        (also appended to ``rebalance_events``). A query point: blocks
        on in-flight feeds."""
        m = self.rebalance_m if m is None else int(m)
        passes = self.rebalance_passes if passes is None else int(passes)
        slack = self.rebalance_slack if slack is None else float(slack)
        if m <= 0 and passes <= 0:
            return {"cursor": self._cursor, "m": 0, "passes": 0, "moved": 0}
        load0 = np.asarray(self._state.edge_load)
        act0 = np.asarray(self._state.active)
        self._state, stats = rebalance_jit(
            self._state, jnp.int32(self._cursor), jnp.float32(slack),
            jnp.float32(self.cfg.max_cap), True,
            m=min(m, self.n), passes=passes)
        # the rebalance jit commits to no particular output layout (under
        # GSPMD over a sharded state, to no sharding either)
        self._state = self._pin(self._state)
        ev = {"cursor": self._cursor, "m": m, "passes": passes,
              "moved": int(stats.moved),
              "cut_before": int(stats.cut_before),
              "cut_after": int(stats.cut_after),
              "imbalance_before": load_imbalance(load0, act0),
              "imbalance_after": load_imbalance(
                  np.asarray(self._state.edge_load),
                  np.asarray(self._state.active))}
        self._rebalances += 1
        self._rebalance_moves += ev["moved"]
        self._rebalance_events.append(ev)
        if self.rebalance_drift is not None:
            # re-base the drift detector on the post-pass signals — the
            # next fire needs fresh drift, not the residue of this one
            self._drift_base = self._drift_signals()
        return ev

    # -- observation --------------------------------------------------------

    def metrics(self) -> dict:
        """Paper metrics (Eq. 9 edge-cut ratio, Eq. 10 imbalance, scaling
        counters) of the state as of the last ``feed``, plus the session
        counters (``cursor`` — also under its historical name
        ``events_ingested`` — and the elastic-geometry counters), so
        observers like ``repro.api.serve.PartitionService`` report them
        without reaching into privates. Blocks on in-flight feeds (a
        query point)."""
        m = state_metrics(self._state)
        m["events_ingested"] = self._cursor
        m["cursor"] = self._cursor
        m["n"] = self.n
        m["max_deg"] = self.max_deg
        m["regeometries"] = self._regeometries
        m["shrinks"] = self._shrinks
        m["compactions"] = self._compactions
        m["state_bytes"] = state_bytes(self._state)
        # program calls per path (a scan slice counts as one call) and
        # the no-op slots that padded the windows among them
        m["windows"] = dict(self._windows)
        m["pad_slots"] = self._pad_slots
        # moves of a dense session's adj into its programs' layout (_pin)
        m["relayouts"] = self._relayouts
        m["rebalances"] = self._rebalances
        m["rebalance_moves"] = self._rebalance_moves
        m["rebalance_drift_fires"] = self._drift_fires
        # vertex-sharding split: how many devices carry this session's
        # row blocks, and the peak per-device resident bytes (each
        # device pays its blocks + a full copy of the replicated K-state;
        # degenerates to ~state_bytes on a dense session)
        m["shard_devices"] = (self._mesh.shape["vertices"]
                              if self._sharded else 1)
        m["per_device_state_bytes"] = per_device_state_bytes(self._state)
        return m

    def trace(self) -> EventTrace:
        """The per-event trace of everything ingested so far (requires
        ``collect_trace=True``)."""
        if not self.collect_trace:
            raise RuntimeError(
                "this session does not collect per-event traces — construct"
                " Partitioner(..., collect_trace=True) (forces the scan "
                "backend, which is the one producing traces)")
        if not self._traces:
            return EventTrace(*(jnp.zeros((0,), dt) for dt in _TRACE_DTYPES))
        if len(self._traces) > 1:
            merged = EventTrace(*(
                jnp.concatenate([getattr(tr, f) for tr in self._traces])
                for f in EventTrace._fields))
            self._traces = [merged]
        return self._traces[0]

    # -- persistence --------------------------------------------------------

    def snapshot(self, directory: str, *, keep: int = 3,
                 blocking: bool = True) -> int:
        """Checkpoint the session under ``directory`` (atomic rename,
        ``keep`` most recent retained) via ``repro.checkpoint``. The
        checkpoint step IS the event cursor; returns it. ``blocking=False``
        writes on a background thread (the state is host-snapshotted
        synchronously first, so a following ``feed`` cannot race it); the
        session keeps one manager per directory, so the next snapshot to
        the same directory — or ``wait()`` — joins the pending writer."""
        mgr = self._managers.get(directory)
        if mgr is None:
            mgr = CheckpointManager(directory, interval=1, keep=keep)
            self._managers[directory] = mgr
        else:
            mgr.keep = keep
        extras = {}
        if self._ext2int is not None:
            extras["ext2int"] = self._ext2int
        if self._last_shrink_check:
            # persist the auto-shrink cadence mark so a restored session
            # checks at the same cursors the original would have
            extras["shrink_mark"] = np.asarray([self._last_shrink_check],
                                               np.int64)
        if self._last_rebalance:
            # same contract for the auto-rebalance cadence: a restored
            # session rebalances at the cursors the original would have
            extras["rebalance_mark"] = np.asarray([self._last_rebalance],
                                                  np.int64)
        if self._drift_base is not None:
            # the adaptive-cadence baseline rides along, so a restored
            # session fires its next drift pass where the original would
            extras["drift_base"] = np.asarray(self._drift_base, np.float64)
        tree, geom = self._state, geometry_of(self._state)
        if self._sharded:
            # persist the gathered CANONICAL layout (row padding sliced
            # off, semantic geometry recorded) so sharded and dense
            # sessions — and different mesh widths — round-trip
            # interchangeably
            tree, geom = gather_state(
                self._state, n=self._sem_geom.n), self._sem_geom
        mgr.save_now(self._cursor, tree, blocking=blocking,
                     geometry=geom, extras=extras or None)
        return self._cursor

    def wait(self) -> None:
        """Join any background snapshot writers (no-op if none pending) —
        call before process exit when using ``snapshot(blocking=False)``."""
        for mgr in self._managers.values():
            mgr.wait()

    @classmethod
    def restore(cls, directory: str, cfg: EngineConfig | None = None, *,
                n: int | None = None, max_deg: int | None = None,
                step: int | None = None, **kw) -> "Partitioner":
        """Resume a session from ``snapshot()`` output (default: latest
        step). The checkpoint's recorded geometry sizes the restore —
        ``n``/``max_deg`` pre-size *larger* (the restored state is grown
        to cover them) or *smaller*: a peak-tier checkpoint restores
        straight into a right-sized session via ``shrink_to`` (which
        raises, with the packed extent, if the live content genuinely
        cannot fit). They are also how checkpoints so old their geometry
        cannot be inferred from the leaf shapes declare it.
        ``cfg.k_max`` larger than the checkpoint's grows the
        partition-slot headroom (smaller still raises — partition slots
        are config-pinned). Also restores bare ``PartitionState``
        checkpoints written by older code: states that predate
        ``cut_matrix`` come back via ``fill_missing`` and are healed
        with ``recount_cut_matrix``; the external-id map of a compacted
        session rides in the checkpoint's extras channel and is restored
        with it. ``cfg``/``policy``/engine knobs are not stored in the
        checkpoint — pass the ones the session ran with. Traces are not
        checkpointed; a restored session's ``trace()`` covers
        post-restore events only.
        """
        cfg = cfg or EngineConfig()
        mgr = CheckpointManager(directory, interval=1)
        step = step if step is not None else mgr.latest()
        if step is None:
            raise FileNotFoundError(
                f"no checkpoint found under {directory!r}")
        ck = mgr.geometry(step)
        if ck is None:
            if n is None or max_deg is None:
                raise ValueError(
                    f"checkpoint at step {step} records no geometry and "
                    "none could be inferred from its leaf shapes — pass "
                    "n= and max_deg= explicitly")
            ck = Geometry(int(n), int(max_deg), cfg.k_max)
        if cfg.k_max < (ck.k_max or 0):
            raise ValueError(
                f"checkpoint was taken at k_max={ck.k_max} but "
                f"cfg.k_max={cfg.k_max}: partition-slot shapes grow, "
                "never shrink — raise cfg.k_max")
        # restore at the union of the checkpoint and requested shapes,
        # then shrink to any smaller requested dimensions below — the
        # payload's leaf shapes dictate the initial restore size either
        # way
        target = Geometry(max(int(n or 0), ck.n),
                          max(int(max_deg or 0), ck.max_deg), cfg.k_max)
        # build the session tier-minimal — its placeholder state is
        # replaced below, and allocating it at the target would hold a
        # third full-size state alive during the restore
        part = cls(cfg, **kw)
        # restore into a `like` at the EXACT checkpoint geometry (the
        # payload dictates leaf shapes), then grow to the target
        like = init_state(ck.n, ck.max_deg, ck.k_max or cfg.k_max,
                          cfg.k_init, 0)
        keys = mgr.leaf_keys(step)
        state, step = mgr.restore(like, step=step, fill_missing=True)
        # the payload dictates the restored leaf shapes, so a checkpoint
        # whose recorded geometry omitted k_max (Geometry.k_max is
        # Optional) is validated here, against the real saved shape
        k_saved = int(state.edge_load.shape[0])
        if k_saved > cfg.k_max:
            raise ValueError(
                f"checkpoint was taken at k_max={k_saved} but "
                f"cfg.k_max={cfg.k_max}: partition-slot shapes grow, "
                "never shrink — raise cfg.k_max")
        if len(keys) < len(jax.tree_util.tree_leaves(like)):
            # pre-cut_matrix checkpoint: fill_missing kept `like`'s zero
            # matrix — rebuild it exactly from the restored adjacency
            state = recount_cut_matrix(state)
        state = grow_state(state, target)
        if part._sharded:
            # the restored canonical layout goes onto the session's
            # vertices mesh (rows re-padded to the new shard count — the
            # cross-layout round-trip: dense↔sharded, any mesh width)
            part._sem_geom = geometry_of(state)
        part._state = part._pin(state)
        part._cursor = int(step)
        # the external-id map of a compacted session rides in the
        # checkpoint's extras — rebuild its dense inverse
        ext = mgr.extras(step)
        if "ext2int" in ext:
            e2i = np.asarray(ext["ext2int"], np.int32)
            part._ext2int = e2i
            valid = np.flatnonzero(e2i >= 0)
            slots = int(e2i[valid].max()) + 1 if valid.size else 0
            inv = np.full(slots, -1, np.int32)
            inv[e2i[valid]] = valid.astype(np.int32)
            part._int2ext = inv
        if "shrink_mark" in ext:
            part._last_shrink_check = int(np.asarray(ext["shrink_mark"])[0])
        if "rebalance_mark" in ext:
            part._last_rebalance = int(np.asarray(ext["rebalance_mark"])[0])
        if "drift_base" in ext:
            base = np.asarray(ext["drift_base"])
            part._drift_base = (float(base[0]), float(base[1]))
        part._record_geometry("restore", ck, part._sem_geometry())
        want_n = int(n) if n is not None and n < target.n else None
        want_d = int(max_deg) if max_deg is not None \
            and max_deg < target.max_deg else None
        if want_n is not None or want_d is not None:
            # restoring into a smaller tier than the checkpoint was taken
            # at: legal whenever the live content (packed) fits — a
            # session snapshotted at its peak right-sizes on restore
            part.shrink_to(n=want_n, max_deg=want_d)
        return part
