"""The benchmark harness: one cell of ``BENCHMARK.json``, run on the chip.

Everything that belongs to one configuration, traffic mix or per-layer
metric sits in a file of its own, found by the name ``BENCHMARK.json``
gives it:

- ``bench/configs/<config>.json``: the deployment — session knobs, the
  chips it needs, the graph's degree law, the snapshot loaded before the
  window, its source, and what was assumed and reduced;
- ``bench/traffic/<traffic>.json``: the traffic mix — the driver that
  offers it, the event mix, and that driver's parameters;
- ``bench/drivers/<driver>.py``: a driver, ``run(run: Run) -> None``;
- ``bench/metrics/<metric>.py``: a per-layer metric reader,
  ``read(run: Run) -> float | None`` (``None``: nothing to read here).

A later cell or metric is added by adding files and a ``BENCHMARK.json``
entry; no file here changes.

A run: start drawing the stream in a child process; check the chips;
build the session; load the configuration's snapshot through it; warm
up on the cell's own traffic; measure for
``--seconds`` (traced with ``--trace 1``); check what the timed path
produced against the plain reference; print the result line last on
standard output, and the compared numbers last on standard error.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import shutil
import sys
import time
from pathlib import Path

import numpy as np

from bench import reference, traffic, xplane

CACHE_DIR = "bench/.jax_cache"        # JAX's persistent compilation cache
TRACE_DIR = "bench/.trace"            # profiler output of a traced run
TRACE_SECONDS = 2.0                   # a traced window is at most this long
# fired for every program compiled or loaded from the persistent cache
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# --- the plan: a cell resolved by name ---------------------------------------

@dataclasses.dataclass
class Plan:
    root: Path
    cell: dict
    config: dict
    traffic: dict
    end_to_end: list[dict]     # this cell's end-to-end metric entries
    per_layer: list[dict]      # this cell's per-layer metric entries

    def driver(self):
        return _load_module(self.root / "bench" / "drivers"
                            / f"{self.traffic['driver']}.py")

    def reader(self, metric: str):
        return _load_module(self.root / "bench" / "metrics" / f"{metric}.py")


def _load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _load_module(path: Path):
    if not path.is_file():
        raise FileNotFoundError(f"no file {path}")
    name = "bench_plugin_" + path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def plan(root: Path, workload: str) -> Plan:
    """Resolve the cell ``workload`` of ``root/BENCHMARK.json`` and the
    files it names."""
    bench = _load_json(root / "BENCHMARK.json")
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    cell = cells[workload]
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if workload in m.get("workloads", [workload])
                 and m["moves"] in reported]
    return Plan(root=root, cell=cell,
                config=_load_json(root / "bench" / "configs"
                                  / f"{cell['config']}.json"),
                traffic=_load_json(root / "bench" / "traffic"
                                   / f"{cell['traffic']}.json"),
                end_to_end=e2e, per_layer=per_layer)


# --- the run: what drivers and metric readers see ----------------------------

@dataclasses.dataclass
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


class Run:
    """One run of one cell. Drivers fill ``e2e``, ``counters``,
    ``attempted``, ``failed`` and ``checks``; metric readers read them and
    ``trace``."""

    def __init__(self, p: Plan, *, seed: int, seconds: float, trace: bool,
                 t_start: float):
        self.plan = p
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.tracing = bool(trace)
        self.t_start = t_start
        self.devices = None
        self.stream: traffic.Stream | None = None
        self.compiles = 0                       # programs compiled or loaded
        self.compile_s = 0.0
        self.e2e: dict[str, float] = {}
        self.counters: dict[str, float] = {}
        self.attempted = 0
        self.failed = 0
        self.checks: list[Check] = []
        self.trace: xplane.TraceSummary | None = None
        self.memory_peak_bytes: int | None = None
        self.gc_pauses: list[float] | None = None   # inside the window
        self._gc_t0 = 0.0

    # -- configuration ------------------------------------------------------

    @property
    def session_cfg(self) -> dict:
        return self.plan.config["session"]

    @property
    def traffic(self) -> dict:
        return self.plan.traffic

    def session(self):
        """A fresh ``Partitioner`` as the configuration states it, with the
        traffic's ``session`` overrides (for example a serving engine)."""
        from repro.api import Partitioner
        from repro.core.config import EngineConfig
        s = dict(self.session_cfg)
        engine = EngineConfig(**s.pop("engine"))
        s.update(self.traffic.get("session", {}))
        return Partitioner(engine, seed=self.seed, **s)

    @property
    def window_seconds(self) -> float:
        """The measured window's length: ``--seconds``, cut to
        ``TRACE_SECONDS`` in a traced run (a trace holds every operation
        of every window, some 18,000 per window at n = 2**22)."""
        return min(self.seconds, TRACE_SECONDS) if self.tracing \
            else self.seconds

    def note(self, what: str) -> None:
        """Log a set-up step's time since the process started, and the
        programs compiled or loaded so far, to standard error."""
        print(f"bench: {what} at {time.perf_counter() - self.t_start:.3f} s"
              f", {self.compiles} programs compiled or loaded "
              f"({self.compile_s:.3f} s)", file=sys.stderr, flush=True)

    # -- instrumentation ----------------------------------------------------

    def on_compile(self, event: str, duration: float, **_) -> None:
        if event == COMPILE_EVENT:
            self.compiles += 1
            self.compile_s += duration

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self.gc_pauses is not None:
            self.gc_pauses.append(time.perf_counter() - self._gc_t0)

    def span(self, name: str):
        """A host span ``bench.<name>`` in the trace of a traced run."""
        if not self.tracing:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(xplane.SPAN_PREFIX + name)

    @contextlib.contextmanager
    def window(self):
        """The measured window: ends set-up by freezing the objects it made
        out of the garbage collector's scans, marks set-up's end, counts
        compiles and collector pauses, and traces the window when the run
        is traced."""
        import jax
        trace_dir = self.plan.root / TRACE_DIR
        gc.freeze()
        if self.tracing:
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(str(trace_dir))
        self.e2e["setup_s"] = time.perf_counter() - self.t_start
        self.note("window opens")
        compiles0 = self.compiles
        self.gc_pauses = []
        gc.callbacks.append(self._on_gc)
        try:
            with self.span("window"):
                yield
        finally:
            gc.callbacks.remove(self._on_gc)
            self.counters["compiles_in_window"] = self.compiles - compiles0
            if self.tracing:
                jax.profiler.stop_trace()
        if self.tracing:
            self.trace = xplane.reduce(xplane.find_trace(str(trace_dir)))
            shutil.rmtree(trace_dir, ignore_errors=True)

    def read_memory_peak(self) -> int:
        """Peak device bytes of the session, on the fullest chip: the
        runtime's ``peak_bytes_in_use`` plus its ``peak_bytes_reserved``,
        the temp it reserves when it loads a program, which the first
        counter does not see."""
        peaks = []
        for d in self.devices:
            stats = d.memory_stats()
            peaks.append(stats["peak_bytes_in_use"]
                         + stats["peak_bytes_reserved"])
        self.memory_peak_bytes = max(peaks)
        self.e2e["peak_hbm_bytes"] = float(self.memory_peak_bytes)
        return self.memory_peak_bytes

    # -- correctness --------------------------------------------------------

    def check_against_reference(self, snap: "Snapshot", consumed: int,
                                where: tuple[np.ndarray, np.ndarray]
                                | None = None) -> None:
        """Replay the first ``consumed`` events of the stream through the
        plain reference and compare the session's state (and the served
        ``where`` answers, ids and labels) with it."""
        s = self.stream
        eng = self.session_cfg["engine"]
        ref = reference.run_reference(
            s.etype[:consumed], s.vertex[:consumed], s.nbrs[:consumed],
            s.n, policy=self.session_cfg["policy"],
            knobs=reference.Knobs(**eng), seed=self.seed)
        self.checks.extend(compare(snap, ref, self.seed))
        if where is not None:
            ids, got = where
            want = np.asarray([ref.assignment.get(int(v), -1) for v in ids],
                              np.int32)
            self.checks.append(Check("where_mismatch",
                                     int(np.sum(got != want)), 0))


# --- the session's state against the reference -------------------------------

@dataclasses.dataclass
class Snapshot:
    """What the timed path left on the device, copied to the host: the
    per-vertex arrays, the neighbour rows of every vertex the stream
    touched, the count of real entries in the whole adjacency, and the
    K-state."""

    fields: dict[str, np.ndarray]
    touched: np.ndarray
    rows: np.ndarray
    adj_entries: int


def touched_ids(stream: traffic.Stream, consumed: int) -> np.ndarray:
    """Ids of the vertices the first ``consumed`` events added."""
    et = stream.etype[:consumed]
    return np.unique(stream.vertex[:consumed][et == traffic.EVENT_ADD])


def snapshot(state, stream: traffic.Stream, consumed: int) -> Snapshot:
    """Copy the session state the timed path produced to the host."""
    import jax
    import jax.numpy as jnp
    touched = touched_ids(stream, consumed)
    size = 1 << max(int(touched.size).bit_length(), 10)
    idx = np.zeros(size, np.int32)
    idx[:touched.size] = touched
    rows, entries = jax.jit(
        lambda adj, i: (adj[i], jnp.sum(adj >= 0, dtype=jnp.int32)))(
            state.adj, jnp.asarray(idx))
    fields = {f: np.asarray(getattr(state, f)) for f in state._fields
              if f != "adj"}
    return Snapshot(fields, touched, np.asarray(rows)[:touched.size],
                    int(entries))


def compare(snap: Snapshot, ref: reference.RefState, seed: int) -> list[Check]:
    """The compared numbers, each a count of disagreements with limit 0."""
    import jax
    f = snap.fields
    n = f["assignment"].shape[0]
    with jax.default_device(reference.host_device()):
        key = np.asarray(jax.random.PRNGKey(seed))
    want = np.full(n, -1, np.int64)
    if ref.assignment:
        v = np.fromiter(ref.assignment.keys(), np.int64, len(ref.assignment))
        want[v] = np.fromiter(ref.assignment.values(), np.int64, v.size)
    d = snap.rows.shape[1]
    want_rows = np.full(snap.rows.shape, -1, np.int64)
    for i, v in enumerate(snap.touched.tolist()):
        nb = sorted(ref.adj.get(v, ()))
        want_rows[i, d - len(nb):] = nb
    got_rows = np.sort(snap.rows, axis=1)
    k_state = {
        "edge_load": np.asarray(ref.edge_load),
        "vertex_count": np.asarray(ref.vertex_count),
        "active": np.asarray(ref.active),
        "num_partitions": np.asarray(ref.num_partitions),
        "total_edges": np.asarray(ref.total_edges),
        "cut_edges": np.asarray(ref.cut_edges),
        "denied_scaleout": np.asarray(ref.denied),
        "scale_events": np.asarray(ref.scale_events),
        "cut_matrix": ref.cut_matrix,
        "key": key,
    }
    bad_k = sum(not np.array_equal(f[k], w) for k, w in k_state.items())
    return [
        Check("assignment_mismatch", int(np.sum(f["assignment"] != want)), 0),
        Check("present_mismatch",
              int(np.sum(f["present"] != (want >= 0))), 0),
        Check("adj_row_mismatch",
              int(np.sum(np.any(got_rows != want_rows, axis=1))), 0),
        Check("adj_untouched_entries",
              snap.adj_entries - int(np.sum(snap.rows >= 0)), 0),
        Check("k_state_mismatch", bad_k, 0),
    ]


# --- the run as a whole --------------------------------------------------------

def churn_mix(p: Plan) -> traffic.ChurnMix:
    """The configuration's degree law and snapshot with the traffic's mix."""
    return traffic.ChurnMix(**p.config["graph"], **p.traffic["mix"],
                            lead_in_adds=int(p.config["snapshot_vertices"]))


def load_snapshot(r: Run, part) -> int:
    """Feed the configuration's snapshot, the stream's all-add lead-in,
    through ``part`` in chunks of 16 windows and wait for it; returns the
    events it took."""
    s = r.stream
    snap = int(r.plan.config["snapshot_vertices"])
    chunk = 16 * int(part.window)
    if snap % chunk:
        raise ValueError(f"snapshot_vertices={snap} is not a whole number "
                         f"of {chunk}-event chunks (16 windows)")
    for t in range(0, snap, chunk):
        part.feed(s.chunk(t, t + chunk))
    part.sync()
    r.note(f"snapshot of {snap} vertices loaded")
    return snap


def chips(n: int):
    """The first ``n`` devices; raises :class:`NoChip` unless they are TPUs
    and there are ``n`` of them."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devices[0].platform}")
    if len(devices) < n:
        raise NoChip(f"the cell needs {n} chips, JAX found {len(devices)}")
    return devices[:n]


def enable_compile_cache(root: Path) -> None:
    import jax
    jax.config.update("jax_compilation_cache_dir", str(root / CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def stream_events(p: Plan) -> int:
    """Events to generate: the traffic's capacity, which a run must not
    exhaust."""
    return int(p.traffic["stream_events"])


def execute(p: Plan, *, seed: int, seconds: float, trace: bool,
            t_start: float) -> Run:
    """Run the cell: everything but printing."""
    import jax
    r = Run(p, seed=seed, seconds=seconds, trace=trace, t_start=t_start)
    sess = p.config["session"]
    jax.monitoring.register_event_duration_secs_listener(r.on_compile)
    try:
        with traffic.StreamProcess(sess["n"], stream_events(p),
                                   max_deg=sess["max_deg"], seed=seed,
                                   mix=churn_mix(p)) as gen:
            r.devices = chips(p.cell["chips"])
            enable_compile_cache(p.root)
            r.note("chips found")
            r.stream = gen.result()
            r.note(f"stream of {r.stream.num_events} events drawn")
        p.driver().run(r)
    finally:
        jax.monitoring.unregister_event_duration_listener(r.on_compile)
    gc.collect()
    return r


def result_line(r: Run) -> dict:
    """The contract's last line of standard output."""
    d = r.devices[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(r.devices),
              "memory_peak_bytes": r.memory_peak_bytes}
    metrics = {}
    if r.tracing:
        device["busy_s"] = float(np.mean(r.trace.busy_s))
        device["window_s"] = r.trace.window_s
        for m in r.plan.per_layer:
            value = r.plan.reader(m["name"]).read(r)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    else:
        for m in r.plan.end_to_end:
            metrics[m["name"]] = {"value": float(r.e2e[m["name"]]),
                                  "unit": m["unit"]}
    out = {"correct": bool(r.checks) and all(c.ok for c in r.checks)
           and r.failed == 0,
           "attempted": int(r.attempted), "failed": int(r.failed),
           "metrics": metrics, "device": device}
    if r.tracing:
        out["breakdown"] = {
            "device_ops": [[name, s] for name, s in r.trace.top_ops],
            "idle_gaps": [[name, s] for name, s in r.trace.idle_gaps]}
    out["window"] = window_record(r)
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in r.checks}
    return out


def window_record(r: Run) -> dict:
    """What every run, traced or not, records of its window besides its
    metrics: programs compiled or loaded in it, the garbage collector's
    pauses in it, and the driver's counters."""
    pauses = r.gc_pauses or [0.0]
    return {"compiles": r.counters.get("compiles_in_window"),
            "gc_pauses": len(r.gc_pauses or []),
            "gc_max_ms": max(pauses) * 1e3, "gc_total_ms": sum(pauses) * 1e3,
            **{k: v for k, v in r.counters.items()
               if k != "compiles_in_window"}}


def main(argv, *, root: Path, t_start: float) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        prog="bench/run.py",
        description="Run one cell of BENCHMARK.json and print its result "
                    "as the last line of standard output.")
    ap.add_argument("--workload", required=True,
                    help="a workload (cell) name from BENCHMARK.json")
    ap.add_argument("--seed", type=int, required=True,
                    help="seeds the stream, its arrivals and the session")
    ap.add_argument("--seconds", type=float, required=True,
                    help="length of the measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0,
                    help="1: trace the window and report per-layer metrics")
    args = ap.parse_args(argv)
    try:
        p = plan(root, args.workload)
        r = execute(p, seed=args.seed, seconds=args.seconds,
                    trace=bool(args.trace), t_start=t_start)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    line = result_line(r)
    print(f"bench: window {json.dumps(line['window'])}", file=sys.stderr)
    for c in r.checks:
        print(f"check {c.name}: {c.value} (limit {c.limit})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0
