#!/usr/bin/env python3
"""The benchmark's control: the plain reference with one guarantee broken,
put in the program's place and judged by a run's own comparison.

    python3 bench/control.py --workload <cell> --events <N> --seeds 1 2 3

The configurations state that every event is placed against the state
every earlier event left. The control breaks that: each arriving vertex
is scored without the vertices added earlier in its own window (the
configuration's ``window``), the placement a window-parallel chooser would
make. For each seed this draws the cell's stream as a run does, replays
its first ``N`` events (as many as a run of the cell commits) through the
reference and through the control, and prints, as one JSON line per seed,
the numbers a run compares. A sound control fails at least one of them. The benchmark's
own runs never run this.
"""
from __future__ import annotations

import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]


def as_snapshot(ref, stream, consumed: int, seed: int):
    """The reference's state in the form a run copies from the device."""
    import jax

    from bench import harness, reference
    n = stream.n
    assignment = np.full(n, -1, np.int32)
    for v, p in ref.assignment.items():
        assignment[v] = p
    touched = harness.touched_ids(stream, consumed)
    d = stream.nbrs.shape[1]
    rows = np.full((touched.size, d), -1, np.int32)
    for i, v in enumerate(touched.tolist()):
        nb = sorted(ref.adj.get(v, ()))
        rows[i, :len(nb)] = nb
    with jax.default_device(reference.host_device()):
        key = np.asarray(jax.random.PRNGKey(seed))
    fields = {
        "assignment": assignment, "present": assignment >= 0,
        "edge_load": np.asarray(ref.edge_load),
        "vertex_count": np.asarray(ref.vertex_count),
        "active": np.asarray(ref.active),
        "num_partitions": np.asarray(ref.num_partitions),
        "total_edges": np.asarray(ref.total_edges),
        "cut_edges": np.asarray(ref.cut_edges),
        "denied_scaleout": np.asarray(ref.denied),
        "scale_events": np.asarray(ref.scale_events),
        "cut_matrix": ref.cut_matrix, "key": key}
    return harness.Snapshot(fields, touched, rows, int(np.sum(rows >= 0)))


def control_checks(p, stream, consumed: int, seed: int) -> tuple:
    """The compared numbers of the control on ``stream[:consumed]``, and
    the reference's state they were compared with."""
    from bench import harness, reference
    sess = p.config["session"]
    knobs = reference.Knobs(**sess["engine"])
    args = (stream.etype[:consumed], stream.vertex[:consumed],
            stream.nbrs[:consumed], stream.n)
    ref = reference.run_reference(*args, policy=sess["policy"], knobs=knobs,
                                  seed=seed)
    ctl = reference.run_reference(*args, policy=sess["policy"], knobs=knobs,
                                  seed=seed, stale_window=sess["window"])
    return harness.compare(as_snapshot(ctl, stream, consumed, seed), ref,
                           seed), ref


def main(argv=None) -> int:
    import argparse
    import json
    import time

    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))
    from bench import harness, traffic

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--events", type=int, required=True,
                    help="events to replay: as many as a run commits")
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    p = harness.plan(ROOT, args.workload)
    sess = p.config["session"]
    for seed in args.seeds:
        t0 = time.perf_counter()
        s = traffic.powerlaw_churn(sess["n"], harness.stream_events(p),
                                   max_deg=sess["max_deg"], seed=seed,
                                   mix=harness.churn_mix(p))
        checks, ref = control_checks(p, s, args.events, seed)
        print(json.dumps({
            "workload": args.workload, "seed": seed, "events": args.events,
            "correct": all(c.ok for c in checks),
            "seconds": time.perf_counter() - t0,
            "reference": {"vertices": len(ref.assignment),
                          "edges": ref.total_edges,
                          "partitions": ref.num_partitions,
                          "scale_events": ref.scale_events,
                          "denied_scaleouts": ref.denied},
            "checks": {c.name: {"value": c.value, "limit": c.limit}
                       for c in checks}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
