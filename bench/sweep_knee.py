#!/usr/bin/env python3
"""Find the serving knee: offer a serving cell's traffic at several fixed
rates, one after another, through one warmed session on one chip.

    python3 bench/sweep_knee.py --workload livejournal1m-serve-poisson \\
        --seed 7 --seconds 8 --rates 6000 8000 10000

For each rate this prints one JSON line: the offered and committed
events per second, the commit latency's median and 99th percentile, the
median of each half of the window (a queue that grows shows as a second
half slower than the first), and the time from the last chunk's due time
to its commit. The knee is the highest rate at which committed events keep
pace with offered ones and the halves agree. The cell's traffic file
fixes its rate below that; the benchmark's own runs never sweep.
"""
from __future__ import annotations

import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    import argparse
    import json

    import numpy as np

    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))
    from bench import harness, traffic

    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)

    p = harness.plan(ROOT, args.workload)
    sess = p.config["session"]
    warm = sess["window"] * (sess["window"] + 1) // 2 + \
        p.config["snapshot_vertices"]
    p.traffic["stream_events"] = int(
        warm + 1.2 * args.seconds * sum(args.rates) + 10 * sess["window"])
    r = harness.Run(p, seed=args.seed, seconds=args.seconds, trace=False,
                    t_start=t_start)
    with traffic.StreamProcess(sess["n"], p.traffic["stream_events"],
                               max_deg=sess["max_deg"], seed=args.seed,
                               mix=harness.churn_mix(p)) as g:
        r.devices = harness.chips(p.cell["chips"])
        harness.enable_compile_cache(ROOT)
        r.stream = g.result()
    drv = p.driver()
    part = r.session()
    t = drv.warm_up(r, part)
    for rate in args.rates:
        svc, bounds, due, lat, lag, length = drv.serve_window(
            r, part, t, rate, args.seconds)
        m = svc.metrics()
        svc.close()
        half = len(lat) // 2
        events = int(bounds[-1] - bounds[0])
        print(json.dumps({
            "rate": rate, "events": events,
            "events_per_s": events / length,
            "p50_ms": float(np.percentile(lat, 50) * 1e3),
            "p99_ms": float(np.percentile(lat, 99) * 1e3),
            "p50_first_half_ms": float(np.percentile(lat[:half], 50) * 1e3),
            "p50_second_half_ms": float(np.percentile(lat[half:], 50) * 1e3),
            "drain_s": length - float(due[-1]),
            "lag_p99_ms": float(np.percentile(lag, 99) * 1e3),
            "batches": m["batches_dispatched"],
            "max_queue_depth": m["max_queue_depth"],
            "compiles": r.counters["compiles_in_window"],
            "gc_pauses": len(r.gc_pauses),
            "gc_max_ms": max(r.gc_pauses or [0.0]) * 1e3,
            "worst_chunks": drv.worst(due, lat)}), flush=True)
        t = int(bounds[-1])
    return 0


if __name__ == "__main__":
    sys.exit(main())
