"""Benchmark harness entry: one module per paper figure/table.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only fig4,...]

Prints ``name,value,derived`` CSV per row-group and writes JSON artifacts
to artifacts/bench/. The roofline table additionally needs dry-run
artifacts (repro.launch.dryrun --all).

Policy/config comparisons (fig4/6/7/8) run through the sweep runtime
(repro.runtime.sweep): all lanes of a comparison execute as ONE device
program (lane axis sharded across devices when more than one exists)
instead of a host loop re-scanning the stream per policy. fig10 times
the mixed-event window engine against the legacy delete-splitting driver
on an interleaved churn stream (BENCH_mixed_window.json); fig9 runs one
vertex-sharded session over mesh widths 1/2/4/8 at fixed n — events/s
and per-device peak state bytes (BENCH_shard_scaling.json; multi-width
rows need XLA_FLAGS=--xla_force_host_platform_device_count=8); fig11 times
host-loop vs vmapped vs sharded vs windowed-lane sweeps
(BENCH_sweep_scaling.json); fig12 times incremental vs recompute
autoscale lanes (BENCH_autoscale_churn.json); fig13 times elastic
geometry growth against a presized session (BENCH_growth.json); fig14
times the double-buffered PartitionService against a synchronous
per-arrival feed loop under Poisson arrivals (BENCH_serving.json);
fig16 tracks partition quality over time on adversarial streams with
and without the online rebalancing subsystem (BENCH_quality.json).
See docs/BENCHMARKS.md for every artifact's provenance and how to
regenerate it.
"""
from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--full", action="store_true",
                   help="paper-scale datasets (slow on CPU); default quick")
    p.add_argument("--only", type=str, default="")
    args = p.parse_args()

    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache(Path(__file__).resolve().parents[1])

    from benchmarks import (fig4_edgecut, fig5_vs_offline, fig6_dynamics,
                            fig7_imbalance, fig8_npartitions, fig9_scaling,
                            fig10_time, fig11_sweep_scaling,
                            fig12_autoscale_churn, fig13_growth,
                            fig14_serving, fig15_lifecycle, fig16_quality,
                            roofline)
    mods = {
        "fig4": fig4_edgecut, "fig5": fig5_vs_offline,
        "fig6": fig6_dynamics, "fig7": fig7_imbalance,
        "fig8": fig8_npartitions, "fig9": fig9_scaling,
        "fig10": fig10_time, "fig11": fig11_sweep_scaling,
        "fig12": fig12_autoscale_churn, "fig13": fig13_growth,
        "fig14": fig14_serving, "fig15": fig15_lifecycle,
        "fig16": fig16_quality,
        "roofline": roofline,
    }
    only = [s for s in args.only.split(",") if s]
    failures = 0
    print("name,value,derived")
    for name, mod in mods.items():
        if only and name not in only:
            continue
        t0 = time.time()
        try:
            rows = mod.run(quick=not args.full)
            for line in mod.summarize(rows):
                print(line, flush=True)
            print(f"#{name} done in {time.time()-t0:.1f}s", flush=True)
        except Exception as e:  # noqa: BLE001
            failures += 1
            import traceback
            traceback.print_exc()
            print(f"{name},ERROR,{type(e).__name__}:{e}", flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
