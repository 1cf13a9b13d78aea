"""Benchmark driver: one benchmark per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (one per reported quantity).
``--full`` runs the 50-seed replication counts from the paper; the default
sizes finish on CPU in minutes and preserve every qualitative claim.
"""

from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale seed counts (slow on CPU)")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset: fig3,fig2,fig4,fig5,async,gp,"
                         "suggest,multijob,remote,multimetric,multifidelity,"
                         "large_n,cost_aware,roofline")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None

    from repro.compile_cache import enable_persistent_cache

    enable_persistent_cache(Path(__file__).resolve().parents[1])

    from benchmarks import async_strategies, bo_vs_random, early_stopping
    from benchmarks import gp_perf, log_scaling, roofline_report, warm_start
    from benchmarks import cost_aware, large_n, multi_job, multifidelity
    from benchmarks import multimetric
    from benchmarks import remote_service
    from benchmarks import suggest_throughput

    suites = []
    if only is None or "fig3" in only:
        suites.append(("fig3", lambda: bo_vs_random.run(
            num_seeds=50 if args.full else 8)))
    if only is None or "fig2" in only:
        suites.append(("fig2", lambda: log_scaling.run(
            num_seeds=50 if args.full else 8)))
    if only is None or "fig4" in only:
        suites.append(("fig4", lambda: early_stopping.run(
            num_seeds=10 if args.full else 6)))
    if only is None or "fig5" in only:
        suites.append(("fig5", lambda: warm_start.run(
            num_seeds=10 if args.full else 6)))
    if only is None or "async" in only:
        suites.append(("async", lambda: async_strategies.run(
            num_seeds=10 if args.full else 5)))
    if only is None or "gp" in only:
        suites.append(("gp", gp_perf.run))
    if only is None or "suggest" in only:
        suites.append(("suggest", suggest_throughput.run))
    if only is None or "multijob" in only:
        suites.append(("multijob", multi_job.run))
    if only is None or "remote" in only:
        suites.append(("remote", remote_service.run))
    if only is None or "multimetric" in only:
        suites.append(("multimetric", multimetric.run))
    if only is None or "multifidelity" in only:
        suites.append(("multifidelity", multifidelity.run))
    if only is None or "large_n" in only:
        suites.append(("large_n", large_n.run))
    if only is None or "cost_aware" in only:
        suites.append(("cost_aware", lambda: cost_aware.run(
            num_seeds=5 if args.full else 3)))
    if only is None or "roofline" in only:
        suites.append(("roofline", roofline_report.run))

    print("name,us_per_call,derived")
    failed = []
    for name, fn in suites:
        t0 = time.perf_counter()
        try:
            rows = fn()
        except Exception as e:  # noqa: BLE001 — report the row, fail at exit
            print(f"{name}_ERROR,0,{type(e).__name__}:{e}", flush=True)
            failed.append(name)
            continue
        for r in rows:
            print(f"{r[0]},{r[1]:.1f},{r[2]}", flush=True)
        sys.stderr.write(f"[{name}] {time.perf_counter()-t0:.1f}s\n")
    if failed:
        sys.stderr.write(f"failed suites: {','.join(failed)}\n")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
