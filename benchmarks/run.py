"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  ``--quick`` shrinks datasets.
Dry-run roofline cells are produced separately by repro.launch.dryrun and
summarized by benchmarks/roofline.py (they need 512 placeholder devices).
"""

from __future__ import annotations

import argparse
import sys
import time
import traceback


MODULES = (
    "fig4_maintenance",
    "fig5_accuracy",
    "fig6_breakeven",
    "fig7_complex_views",
    "fig8_outlier",
    "fig9_distributed",
    "fig10_cube",
    "fig13_median",
    "fig14_minibatch",
    "fig_query_throughput",
    "fig_planner_fleet",
    "fig_chaos_soak",
    "fig_serving_soak",
    "fig_obs_overhead",
    "appendix_minmax",
    "kernels_bench",
    "svc_training",
)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None, help="comma-separated module filter")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None

    from repro.compile_cache import configure_compile_cache

    configure_compile_cache()
    print("name,us_per_call,derived")
    failures = 0
    for mod_name in MODULES:
        if only and mod_name not in only:
            continue
        t0 = time.time()
        try:
            mod = __import__(f"benchmarks.{mod_name}", fromlist=["run"])
            for row in mod.run(quick=args.quick):
                print(row.csv(), flush=True)
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"{mod_name},NaN,ERROR {type(e).__name__}: {e}", flush=True)
            traceback.print_exc(file=sys.stderr)
        print(f"# {mod_name} took {time.time() - t0:.1f}s", file=sys.stderr, flush=True)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
