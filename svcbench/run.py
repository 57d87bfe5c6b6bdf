#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python svcbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process: it builds the cell's deployment from the seed, warms every
shape the window uses, drives the open-loop window on the TPU, checks what
the timed path produced against the plain reference, and prints one JSON
object as the last line of standard output.  With ``--trace 0`` the
metrics are the cell's end-to-end metrics (obs tracer and kernel profiler
off); with ``--trace 1`` its per-layer metrics, read from the obs spans
and a device trace of a sub-window.  It exits non-zero, printing no
result, when JAX finds no TPU or fewer chips than the cell asks for.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO / "src"))
sys.path.insert(0, str(REPO))


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def result_line(run, checks: dict, limits: dict) -> dict:
    """The contract's JSON object; the numbers compared come last."""
    from svcbench.harness import ReadContext
    from svcbench.metrics import reader

    cell = run.cell
    metrics = {}
    if run.trace:
        ctx = ReadContext(run)
        for m in cell["per_layer"]:
            v = reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        e2e = run.end_to_end()
        for m in cell["end_to_end"]:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    device = dict(run.device_info)
    if run.trace and ctx.trace is not None:
        device["busy_s"] = ctx.trace["busy_s"]
        device["window_s"] = ctx.trace["window_s"]
    fails = run.failures()
    compared = {k: {"value": v, "limit": limits[k]} for k, v in checks.items()}
    correct = all(v <= limits[k] for k, v in checks.items())
    out = {
        "correct": bool(correct),
        "attempted": sum(len(a.specs) for a in run.asked if a.done is not None)
        + sum(1 for b in run.window_batches if b.done is not None),
        "failed": sum(fails.values()),
        "metrics": metrics,
        "device": device,
    }
    if run.trace and ctx.trace is not None:
        from svcbench import devtrace

        out["breakdown"] = devtrace.breakdown(ctx.trace)
    out["checks"] = compared
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from svcbench.harness import NoChip, Run, load_cell, load_limits

    cell = load_cell(args.workload)
    run = Run(cell, args.seed, args.seconds, bool(args.trace), log=log)
    try:
        run.setup()
    except NoChip as e:
        log(f"svcbench: {e}")
        return 2
    run.window()
    run.collect()
    t = time.perf_counter()
    checks = run.check()
    log(f"check: reference comparison {time.perf_counter() - t:.3f} s")
    limits = load_limits(args.workload)
    line = result_line(run, checks, limits)
    log(f"failed: {run.failures()} of {line['attempted']} attempted")
    for k, v in line["checks"].items():
        log(f"compared {k} = {v['value']} limit {v['limit']}")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
