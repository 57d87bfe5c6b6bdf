#!/usr/bin/env python3
"""Find the rates a cell's mix fixes: one set-up, then short open-loop
windows at increasing offered rates.

    python svcbench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --kind queries|inserts --rates 5,10,20,40

``--kind queries`` raises the dashboard batch rate with the mix's inserts
held; ``--kind inserts`` raises the insert rate with no queries.  Each
step prints one JSON line: the offered rate, the share of due events
issued, the generator's lateness, and the latency tails.  A rate is
sustained while every due event is issued and the lateness stays bounded.
The deltas of all steps accumulate, so the steps together must stay
within the pending-arena bucket the cell's window uses.
"""

from __future__ import annotations

import argparse
import copy
import json
import pathlib
import sys

import numpy as np

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO / "src"))
sys.path.insert(0, str(REPO))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--kind", choices=("queries", "inserts"), required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)

    from svcbench import e2e
    from svcbench.harness import NoChip, Run, load_cell

    cell = load_cell(args.workload)
    base_mix = copy.deepcopy(cell["mix"])
    run = Run(cell, args.seed, args.seconds, False,
              log=lambda m: print(m, file=sys.stderr, flush=True))
    try:
        run.setup()
    except NoChip as e:
        print(f"svcbench: {e}", file=sys.stderr)
        return 2
    for rate in (float(r) for r in args.rates.split(",")):
        mix = copy.deepcopy(base_mix)
        if args.kind == "queries":
            mix["queries"]["rate_per_s"] = rate
        else:
            mix["inserts"]["rate_rows_per_s"] = rate
            mix["queries"]["rate_per_s"] = 0.0
        run.mix = mix
        run.asked, run.window_batches = [], []
        run._prepare_window()
        n_epochs = len(run.epochs)
        run.window()
        W = run.window_s
        lat = e2e.query_latencies([(a.due, a.done) for a in run.asked], W)
        eps = run.epochs[n_epochs:]
        issued = sum(1 for a in run.asked if a.done is not None) + sum(
            1 for b in run.window_batches if b.done is not None)
        line = {
            "kind": args.kind, "rate": rate, "events": len(run.events),
            "issued": issued,
            "lateness_p95_ms": 1e3 * float(np.percentile(run.lateness, 95)),
            "lateness_max_ms": 1e3 * float(max(run.lateness)),
            "query_p50_ms": 1e3 * e2e.percentile(lat, 50) if lat else None,
            "query_p95_ms": 1e3 * e2e.percentile(lat, 95) if lat else None,
            "service_mean_ms": 1e3 * float(np.mean(
                [a.done - a.start for a in run.asked if a.done is not None]))
            if any(a.done is not None for a in run.asked) else None,
            "epochs": len(eps),
            "epoch_p50_ms": 1e3 * float(np.median([e.t1 - e.t0 for e in eps]))
            if eps else None,
            "compiles_in_window": run.compiles_in_window,
        }
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
