#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip.

    python svcbench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds <s>

For each seed, in one process: one run of the cell (set-up, window,
collect), then the numbers compared for the program and for the control
— the reference computed in bfloat16 and put in the program's place.  One
JSON line per seed.  The benchmark's own runs never run the control.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO / "src"))
sys.path.insert(0, str(REPO))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    from svcbench.harness import NoChip, Run, load_cell

    cell = load_cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        run = Run(cell, seed, args.seconds, False,
                  log=lambda m: print(m, file=sys.stderr, flush=True))
        try:
            run.setup()
        except NoChip as e:
            print(f"svcbench: {e}", file=sys.stderr)
            return 2
        run.window()
        run.collect()
        line = {"seed": seed, "program": run.check(),
                "control": run.check(control=True),
                "compiles_in_window": run.compiles_in_window,
                "answers": sum(len(a.specs) for a in run.asked if a.answers)}
        print(json.dumps(line, default=float), flush=True)
        del run
    return 0


if __name__ == "__main__":
    sys.exit(main())
