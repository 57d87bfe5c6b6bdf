"""Schema check for the BENCH_*.json benchmark artifacts in the repo root.

The CI guard jobs gate on fields inside these files (wall-clock ratios,
availability, reconciliation booleans); a benchmark refactor that renames
or drops a field silently disarms its guard.  This checker pins the
contract: every known artifact present in the repo root must carry its
required fields with the right shapes, and every boolean guard it
declares must be true.

Artifacts are optional (a fresh clone before any bench run has none) —
only files that exist are validated.  Unknown BENCH_*.json files fail the
check: new artifacts must register a schema here.

Run:  python tools/check_bench_schema.py [--require NAME ...]

``--require BENCH_obs_overhead.json`` (e.g.) additionally fails when the
named artifact is missing — the CI jobs that just produced a file use
this to catch a bench that silently wrote nothing.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent

NUM = (int, float)

# name -> {dotted.path: type or tuple-of-types}; "guards.*: bool" entries
# must also be TRUE (they are the CI gate itself).
SCHEMAS = {
    "BENCH_planner.json": {
        "quick": bool,
        "n_views": int,
        "epochs": int,
        "budget_s": NUM,
        "policies.planner.median_rel_err": NUM,
        "policies.planner.wall_s": NUM,
    },
    "BENCH_planner_breakdown.json": {
        "epochs": int,
        "breakdown.snapshot_s": NUM,
        "breakdown.schedule_s": NUM,
        "breakdown.act_s": NUM,
        "wall_guard.planner_wall_s": NUM,
        "wall_guard.clean_all_wall_s": NUM,
        "wall_guard.ratio": NUM,
        "wall_guard.ok": bool,
    },
    "BENCH_chaos.json": {
        "quick": bool,
        "epochs": int,
        "fault_schedule": list,
        "availability": NUM,
        "guards.availability_ok": bool,
        "guards.inflation_ok": bool,
        "guards.differential_ok": bool,
        "guards.recovered_ok": bool,
    },
    "BENCH_serving.json": {
        "quick": bool,
        "epochs": int,
        "availability": NUM,
        "p99_ms": NUM,
        "guards.availability_ok": bool,
        "guards.p99_ok": bool,
        "guards.cache_wins": bool,
        "guards.accounting_ok": bool,
    },
    "BENCH_distributed.json": {
        "quick": bool,
        "platform": str,
        "devices": int,
        "n_views": int,
        "rows_per_view": int,
        "curve": list,
        "combine_s": NUM,
        "scaling_at_8": NUM,
        "availability": NUM,
        "wall_s": NUM,
        "guards.scaling_ok": bool,
        "guards.parity_ok": bool,
        "guards.availability_ok": bool,
        "guards.drain_ok": bool,
    },
    "BENCH_obs_overhead.json": {
        "quick": bool,
        "epochs": int,
        "untraced_s": NUM,
        "traced_s": NUM,
        "overhead_ratio": NUM,
        "trace_records": int,
        "guards.overhead_ok": bool,
        "guards.reconciled_ok": bool,
    },
}


def _lookup(doc, dotted: str):
    cur = doc
    for part in dotted.split("."):
        if not isinstance(cur, dict) or part not in cur:
            return None, False
        cur = cur[part]
    return cur, True


def check_file(path: pathlib.Path, schema) -> list:
    problems = []
    try:
        doc = json.loads(path.read_text())
    except (OSError, json.JSONDecodeError) as e:
        return [f"{path.name}: unreadable ({e})"]
    for dotted, want in schema.items():
        val, found = _lookup(doc, dotted)
        if not found:
            problems.append(f"{path.name}: missing field {dotted!r}")
            continue
        if want is bool:
            # bool is an int subclass: check it explicitly, and guard
            # fields must also HOLD
            if not isinstance(val, bool):
                problems.append(
                    f"{path.name}: {dotted!r} should be bool, got "
                    f"{type(val).__name__}")
            elif (dotted.startswith("guards.")
                  or dotted.endswith(".ok")) and not val:
                problems.append(f"{path.name}: guard {dotted!r} is false")
        elif not isinstance(val, want) or isinstance(val, bool):
            names = (want.__name__ if isinstance(want, type)
                     else "/".join(t.__name__ for t in want))
            problems.append(
                f"{path.name}: {dotted!r} should be {names}, got "
                f"{type(val).__name__}")
    return problems


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--require", action="append", default=[],
                    help="fail if this artifact is absent (repeatable)")
    args = ap.parse_args(argv)

    problems = []
    checked = 0
    for path in sorted(ROOT.glob("BENCH_*.json")):
        schema = SCHEMAS.get(path.name)
        if schema is None:
            problems.append(
                f"{path.name}: unknown artifact — register its schema in "
                f"tools/check_bench_schema.py")
            continue
        problems += check_file(path, schema)
        checked += 1
    for name in args.require:
        if not (ROOT / name).exists():
            problems.append(f"required artifact {name} is missing")

    if problems:
        print("bench schema problems:")
        for p in problems:
            print("  " + p)
        return 1
    print(f"bench schema OK ({checked} artifact(s) checked)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
