#!/usr/bin/env python3
"""Drive the SVC serving path once on a TPU chip and check what comes out.

One process, no children.  The one-chip run (the default) builds TPC-H
scale factor 1 from ``--seed`` on the device and drives the main path a
user calls: ``ViewManager`` → ``StreamingViewService`` with a
``MaintenancePlanner`` attached → batched queries.

* data: lineitem 6,000,000 rows, orders 1,500,000, customer 150,000 and a
  200,000-part key domain (``data.synthetic.make_lineitem_orders``);
* views at m = 0.1: the paper's ``joinView`` (lineitem ⋈ orders by
  l_orderkey — 1.5M groups, past ``MAX_FUSED_GROUPS``, so its cleans take
  the sort-based executor) and ``partView`` (lineitem by l_partkey, the
  same aggregates; its cleans take ``fused_clean`` alone and ride
  ``svc_refresh_many`` → ``fleet_merge`` in an epoch that cleans both —
  the batched merge takes only pin-free views with no dimension join);
  an outlier index on joinView (``outlier_member``);
* traffic: 10% lineitem inserts (600,000 rows) in 9 equal micro-batches
  whose size watermark trips a planner epoch every third batch — the
  third is the maintenance period, where the starvation guard forces full
  IVM — with sum/count/avg range-query batches between the epochs.  Equal
  windows keep one pending-delta shape, so later epochs reuse compiles.

Checks (each failure is printed; any failure exits 1 without ``"ok"``):
  1. the device is a TPU;
  2. each main-path kernel op dispatched, with 0 fallbacks on the TPU;
  3. no quarantined view and ``fleet_merge_failures == 0``;
  4. no answer's method carries ``+degraded``;
  5. fused and unfused cleans of partView give bit-identical samples;
  6. SVC answers fall inside their CI of ``query_exact_fresh`` (with the
     binomial miss allowance of a 95% interval), and after the
     maintenance epoch the exact answer matches it to 1e-5 relative.

``--chips 4`` runs only the four-chip phase: a 4-shard ``ShardedFleet``
epoch on a 4-device mesh against the flat planner on the same schedule.

Usage:
  python chip_smoke.py                 # one chip, SF1
  python chip_smoke.py --chips 4       # four-chip ShardedFleet phase
  JAX_PLATFORMS=cpu python chip_smoke.py --scale 0.001   # CPU rehearsal

``--scale`` shrinks the row counts only; off the TPU the script runs every
phase and then fails the device check.  Without ``--scale`` it refuses to
build SF1 off the TPU.  The last stdout line of a passing run is
``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import json
import math
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import numpy as np  # noqa: E402

# TPC-H scale factor 1 (TPC-H specification, clause 4.2.5)
SF1 = {"lineitem": 6_000_000, "orders": 1_500_000,
       "customer": 150_000, "parts": 200_000}
M = 0.1                 # sampling ratio of both views
Z = 2.0                 # TPCD-Skew zipf parameter of prices
INSERT_FRAC = 0.10      # lineitem inserts streamed, as a share of lineitem
N_BATCHES = 9           # micro-batches carrying them
EPOCH_BATCHES = 3       # size watermark: one epoch per this many batches
OUTLIER_K = 256         # outlier index capacity (top lineitem prices)
N_QUERIES = 8           # queries per dashboard batch
MAIN_OPS = ("fused_clean", "outlier_member", "multi_agg", "fleet_moments",
            "fleet_score", "fleet_merge")
EXACT_RTOL = 1e-5


def log(msg: str) -> None:
    print(msg, flush=True)


class Checks:
    def __init__(self):
        self.failed = []

    def expect(self, cond: bool, name: str, detail: str = "") -> None:
        log(f"check {'PASS' if cond else 'FAIL'}: {name}"
            + (f" — {detail}" if detail else ""))
        if not cond:
            self.failed.append(name)


def device_info():
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def rel_bytes(rel) -> int:
    return sum(int(c.nbytes) for c in rel.columns.values()) + int(rel.valid.nbytes)


def miss_allowance(n: int, p: float = 0.05, tail: float = 1e-3) -> int:
    """Largest miss count a correct p-miss interval exceeds with prob < tail."""
    cdf = 0.0
    for k in range(n + 1):
        cdf += math.comb(n, k) * p ** k * (1 - p) ** (n - k)
        if 1.0 - cdf < tail:
            return k
    return n


def dashboard_queries(rng, n: int, qty_mean: float):
    """``benchmarks.common.random_join_queries``' shape — sum/count/avg of
    revenue/qty/items over a ``qty`` range — with the range drawn on this
    view's own qty scale (mean qty per group)."""
    from repro.core import Query
    from repro.relational.expr import Cmp, Col, Lit, and_

    out = []
    for _ in range(n):
        agg = str(rng.choice(["sum", "count", "avg"]))
        col = str(rng.choice(["revenue", "qty", "items"]))
        lo = float(rng.uniform(0.2, 1.0)) * qty_mean
        hi = lo + float(rng.uniform(0.3, 1.0)) * qty_mean
        pred = and_(Cmp("ge", Col("qty"), Lit(lo)), Cmp("le", Col("qty"), Lit(hi)))
        out.append(Query(agg=agg, col=None if agg == "count" else col, pred=pred))
    return out


def view_plan(key: str, num_groups: int, join: bool):
    from repro.relational.plan import FKJoin, GroupByNode, Scan

    child = Scan("lineitem", pk=("l_linekey",))
    if join:
        child = FKJoin(fact=child, dim=Scan("orders", pk=("o_orderkey",)),
                       fact_key="l_orderkey")
    return GroupByNode(
        child=child,
        keys=(key,),
        aggs=(("revenue", "sum", "l_extendedprice"),
              ("qty", "sum", "l_quantity"),
              ("items", "count", None)),
        num_groups=num_groups,
    )


def print_kernel_table(prof) -> None:
    log("op                   dispatches fallbacks compiles  compile_s  execute_s  occupancy")
    for op in sorted(prof.ops):
        s = prof.ops[op]
        log(f"{op:20s} {s.dispatches:10d} {s.fallbacks:9d} {s.compiles:8d} "
            f"{s.compile_s:10.3f} {s.execute_s:10.3f} {s.occupancy:10.4f}")


def one_chip(args, checks: Checks, on_tpu: bool) -> None:
    import jax

    from repro.core import ViewDef
    from repro.core.maintenance import MAX_FUSED_GROUPS
    from repro.data.synthetic import grow_lineitem, make_lineitem_orders
    from repro.kernels import KernelProfiler, set_profiler
    from repro.planner import MaintenancePlanner
    from repro.relational.relation import to_host
    from repro.streaming import StreamConfig
    from repro.views import ViewManager

    n = {k: max(1, int(round(v * args.scale))) for k, v in SF1.items()}
    n_new = int(n["lineitem"] * INSERT_FRAC)
    per_batch = n_new // N_BATCHES
    rng = np.random.default_rng(args.seed)
    prof = KernelProfiler()
    set_profiler(prof)

    # -- data -----------------------------------------------------------------
    t0 = time.perf_counter()
    lineitem, orders, customer, nation, region = make_lineitem_orders(
        rng, n["orders"], n["lineitem"], n["customer"], n["parts"], z=Z)
    deltas = [
        grow_lineitem(rng, n["orders"], n["parts"],
                      start_key=n["lineitem"] + i * per_batch,
                      n_new=per_batch, z=Z)
        for i in range(N_BATCHES)
    ]
    jax.block_until_ready((lineitem, orders, customer, deltas))
    t_data = time.perf_counter() - t0
    log(f"data: lineitem={n['lineitem']} orders={n['orders']} "
        f"customer={n['customer']} parts={n['parts']} "
        f"inserts={per_batch * N_BATCHES} in {N_BATCHES} batches "
        f"({t_data:.3f} s to generate and upload)")

    # -- registration ---------------------------------------------------------
    vm = ViewManager()
    for name, rel in (("lineitem", lineitem), ("orders", orders),
                      ("customer", customer), ("nation", nation),
                      ("region", region)):
        vm.register_base(name, rel)
    groups = {"joinView": int(n["orders"] * 1.25),
              "partView": int(n["parts"] * 1.25)}
    keys = {"joinView": "l_orderkey", "partView": "l_partkey"}
    t0 = time.perf_counter()
    for name in ("joinView", "partView"):
        plan = view_plan(keys[name], groups[name], join=name == "joinView")
        vm.register_view(ViewDef(name, plan),
                         delta_bases=("lineitem",), m=M, seed=args.seed,
                         delta_group_capacity=groups[name])
    vm.register_outlier_index("joinView", "lineitem", "l_extendedprice",
                              k=OUTLIER_K)
    jax.block_until_ready([mv.stale_sample.valid for mv in vm.views.values()])
    t_register = time.perf_counter() - t0
    sort_based = groups["joinView"] > MAX_FUSED_GROUPS
    log(f"register: 2 views + outlier index in {t_register:.3f} s "
        f"(compile and warm-up); joinView groups={groups['joinView']} "
        f"({'>' if sort_based else '<='} MAX_FUSED_GROUPS={MAX_FUSED_GROUPS}: "
        f"{'sort-based' if sort_based else 'fused'} cleans), "
        f"partView groups={groups['partView']}")

    # -- streaming service + planner -------------------------------------------
    svc = vm.configure_streaming(StreamConfig(
        max_rows=EPOCH_BATCHES * per_batch, max_age_s=1e9,
        max_batches=4 * N_BATCHES))
    # every action fits the budget and no first-epoch compile counts as an
    # overrun; the starvation guard stays off until the maintenance period
    planner = svc.attach_planner(MaintenancePlanner(
        vm, budget_s=1e9, age_cap_s=1e9, deadline_floor_s=1e9))
    qty_mean = {"joinView": 25.0 * n["lineitem"] / n["orders"],
                "partView": 25.0 * n["lineitem"] / n["parts"]}
    qrng = np.random.default_rng(args.seed + 1)
    answers = []   # (view, query, estimate, truth)
    timings = {"epochs_s": [], "query_batches_s": []}

    def ask(tag: str) -> None:
        for view in ("joinView", "partView"):
            qs = dashboard_queries(qrng, N_QUERIES, qty_mean[view])
            t = time.perf_counter()
            got = svc.query_batch(view, qs)
            jax.block_until_ready([g.value for g in got])
            dt = time.perf_counter() - t
            timings["query_batches_s"].append(dt)
            for q, g in zip(qs, got):
                truth = float(vm.query_exact_fresh(view, q))
                answers.append((view, q, g.estimate, truth))
            log(f"query batch {tag} {view}: {len(qs)} queries in {dt:.3f} s")

    ask("pre-stream")
    epochs_before = svc.refresh_count
    maintained = set()
    for i, d in enumerate(deltas):
        last = i == N_BATCHES - 1
        if last:
            # the maintenance period: in the epoch this window's watermark
            # triggers, the starvation guard forces full IVM of every view
            planner.age_cap_s = 0.0
        t = time.perf_counter()
        fired = vm.ingest("lineitem", inserts=d, seq=i)
        if not fired:
            continue
        dt = time.perf_counter() - t
        timings["epochs_s"].append(dt)
        rep = planner.last_report
        log(f"epoch {rep.epoch} (watermark after batch {i}"
            f"{', maintenance period' if last else ''}): "
            f"{[(a.view, a.action, round(a.actual_s, 3)) for a in rep.actions]} "
            f"in {dt:.3f} s")
        if last:
            maintained = {a.view for a in rep.actions if a.action == "maintain"}
            continue
        ask(f"after epoch {rep.epoch}")
        if i == EPOCH_BATCHES - 1:
            # check 5 on the live state: both paths clean partView from
            # the same stale sample and the same pending deltas
            t_fused = vm.svc_refresh("partView", fused=True)
            fused_rows = to_host(vm.views["partView"].clean_sample)
            t_plain = vm.svc_refresh("partView", fused=False)
            plain_rows = to_host(vm.views["partView"].clean_sample)
            log(f"partView clean, first call each: fused {t_fused:.3f} s, "
                f"unfused {t_plain:.3f} s")
            diff = {}
            if fused_rows.keys() != plain_rows.keys():
                diff["columns"] = sorted(fused_rows.keys() ^ plain_rows.keys())
            for c in sorted(fused_rows.keys() & plain_rows.keys()):
                a, b = fused_rows[c], plain_rows[c]
                if a.shape != b.shape:
                    diff[c] = f"{a.shape} vs {b.shape} rows"
                elif not np.array_equal(a.view(np.uint8), b.view(np.uint8)):
                    bad = a != b
                    diff[c] = (f"{int(bad.sum())} rows differ, max abs "
                               f"{float(np.max(np.abs(a[bad] - b[bad]))):.6g}")
            checks.expect(not diff, "fused and unfused partView cleans "
                          "are bit-identical",
                          f"{len(fused_rows['l_partkey'])} valid sample rows, "
                          f"{vm.drift_rows('partView')} pending delta rows"
                          + (f"; {diff}" if diff else ""))
    watermark_epochs = svc.refresh_count - epochs_before

    exact_err = 0.0
    for view in ("joinView", "partView"):
        for q in dashboard_queries(qrng, N_QUERIES, qty_mean[view]):
            got = float(vm.query_stale(view, q))
            truth = float(vm.query_exact_fresh(view, q))
            exact_err = max(exact_err, abs(got - truth) / max(abs(truth), 1e-12))
    ask("after maintenance")

    # -- report -----------------------------------------------------------------
    rows = sum(int(r.valid.shape[0]) for r in vm.base.values())
    nbytes = sum(rel_bytes(r) for r in vm.base.values())
    for mv in vm.views.values():
        for r in (mv.materialized, mv.stale_sample, mv.clean_sample):
            rows += int(r.valid.shape[0])
            nbytes += rel_bytes(r)
    log(f"resident: {rows} rows (capacity) in {nbytes} bytes "
        f"across base relations, views and samples")
    stats = jax.devices()[0].memory_stats() or {}
    log(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use', 'not reported')} "
        f"bytes_in_use: {stats.get('bytes_in_use', 'not reported')}")
    compile_s = sum(s.compile_s for s in prof.ops.values())
    execute_s = sum(s.execute_s for s in prof.ops.values())
    log(f"warm-up: data {t_data:.3f} s, register {t_register:.3f} s, "
        f"kernel first-sight (compile) {compile_s:.3f} s")
    log(f"steady: kernel repeat dispatches {execute_s:.3f} s; epochs "
        f"{[round(x, 3) for x in timings['epochs_s']]} s; query batches "
        f"{[round(x, 3) for x in timings['query_batches_s']]} s")
    print_kernel_table(prof)

    # -- checks -----------------------------------------------------------------
    for op in MAIN_OPS:
        s = prof.ops.get(op)
        d = s.dispatches if s else 0
        f = s.fallbacks if s else 0
        ok = d >= 1 and (f == 0 or not on_tpu)
        note = "" if on_tpu else " (off the TPU the XLA reference path is the design)"
        checks.expect(ok, f"{op} dispatched on its kernel path",
                      f"dispatches={d} fallbacks={f}{note}")
    checks.expect(watermark_epochs >= 2, "the watermark triggered >= 2 epochs",
                  f"{watermark_epochs} epochs")
    checks.expect(prof.ops.get("fleet_merge") is not None, "an epoch cleaned "
                  "through svc_refresh_many -> fleet_merge")
    checks.expect(maintained == {"joinView", "partView"},
                  "the maintenance epoch maintained both views",
                  f"maintained={sorted(maintained)}")
    degraded = vm.health.degraded_views()
    checks.expect(not degraded and vm.fleet_merge_failures == 0,
                  "no quarantined view and no fleet-merge failure",
                  f"degraded={degraded} fleet_merge_failures="
                  f"{vm.fleet_merge_failures}")
    tagged = [e.method for _v, _q, e, _t in answers if "+degraded" in e.method]
    checks.expect(not tagged, "no answer is degraded",
                  f"{len(answers)} answers, degraded methods={tagged[:3]}")
    misses, errs = 0, []
    for view, q, e, truth in answers:
        lo, hi = float(e.ci_low), float(e.ci_high)
        inside = lo - 1e-6 * abs(truth) <= truth <= hi + 1e-6 * abs(truth)
        misses += not inside
        if abs(truth) > 0:
            errs.append(abs(float(e.value) - truth) / abs(truth))
    allowed = miss_allowance(len(answers))
    log(f"answer errors vs query_exact_fresh: median rel "
        f"{float(np.median(errs)) if errs else float('nan'):.6g}, max rel "
        f"{max(errs) if errs else float('nan'):.6g}; CI misses {misses}/"
        f"{len(answers)} (allowed {allowed}); methods "
        f"{sorted({e.method for _v, _q, e, _t in answers})}")
    checks.expect(misses <= allowed, "answers fall inside their CI of "
                  "query_exact_fresh", f"{misses} misses of {len(answers)}")
    checks.expect(exact_err <= EXACT_RTOL, "after maintenance the exact "
                  "answer matches query_exact_fresh",
                  f"max rel err {exact_err:.3g} (limit {EXACT_RTOL})")


def four_chips(args, checks: Checks) -> None:
    import jax
    from jax.sharding import Mesh

    from repro.core import Query, ViewDef
    from repro.distributed import ShardedFleet
    from repro.kernels import KernelProfiler, set_profiler
    from repro.planner import MaintenancePlanner
    from repro.relational.plan import GroupByNode, Scan
    from repro.relational.relation import from_columns
    from repro.views import ViewManager

    devices = jax.devices()
    checks.expect(len(devices) == 4, "four devices", f"{len(devices)}")
    if len(devices) != 4:
        return
    mesh = Mesh(np.asarray(devices), ("data",))
    prof = KernelProfiler()
    set_profiler(prof)
    n_views, rows = 8, max(64, int(round(SF1["lineitem"] / 8 * args.scale)))
    groups = max(16, int(round(SF1["parts"] * args.scale)))

    def base(i, start, count):
        r = np.random.default_rng(args.seed * 1000 + i + start)
        return from_columns(
            {"k": np.arange(start, start + count, dtype=np.int32),
             "g": r.integers(0, groups, count).astype(np.int32),
             "v": r.exponential(5.0, count).astype(np.float32)},
            pk=["k"], capacity=2 * count)

    def plan(i):
        return GroupByNode(child=Scan(f"Log{i}", pk=("k",)), keys=("g",),
                           aggs=(("total", "sum", "v"), ("cnt", "count", None)),
                           num_groups=int(groups * 1.25))

    clock = lambda: 0.0  # noqa: E731 — plans must not depend on wall time
    fleet = ShardedFleet(n_shards=4, budget_s=10.0, clock=clock, mesh=mesh,
                         heartbeat_timeout_s=1e9)
    flat = ViewManager(clock=clock)
    planner = MaintenancePlanner(flat, budget_s=10.0, age_cap_s=1e9,
                                 clock=clock, deadline_floor_s=1e9)
    t0 = time.perf_counter()
    for i in range(n_views):
        b = base(i, 0, rows)
        fleet.register_base(f"Log{i}", b)
        flat.register_base(f"Log{i}", b)
        for target in (fleet, flat):
            target.register_view(ViewDef(f"v{i}", plan(i)),
                                 delta_bases=(f"Log{i}",), m=M, seed=i,
                                 delta_group_capacity=int(groups * 1.25))
    for cm in fleet.cost_models + [planner.cost_model]:
        cm.pin_costs(0.05, 0.25)
    log(f"four-chip fleet: {n_views} views x {rows} rows, {groups} groups, "
        f"registered in {time.perf_counter() - t0:.3f} s")
    for i in range(n_views):
        d = base(i, 10 * rows, rows // 10)
        fleet.ingest(f"Log{i}", inserts=d, seq=0, key=f"e{i}")
        flat.ingest(f"Log{i}", inserts=d)
    t0 = time.perf_counter()
    rep = fleet.epoch_step()
    t_sharded = time.perf_counter() - t0
    t0 = time.perf_counter()
    flat_rep = planner.step()
    t_flat = time.perf_counter() - t0
    sharded_plan = sorted((a.view, a.action) for a in rep.actions)
    flat_plan = sorted((a.view, a.action) for a in flat_rep.actions)
    log(f"sharded epoch {t_sharded:.3f} s plan {sharded_plan}")
    log(f"flat epoch {t_flat:.3f} s plan {flat_plan}")
    checks.expect(sharded_plan == flat_plan and len(sharded_plan) == n_views,
                  "sharded and flat plans are identical")
    q = Query(agg="sum", col="total")
    same = all(
        float(fleet.query(f"v{i}", q).value) == float(flat.query(f"v{i}", q).value)
        for i in range(n_views))
    checks.expect(same, "sharded and flat answers are identical")
    s = prof.ops.get("fleet_score_sharded")
    checks.expect(s is not None and s.dispatches >= 1 and s.fallbacks == 0,
                  "fleet_score_sharded took the mesh path",
                  f"dispatches={s.dispatches if s else 0} "
                  f"fallbacks={s.fallbacks if s else 0}")
    checks.expect(not fleet.degraded_views(), "no degraded sharded view")
    print_kernel_table(prof)
    for d in devices:
        st = d.memory_stats() or {}
        log(f"device {d.id} ({d.device_kind}): bytes_in_use="
            f"{st.get('bytes_in_use', 'not reported')} peak_bytes_in_use="
            f"{st.get('peak_bytes_in_use', 'not reported')}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--scale", type=float, default=1.0,
                    help="row-count factor for a rehearsal off the chip")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax

    from repro.compile_cache import configure_compile_cache

    cache = configure_compile_cache()
    dev = device_info()
    on_tpu = dev["platform"] == "tpu"
    log(f"device: platform={dev['platform']} kind={dev['kind']} "
        f"count={dev['count']} jax={jax.__version__} compile_cache={cache}")
    checks = Checks()
    if not on_tpu and args.scale >= 1.0:
        checks.expect(False, "the device is a TPU",
                      f"platform={dev['platform']}; pass --scale to rehearse")
        return 1
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(args, checks)
    else:
        one_chip(args, checks, on_tpu)
    log(f"wall: {time.perf_counter() - t0:.3f} s")
    checks.expect(on_tpu, "the device is a TPU", f"platform={dev['platform']}")
    if checks.failed:
        log(f"FAILED: {checks.failed}")
        return 1
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
