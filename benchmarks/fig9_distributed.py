"""Fig 9 fleet edition: sharded epoch execution on 8 placeholder devices.

SVC §7.5: hashed sampled cleaning is deterministic and row-local, so an
epoch over a fleet of views parallelizes across a mesh with only the
small score panel to combine.  This benchmark runs in a child process
with ``--xla_force_host_platform_device_count=8`` (merged into, never
clobbering, the user's own ``XLA_FLAGS``) and produces three guarded
results in ``BENCH_distributed.json``:

  * **scaling curve** — the per-epoch work of a thousands-of-views fleet
    (moments → scores → global knapsack → masked clean/merge act), timed
    as the per-shard critical path: the wall of ONE shard's slice program
    plus the measured global-combine cost (score-panel gather + host
    knapsack — the only non-parallel term).  That is what S physical
    devices realize per epoch; the guard is ≥ 0.7× linear at 8 shards.
    (This container exposes one CPU core, so raw 8-program wall cannot
    show the speedup; the critical path is the honest device-count model
    and is reported alongside the measured single-core walls.)
  * **parity** — the mesh-combined score panel (shard_map + all_gather on
    the 8 devices) is bit-equal to the single-device pass on the same
    schedule, and the global knapsack picks the identical plan.
  * **availability** — a live ``ShardedFleet`` on the 8-device mesh loses
    a shard mid-run: its views suspend to serve-stale (every query still
    answers → availability 1.0), its ingest partitions keep queueing, and
    the post-revive drain epoch clears the backlog.
"""

from __future__ import annotations

import argparse
import json
import os
from typing import List

from benchmarks.common import Row, run_forced_device_child

DEVICES = 8
SCALING_FLOOR = 0.7

_CHILD = r"""
import json, time
import numpy as np, jax, jax.numpy as jnp

QUICK = bool(@QUICK@)
assert jax.device_count() == 8, jax.devices()

from repro.core import Query, ViewDef
from repro.distributed import ShardedFleet
from repro.kernels.fleet_moments.ref import fleet_moments_ref
from repro.kernels.fleet_score import fleet_scores, fleet_scores_sharded
from repro.kernels.fleet_score.ref import (
    A_CLEAN, A_MAINTAIN, F_AGE, F_COST_CLEAN, F_COST_MAINTAIN, F_COST_RETUNE,
    F_DRIFT_CLEAN, F_DRIFT_IVM, F_EX2, F_HT_AQP, F_HT_CORR, F_M, F_MEAN, F_N,
    F_TRAFFIC, N_FEATURES, fleet_score_ref,
)
from repro.launch.mesh import make_local_mesh
from repro.planner.scheduler import greedy_knapsack
from repro.relational.plan import GroupByNode, Scan
from repro.relational.relation import from_columns

t_start = time.perf_counter()
V = 512 if QUICK else 2048       # fleet size (views)
R = 256                          # sample-panel rows per view
D = 24                           # act-pass aggregate depth (merge work)
REPEATS = 5 if QUICK else 9
COST_C, COST_M = 0.05, 0.25

rng = np.random.default_rng(0)
x = rng.exponential(5.0, (V, R)).astype(np.float32)
val = (rng.random((V, R)) < 0.9).astype(np.float32)
w = np.full((V, R), 10.0, np.float32)
ompi = np.full((V, R), 0.9, np.float32)
xo = (x + rng.normal(0.0, 0.5, (V, R))).astype(np.float32)
CH = (x, val, w, ompi, xo, val, w, ompi)
drift = rng.integers(1, 200, V).astype(np.float32)
traffic = (rng.random(V) + 0.1).astype(np.float32)


def build_features(mom, dr, tr):
    v = mom.shape[0]
    f = jnp.zeros((v, N_FEATURES), jnp.float32)
    n = mom[:, 0]
    f = f.at[:, F_N].set(n)
    f = f.at[:, F_MEAN].set(mom[:, 1] / jnp.maximum(n, 1.0))
    f = f.at[:, F_EX2].set(mom[:, 2] / jnp.maximum(n, 1.0))
    f = f.at[:, F_HT_AQP].set(mom[:, 3])
    f = f.at[:, F_HT_CORR].set(mom[:, 4])
    f = f.at[:, F_DRIFT_CLEAN].set(dr)
    f = f.at[:, F_DRIFT_IVM].set(dr)
    f = f.at[:, F_TRAFFIC].set(tr)
    f = f.at[:, F_COST_CLEAN].set(COST_C)
    f = f.at[:, F_COST_MAINTAIN].set(COST_M)
    f = f.at[:, F_COST_RETUNE].set(2.0 * COST_C)
    f = f.at[:, F_M].set(0.1)
    return f


def shard_program(ch, dr, tr, mask):
    # one shard's whole epoch slice: moments -> features -> scores, then
    # the masked clean/merge act pass (row-local, like fleet_clean_merge)
    mom = fleet_moments_ref(*ch)
    scores = fleet_score_ref(build_features(mom, dr, tr))
    acc = jnp.zeros((ch[0].shape[0],), jnp.float32)
    t_rows = ch[2] * ch[0] * ch[1] * mask[:, None]
    for i in range(D):
        t = jnp.sin(t_rows * (0.1 * (i + 1))) + t_rows / (i + 1.0)
        acc = acc + jnp.sum(t, axis=1)
    return scores, acc


jitted = jax.jit(shard_program)


def slice_args(lo, hi):
    ch = tuple(jnp.asarray(c[lo:hi]) for c in CH)
    mask = jnp.asarray((np.arange(hi - lo) % 2 == 0).astype(np.float32))
    return ch, jnp.asarray(drift[lo:hi]), jnp.asarray(traffic[lo:hi]), mask


def median_wall(fn, *args):
    jax.block_until_ready(fn(*args))  # compile outside the timings
    ts = []
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


# -- the global combine: score-panel gather + ONE host knapsack over V views
full_scores = np.asarray(jitted(*slice_args(0, V))[0])


def make_cands(scores):
    out = []
    for i in range(V):
        out.append((float(scores[i, A_CLEAN]), f"v{i:05d}", "clean", COST_C))
        out.append((float(scores[i, A_MAINTAIN]), f"v{i:05d}", "maintain",
                    COST_M))
    return out


CANDS = make_cands(full_scores)
BUDGET = V * COST_C * 0.5


def combine(parts):
    np.concatenate(parts)  # the gathered (S, Vs, N_SCORES) panel, stacked
    chosen = {}
    greedy_knapsack(CANDS, BUDGET, chosen)
    return chosen


parts8 = [full_scores[s * (V // 8):(s + 1) * (V // 8)] for s in range(8)]
ts = []
for _ in range(REPEATS):
    t0 = time.perf_counter()
    plan_ref = combine(parts8)
    ts.append(time.perf_counter() - t0)
combine_s = float(np.median(ts))

# -- scaling: per-shard critical path = one slice program + the combine
curve = []
for S in (1, 2, 4, 8):
    vs = V // S
    slice_s = median_wall(jitted, *slice_args(0, vs))
    cp = slice_s + combine_s
    curve.append({"shards": S, "views_per_shard": vs, "slice_s": slice_s,
                  "combine_s": combine_s, "critical_path_s": cp,
                  "views_per_s": V / cp})
scaling_at_8 = curve[0]["critical_path_s"] / (8 * curve[-1]["critical_path_s"])

# -- parity: mesh-combined scores vs the single-device pass, same schedule
mesh = make_local_mesh(data=8, model=1)
Vs = V // 8
mom_all = np.asarray(fleet_moments_ref(*CH))
feats_flat = np.asarray(build_features(jnp.asarray(mom_all),
                                       jnp.asarray(drift),
                                       jnp.asarray(traffic)))
stacked = feats_flat.reshape(8, Vs, N_FEATURES)
scores_mesh = np.asarray(fleet_scores_sharded(stacked, mesh=mesh))
scores_host = np.asarray(fleet_scores_sharded(stacked))
scores_flat = np.asarray(fleet_scores(feats_flat))  # the single-device op
parity_mesh = bool(np.array_equal(scores_mesh, scores_host))
parity_flat = bool(np.array_equal(scores_host.reshape(V, -1), scores_flat))
chosen_mesh = {}
greedy_knapsack(make_cands(scores_mesh.reshape(V, -1)), BUDGET, chosen_mesh)
plan_identical = (
    sorted((a.view, a.action) for a in chosen_mesh.values())
    == sorted((a.view, a.action) for a in plan_ref.values()))

# -- availability: a live 8-shard fleet loses a shard and serves through it
N_AV = 8
fleet = ShardedFleet(n_shards=8, budget_s=10.0, mesh=mesh)
arng = np.random.default_rng(7)


def rel(start, n):
    return from_columns(
        {"k": np.arange(start, start + n, dtype=np.int32),
         "g": arng.integers(0, 8, n).astype(np.int32),
         "v": arng.exponential(5.0, n).astype(np.float32)},
        pk=["k"])


for i in range(N_AV):
    fleet.register_base(f"Log{i}", rel(0, 200))
    plan = GroupByNode(child=Scan(f"Log{i}", pk=("k",)), keys=("g",),
                      aggs=(("total", "sum", "v"), ("cnt", "count", None)),
                      num_groups=16)
    fleet.register_view(ViewDef(f"av{i}", plan), delta_bases=(f"Log{i}",),
                        m=0.4, seed=i, delta_group_capacity=16, shard=i)

for i in range(N_AV):
    fleet.ingest(f"Log{i}", inserts=rel(1000 + i * 50, 40), seq=0, key=f"a{i}")
fleet.epoch_step()

LOST = 3
fleet.kill_shard(LOST)
for i in range(N_AV):
    fleet.ingest(f"Log{i}", inserts=rel(2000 + i * 50, 40), seq=1, key=f"b{i}")
rep = fleet.epoch_step()
suspended = list(rep.suspended)
backlog = fleet.pending_rows()
answered = 0
for i in range(N_AV):
    try:
        est = fleet.query(f"av{i}", Query(agg="sum", col="total"))
        if np.isfinite(est.value):
            answered += 1
    except Exception:
        pass
availability = answered / N_AV
lost_degraded = all(fleet.is_degraded(n) for n in suspended)

fleet.revive_shard(LOST)
rep2 = fleet.epoch_step()
drained = (fleet.pending_rows() == 0 and not rep2.excluded_shards
           and any(a.shard == LOST for a in rep2.actions))

print(json.dumps({
    "platform": jax.devices()[0].platform, "devices": 8, "n_views": V, "rows_per_view": R, "act_depth": D,
    "curve": curve, "combine_s": combine_s, "scaling_at_8": scaling_at_8,
    "parity": {"mesh_vs_host_bit_equal": parity_mesh,
               "host_vs_flat_bit_equal": parity_flat,
               "plan_identical": plan_identical},
    "availability": availability, "answered": answered, "asked": N_AV,
    "lost_shard": LOST, "suspended_views": suspended,
    "backlog_rows_during_loss": int(backlog),
    "lost_views_degraded": bool(lost_degraded and len(suspended) == 1),
    "drained_after_revive": bool(drained),
    "wall_s": time.perf_counter() - t_start,
}))
"""


def run(quick: bool = False) -> List[Row]:
    code = _CHILD.replace("@QUICK@", "1" if quick else "0")
    proc = run_forced_device_child(code, DEVICES, timeout=1800)
    if proc.returncode != 0:
        return [Row("fig9_distributed", 0.0, "ERROR: " + proc.stderr[-300:])]
    out = json.loads(proc.stdout.strip().splitlines()[-1])

    parity = out["parity"]
    payload = {
        "quick": bool(quick),
        "platform": out["platform"],
        "devices": out["devices"],
        "n_views": out["n_views"],
        "rows_per_view": out["rows_per_view"],
        "act_depth": out["act_depth"],
        "curve": out["curve"],
        "combine_s": out["combine_s"],
        "scaling_at_8": out["scaling_at_8"],
        "parity": parity,
        "availability": out["availability"],
        "lost_shard": out["lost_shard"],
        "suspended_views": out["suspended_views"],
        "backlog_rows_during_loss": out["backlog_rows_during_loss"],
        "wall_s": out["wall_s"],
        "guards": {
            "scaling_ok": out["scaling_at_8"] >= SCALING_FLOOR,
            "parity_ok": (parity["mesh_vs_host_bit_equal"]
                          and parity["host_vs_flat_bit_equal"]
                          and parity["plan_identical"]),
            "availability_ok": (out["availability"] == 1.0
                                and out["lost_views_degraded"]
                                and out["backlog_rows_during_loss"] > 0),
            "drain_ok": out["drained_after_revive"],
        },
    }
    out_path = os.environ.get("BENCH_OUT", "BENCH_distributed.json")
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=2)

    cp8 = out["curve"][-1]["critical_path_s"]
    der = (f"platform={out['platform']} "
           f"scaling_at_8={out['scaling_at_8']:.2f}x "
           f"parity={payload['guards']['parity_ok']} "
           f"availability={out['availability']:.2f} "
           f"drain={out['drained_after_revive']} "
           f"({out['n_views']} views, critical_path@8={cp8 * 1e3:.1f}ms)")
    return [Row("fig9_distributed", cp8 * 1e6, der)]


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    for row in run(quick=args.quick):
        print(row.csv(), flush=True)
