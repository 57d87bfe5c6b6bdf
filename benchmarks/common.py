"""Shared benchmark scaffolding: scenarios, timing, CSV rows.

Every ``fig*.py`` module exposes ``run(quick: bool) -> list[Row]``; rows are
``(name, us_per_call, derived)`` — one benchmark per paper table/figure.
"""

from __future__ import annotations

import dataclasses
import os
import pathlib
import subprocess
import sys
import time
from typing import Callable, Dict, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core import Query, ViewDef, exact
from repro.data.synthetic import (
    grow_lineitem,
    grow_log,
    make_lineitem_orders,
    make_log_video,
)
from repro.relational.expr import Col, Lit, Cmp, and_
from repro.relational.plan import FKJoin, GroupByNode, ProjectNode, Scan
from repro.views import ViewManager


@dataclasses.dataclass
class Row:
    name: str
    us_per_call: float
    derived: str

    def csv(self) -> str:
        return f"{self.name},{self.us_per_call:.1f},{self.derived}"


def run_forced_device_child(code: str, device_count: int,
                            timeout: int = 900) -> subprocess.CompletedProcess:
    """Run ``code`` in a child interpreter with ``device_count`` placeholder
    XLA host devices (the multi-device benchmarks can't set the flag in
    THIS process — jax locks its device count at first init).

    The child environment is derived, not replaced: any existing
    ``XLA_FLAGS`` tokens are kept (only a previous device-count force is
    replaced with ours), and the repo's ``src`` is PREPENDED to whatever
    ``PYTHONPATH`` the user already exported.  The child is a virtual-device
    CPU run by design, so ``JAX_PLATFORMS=cpu`` keeps it off any accelerator
    the parent may hold."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = [t for t in env.get("XLA_FLAGS", "").split()
             if not t.startswith("--xla_force_host_platform_device_count")]
    flags.append(f"--xla_force_host_platform_device_count={int(device_count)}")
    env["XLA_FLAGS"] = " ".join(flags)
    src = str(pathlib.Path(__file__).resolve().parent.parent / "src")
    prev = env.get("PYTHONPATH", "")
    env["PYTHONPATH"] = src + (os.pathsep + prev if prev else "")
    return subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, env=env, timeout=timeout)


def timeit(fn: Callable, repeats: int = 3, warmup: int = 1) -> float:
    """Median wall time (µs)."""
    for _ in range(warmup):
        fn()
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        ts.append((time.perf_counter() - t0) * 1e6)
    return float(np.median(ts))


# ---------------------------------------------------------------------------
# Scenario: TPCD-ish join view (lineitem ⋈ orders, group by orderkey)
# ---------------------------------------------------------------------------

def join_view_scenario(
    quick: bool, z: float = 2.0, update_frac: float = 0.10, m: float = 0.1,
    seed: int = 0,
) -> Tuple[ViewManager, Dict]:
    scale = 1 if quick else 4
    n_orders, n_items = 4000 * scale, 20_000 * scale
    n_cust, n_parts = 800 * scale, 500 * scale
    rng = np.random.default_rng(seed)
    lineitem, orders, customer, nation, region = make_lineitem_orders(
        rng, n_orders, n_items, n_cust, n_parts, z=z
    )
    plan = GroupByNode(
        child=FKJoin(fact=Scan("lineitem", pk=("l_linekey",)),
                     dim=Scan("orders", pk=("o_orderkey",)),
                     fact_key="l_orderkey"),
        keys=("l_orderkey",),
        aggs=(
            ("revenue", "sum", "l_extendedprice"),
            ("qty", "sum", "l_quantity"),
            ("items", "count", None),
        ),
        num_groups=int(n_orders * 1.25),
    )
    vm = ViewManager()
    vm.register_base("lineitem", lineitem)
    vm.register_base("orders", orders)
    vm.register_view(ViewDef("joinView", plan), delta_bases=("lineitem",), m=m,
                     seed=seed, delta_group_capacity=int(n_orders * 1.25))
    n_new = int(n_items * update_frac)
    delta = grow_lineitem(rng, n_orders, n_parts, start_key=n_items, n_new=n_new, z=z)
    meta = {"rng": rng, "n_orders": n_orders, "n_items": n_items,
            "n_parts": n_parts, "delta": delta, "z": z}
    return vm, meta


def random_join_queries(rng: np.random.Generator, n: int) -> List[Query]:
    out = []
    for _ in range(n):
        agg = rng.choice(["sum", "count", "avg"])
        col = rng.choice(["revenue", "qty", "items"])
        lo = float(rng.uniform(0, 30))
        hi = lo + float(rng.uniform(5, 60))
        pred = and_(Cmp("ge", Col("qty"), Lit(lo)), Cmp("le", Col("qty"), Lit(hi)))
        out.append(Query(agg=agg, col=None if agg == "count" else col, pred=pred))
    return out


def median_rel_error(vm: ViewManager, view: str, queries: List[Query],
                     answer: Callable[[Query], float]) -> float:
    errs = []
    for q in queries:
        truth = float(vm.query_exact_fresh(view, q))
        if abs(truth) < 1e-9:
            continue
        errs.append(abs(answer(q) - truth) / abs(truth))
    return float(np.median(errs)) if errs else float("nan")


# ---------------------------------------------------------------------------
# Scenario: visitView (running example / Conviva-shaped)
# ---------------------------------------------------------------------------

def visit_view_scenario(quick: bool, m: float = 0.1, seed: int = 0):
    scale = 1 if quick else 4
    nv, nl = 2000 * scale, 20_000 * scale
    rng = np.random.default_rng(seed)
    log, video = make_log_video(rng, nv, nl)
    plan = GroupByNode(
        child=FKJoin(fact=Scan("Log", pk=("sessionId",)),
                     dim=Scan("Video", pk=("videoId",)), fact_key="videoId"),
        keys=("videoId",),
        aggs=(("visitCount", "count", None), ("totalBytes", "sum", "bytes")),
        num_groups=int(nv * 1.5),
    )
    vm = ViewManager()
    vm.register_base("Log", log)
    vm.register_base("Video", video)
    vm.register_view(ViewDef("visitView", plan), delta_bases=("Log",), m=m,
                     seed=seed, delta_group_capacity=int(nv * 1.5))
    return vm, {"rng": rng, "nv": nv, "nl": nl}


# ---------------------------------------------------------------------------
# Scenario: data-cube view (§7.6.1, appendix 12.6.3)
# ---------------------------------------------------------------------------

def cube_view_scenario(quick: bool, z: float = 1.0, m: float = 0.1, seed: int = 0):
    scale = 1 if quick else 4
    n_orders, n_items = 4000 * scale, 20_000 * scale
    n_cust, n_parts = 200 * scale, 50
    rng = np.random.default_rng(seed)
    lineitem, orders, customer, nation, region = make_lineitem_orders(
        rng, n_orders, n_items, n_cust, n_parts, z=z
    )
    # revenue = l_extendedprice * (1 - l_discount), cube over (custkey, nation, part)
    # base: lineitem ⋈ orders ⋈ customer; group key = synthetic cube key
    j1 = FKJoin(fact=Scan("lineitem", pk=("l_linekey",)),
                dim=Scan("orders", pk=("o_orderkey",)), fact_key="l_orderkey")
    j2 = FKJoin(fact=j1, dim=Scan("customer", pk=("c_custkey",)), fact_key="o_custkey")
    from repro.relational.expr import Bin
    proj = ProjectNode(
        child=j2,
        outputs=(
            ("l_linekey", "l_linekey"),
            ("o_orderkey", "o_orderkey"),
            ("c_custkey", "c_custkey"),
            ("c_nationkey", "c_nationkey"),
            ("l_partkey", "l_partkey"),
            ("revenue", Bin("mul", Col("l_extendedprice"),
                            Bin("sub", Lit(1.0), Col("l_discount")))),
        ),
    )
    # composite cube key (custkey, partkey); nation/region roll-ups are
    # queries with predicates on the retained dimension columns
    plan = GroupByNode(
        child=proj,
        keys=("c_custkey", "l_partkey"),
        aggs=(
            ("revenue", "sum", "revenue"),
            ("cnt", "count", None),
        ),
        num_groups=int(n_cust * n_parts * 1.3),
    )
    vm = ViewManager()
    vm.register_base("lineitem", lineitem)
    vm.register_base("orders", orders)
    vm.register_base("customer", customer)
    vm.register_view(ViewDef("cubeView", plan), delta_bases=("lineitem",), m=m,
                     seed=seed, delta_group_capacity=int(n_cust * n_parts * 1.3))
    meta = {"rng": rng, "n_orders": n_orders, "n_items": n_items,
            "n_parts": n_parts, "n_cust": n_cust}
    return vm, meta
