"""TPC-H at a scale factor, every table and column, with TPCD-Skew prices;
views over lineitem; an RF1-shaped insert stream.

The insert stream is TPC-H's RF1: new orders, each with its 1–7 lineitems,
made by the same rules as the base tables, with order keys from the
arena the views reserve past the base orders (``group_slack``).  The
lineitems arrive through the delta log in the order of their orders,
cut into the mix's fixed-size micro-batches (an order may straddle two,
as a feed that flushes by row count cuts it).  The new orders' rows are
loaded into ``orders`` at set-up, ahead of their lineitems: the program
maintains its join views under deltas of the fact table (lineitem) only,
and an order with no lineitems changes no view.
"""

from __future__ import annotations

import numpy as np

from svcbench.data import dimensions, orders_lineitems
from svcbench.datasets import Deployment, Table, ViewSpec

PKS = {"lineitem": "l_linekey", "orders": "o_orderkey", "customer": "c_custkey",
       "part": "p_partkey", "partsupp": "ps_key", "supplier": "s_suppkey",
       "nation": "n_nationkey", "region": "r_regionkey"}


def build(config: dict, rng: np.random.Generator) -> Deployment:
    n = config["rows"]
    slack = config["capacity_slack"]
    dims = dimensions(rng, n["customer"], n["part"], n["supplier"], config["z"])
    orders, lineitem = orders_lineitems(rng, 0, n["orders"], 0, n["customer"],
                                        dims["part"], n["supplier"])
    n_new = int(n["orders"] * config["group_slack"]) - n["orders"]
    rf1_orders, rf1 = orders_lineitems(rng, n["orders"], n_new,
                                       lineitem["l_linekey"].shape[0],
                                       n["customer"], dims["part"], n["supplier"])
    orders = {c: np.concatenate([orders[c], rf1_orders[c]]) for c in orders}
    cols = dict(dims, orders=orders, lineitem=lineitem)
    capacity = {"lineitem": int(n["lineitem"] * slack),
                "orders": int(n["orders"] * slack)}
    tables = {name: Table(c, PKS[name], max(capacity.get(name, 0), len(c[PKS[name]])))
              for name, c in cols.items()}
    views = []
    for v in config["views"]:
        groups = int(n[v["groups_of"]] * config["group_slack"])
        per_group = n["lineitem"] / n[v["groups_of"]]
        views.append(ViewSpec(
            name=v["name"], base="lineitem", key=v["key"],
            aggs=tuple(tuple(a) for a in v["aggs"]), groups=groups,
            join=tuple(v["join"]) if v.get("join") else None,
            outlier=tuple(v["outlier"]) if v.get("outlier") else None,
            seed=int(v["seed"]), pred_col=config["query"]["pred_col"],
            pred_scale=config["query"]["pred_mean_per_row"] * per_group,
        ))
    first = lineitem["l_linekey"].shape[0]

    def new_rows(_rng, base, start, count):
        lo = start - first
        if lo + count > rf1["l_linekey"].shape[0]:
            raise ValueError("the run inserts more lineitems than the "
                             "reserved order keys hold")
        return {c: v[lo:lo + count] for c, v in rf1.items()}

    return Deployment(tables, views, ["lineitem"], new_rows)
