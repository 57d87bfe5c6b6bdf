"""CPU checks of the TPC-H generator against the specification's rules
(clause 4.2.3) and of the RF1-shaped insert stream."""

import numpy as np
import pytest

from svcbench import data
from svcbench.datasets import load

CONFIG = {"rows": {"lineitem": 40_000, "orders": 10_000, "customer": 1_500,
                   "part": 2_000, "supplier": 100},
          "z": 2.0, "capacity_slack": 1.5, "group_slack": 1.25,
          "views": [], "query": {"pred_col": "qty", "pred_mean_per_row": 25.5}}
WIDTHS = {"lineitem": 17, "orders": 9, "customer": 8, "part": 9,
          "partsupp": 6, "supplier": 7, "nation": 4, "region": 3}


@pytest.fixture(scope="module")
def dep():
    return load("tpch").build(CONFIG, np.random.default_rng(7))


def test_every_table_has_every_column(dep):
    # TPC-H's columns, plus the row ids lineitem and partsupp are keyed on
    assert {n: len(t.cols) for n, t in dep.tables.items()} == WIDTHS
    for t in dep.tables.values():
        n = {c.shape[0] for c in t.cols.values()}
        assert len(n) == 1 and n.pop() <= t.capacity
        assert len(np.unique(t.cols[t.pk])) == t.cols[t.pk].shape[0]


def test_each_order_has_one_to_seven_numbered_lineitems(dep):
    li = dep.tables["lineitem"].cols
    per = np.bincount(li["l_orderkey"], minlength=10_000)[:10_000]
    assert per.min() >= 1 and per.max() <= 7
    assert abs(per.mean() - 4.0) < 0.1
    first = np.r_[True, li["l_orderkey"][1:] != li["l_orderkey"][:-1]]
    assert np.all(li["l_linenumber"][first] == 1)
    assert np.all(np.diff(li["l_linenumber"])[~first[1:]] == 1)


def test_lineitem_values_follow_the_rules(dep):
    li = dep.tables["lineitem"].cols
    part = dep.tables["part"].cols
    q = li["l_quantity"]
    assert q.min() == 1 and q.max() == 50 and np.all(q == np.round(q))
    np.testing.assert_array_equal(
        li["l_extendedprice"], (q * part["p_retailprice"][li["l_partkey"]]).astype(np.float32))
    assert 0.0 <= li["l_discount"].min() and li["l_discount"].max() <= np.float32(0.10)
    assert 0.0 <= li["l_tax"].min() and li["l_tax"].max() <= np.float32(0.08)
    odate = dep.tables["orders"].cols["o_orderdate"][li["l_orderkey"]]
    assert np.all((li["l_shipdate"] - odate >= 1) & (li["l_shipdate"] - odate <= 121))
    assert np.all((li["l_commitdate"] - odate >= 30) & (li["l_commitdate"] - odate <= 90))
    gap = li["l_receiptdate"] - li["l_shipdate"]
    assert gap.min() >= 1 and gap.max() <= 30
    late = li["l_receiptdate"] > data.CURRENT_DATE
    assert np.all(li["l_returnflag"][late] == data.FLAG_N)
    assert set(np.unique(li["l_returnflag"][~late])) == {data.FLAG_A, data.FLAG_R}
    np.testing.assert_array_equal(
        li["l_linestatus"] == data.STATUS_O, li["l_shipdate"] > data.CURRENT_DATE)
    # each lineitem's supplier is one of its part's four
    ps = dep.tables["partsupp"].cols
    pairs = set(zip(ps["ps_partkey"].tolist(), ps["ps_suppkey"].tolist()))
    assert all(p in pairs for p in zip(li["l_partkey"].tolist(), li["l_suppkey"].tolist()))
    assert len(pairs) == 4 * 2_000


def test_orders_follow_their_lineitems(dep):
    li = dep.tables["lineitem"].cols
    o = {c: v[:10_000] for c, v in dep.tables["orders"].cols.items()}
    charge = (li["l_extendedprice"].astype(np.float64) * (1 + li["l_tax"])
              * (1 - li["l_discount"]))
    total = np.bincount(li["l_orderkey"], weights=charge, minlength=10_000)
    np.testing.assert_allclose(o["o_totalprice"], total, rtol=1e-6)
    n_open = np.bincount(li["l_orderkey"], weights=li["l_linestatus"] == data.STATUS_O,
                         minlength=10_000)
    n = np.bincount(li["l_orderkey"], minlength=10_000)
    want = np.where(n_open == n, data.STATUS_O,
                    np.where(n_open == 0, data.STATUS_F, data.STATUS_P))
    np.testing.assert_array_equal(o["o_orderstatus"], want)
    # customers whose key (from 1) is divisible by 3 place no orders
    assert np.all((o["o_custkey"] + 1) % 3 != 0)
    assert o["o_orderdate"].max() <= data.END_DATE - 151


def test_rf1_stream_is_new_orders_with_their_lineitems(dep):
    li = dep.tables["lineitem"].cols
    orders = dep.tables["orders"].cols
    start = li["l_linekey"].shape[0]
    rows = dep.new_rows(None, "lineitem", start, 5_000)
    np.testing.assert_array_equal(rows["l_linekey"], start + np.arange(5_000))
    keys = rows["l_orderkey"]
    assert keys.min() == 10_000 and np.all(np.diff(keys) >= 0)
    # the new orders' rows are in orders, inside the 1.25x key arena
    assert orders["o_orderkey"].max() == 12_499
    assert np.all(np.isin(keys, orders["o_orderkey"]))
    full = np.bincount(keys)[10_000:keys.max()]  # the last may straddle
    assert full.min() >= 1 and full.max() <= 7
    more = dep.new_rows(None, "lineitem", start + 5_000, 3)
    assert more["l_linekey"][0] == start + 5_000
    with pytest.raises(ValueError):
        dep.new_rows(None, "lineitem", start, 10**6)


def test_same_seed_same_tables_other_seed_other_tables(dep):
    again = load("tpch").build(CONFIG, np.random.default_rng(7))
    other = load("tpch").build(CONFIG, np.random.default_rng(8))
    for name, t in dep.tables.items():
        for c, v in t.cols.items():
            np.testing.assert_array_equal(v, again.tables[name].cols[c])
    assert not np.array_equal(dep.tables["lineitem"].cols["l_partkey"][:100],
                              other.tables["lineitem"].cols["l_partkey"][:100])


def test_suppkey_rule_gives_four_distinct_suppliers():
    parts = np.repeat(np.arange(5_000), 4)
    s = data.suppkey(parts, np.tile(np.arange(4), 5_000), 250)
    assert s.min() >= 0 and s.max() < 250
    assert np.all(np.array([len(set(x)) for x in s.reshape(-1, 4)]) == 4)
