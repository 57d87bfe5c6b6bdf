"""Relational operator semantics vs the pure-Python oracle (+ hypothesis)."""

import numpy as np
import pytest
from tests._hypothesis_compat import given, settings, st

from repro.relational import from_columns, ops
from repro.relational.expr import Col, Lit, Cmp, Bin
from repro.relational.relation import SENTINEL_KEY, compact, to_host

from tests import oracle


def mk_fact(rng, n, n_dim):
    return from_columns(
        {
            "fid": np.arange(n, dtype=np.int32),
            "dkey": rng.integers(0, n_dim, n).astype(np.int32),
            "val": rng.normal(size=n).astype(np.float32),
        },
        pk=["fid"],
        capacity=n + 7,  # exercise padding slots
    )


def mk_dim(rng, n):
    return from_columns(
        {"dkey": np.arange(n, dtype=np.int32),
         "w": rng.normal(size=n).astype(np.float32)},
        pk=["dkey"],
    )


@given(n=st.integers(1, 60), nd=st.integers(1, 12), seed=st.integers(0, 999))
@settings(max_examples=25, deadline=None)
def test_fk_join_matches_oracle(n, nd, seed):
    rng = np.random.default_rng(seed)
    fact, dim = mk_fact(rng, n, nd), mk_dim(rng, nd)
    got = oracle.from_relation(ops.fk_join(fact, dim, "dkey"))
    want = oracle.fk_join(oracle.from_relation(fact), oracle.from_relation(dim),
                          "dkey", "dkey")
    assert oracle.rows_equal(got, want, keys=("fid",))


@given(n=st.integers(1, 80), nd=st.integers(1, 10), seed=st.integers(0, 999))
@settings(max_examples=25, deadline=None)
def test_groupby_matches_oracle(n, nd, seed):
    rng = np.random.default_rng(seed)
    fact = mk_fact(rng, n, nd)
    got = oracle.from_relation(
        ops.groupby(fact, ("dkey",),
                    {"c": ("count", None), "s": ("sum", "val"),
                     "mn": ("min", "val"), "mx": ("max", "val")},
                    num_groups=nd + 4)
    )
    want = oracle.groupby(oracle.from_relation(fact), ("dkey",),
                          {"c": ("count", None), "s": ("sum", "val"),
                           "mn": ("min", "val"), "mx": ("max", "val")})
    assert oracle.rows_equal(got, want, keys=("dkey",))


@given(n=st.integers(1, 60), seed=st.integers(0, 999), thr=st.floats(-1, 1))
@settings(max_examples=25, deadline=None)
def test_select_project_match_oracle(n, seed, thr):
    rng = np.random.default_rng(seed)
    fact = mk_fact(rng, n, 5)
    sel = ops.select(fact, Cmp("gt", Col("val"), Lit(float(thr))))
    got = oracle.from_relation(sel)
    want = oracle.select(oracle.from_relation(fact), lambda r: r["val"] > thr)
    assert oracle.rows_equal(got, want, keys=("fid",))

    proj = ops.project(sel, {"fid": "fid", "v2": Bin("mul", Col("val"), Lit(2.0))})
    got2 = oracle.from_relation(proj)
    want2 = oracle.project(want, {"fid": lambda r: r["fid"], "v2": lambda r: r["val"] * 2})
    assert oracle.rows_equal(got2, want2, keys=("fid",))


def test_outer_join_unique_fill_and_presence():
    left = from_columns({"k": np.array([1, 2, 3], np.int32),
                         "a": np.array([10., 20., 30.], np.float32)}, pk=["k"])
    right = from_columns({"k": np.array([2, 3, 4], np.int32),
                          "b": np.array([1., 2., 3.], np.float32)}, pk=["k"])
    j = ops.outer_join_unique(left, right, on=("k",), how="outer")
    rows = {r["k"]: r for r in oracle.from_relation(j)}
    assert set(rows) == {1, 2, 3, 4}
    assert rows[1]["b"] == 0.0  # Ø→0 per Def. 4
    assert rows[4]["a"] == 0.0
    assert rows[2]["a"] == 20.0 and rows[2]["b"] == 1.0
    got_presence = {r["k"]: (r["__left_present"], r["__right_present"])
                    for r in [
                        {k: np.asarray(v)[i].item() for k, v in j.columns.items()}
                        for i in range(j.capacity) if bool(np.asarray(j.valid)[i])
                    ]}
    assert got_presence[1] == (1, 0) and got_presence[4] == (0, 1)


def test_union_intersect_difference():
    a = from_columns({"k": np.array([1, 2, 3], np.int32),
                      "v": np.array([1., 2., 3.], np.float32)}, pk=["k"])
    b = from_columns({"k": np.array([3, 4], np.int32),
                      "v": np.array([30., 40.], np.float32)}, pk=["k"])
    u = oracle.from_relation(ops.union_keyed(a, b))
    assert {r["k"] for r in u} == {1, 2, 3, 4}
    assert {r["k"]: r["v"] for r in u}[3] == 3.0  # left priority
    i = oracle.from_relation(ops.intersect_keyed(a, b))
    assert {r["k"] for r in i} == {3}
    d = oracle.from_relation(ops.difference_keyed(a, b))
    assert {r["k"] for r in d} == {1, 2}


def test_intersect_difference_composite_keys_sorted_search():
    """Composite-key ∩/− use an exact lexicographic binary search (the
    seed unrolled a compare chain over rel.capacity; a digest would be
    probabilistic on this exact path); exact per-tuple semantics must
    hold, including a same-x different-y near-miss."""
    a = from_columns(
        {"x": np.array([1, 2, 3, 4], np.int32),
         "y": np.array([10, 20, 30, 40], np.int32)},
        pk=["x", "y"], capacity=8,
    )
    b = from_columns(
        {"x": np.array([2, 3, 9], np.int32),
         "y": np.array([20, 31, 90], np.int32)},
        pk=["x", "y"], capacity=4,
    )
    inter = to_host(ops.intersect_keyed(a, b))
    assert inter["x"].tolist() == [2] and inter["y"].tolist() == [20]
    diff = to_host(ops.difference_keyed(a, b))
    assert sorted(diff["x"].tolist()) == [1, 3, 4]


def test_compact_preserves_rows():
    rng = np.random.default_rng(0)
    fact = mk_fact(rng, 20, 4)
    sel = ops.select(fact, Cmp("gt", Col("val"), Lit(0.0)))
    c = compact(sel, 15)
    assert oracle.rows_equal(oracle.from_relation(c), oracle.from_relation(sel),
                             keys=("fid",))


@pytest.mark.parametrize("name,program", [("joinView", "jit_plan_joinView"),
                                          (None, "jit_plan_GroupByNode")])
def test_compiled_plan_is_named_after_its_view(name, program):
    """A profile tells compiled plans apart by name: ``plan_<view>``, else
    ``plan_<root node>``."""
    from repro.relational.execute import _jitted_executor, execute_jit
    from repro.relational.plan import GroupByNode, Scan

    rel = from_columns({"k": np.arange(8, dtype=np.int32),
                        "g": np.arange(8, dtype=np.int32) % 2,
                        "v": np.ones(8, np.float32)}, pk=["k"])
    plan = GroupByNode(child=Scan("T", pk=("k",)), keys=("g",),
                       aggs=(("total", "sum", "v"),), num_groups=4)
    text = _jitted_executor(plan, name).lower({"T": rel}).as_text()
    assert f"module @{program}" in text
    out = to_host(execute_jit(plan, {"T": rel}, name=name))
    assert sorted(out["total"]) == [4.0, 4.0]
