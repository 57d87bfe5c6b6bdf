"""CPU checks of the traffic generator."""

import json
import pathlib

import numpy as np
import pytest

from svcbench import traffic

MIXES = sorted((pathlib.Path(__file__).parent / "traffic").glob("*.json"))


def _mix(**kw):
    mix = {"queries": {"rate_per_s": 7.0, "batch": 8},
           "inserts": {"rate_rows_per_s": 8192, "micro_batch_rows": 1024,
                       "watermark_batches": 4}}
    for k, v in kw.items():
        mix[k].update(v)
    return mix


def _sched(mix, views=2, bases=1, seconds=30.0):
    return traffic.schedule(mix, views, bases, seconds)


def test_every_seed_replays_one_trace():
    mix = _mix()
    a = _sched(mix, 2, 1)
    assert a == _sched(mix, 2, 1)
    # another rate is another trace
    assert a != _sched(_mix(queries={"rate_per_s": 8.0}), 2, 1)


def test_different_seeds_ask_different_queries():
    from svcbench.datasets import load
    from svcbench.harness import Run, load_cell

    cell = load_cell("tpch_sf1_2view.adhoc")
    cell["config"]["rows"] = {k: 1000 for k in cell["config"]["rows"]}
    asked = []
    for seed in (5, 5, 6):
        run = Run(cell, seed, 1.0, False, require_tpu=False)
        run.dep = load(run.cfg["dataset"]).build(run.cfg, run.rng_data)
        asked.append(run._queries(0, run.rng_query, 8)[1])
    assert asked[0] == asked[1]
    assert asked[0] != asked[2]


def test_rates_match_the_mix():
    mix = _mix()
    ev = _sched(mix, seconds=30.0)
    nq = sum(e.kind == "query" for e in ev)
    ni = sum(e.kind == "insert" for e in ev)
    assert nq == 210
    assert ni == 240  # 8192 rows/s * 30 s / 1024 rows
    assert all(0.0 < e.due_s < 30.0 for e in ev)
    assert [e.due_s for e in ev] == sorted(e.due_s for e in ev)
    # the feed flushes micro-batches evenly; dashboards arrive Poisson
    ins = np.diff([e.due_s for e in ev if e.kind == "insert"])
    assert np.allclose(ins, 30.0 / 241)
    qry = np.diff([e.due_s for e in ev if e.kind == "query"])
    assert qry.std() > 0.5 * qry.mean()


@pytest.mark.parametrize("views", [1, 2, 3])
def test_batches_split_evenly_over_the_views(views):
    ev = _sched(_mix(queries={"rate_per_s": 10.0}), views=views, seconds=30.0)
    got = np.bincount([e.target for e in ev if e.kind == "query"],
                      minlength=views)
    assert got.sum() == 300 and got.max() - got.min() <= 1
    # in a permuted order, not in turns
    tgt = [e.target for e in ev if e.kind == "query"]
    assert views == 1 or tgt != [i % views for i in range(len(tgt))]


def test_largest_remainder_split():
    assert list(traffic.target_counts(10, [1, 1])) == [5, 5]
    assert list(traffic.target_counts(10, [1, 1, 1])) == [4, 3, 3]
    assert list(traffic.target_counts(7, [3, 1])) == [5, 2]


@pytest.mark.parametrize("path", MIXES, ids=[p.stem for p in MIXES])
def test_committed_mixes_are_whole_watermarks(path):
    mix = json.loads(path.read_text())
    ins = mix["inserts"]
    assert ins["watermark_batches"] >= 1 and ins["micro_batch_rows"] >= 1
    nq, ni = traffic.counts(mix, 30.0)
    assert nq > 0 and ni > 0
