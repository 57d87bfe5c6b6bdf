"""CPU checks of the benchmark's arithmetic: latency, lag, work counts
and the peaks table."""

import math

import pytest

from svcbench import e2e, roofline


def test_visibility_picks_first_epoch_that_drained_the_batch():
    ends = [(1.0, 3), (2.5, 3), (4.0, 7)]
    assert e2e.visibility(ends, 0) == 1.0
    assert e2e.visibility(ends, 2) == 1.0
    assert e2e.visibility(ends, 3) == 4.0
    assert e2e.visibility(ends, 6) == 4.0
    assert e2e.visibility(ends, 7) is None


def test_fresh_lag_counts_unseen_pairs_to_window_end():
    # three batches due at 0.5, 1.5, 9.0 s; the last is never visible,
    # and one pair becomes visible only after the window closed
    pairs = [(0.5, 2.0), (1.5, 2.0), (9.0, None), (1.0, 12.0)]
    lags = e2e.fresh_lags(pairs, window_end=10.0)
    assert lags == pytest.approx([1.5, 0.5, 1.0, 9.0])
    assert e2e.percentile(lags, 50) == pytest.approx(1.25)


def test_query_latencies_count_unissued_batches_to_window_end():
    lat = e2e.query_latencies([(1.0, 1.2), (2.0, None)], window_end=5.0)
    assert lat == pytest.approx([0.2, 3.0])


def test_span_p50_is_the_median_of_the_named_spans():
    spans = [{"name": "act", "dur_s": 0.01}, {"name": "act", "dur_s": 0.05},
             {"name": "snapshot", "dur_s": 9.0}, {"name": "act", "dur_s": 0.02}]
    assert e2e.span_p50_ms(spans, ["act"]) == pytest.approx(20.0)
    assert e2e.span_p50_ms(spans, ["drain"]) is None


def test_multi_agg_work_hand_count():
    # 1000 rows, 4 columns, 8 queries, 1 predicate term, two sides:
    # per side 1000 * (4*4 + 1 + 2*4) = 25,000 B; small arrays
    # ((1+1)*4 + 2 + 4 + 12) * 8 * 4 = 832 B
    b, ops = roofline.multi_agg_work(1000, 4, 8, 1, two_sided=True)
    assert b == 2 * 25_000 + 832
    assert ops == 1000 * 8 * (2 * 10 + 5)
    b1, ops1 = roofline.multi_agg_work(1000, 4, 8, 1, two_sided=False)
    assert b1 == 25_000 + 832
    assert ops1 == 1000 * 8 * 10


def test_roofline_share_uses_the_binding_bound():
    kind = "TPU v5 lite"
    p = roofline.peaks(kind)
    # 819 MB in 2 ms: the memory bound is 1 ms → 50 %
    assert roofline.share_pct(819e6, 1.0, 2e-3, kind) == pytest.approx(50.0)
    # compute-bound case
    ops = p["flops_per_s"] * 1e-3
    assert roofline.share_pct(1.0, ops, 4e-3, kind) == pytest.approx(25.0)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        roofline.peaks("cpu")
    assert math.isclose(roofline.PEAKS["TPU v5 lite"]["hbm_bytes_per_s"], 819e9)
