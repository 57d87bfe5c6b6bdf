"""CPU checks of the device-trace reduction, on a synthetic trace and on a
small piece of a real TPU v5e trace committed beside this file."""

import json
import pathlib

import pytest

from svcbench import devtrace

FIXTURE = pathlib.Path(__file__).parent / "testdata" / "trace_v5e_query.json"


def _trace():
    # one device; ops at [0,10) [5,20) [30,40) ns; modules over them;
    # the host annotations cover [0, 50)
    return {
        "devices": {"/device:TPU:0": {
            "ops": [["fusion.1", 0, 10], ["fusion.2", 5, 15], ["copy.3", 30, 10]],
            "modules": [["jit_a(1)", 0, 20], ["jit_b(2)", 30, 10]],
        }},
        "host": [["svcbench.query", 0, 24], ["svcbench.insert", 25, 25]],
    }


def test_union_and_gaps():
    busy = devtrace.union([(0, 10), (5, 20), (30, 40), (45, 60)], 0, 50)
    assert busy == [(0, 20), (30, 40), (45, 50)]
    assert devtrace.gaps(busy, 0, 50) == [(20, 30), (40, 45)]


def test_reduce_busy_idle_modules_and_labels():
    red = devtrace.reduce(_trace(), (0, 50),
                          host_spans=[("estimate", 18, 24)])
    assert red["busy_s"] == pytest.approx(30e-9)
    assert red["window_s"] == pytest.approx(50e-9)
    assert red["idle_pct"] == pytest.approx(40.0)
    assert red["modules"] == pytest.approx({"jit_a(1)": 20e-9, "jit_b(2)": 10e-9})
    assert red["ops"]["fusion.2"] == pytest.approx(15e-9)
    # gap [20,30) has its middle at 25: the insert annotation starts there
    # (the estimate span ended at 24); gap [40,50) lies in the insert too
    assert red["gaps_by_label"] == pytest.approx({"svcbench.insert": 20e-9})
    assert devtrace.module_time(red, ["jit_a"]) == pytest.approx(20e-9)
    b = devtrace.breakdown(red)
    assert b["device_ops"][0][0] == "jit_a(1)"
    assert b["idle_gaps"] == [["svcbench.insert", pytest.approx(20e-9)]]


def test_window_defaults_to_the_annotations():
    red = devtrace.reduce(_trace())
    assert red["window_s"] == pytest.approx(50e-9)


def test_a_trace_without_a_device_plane_is_an_error():
    with pytest.raises(ValueError):
        devtrace.reduce({"devices": {}, "host": [["svcbench.query", 0, 5]]})


def test_recorded_v5e_trace():
    """One dashboard batch of the adhoc cell, traced on a TPU v5e: the
    reduction's busy time is the union of the device's op intervals, and
    the batch's moment passes appear as multi_agg modules."""
    trace = json.loads(FIXTURE.read_text())
    lo, hi = trace["window"]
    red = devtrace.reduce(trace, (lo, hi))
    dev = next(iter(trace["devices"].values()))
    ops = sorted((s, s + d) for _n, s, d in dev["ops"])
    busy = 0.0
    end = lo
    for s, e in ops:
        s, e = max(s, end, lo), min(e, hi)
        if e > s:
            busy += e - s
            end = e
        end = max(end, min(e, hi))
    assert red["busy_s"] == pytest.approx(busy * 1e-9, rel=1e-9)
    assert 0.0 < red["idle_pct"] < 100.0
    assert devtrace.module_time(red, ["multi_agg_tiles"]) > 0.0
    gaps = sum(red["gaps_by_label"].values())
    assert gaps == pytest.approx(red["window_s"] - red["busy_s"], rel=1e-9)
