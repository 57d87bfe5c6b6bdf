"""CPU checks of the per-layer readers of the program's spans, on
synthetic span lists: the estimate's host time less its fetches and its
correspondence build, and None where the program has no such span."""

import types

import pytest

from svcbench.metrics import reader
from svcbench.spans import walls_less

READERS = ("estimate_host_ms_p50.joinView", "estimate_host_ms_p50.partView",
           "corr_build_ms_p50", "concat_ms_p50")


def _span(id_, name, dur, parent=None, **attrs):
    return {"id": id_, "parent": parent, "name": name, "dur_s": dur,
            "attrs": attrs}


def _spans():
    return [
        # a joinView batch after a clean: 100 ms, of which the build takes
        # 40 (with a read inside it) and two fetches 22
        _span(1, "estimate", 0.100, view="joinView"),
        _span(2, "encode", 0.005, 1),
        _span(3, "corr_build", 0.040, 1, view="joinView", rows=64),
        _span(4, "fetch", 0.010, 3, bytes=4),
        _span(5, "moments", 0.030, 1),
        _span(6, "fetch", 0.020, 5, bytes=384),
        _span(7, "assemble", 0.010, 1, refits=1),
        _span(8, "fetch", 0.002, 7, bytes=4),
        # a warm joinView batch: 50 ms, 25 of them in its fetch
        _span(9, "estimate", 0.050, view="joinView"),
        _span(10, "moments", 0.030, 9),
        _span(11, "fetch", 0.025, 10, bytes=384),
        # a partView batch with nothing to subtract
        _span(12, "estimate", 0.020, view="partView"),
        _span(13, "encode", 0.001, 12),
        # an estimate the program did not split: left out
        _span(14, "estimate", 0.500, view="joinView"),
        _span(15, "corr_build", 0.060, None, view="partView", rows=64),
        _span(16, "concat", 0.300, None, segments=24, rows=98304,
              cap=131072, bytes=9437184),
        _span(17, "concat", 0.100, None, segments=48, rows=196608,
              cap=262144, bytes=18874368),
        _span(18, "act", 0.900),
    ]


def test_walls_less_takes_each_named_subtree_once():
    got = walls_less(_spans(), "estimate", ("fetch", "corr_build"),
                     view="joinView")
    assert got == pytest.approx([0.100 - 0.040 - 0.020 - 0.002,
                                 0.050 - 0.025])


@pytest.mark.parametrize("name,want", [
    ("estimate_host_ms_p50.joinView", 0.5e3 * (0.038 + 0.025)),
    ("estimate_host_ms_p50.partView", 20.0),
    ("corr_build_ms_p50", 50.0),
    ("concat_ms_p50", 200.0),
])
def test_reader_on_synthetic_spans(name, want):
    ctx = types.SimpleNamespace(spans=_spans())
    assert reader(name)(ctx) == pytest.approx(want)


@pytest.mark.parametrize("name", READERS)
def test_reader_without_the_spans_is_none(name):
    """The spans of a program that lacks the new ones: an unsplit estimate
    and an epoch."""
    spans = [_span(1, "estimate", 0.060, view="joinView"),
             _span(2, "estimate", 0.030, view="partView"),
             _span(3, "act", 0.900), _span(4, "clean", 0.800, 3)]
    assert reader(name)(types.SimpleNamespace(spans=spans)) is None
