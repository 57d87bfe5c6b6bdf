"""The staleness observatory: registry, tracer, kernel profiler, and the
trace-reconciliation contract over real pipeline workloads."""

from __future__ import annotations

import pathlib
import sys

import numpy as np
import pytest

from repro.core import Query, ViewDef
from repro.core.estimators import Estimate
from repro.obs import export_service_trace, observatory_panel
from repro.obs import kprof
from repro.obs import trace as obs_trace
from repro.obs.reconcile import check_shard_accounting, load_jsonl, reconcile
from repro.obs.registry import MetricsRegistry, counter_attr
from repro.obs.trace import NOOP_SPAN, Tracer
from repro.relational.plan import GroupByNode, Scan
from repro.relational.relation import from_columns
from repro.serving.admission import ADMIT, AdmissionConfig, AdmissionController
from repro.serving.result_cache import ResultCache
from repro.streaming import StreamConfig, StreamingViewService
from repro.views import ViewManager

ROOT = pathlib.Path(__file__).resolve().parent.parent


@pytest.fixture(autouse=True)
def _clean_observability_globals():
    """Tracer/profiler are process-wide: every test starts and ends bare."""
    obs_trace.set_tracer(None)
    kprof.set_profiler(None)
    yield
    obs_trace.set_tracer(None)
    kprof.set_profiler(None)


# -- fixtures ----------------------------------------------------------------

def _fleet(n_views=2, n=300, groups=8, seed=3):
    rng = np.random.default_rng(seed)
    vm = ViewManager()
    for i in range(n_views):
        base = f"Log{i}"
        vm.register_base(base, from_columns(
            {
                "k": np.arange(n, dtype=np.int32),
                "g": rng.integers(0, groups, n).astype(np.int32),
                "v": rng.exponential(5.0, n).astype(np.float32),
            },
            pk=["k"], capacity=2048,
        ))
        plan = GroupByNode(
            child=Scan(base, pk=("k",)), keys=("g",),
            aggs=(("total", "sum", "v"), ("cnt", "count", None)),
            num_groups=2 * groups,
        )
        vm.register_view(ViewDef(f"v{i}", plan), delta_bases=(base,), m=0.4,
                         seed=i, delta_group_capacity=2 * groups)
    return vm, rng


def _delta(start, n, groups, rng):
    return from_columns(
        {
            "k": np.arange(start, start + n, dtype=np.int32),
            "g": rng.integers(0, groups, n).astype(np.int32),
            "v": rng.exponential(5.0, n).astype(np.float32),
        },
        pk=["k"],
    )


# -- registry ----------------------------------------------------------------

def test_counter_is_monotone():
    reg = MetricsRegistry()
    c = reg.counter("stream_refreshes")
    c.inc()
    c.inc(3.0)
    assert c.value == 4.0
    with pytest.raises(ValueError):
        c.inc(-1.0)


def test_registry_interns_by_name_and_labels():
    reg = MetricsRegistry()
    a = reg.counter("admission_verdicts", tenant="t0", verdict="admit")
    b = reg.counter("admission_verdicts", verdict="admit", tenant="t0")
    c = reg.counter("admission_verdicts", tenant="t1", verdict="admit")
    assert a is b and a is not c
    a.inc(2)
    c.inc(3)
    assert reg.total("admission_verdicts") == 5.0
    snap = reg.snapshot()
    assert snap["admission_verdicts{tenant=t0,verdict=admit}"] == 2.0


def test_registry_rejects_kind_collision():
    reg = MetricsRegistry()
    reg.counter("planner_traffic")
    with pytest.raises(TypeError):
        reg.gauge("planner_traffic")


def test_histogram_streams_moments():
    reg = MetricsRegistry()
    h = reg.histogram("planner_refresh_s", view="v0")
    for v in (0.5, 0.1, 0.9):
        h.observe(v)
    assert h.count == 3
    assert h.min == pytest.approx(0.1) and h.max == pytest.approx(0.9)
    assert h.mean == pytest.approx(0.5)
    assert h.last == pytest.approx(0.9)


def test_counter_attr_is_bit_compatible_and_monotone():
    class Thing:
        hits = counter_attr()

        def __init__(self, reg):
            self._c_hits = reg.counter("cache_hits")

    reg = MetricsRegistry()
    t = Thing(reg)
    assert t.hits == 0 and isinstance(t.hits, int)
    t.hits += 1
    t.hits += 2
    assert t.hits == 3
    assert reg.counter("cache_hits").value == 3.0
    with pytest.raises(ValueError):
        t.hits -= 1  # counters cannot decrease


# -- tracer ------------------------------------------------------------------

def test_tracer_nests_spans_and_exports(tmp_path):
    tr = obs_trace.enable()
    with obs_trace.span("epoch", epoch=1):
        with obs_trace.span("drain", base="Log0") as sp:
            sp.set(rows=7)
        obs_trace.event("offer", base="Log0", seq=3)
    path = tmp_path / "t.jsonl"
    n = tr.export_jsonl(str(path), meta={"extra": 1,
                                         "pending": {"Log0": [3]}})
    assert n == 3
    meta, records = load_jsonl(str(path))
    assert meta["dropped"] == 0 and meta["extra"] == 1
    by_name = {r["name"]: r for r in records}
    epoch, drain, offer = by_name["epoch"], by_name["drain"], by_name["offer"]
    assert drain["parent"] == epoch["id"]
    assert offer["parent"] == epoch["id"]
    assert drain["attrs"] == {"base": "Log0", "rows": 7}
    assert epoch["t0"] <= drain["t0"] and drain["t1"] <= epoch["t1"]
    assert not reconcile(meta, records)["problems"]


def test_tracer_disabled_is_shared_noop():
    assert obs_trace.get_tracer() is None
    sp = obs_trace.span("epoch")
    assert sp is NOOP_SPAN
    with sp as inner:
        inner.set(anything=1)  # never raises, never records
    obs_trace.event("offer", seq=1)


def test_tracer_ring_retention_counts_drops():
    tr = obs_trace.enable(capacity=4)
    for i in range(10):
        obs_trace.event("offer", seq=i)
    assert len(tr.records) == 4
    assert tr.dropped == 6
    assert tr.summary()["dropped"] == 6


def test_span_records_exception_and_unwinds():
    tr = obs_trace.enable()
    with pytest.raises(RuntimeError):
        with obs_trace.span("clean", view="v0"):
            raise RuntimeError("boom")
    rec = list(tr.records)[-1]
    assert rec["attrs"]["error"] == "RuntimeError"
    assert tr.summary()["open_spans"] == 0


# -- kernel profiler ---------------------------------------------------------

def test_profiled_tail_calls_without_profiler():
    assert kprof.get_profiler() is None
    assert kprof.profiled("fused_clean", lambda a, b: a + b, 2, 3) == 5


def test_profiler_splits_compile_and_execute():
    import jax.numpy as jnp

    prof = kprof.set_profiler(kprof.KernelProfiler())
    x = jnp.arange(8, dtype=jnp.float32)
    for _ in range(3):
        kprof.profiled("fused_clean", lambda a: a * 2, x, rows=6, padded=8)
    kprof.profiled("fused_clean", lambda a: a, x[:4], fallback=True,
                   rows=4, padded=4)
    st = prof.summary()["fused_clean"]
    assert st["dispatches"] == 4 and st["fallbacks"] == 1
    assert st["compiles"] == 2  # one per distinct shape key
    assert st["rows_real"] == 22 and st["rows_padded"] == 28
    assert st["occupancy"] == pytest.approx(22 / 28)


def test_profiler_sees_pipeline_dispatches():
    prof = kprof.set_profiler(kprof.KernelProfiler())
    vm, rng = _fleet()
    vm.ingest("Log0", inserts=_delta(1000, 40, 8, rng))
    vm.svc_refresh("v0")
    vm.query_batch("v0", [Query(agg="sum", col="total")])
    ops = prof.summary()
    assert "multi_agg" in ops and ops["multi_agg"]["dispatches"] >= 1
    assert all(st["dispatches"] >= st["compiles"] for st in ops.values())


# -- per-shard kernel attribution --------------------------------------------

def test_profiler_fans_dispatches_out_to_shards():
    import jax.numpy as jnp

    prof = kprof.set_profiler(kprof.KernelProfiler())
    x = jnp.arange(8, dtype=jnp.float32)
    kprof.profiled("fleet_score_sharded", lambda a: a * 2, x,
                   rows=12, padded=16, shards=[0, 1],
                   shard_rows=[5, 7], shard_padded=[8, 8])
    s = prof.shard_summary()
    fl = s["fleet"]["fleet_score_sharded"]
    per = s["shards"]["fleet_score_sharded"]
    assert fl["dispatches"] == 1 and fl["rows_real"] == 12
    assert set(per) == {0, 1}
    assert per[0]["rows_real"] == 5 and per[1]["rows_real"] == 7
    assert per[0]["rows_padded"] == 8 and per[1]["rows_padded"] == 8
    # each shard sees the dispatch; the wall is split, not duplicated
    assert per[0]["dispatches"] == per[1]["dispatches"] == 1
    wall = lambda st: st["compile_s"] + st["execute_s"]
    assert wall(per[0]) + wall(per[1]) == pytest.approx(wall(fl))
    assert check_shard_accounting(s) == []


def test_shard_scope_attributes_ambient_dispatches():
    prof = kprof.set_profiler(kprof.KernelProfiler())
    assert kprof.current_shard() is None
    with kprof.shard_scope(2):
        assert kprof.current_shard() == 2
        kprof.profiled("fused_clean", lambda a, b: a + b, 2, 3,
                       rows=4, padded=4)
        with kprof.shard_scope(None):  # explicit clear nests
            kprof.profiled("fused_clean", lambda a, b: a + b, 2, 3,
                           rows=4, padded=4)
    assert kprof.current_shard() is None
    s = prof.shard_summary()
    per = s["shards"]["fused_clean"]
    assert set(per) == {2} and per[2]["rows_real"] == 4
    # the un-scoped dispatch stays out of BOTH shard-side ledgers (the
    # global ``ops`` ledger still has it), so the mirror reconciles exactly
    assert s["fleet"]["fused_clean"]["rows_real"] == 4
    assert prof.summary()["fused_clean"]["rows_real"] == 8
    assert check_shard_accounting(s) == []


def test_check_shard_accounting_catches_drift():
    ok = {"fleet": {"op": {"dispatches": 2, "rows_real": 10, "rows_padded": 12,
                           "compile_s": 0.5, "execute_s": 0.1}},
          "shards": {"op": {0: {"dispatches": 1, "rows_real": 4,
                                "rows_padded": 6, "compile_s": 0.25,
                                "execute_s": 0.05},
                            1: {"dispatches": 1, "rows_real": 6,
                                "rows_padded": 6, "compile_s": 0.25,
                                "execute_s": 0.05}}}}
    assert check_shard_accounting(ok) == []
    bad = {"fleet": dict(ok["fleet"]),
           "shards": {"op": {0: dict(ok["shards"]["op"][0],
                                     rows_real=5)}}}
    probs = check_shard_accounting(bad)
    assert any("rows_real" in p for p in probs)
    assert check_shard_accounting({"fleet": {}, "shards": {"x": {}}})
    assert check_shard_accounting({"fleet": {"y": {}}, "shards": {}})


def test_reconcile_includes_shard_checks():
    prof = kprof.set_profiler(kprof.KernelProfiler())
    with kprof.shard_scope(0):
        kprof.profiled("fused_clean", lambda a, b: a + b, 1, 2,
                       rows=3, padded=3)
    tr = obs_trace.enable()
    vm, rng = _fleet(n_views=1)
    vm.query("v0", Query(agg="sum", col="total"))
    meta = {"metrics": vm.metrics.snapshot(),
            "quarantines": sum(h.failures for h in vm.health.views.values())}
    rep = reconcile(meta, list(tr.records),
                    shard_summary=prof.shard_summary())
    assert rep["ok"] and rep["checks"]["shards"] == 0
    drifted = prof.shard_summary()
    drifted["shards"]["fused_clean"][0]["rows_real"] += 1
    rep = reconcile(meta, list(tr.records), shard_summary=drifted)
    assert not rep["ok"] and rep["checks"]["shards"] == 1
    assert any("rows_real" in p for p in rep["problems"])


def test_sharded_fleet_epoch_reconciles_per_shard():
    from repro.distributed import ShardedFleet
    from repro.core import ViewDef
    from repro.relational.plan import GroupByNode, Scan

    prof = kprof.set_profiler(kprof.KernelProfiler())
    fleet = ShardedFleet(n_shards=2, budget_s=10.0, heartbeat_timeout_s=1e9)
    rng = np.random.default_rng(7)
    for i in range(2):
        base = f"Log{i}"
        n = 200
        fleet.register_base(base, from_columns(
            {"k": np.arange(n, dtype=np.int32),
             "g": rng.integers(0, 8, n).astype(np.int32),
             "v": rng.exponential(4.0, n).astype(np.float32)},
            pk=["k"], capacity=1024))
        fleet.register_view(
            ViewDef(f"v{i}", GroupByNode(
                child=Scan(base, pk=("k",)), keys=("g",),
                aggs=(("total", "sum", "v"), ("cnt", "count", None)),
                num_groups=16)),
            delta_bases=(base,), m=0.4, seed=i, delta_group_capacity=16)
        fleet.ingest(base, inserts=from_columns(
            {"k": np.arange(1000, 1040, dtype=np.int32),
             "g": rng.integers(0, 8, 40).astype(np.int32),
             "v": rng.exponential(4.0, 40).astype(np.float32)},
            pk=["k"]))
    rep = fleet.epoch_step()
    assert rep.actions
    s = prof.shard_summary()
    # the epoch's kernel work is attributed shard-by-shard and sums back
    assert any(per for per in s["shards"].values())
    assert check_shard_accounting(s) == []
    seen_shards = {sh for per in s["shards"].values() for sh in per}
    assert seen_shards <= {0, 1} and seen_shards


# -- serving-plane counters back onto the registry ---------------------------

def test_result_cache_counters_ride_the_registry():
    reg = MetricsRegistry()
    cache = ResultCache(capacity=4, registry=reg)
    digest = (1, 2)
    est = Estimate(value=1.0, stderr=0.0, ci_low=1.0, ci_high=1.0,
                   method="svc+aqp", confidence=0.95)
    assert cache.get("v0", 1, digest) is None
    cache.put("v0", 1, digest, est)
    assert cache.get("v0", 1, digest) is not None
    assert isinstance(cache.hits, int) and cache.hits == 1
    assert cache.misses == 1 and cache.puts == 1
    snap = reg.snapshot()
    assert snap["cache_hits"] == 1.0 and snap["cache_misses"] == 1.0


def test_admission_counters_ride_the_registry():
    reg = MetricsRegistry()
    t = [0.0]
    adm = AdmissionController(
        AdmissionConfig(tenant_qps=1.0, tenant_burst=2.0,
                        fleet_qps=100.0, fleet_burst=100.0),
        clock=lambda: t[0], registry=reg,
    )
    verdicts = [adm.decide("t0") for _ in range(5)]
    assert verdicts.count(ADMIT) == adm.admitted
    assert adm.admitted + adm.throttled + adm.shed == 5
    assert reg.total("admission_verdicts") == 5.0
    assert reg.counter("admission_admitted").value == float(adm.admitted)


# -- pipeline workloads ------------------------------------------------------

CUMULATIVE_STALENESS_FIELDS = (
    "shed_rows", "corrupt_batches", "spills", "deduped_batches",
    "deduped_rows", "throttled_queries", "shed_queries", "admitted_queries",
    "cache_hits", "cache_stale_hits", "cache_poison_rejected",
)


def test_staleness_counters_are_monotone_over_workload():
    vm, rng = _fleet()
    svc = StreamingViewService(
        vm, StreamConfig(auto_refresh=False, admission=AdmissionConfig()))
    vm.stream = svc
    prev = None
    for epoch in range(4):
        svc.offer("Log0", inserts=_delta(1000 + epoch * 30, 30, 8, rng),
                  seq=epoch, key=f"e{epoch}")
        svc.offer("Log0", inserts=_delta(1000 + epoch * 30, 30, 8, rng),
                  seq=epoch, key=f"e{epoch}")  # at-least-once replay
        svc.refresh()
        svc.query_batch("v0", [Query(agg="sum", col="total")])
        st = svc.staleness()
        cur = {f: getattr(st, f) for f in CUMULATIVE_STALENESS_FIELDS}
        assert all(isinstance(v, int) and v >= 0 for v in cur.values())
        if prev is not None:
            for f in CUMULATIVE_STALENESS_FIELDS:
                assert cur[f] >= prev[f], f"staleness counter {f} decreased"
        prev = cur
    assert prev["deduped_batches"] >= 1  # the replays were absorbed
    assert prev["admitted_queries"] >= 1


def test_serving_soak_admission_ledger_reconciles():
    """Under the fig_serving_soak quick schedule every query lands in
    exactly one verdict bucket: admitted + throttled + shed == attempted."""
    sys.path.insert(0, str(ROOT))
    try:
        from benchmarks.fig_planner_fleet import _traffic_weights, epoch_deltas
        from benchmarks.fig_serving_soak import N_VIEWS, _soak
    finally:
        sys.path.pop(0)
    deltas = epoch_deltas(N_VIEWS, 256, 8, 24, 3)
    out = _soak(True, 3, 256, 8, deltas, _traffic_weights(N_VIEWS), None)
    assert out["attempted"] > 0
    assert out["admitted"] + out["throttled"] + out["shed"] == out["attempted"]
    assert out["availability"] == 1.0


def test_service_trace_exports_and_reconciles(tmp_path):
    obs_trace.enable()
    vm, rng = _fleet()
    svc = StreamingViewService(
        vm, StreamConfig(auto_refresh=False, admission=AdmissionConfig()))
    vm.stream = svc
    for epoch in range(3):
        svc.offer("Log0", inserts=_delta(1000 + epoch * 30, 30, 8, rng),
                  seq=epoch)
        svc.offer("Log1", inserts=_delta(2000 + epoch * 30, 30, 8, rng),
                  seq=epoch)
        svc.refresh()
        svc.query_batch("v0", [Query(agg="sum", col="total")] * 2)
        svc.query("v1", Query(agg="avg", col="total"))
    path = tmp_path / "trace.jsonl"
    export_service_trace(svc, str(path))
    meta, records = load_jsonl(str(path))
    result = reconcile(meta, records)
    assert result["ok"], result["problems"]
    query_spans = [r for r in records
                   if r["kind"] == "span" and r["name"] == "query"]
    assert query_spans
    assert all("verdict" in r["attrs"] for r in query_spans)
    assert sum(int(r["attrs"]["n"]) for r in query_spans) == 9
    # epoch spans parent the per-base drains
    epochs = {r["id"] for r in records
              if r["kind"] == "span" and r["name"] == "epoch"}
    drains = [r for r in records
              if r["kind"] == "span" and r["name"] == "drain"]
    assert drains and all(r["parent"] in epochs for r in drains)


def _estimate_and_epoch_workload():
    """Two epochs of inserts on a two-view fleet, each followed by two
    query batches per view straight through ``query_batch`` (no result
    cache), the second view's forced to CORR so the exact scan runs."""
    vm, rng = _fleet()
    svc = StreamingViewService(vm, StreamConfig(auto_refresh=False))
    vm.stream = svc
    qs = [Query(agg="sum", col="total"), Query(agg="avg", col="total")]
    for epoch in range(2):
        for i in range(2):
            svc.offer(f"Log{i}", inserts=_delta(1000 * (i + 1) + 30 * epoch,
                                                30, 8, rng), seq=epoch)
        svc.refresh()
        for _ in range(2):
            vm.query_batch("v0", qs)
            vm.query_batch("v1", qs, prefer="corr")
    return svc


def _spans(records, name):
    return [r for r in records if r["kind"] == "span" and r["name"] == name]


def test_query_batch_spans_split_estimate():
    tr = obs_trace.enable()
    _estimate_and_epoch_workload()
    records = list(tr.records)
    by_id = {r["id"]: r for r in records}
    estimates = [r for r in _spans(records, "estimate")
                 if r["attrs"]["view"] == "v1"]
    assert len(estimates) == 4
    for k, est in enumerate(estimates):
        kids = [r for r in records if r.get("parent") == est["id"]]
        names = [r["name"] for r in kids]
        # the first batch after each clean rebuilds the correspondence
        want = ["encode", "moments", "exact_scan", "assemble"]
        if k % 2 == 0:
            want.insert(1, "corr_build")
        assert names == want
        for kid in kids:
            assert est["t0"] <= kid["t0"] and kid["t1"] <= est["t1"]
    builds = [r for r in _spans(records, "corr_build")
              if r["attrs"]["view"] == "v1"]
    assert all(r["attrs"]["rows"] > 0 for r in builds)
    fetches = _spans(records, "fetch")
    assert fetches and all(r["attrs"]["bytes"] > 0 for r in fetches)
    parents = {by_id[r["parent"]]["name"] for r in fetches}
    assert parents == {"moments", "exact_scan"}
    assert all(r["attrs"]["refits"] >= 0 for r in _spans(records, "assemble"))


def test_epoch_spans_concat_and_sync():
    tr = obs_trace.enable()
    _estimate_and_epoch_workload()
    records = list(tr.records)
    concats = _spans(records, "concat")
    assert len(concats) == 2  # one pending-delta merge per epoch
    for epoch, r in enumerate(concats, 1):
        a = r["attrs"]  # one 30-row segment per base and epoch
        assert a["segments"] == 2 * epoch
        assert a["rows"] == 60 * epoch and a["cap"] == 4096
        assert a["bytes"] > 0
    syncs = _spans(records, "sync")
    assert {r["attrs"]["view"] for r in syncs} == {"v0", "v1"}


def test_tracer_off_adds_no_span_and_no_sync(monkeypatch):
    """The new spans cost nothing when tracing is off: nothing is recorded,
    and the only device waits are the ones each ``sync`` span wraps."""
    import jax
    from jax._src.array import ArrayImpl

    calls = {"method": 0, "function": 0, "spans": 0}
    method, function = ArrayImpl.block_until_ready, jax.block_until_ready
    opened = Tracer.span

    def counted_method(self):
        calls["method"] += 1
        return method(self)

    def counted_function(x):
        calls["function"] += 1
        return function(x)

    def counted_span(self, name, **attrs):
        calls["spans"] += 1
        return opened(self, name, **attrs)

    monkeypatch.setattr(ArrayImpl, "block_until_ready", counted_method)
    monkeypatch.setattr(jax, "block_until_ready", counted_function)
    monkeypatch.setattr(Tracer, "span", counted_span)
    _estimate_and_epoch_workload()
    off = dict(calls)
    assert off["spans"] == 0 and off["function"] == 0

    tr = obs_trace.enable()
    _estimate_and_epoch_workload()
    records = list(tr.records)
    # untraced, the waits are exactly the existing ones the sync spans wrap
    assert off["method"] == len(_spans(records, "sync")) > 0
    # traced, each correspondence build adds one wait for its panels
    assert calls["function"] == len(_spans(records, "corr_build")) > 0


def test_estimate_and_epoch_spans_reconcile(tmp_path):
    obs_trace.enable()
    svc = _estimate_and_epoch_workload()
    path = tmp_path / "trace.jsonl"
    export_service_trace(svc, str(path))
    meta, records = load_jsonl(str(path))
    names = {r["name"] for r in records if r["kind"] == "span"}
    assert names >= {"encode", "corr_build", "moments", "exact_scan",
                     "assemble", "fetch", "concat", "sync"}
    assert not any(r["name"] == "ingest" for r in records)
    result = reconcile(meta, records)
    assert result["ok"], result["problems"]


def test_observatory_panel_reconciles_live():
    obs_trace.enable()
    kprof.set_profiler(kprof.KernelProfiler())
    vm, rng = _fleet()
    svc = StreamingViewService(
        vm, StreamConfig(auto_refresh=False, admission=AdmissionConfig()))
    vm.stream = svc
    svc.offer("Log0", inserts=_delta(1000, 30, 8, rng), seq=0)
    svc.refresh()
    svc.query_batch("v0", [Query(agg="sum", col="total")])
    panel = observatory_panel(svc)
    assert set(panel) >= {"metrics", "trace", "kernels", "staleness",
                          "reconciliation"}
    assert panel["trace"]["enabled"] and panel["trace"]["records"] > 0
    assert panel["kernels"]  # at least one profiled dispatch
    assert panel["reconciliation"]["queries_ok"]
    assert panel["reconciliation"]["issued"] == 1
    assert panel["metrics"]["stream_refreshes"] >= 1.0
