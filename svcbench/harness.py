"""One benchmark run of one cell: set-up, open-loop window, check, result.

The cell's configuration (``configs/<config>.json``) names its data set
(``datasets/<dataset>.py``), views, planner and stream settings; its
traffic mix (``traffic/<mix>.json``) gives rates and sizes.  The harness
drives the system the way a user does: ``ViewManager`` →
``StreamingViewService`` (``vm.ingest`` routes micro-batches there) with a
``MaintenancePlanner`` attached, and ``StreamingViewService.query_batch``
for dashboards.  Epochs run inline when the size watermark trips.

Each event of the window is timed from when it was due.  The window
closes ``seconds`` after it opens: nothing is issued after that, and an
event not issued by then counts until the close.  The run's answers and
clean samples are compared with ``svcbench.reference`` once the window has
closed and the program's state is freed.
"""

from __future__ import annotations

import dataclasses
import gc
import json
import os
import pathlib
import shutil
import time
from typing import Dict, List, Optional

import numpy as np

from svcbench import e2e, traffic
from svcbench import reference as ref
from svcbench.datasets import Deployment, load as load_dataset

ROOT = pathlib.Path(__file__).resolve().parent
REPO = ROOT.parent


def _gc_timer(passes: list):
    """A ``gc.callbacks`` hook that appends (generation, seconds) per
    collector pass."""
    t0 = [0.0]

    def hook(phase, info):
        if phase == "start":
            t0[0] = time.perf_counter()
        else:
            passes.append((info["generation"], time.perf_counter() - t0[0]))
    return hook


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def process_start_s() -> float:
    """Wall-clock time at which this process started (Linux /proc)."""
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/stat") as f:
        btime = next(int(line.split()[1]) for line in f
                     if line.startswith("btime"))
    return btime + start_ticks / os.sysconf("SC_CLK_TCK")


def load_cell(name: str, root: pathlib.Path = REPO) -> dict:
    """The workload entry of ``BENCHMARK.json`` with its configuration and
    mix read from the files named after them."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    if cell is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cfg = next(c for c in bench["configs"] if c["name"] == cell["config"])
    return {
        "name": name,
        "chips": int(cell["chips"]),
        "config": json.loads((root / cfg["file"]).read_text()),
        "mix": json.loads((ROOT / "traffic" / f"{cell['traffic']}.json").read_text()),
        "per_layer": [m for m in bench["per_layer"]
                      if name in m.get("workloads", [name])],
        "end_to_end": [m for m in bench["end_to_end"]
                       if name in m.get("workloads", [name])],
    }


def load_limits(cell: str) -> Dict[str, float]:
    """The limit of each number compared in ``cell`` (``limits/<cell>.json``;
    the readings each was set from are in PERF.md)."""
    data = json.loads((ROOT / "limits" / f"{cell}.json").read_text())
    return {k: v["limit"] for k, v in data.items()}


@dataclasses.dataclass
class Epoch:
    t0: float
    t1: float
    drained: int                 # micro-batches handed to ingest before it
    cleaned: List[str]           # views whose clean sample moved
    maintained: List[str]        # views whose stale sample moved


@dataclasses.dataclass
class Batch:
    """One delta micro-batch (or the set-up backlog) in offer order."""

    base: str
    cols: Dict[str, np.ndarray]
    rows: int
    due: Optional[float] = None  # window offset; None outside the window
    done: Optional[float] = None


@dataclasses.dataclass
class Asked:
    view: int
    queries: list
    specs: List[ref.QuerySpec]
    due: float
    start: Optional[float] = None
    done: Optional[float] = None
    epochs: int = 0              # epochs completed when answered
    answers: Optional[list] = None


class Run:
    """All state of one run: ``setup`` → ``window`` → ``collect`` →
    ``check``; ``run.py`` turns it into the result line."""

    def __init__(self, cell: dict, seed: int, seconds: float, trace: bool,
                 require_tpu: bool = True, out_dir: Optional[pathlib.Path] = None,
                 log=print):
        self.cell, self.cfg, self.mix = cell, cell["config"], cell["mix"]
        self.seed, self.seconds, self.trace = int(seed), float(seconds), bool(trace)
        self.require_tpu = require_tpu
        self.out_dir = out_dir or (REPO / ".svcbench_traces")
        self.log = log
        ss = np.random.SeedSequence(self.seed)
        (self.rng_data, self.rng_delta, self.rng_query,
         self.rng_warm) = [np.random.default_rng(s) for s in ss.spawn(4)]
        self.batches: List[Batch] = []
        self.epochs: List[Epoch] = []
        self.asked: List[Asked] = []
        self.offered = 0  # micro-batches handed to ingest so far
        self.refresh_failures = 0
        self.setup_s = float("nan")

    # ------------------------------------------------------------------ set-up
    def device(self):
        import jax

        devs = jax.devices()
        platform = devs[0].platform
        if self.require_tpu and (platform != "tpu" or len(devs) < self.cell["chips"]):
            raise NoChip(f"need {self.cell['chips']} TPU chip(s); JAX found "
                         f"{len(devs)} {platform} device(s)")
        return devs

    def setup(self) -> None:
        import jax

        self.devs = self.device()
        if self.require_tpu:  # a rehearsal off the chip keeps JAX's config
            from repro.compile_cache import configure_compile_cache

            configure_compile_cache()
            # every program goes to the persistent cache, however fast it
            # compiled, so a warm run loads them all and compiles none
            jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        from svcbench.compiles import CompileCounter

        self.compiles = CompileCounter().__enter__()
        t = time.perf_counter()
        self.dep: Deployment = load_dataset(self.cfg["dataset"]).build(
            self.cfg, self.rng_data)
        self.names = [v.name for v in self.dep.views]
        self.log(f"setup: generate data {time.perf_counter() - t:.3f} s")
        self._build_system()
        t = time.perf_counter()
        self._warm()
        self.log(f"setup: warm epochs and queries {time.perf_counter() - t:.3f} s")
        self._prepare_window()
        jax.block_until_ready([b.rel.valid for b in self.window_batches])
        gc.collect()

    def _relation(self, cols, pk, capacity=None):
        from repro.relational.relation import from_columns

        return from_columns(cols, pk=[pk], capacity=capacity)

    def _build_system(self) -> None:
        import jax

        from repro.core import ViewDef
        from repro.planner import MaintenancePlanner
        from repro.relational.plan import FKJoin, GroupByNode, Scan
        from repro.streaming import StreamConfig
        from repro.views import ViewManager

        cfg, dep = self.cfg, self.dep
        vm = ViewManager()
        t = time.perf_counter()
        for name, tab in dep.tables.items():
            vm.register_base(name, self._relation(tab.cols, tab.pk, tab.capacity))
        jax.block_until_ready([r.valid for r in vm.base.values()])
        self.log(f"setup: upload base tables {time.perf_counter() - t:.3f} s")
        t = time.perf_counter()
        for v in dep.views:
            child = Scan(v.base, pk=(dep.tables[v.base].pk,))
            if v.join:
                child = FKJoin(fact=child, dim=Scan(v.join[0], pk=(v.join[1],)),
                               fact_key=v.key)
            plan = GroupByNode(child=child, keys=(v.key,), aggs=v.aggs,
                               num_groups=v.groups)
            vm.register_view(ViewDef(v.name, plan), delta_bases=(v.base,),
                             m=cfg["m"], seed=v.seed, delta_group_capacity=v.groups)
            if v.outlier:
                vm.register_outlier_index(v.name, v.base, v.outlier[0],
                                          k=int(v.outlier[1]))
        jax.block_until_ready([mv.stale_sample.valid for mv in vm.views.values()])
        self.log(f"setup: register views {time.perf_counter() - t:.3f} s")
        t = time.perf_counter()
        self.vm = vm
        # the backlog: deltas pending since the last maintenance period,
        # ingested before streaming starts (the plain batch-ingest API)
        per_base = traffic.target_counts(int(self.mix["backlog_rows"]),
                                         np.ones(len(dep.insert_bases)))
        self.next_key = {b: dep.tables[b].cols[dep.tables[b].pk].shape[0]
                         for b in dep.insert_bases}
        for b, n in zip(dep.insert_bases, per_base):
            if n:
                batch = self._new_batch(b, int(n), self.rng_delta)
                vm.ingest(b, inserts=batch.rel)
                self.offered += 1
        st = cfg["stream"]
        ins = self.mix["inserts"]
        svc = vm.configure_streaming(StreamConfig(
            max_rows=ins["watermark_batches"] * ins["micro_batch_rows"],
            max_age_s=st["max_age_s"], max_batches=st["max_batches"],
            cache_capacity=st["cache_capacity"]))
        self.svc = svc
        self._wrap_refresh()
        # first clean of every view: the backlog, clean-all (no planner yet)
        before = self._versions()
        t0 = time.perf_counter()
        self.log(f"setup: backlog {self.mix['backlog_rows']} rows "
                 f"{t0 - t:.3f} s")
        vm.svc_refresh_many(self.names)
        self._record_epoch(t0, before)
        self.log(f"setup: first clean {time.perf_counter() - t0:.3f} s")
        pl = cfg["planner"]
        self.planner = svc.attach_planner(MaintenancePlanner(
            vm, budget_s=pl["budget_s"], age_cap_s=pl["age_cap_s"],
            deadline_floor_s=pl["deadline_floor_s"], adapt_m=pl["adapt_m"]))

    def _new_batch(self, base: str, n: int, rng, due=None) -> Batch:
        cols = self.dep.new_rows(rng, base, self.next_key[base], n)
        self.next_key[base] += n
        b = Batch(base, cols, n, due)
        b.rel = self._relation(cols, self.dep.tables[base].pk)
        self.batches.append(b)
        return b

    def _versions(self):
        return {n: (mv.sample_version, mv.stale_version)
                for n, mv in self.vm.views.items()}

    def _record_epoch(self, t0: float, before) -> None:
        after = self._versions()
        self.epochs.append(Epoch(
            t0, time.perf_counter(), self.offered,
            [n for n in self.names if after[n][0] != before[n][0]],
            [n for n in self.names if after[n][1] != before[n][1]]))

    def _wrap_refresh(self) -> None:
        """Record each epoch's time, drained batches and the views whose
        samples it moved (the service calls ``self.refresh`` itself)."""
        inner = self.svc.refresh

        def refresh(plan=None):
            before = self._versions()
            t0 = time.perf_counter()
            try:
                return inner(plan)
            except Exception:
                self.refresh_failures += 1
                raise
            finally:
                self._record_epoch(t0, before)

        self.svc.refresh = refresh

    def _offer(self, b: Batch) -> None:
        self.offered += 1
        self.vm.ingest(b.base, inserts=b.rel)

    def _ask(self, view: int, queries) -> list:
        out = self.svc.query_batch(self.names[view], queries)
        return [(float(e.estimate.value), float(e.estimate.ci_low),
                 float(e.estimate.ci_high), e.estimate.method) for e in out]

    def _queries(self, view: int, rng, n: int):
        """n dashboard queries on one view (``dashboard_queries`` shape):
        sum/count/avg of a value column over a predicate-column range
        drawn on the view's own scale; bounds are float32 values."""
        from repro.core import Query
        from repro.relational.expr import Cmp, Col, Lit, and_

        q = self.cfg["query"]
        v = self.dep.views[view]
        qs, specs = [], []
        for _ in range(n):
            agg = str(rng.choice(q["aggs"]))
            col = None if agg == "count" else str(rng.choice(q["cols"]))
            lo = float(np.float32(rng.uniform(*q["lo"]) * v.pred_scale))
            hi = float(np.float32(lo + rng.uniform(*q["width"]) * v.pred_scale))
            pred = and_(Cmp("ge", Col(v.pred_col), Lit(lo)),
                        Cmp("le", Col(v.pred_col), Lit(hi)))
            qs.append(Query(agg=agg, col=col, pred=pred))
            specs.append(ref.QuerySpec(agg, col, lo, hi))
        return qs, specs

    def _insert_targets(self, n: int, rng) -> List[str]:
        n_bases = len(self.dep.insert_bases)
        tgt = np.repeat(np.arange(n_bases),
                        traffic.target_counts(n, np.ones(n_bases)))
        return [self.dep.insert_bases[i] for i in rng.permutation(tgt)]

    def _warm(self) -> None:
        """Warm epochs through the stream, then every query shape: fresh
        batches on each view after each epoch, and narrow avg ranges that
        take the engine's two-pass variance on both estimator paths."""
        ins, qm = self.mix["inserts"], self.mix["queries"]
        k, B = ins["watermark_batches"], ins["micro_batch_rows"]
        batch = qm["batch"]
        for _ in range(int(self.mix["warm_epochs"])):
            t = time.perf_counter()
            for base in self._insert_targets(k, self.rng_warm):
                self._offer(self._new_batch(base, B, self.rng_delta))
            ep = self.epochs[-1]
            t1 = time.perf_counter()
            for _ in range(int(self.mix["warm_query_batches"])):
                for v in range(len(self.names)):
                    self._ask(v, self._queries(v, self.rng_warm, batch)[0])
            self.log(f"setup: warm epoch {ep.t1 - ep.t0:.3f} s (cleaned "
                     f"{ep.cleaned}, maintained {ep.maintained}), offers "
                     f"{t1 - t:.3f} s, queries {time.perf_counter() - t1:.3f} s")
        self._warm_outlier_merge()
        t = time.perf_counter()
        from repro.core import Query
        from repro.relational.expr import Cmp, Col, Lit, and_

        # averages over a single predicate value have no spread, so the
        # engine recomputes their variance in two passes; which estimator
        # it picks depends on how many groups moved across the value, so
        # a spread of values reaches both paths
        for v, spec in enumerate(self.dep.views):
            values = np.floor(spec.pred_scale * np.linspace(0.5, 1.5, 4 * batch))
            for chunk in np.split(values, 4):
                qs = [Query(agg="avg", col=spec.pred_col, pred=and_(
                    Cmp("ge", Col(spec.pred_col), Lit(float(lo))),
                    Cmp("le", Col(spec.pred_col), Lit(float(lo) + 0.5))))
                    for lo in chunk]
                self.svc.query_batch(self.names[v], qs)
                self.svc.query_batch(self.names[v], qs, prefer="corr")
        self.log(f"setup: warm two-pass variance {time.perf_counter() - t:.3f} s")

    def _warm_outlier_merge(self) -> None:
        """Compile each outlier index's merge at the epoch's delta shape.

        A new row can enter a view's top-k index in any epoch, and the
        merge that places it compiles at its first use; whether a warm
        epoch needed it depends on the seed.  So the merge runs once more,
        on a copy of the last warm epoch's drained delta whose first row
        crosses the index's threshold, and its result is dropped."""
        import jax.numpy as jnp

        from repro.core.outliers import update_outlier_index
        from repro.relational.relation import Relation

        last = self.vm.pending_segments[-1].inserts
        for mv in self.vm.views.values():
            idx = mv.outlier_index
            if idx is None or idx.base not in last:
                continue
            delta = last[idx.base]
            cols = dict(delta.columns)
            col = cols[idx.attr]
            cols[idx.attr] = col.at[0].set(jnp.finfo(col.dtype).max)
            update_outlier_index(idx, Relation(cols, delta.valid.at[0].set(True),
                                               delta.schema))

    def _prepare_window(self) -> None:
        ins, qm = self.mix["inserts"], self.mix["queries"]
        self.events = traffic.schedule(self.mix, len(self.names),
                                       len(self.dep.insert_bases), self.seconds)
        self.window_batches = []
        for ev in self.events:
            if ev.kind == "insert":
                b = self._new_batch(self.dep.insert_bases[ev.target],
                                    ins["micro_batch_rows"], self.rng_delta,
                                    due=ev.due_s)
                self.window_batches.append(b)
        for ev in self.events:
            if ev.kind == "query":
                qs, specs = self._queries(ev.target, self.rng_query, qm["batch"])
                self.asked.append(Asked(ev.target, qs, specs, ev.due_s))

    # ------------------------------------------------------------------ window
    def window(self) -> None:
        """Issue every event at its due time; record when each finished."""
        import jax

        from repro.obs import trace as obs_trace

        self.setup_s = time.time() - process_start_s()
        c0, tr0 = self.compiles.compiles, self.compiles.traces
        self.counters0 = self.vm.metrics.snapshot()
        traced = self.trace
        if traced:
            self.prof_dir = self.out_dir / f"trace_{self.cell['name']}_{self.seed}"
            self.tracer = obs_trace.enable()
            jax.profiler.start_trace(str(self.prof_dir))
            with jax.profiler.TraceAnnotation("svcbench.align"):
                self.align_pc = time.perf_counter()
            Annotation = jax.profiler.TraceAnnotation
            sub_s = float(self.mix["trace_subwindow_s"])
        batches, asked = iter(self.window_batches), iter(self.asked)
        late, served, gc_passes = [], [], []
        gc.callbacks.append(_gc_timer(gc_passes))
        start = time.perf_counter()
        close = start + self.seconds
        for ev in self.events:
            now = time.perf_counter()
            if traced and now - start >= sub_s:
                jax.profiler.stop_trace()
                traced = False
                self.prof_end = now - start
            if now >= close:
                break
            due = start + ev.due_s
            if now < due:
                time.sleep(due - now)
            t = time.perf_counter()
            late.append(t - due)
            if ev.kind == "insert":
                item = next(batches)
                if traced:
                    with Annotation("svcbench.insert"):
                        self._offer(item)
                else:
                    self._offer(item)
            else:
                item = next(asked)
                item.start = t - start
                if traced:
                    with Annotation("svcbench.query"):
                        item.answers = self._ask(item.view, item.queries)
                else:
                    item.answers = self._ask(item.view, item.queries)
                item.epochs = len(self.epochs)
            item.done = time.perf_counter() - start
            served.append((item.done - (t - start), ev))
        end = time.perf_counter()
        gc.callbacks.pop()
        if traced:
            jax.profiler.stop_trace()
            self.prof_end = end - start
        self.t_open = start
        self.window_s = max(end, close) - start
        self.compiles_in_window = self.compiles.compiles - c0
        self.traces_in_window = self.compiles.traces - tr0
        self.counters1 = self.vm.metrics.snapshot()
        if self.trace:
            self.spans = [r for r in self.tracer.drain() if r["kind"] == "span"]
            obs_trace.disable()
        self.lateness = late
        self.log(f"window: {len(late)} of {len(self.events)} events issued, "
                 f"{self.window_s:.6f} s; generator lateness p50 "
                 f"{1e3 * np.median(late):.3f} ms, p95 "
                 f"{1e3 * np.percentile(late, 95):.3f} ms, max "
                 f"{1e3 * max(late):.3f} ms")
        self._log_window(start)
        slow = sorted(served, key=lambda x: -x[0])[:5]
        self.log("window: slowest calls: " + "; ".join(
            f"{ev.kind} {ev.target} due {ev.due_s:.3f} s took {1e3 * d:.3f} ms"
            for d, ev in slow))
        self.log(f"window: gc passes {len(gc_passes)} (generation 2: "
                 f"{sum(1 for g, _d in gc_passes if g == 2)}), longest "
                 f"{1e3 * max((d for _g, d in gc_passes), default=0.0):.3f} ms, "
                 f"total {sum(d for _g, d in gc_passes):.3f} s")
        self.log(f"window: compilations {self.compiles_in_window}, "
                 f"jaxpr traces {self.traces_in_window}, epochs "
                 f"{sum(1 for e in self.epochs if e.t0 >= start)}")

    def _log_window(self, start: float) -> None:
        """Where the window's time went, per kind of call (host clock)."""
        def stats(xs):
            if not xs:
                return "none"
            return (f"n={len(xs)} p50={1e3 * np.median(xs):.3f} ms "
                    f"p95={1e3 * np.percentile(xs, 95):.3f} ms "
                    f"max={1e3 * max(xs):.3f} ms")
        eps = [e for e in self.epochs if e.t0 >= start]
        for v, name in enumerate(self.names):
            busy = [a.done - a.start for a in self.asked
                    if a.view == v and a.done is not None]
            self.log(f"window: query batches {name}: {stats(busy)}")
        self.log(f"window: epochs: {stats([e.t1 - e.t0 for e in eps])}; "
                 f"cleaned {[len(e.cleaned) for e in eps]}, maintained "
                 f"{sum(len(e.maintained) for e in eps)}")
        pend = {n: self.vm.drift_rows(n, since="ivm") for n in self.names}
        self.log(f"window: pending rows since maintenance at close {pend}")

    # ------------------------------------------------------------------ after
    def collect(self) -> None:
        """Read what the check needs from the program, then free it."""
        from repro.relational.relation import to_host

        self.compiles.__exit__(None, None, None)
        dev = self.devs[0]
        stats = dev.memory_stats() or {}
        self.device_info = {"platform": dev.platform, "kind": dev.device_kind,
                            "count": len(self.devs),
                            "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
        vm, svc = self.vm, self.svc
        self.final_samples = {n: to_host(vm.views[n].clean_sample)
                              for n in self.names}
        self.accounted_rows = {b: vm.ingested_rows.get(b, 0)
                               + (svc.logs[b].pending_rows() if b in svc.logs else 0)
                               for b in self.dep.insert_bases}
        self.rejected = sum(log.corrupt_batches + log.shed_batches
                            for log in svc.logs.values())
        self.quarantined = sorted(vm.health.degraded_views())
        del self.vm, self.svc, self.planner
        for b in self.batches:
            b.rel = None
        for a in self.asked:
            a.queries = None
        gc.collect()

    # ------------------------------------------------------------------ check
    def check(self, control: bool = False) -> dict:
        """Compare every answer of the window, and each view's last clean
        sample, with the reference.  ``control=True`` puts the bf16
        reference in the program's place."""
        cfg, dep = self.cfg, self.dep
        m, conf = cfg["m"], cfg["confidence"]
        answered = [a for a in self.asked if a.answers is not None]
        out = {"answer_gap": 0.0, "ci_gap": 0.0, "method_mismatch": 0,
               "sample_mismatch": 0, "sample_gap": 0.0}
        self.sizes = {}
        for vi, spec in enumerate(dep.views):
            base_cols = dep.tables[spec.base].cols
            dim = dep.tables[spec.join[0]].cols[spec.join[1]] if spec.join else None
            state = ref.ViewState(spec, base_cols, dim)
            pins = ref.OutlierPins(spec, base_cols) if spec.outlier else None
            pin_groups = pins.groups() if pins else np.zeros(0, np.int64)
            sampled = ref.eta(np.arange(spec.groups), m, spec.seed)
            stale_view = state.snapshot()
            stale_pins = pin_groups
            stale = ref.sample_of(stale_view, sampled, stale_pins, pin_groups)
            mine = [i for i, b in enumerate(self.batches) if b.base == spec.base]
            todo = sorted((a for a in answered if a.view == vi),
                          key=lambda a: a.epochs)
            applied = flushed = ai = 0
            clean = None
            marks = [(ei, ep) for ei, ep in enumerate(self.epochs)
                     if spec.name in ep.cleaned or spec.name in ep.maintained]
            for ei, ep in marks + [(len(self.epochs), None)]:
                here = []
                while ai < len(todo) and todo[ai].epochs <= ei:
                    here.append(todo[ai])
                    ai += 1
                if here:
                    g = self._compare_answers(here, clean, stale, stale_view,
                                              spec, m, conf, control)
                    out["answer_gap"] = max(out["answer_gap"], g[0])
                    out["ci_gap"] = max(out["ci_gap"], g[1])
                    out["method_mismatch"] += g[2]
                if ep is None:
                    break
                for i in mine:
                    if applied <= i < ep.drained:
                        state.add(self.batches[i].cols)
                applied = max(applied, ep.drained)
                if pins is not None:
                    pins.merge([self.batches[i].cols for i in mine
                                if flushed <= i < ep.drained])
                    flushed = max(flushed, ep.drained)
                    pin_groups = pins.groups()
                snap = state.snapshot()
                if spec.name in ep.maintained:
                    # full IVM: the stale view and its sample restart here
                    stale_view, stale_pins = snap, pin_groups
                stale = ref.sample_of(stale_view, sampled, stale_pins, pin_groups)
                clean = ref.sample_of(snap, sampled, pin_groups, pin_groups)
            got = self.final_samples[spec.name]
            if control:
                got = ref.sample_as_columns(clean, spec.key, cast="bf16")
            mis, gap = ref.compare_sample(got, spec.key, clean)
            out["sample_mismatch"] += mis
            out["sample_gap"] = max(out["sample_gap"], gap)
            self.sizes[spec.name] = {
                "joined": int(np.union1d(clean.keys, stale.keys).shape[0]),
                "view": int(stale_view.present.sum()),
                "cols": 1 + len(spec.aggs)}
        missing = 0
        for b in dep.insert_bases:
            acked = sum(x.rows for x in self.batches
                        if x.base == b and (x.due is None or x.done is not None))
            missing += abs(acked - self.accounted_rows[b])
        out["rows_unaccounted"] = missing
        return {k: v if isinstance(v, int) else float(v) for k, v in out.items()}

    def _compare_answers(self, asked, clean, stale, stale_view, spec, m, conf,
                         control):
        specs = [s for a in asked for s in a.specs]
        est = ref.estimates(clean, stale, stale_view, spec.pred_col, specs, m, conf)
        if control:
            low = ref.estimates(clean, stale, stale_view, spec.pred_col, specs,
                                m, conf, cast="bf16")
            got = []
            for i in range(len(specs)):
                kind = "corr" if low["ht_corr"][i] <= low["ht_aqp"][i] else "aqp"
                v, hw = low[kind][i], low[kind + "_hw"][i]
                got.append((v, v - hw, v + hw, f"SVC+{kind.upper()}"))
        else:
            got = [x for a in asked for x in a.answers]
        vgap = cgap = 0.0
        bad = 0
        for i, (value, lo, hi, method) in enumerate(got):
            corr = method.startswith("SVC+CORR")
            if corr != (est["ht_corr"][i] <= est["ht_aqp"][i]) and not ref.near_tie(
                    est["ht_aqp"][i], est["ht_corr"][i]):
                bad += 1
            kind = "corr" if corr else "aqp"
            rv, rhw = est[kind][i], est[kind + "_hw"][i]
            vgap = max(vgap, ref.value_gap(value, rv))
            cgap = max(cgap, ref.hw_gap(0.5 * (hi - lo), rhw, rv))
        return vgap, cgap, bad

    # ------------------------------------------------------------------ result
    def end_to_end(self) -> Dict[str, float]:
        """Every end-to-end metric this run can compute, by name."""
        W = self.window_s
        out = {"setup_s": self.setup_s}
        lat = e2e.query_latencies([(a.due, a.done) for a in self.asked], W)
        if lat:
            out["query_p95_ms"] = 1e3 * e2e.percentile(lat, 95)
        for v, name in enumerate(self.names):
            lat = e2e.query_latencies([(a.due, a.done) for a in self.asked
                                       if a.view == v], W)
            if lat:
                out[f"query_p50_ms.{name}"] = 1e3 * e2e.percentile(lat, 50)
        ends = {}
        for n in self.names:
            ends[n] = [(ep.t1 - self.t_open, ep.drained) for ep in self.epochs
                       if n in ep.cleaned or n in ep.maintained]
        lag_pairs = [(b.due, e2e.visibility(ends[v.name], i))
                     for i, b in enumerate(self.batches) if b.due is not None
                     for v in self.dep.views if v.base == b.base]
        if lag_pairs:
            out["fresh_lag_p95_s"] = e2e.percentile(e2e.fresh_lags(lag_pairs, W), 95)
        return out

    def failures(self) -> Dict[str, int]:
        degraded = sum(1 for a in self.asked if a.answers
                       for x in a.answers if "+degraded" in x[3])
        return {"degraded_answers": degraded, "rejected_batches": self.rejected,
                "quarantined_views": len(self.quarantined),
                "failed_refreshes": self.refresh_failures}


class ReadContext:
    """What a per-layer reader may read from one traced run."""

    def __init__(self, run: Run):
        from svcbench import devtrace

        self.run = run
        self.spans = list(run.spans)
        # the program's registry (``repro.obs.registry``) at the window's
        # open and close, for readers of program counters
        self.counters = (run.counters0, run.counters1)
        self.device_kind = run.device_info["kind"]
        self.trace = None
        self.work = {}
        events = devtrace.load(str(run.prof_dir))
        shutil.rmtree(run.prof_dir, ignore_errors=True)  # tens of MB per run
        if not events["devices"] and not run.require_tpu:
            return  # a rehearsal off the chip: no device plane to read
        anchor = next((s for n, s, _d in events["host"] if n == "svcbench.align"),
                      None)
        host = []
        if anchor is not None:
            off = anchor - run.align_pc * 1e9
            host = [(s["name"], s["t0"] * 1e9 + off, s["t1"] * 1e9 + off)
                    for s in self.spans]
            lo = (run.t_open * 1e9) + off
            hi = lo + run.prof_end * 1e9
            self.trace = devtrace.reduce(events, (lo, hi), host)
        else:
            self.trace = devtrace.reduce(events, None, host)
        self.work["multi_agg"] = self._multi_agg_work(run)

    def _multi_agg_work(self, run: Run):
        from svcbench import roofline

        b = f = 0.0
        preds = 1
        for a in run.asked:
            if a.answers is None or a.start is None or a.start >= run.prof_end:
                continue
            # no cell runs the result cache, so every answer is computed (a
            # cell with the cache would subtract its hits)
            computed = len(a.answers)
            sz = run.sizes[run.names[a.view]]
            wb, wf = roofline.multi_agg_work(sz["joined"], sz["cols"], computed,
                                             preds, two_sided=True)
            b, f = b + wb, f + wf
            if any(x[3].startswith("SVC+CORR") for x in a.answers):
                wb, wf = roofline.multi_agg_work(sz["view"], sz["cols"], computed,
                                                 preds, two_sided=False)
                b, f = b + wb, f + wf
        return b, f
