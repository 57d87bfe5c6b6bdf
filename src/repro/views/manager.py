"""ViewManager: the production face of SVC (§3.2 workflow).

Owns base relations, registered materialized views, their hash samples and
optional outlier indices.  Deltas are ingested continuously; **full IVM runs
only at maintenance periods** (in a training framework: at checkpoint
cadence), while ``svc_refresh`` cleans just the samples in between so that
``query`` always answers from fresh, bounded estimates.

Estimator selection follows the §5.2.2 break-even analysis: SVC+CORR while
σ_S² ≤ 2·cov(S,S'), SVC+AQP beyond it (or force with ``prefer=``).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp

from repro.core import hashing
from repro.core.bootstrap import bootstrap_aqp, bootstrap_corr
from repro.core.estimators import Estimate, Query, exact, svc_aqp, svc_corr, variance_comparison
from repro.query import (
    QueryBatch,
    build_correspondence_cache,
    is_encodable,
    run_batch,
    run_batch_aqp,
    sample_columns,
)
from repro.core.maintenance import (
    INS,
    DEL,
    DeltaSet,
    ViewDef,
    change_table_strategy,
    clean_sample,
    full_maintenance,
    upsert,
    delete_keys,
    _replace_groupby_capacity,
)
from repro.core.minmax import svc_minmax
from repro.core.outliers import (OutlierIndex, build_outlier_index, flag_outliers,
    propagate_outlier_keys, update_outlier_index)
from repro.relational.plan import plan_leaves
from repro.relational.execute import execute
from repro.relational.relation import Relation, compact, from_columns
from repro.relational.relation import empty as empty_relation
from repro.obs import trace as obs_trace
from repro.obs.registry import MetricsRegistry, counter_attr
from repro.robustness.health import FleetHealth
import numpy as np


@dataclasses.dataclass
class ManagedView:
    view: ViewDef
    strategy: object  # maintenance plan M
    sampled_strategy: object  # M with m-scaled group arenas (§Perf C.2)
    m: float
    seed: int
    materialized: Relation  # the (possibly stale) full view S
    stale_sample: Relation  # Ŝ = η(S)
    clean_sample: Relation  # Ŝ' after last svc_refresh
    sample_capacity: int
    delta_bases: Tuple[str, ...]
    outlier_index: Optional[OutlierIndex] = None
    outlier_pin: Optional[Relation] = None  # view-key pin set from push-up
    stale_since_ivm: bool = False
    maintenance_s: float = 0.0  # last timed op (refresh OR maintain) wall time
    refresh_s: float = 0.0  # last svc_refresh wall time (cost-model seed)
    ivm_s: float = 0.0  # last full-maintenance wall time (cost-model seed)
    # per-refresh-window correspondence cache (repro.query.engine): the
    # query-independent clean↔stale outer-join alignment, built lazily on
    # the first query of a window and invalidated by refresh/maintain
    corr_cache: Optional[object] = None
    # -- control-plane bookkeeping (repro.planner) ---------------------------
    # pending-segment cursor: segments [0, applied_seg) are already folded
    # into ``materialized`` (per-view IVM pace under the budgeted scheduler)
    applied_seg: int = 0
    # per-base lifetime delta-row counts at the last maintain / svc_refresh
    # (drift counters: pending rows = ViewManager.ingested_rows − these)
    applied_rows: Dict[str, int] = dataclasses.field(default_factory=dict)
    cleaned_rows: Dict[str, int] = dataclasses.field(default_factory=dict)
    # delta micro-batches offered to the outlier index but not yet merged;
    # flushed as ONE update_outlier_index call per refresh window
    outlier_offers: List[Relation] = dataclasses.field(default_factory=list)
    # bumped whenever either sample moves (planner moment-snapshot and
    # fleet-panel slot staleness)
    sample_version: int = 0
    # bumped only when the STALE sample is re-derived (maintain, sample-
    # ratio retune, pin refresh) — cleans leave it alone, so the fleet
    # panel's merge slots stay warm across clean-only epochs
    stale_version: int = 0
    # planner-recommended sampling ratio (fleet scorer REC_M); applied by
    # svc_refresh only when ViewManager.adaptive_m is opted in
    recommended_m: Optional[float] = None
    delta_group_capacity: int = 1024  # registration-time arena bound


class ViewManager:
    # batched fleet-merge dispatches that fell back to per-view cleans
    # because the dispatch itself raised (telemetry: a persistent count
    # here means the fleet path is silently degraded to the slow path);
    # a bit-compatible view over the metrics registry
    fleet_merge_failures = counter_attr()

    def __init__(self, clock: Optional[Callable[[], float]] = None):
        # every wall-clock duration in the manager/planner plane reads THIS
        # clock (injectable: simulation tests pass a fake, production gets
        # perf_counter) — one time source instead of scattered call sites
        self.clock: Callable[[], float] = clock or time.perf_counter
        # the unified metrics registry for the whole pipeline: serving-
        # plane and DeltaLog instruments are created against this registry
        # by configure_streaming, so one snapshot covers every subsystem
        self.metrics = MetricsRegistry()
        self.base: Dict[str, Relation] = {}
        self.views: Dict[str, ManagedView] = {}
        # pending deltas as an ordered SEGMENT log (one DeltaSet per ingest
        # batch): per-view cursors let the budgeted planner maintain views
        # at different paces; a segment is applied to the base relations and
        # popped once every dependent view has folded it in (the floor)
        self.pending_segments: List[DeltaSet] = []
        self._merged_cache: Dict[Tuple[int, int], DeltaSet] = {}
        self.ingested_rows: Dict[str, int] = {}  # lifetime delta rows per base
        self._base_applied_rows: Dict[str, int] = {}  # rows folded into base
        self.stream = None  # StreamingViewService once configure_streaming ran
        self.cost_model = None  # planner/costs.CostModel once attached
        self._panel = None  # FleetPanel once fleet_panel() ran
        # opt-in: svc_refresh honors planner-recommended sampling ratios
        # (MaintenancePlanner(adapt_m=True) turns this on)
        self.adaptive_m = False
        # -- failure axis (repro.robustness) ---------------------------------
        # per-view quarantine/backoff registry: every clean/maintain outcome
        # is recorded here; the serving and planner layers read it to decide
        # serve-stale-with-wider-CI vs retry
        self.health = FleetHealth()
        # chaos-test injection point (robustness.faults.FaultPlan.attach);
        # None in production — the hooks below are single attribute checks
        self.fault_plan = None
        # extra attributes stamped onto every span this manager opens (the
        # sharded fleet sets {"shard": s} so the observatory can slice one
        # trace per mesh shard); empty in the single-device fleet
        self.obs_attrs: Dict[str, object] = {}
        self._c_fleet_merge_failures = self.metrics.counter(
            "fleet_merge_failures"
        )

    def _inject_fault(self, point: str, name: Optional[str]) -> float:
        """Fire the chaos hook at a designed failure point; returns injected
        latency seconds (0.0 in production — one None check)."""
        if self.fault_plan is None:
            return 0.0
        return self.fault_plan.fire(point, name)

    @property
    def pending(self) -> DeltaSet:
        """All not-yet-base-applied deltas merged per base (read-only view)."""
        return self._pending_from(0)

    # -- streaming -----------------------------------------------------------
    def configure_streaming(self, config=None, clock=None):
        """Route ``ingest`` through the streaming engine: micro-batches are
        buffered in bounded DeltaLogs and ``svc_refresh`` fires on size/age
        watermarks instead of manual calls (repro.streaming).  ``clock`` is
        injectable for deterministic age/throttle tests."""
        import time

        from repro.streaming import StreamConfig, StreamingViewService

        self.stream = StreamingViewService(self, config or StreamConfig(),
                                           clock=clock or time.monotonic)
        return self.stream

    # -- registration --------------------------------------------------------
    def register_base(self, name: str, rel: Relation) -> None:
        self.base[name] = rel

    def register_view(
        self,
        view: ViewDef,
        delta_bases: Tuple[str, ...],
        m: float,
        seed: int = 0,
        delta_group_capacity: int = 1024,
        sample_capacity: Optional[int] = None,
        with_deletes: bool = False,
    ) -> ManagedView:
        strategy = change_table_strategy(
            view, delta_bases, delta_group_capacity, with_deletes=with_deletes
        )
        materialized = execute(view.plan, self.base)
        materialized = compact(materialized)
        stale_sample = hashing.apply_hash(materialized, view.pk, m, seed)
        # §Perf hillclimb C.2: the cleaning pipeline's sorts/merges run at
        # relation CAPACITY, so sample-side arenas are m-scaled (4x slack
        # against binomial overflow) instead of inheriting the full view
        # capacity — the sampling saving becomes a *capacity* saving.
        cap = sample_capacity or _next_pow2(
            max(64, int(materialized.capacity * m * 4))
        )
        sampled_strategy = _replace_groupby_capacity(
            strategy, _next_pow2(max(64, int(delta_group_capacity * m * 4)))
        )
        mv = ManagedView(
            view=view,
            strategy=strategy,
            sampled_strategy=sampled_strategy,
            m=m,
            seed=seed,
            materialized=materialized,
            stale_sample=compact(stale_sample, cap),
            clean_sample=compact(stale_sample, cap),
            sample_capacity=cap,
            delta_bases=delta_bases,
            # drift counters start at the base-applied watermark: rows
            # already folded into the base are part of ``materialized``
            applied_rows={b: self._base_applied_rows.get(b, 0) for b in delta_bases},
            cleaned_rows={b: self._base_applied_rows.get(b, 0) for b in delta_bases},
            delta_group_capacity=delta_group_capacity,
        )
        self.views[view.name] = mv
        return mv

    # -- the fleet panel ------------------------------------------------------
    def fleet_panel(self):
        """The stacked (V, R) clean/stale sample panel of the whole fleet
        (repro.views.panel.FleetPanel), created lazily.  Slots are
        incrementally invalidated per view by ``svc_refresh``/``maintain``
        (via ``_bump_sample_version``); accessing the panel rebuilds only
        the views whose samples moved."""
        if self._panel is None:
            from repro.views.panel import FleetPanel

            self._panel = FleetPanel(self)
        return self._panel

    def _bump_sample_version(self, mv: ManagedView) -> None:
        mv.sample_version += 1
        if self._panel is not None:
            self._panel.invalidate(mv.view.name)

    def register_outlier_index(self, view_name: str, base: str, attr: str, k: int) -> None:
        """§6: index top-k of base[attr]; push keys up into the view pin set."""
        mv = self.views[view_name]
        idx = build_outlier_index(self.base[base], base, attr, k)
        mv.outlier_index = idx
        self._refresh_pin(mv)

    def _refresh_pin(self, mv: ManagedView) -> None:
        idx = mv.outlier_index
        if idx is None:
            return
        keys = propagate_outlier_keys(mv.view.plan, self.base, idx)
        pin_cols = {c: keys[i] for i, c in enumerate(mv.view.pk)}
        mv.outlier_pin = from_columns(
            pin_cols, pk=mv.view.pk, valid=keys[0] != np.iinfo(np.int32).max
        )
        # re-derive both samples with the pin so strata stay consistent
        mv.stale_sample = compact(
            hashing.apply_hash(mv.materialized, mv.view.pk, mv.m, mv.seed, pin=mv.outlier_pin),
            mv.sample_capacity,
        )
        mv.clean_sample = mv.stale_sample
        mv.corr_cache = None
        mv.stale_version += 1
        self._bump_sample_version(mv)

    # -- delta ingestion -----------------------------------------------------
    def ingest(self, base: str, inserts: Optional[Relation] = None,
               deletes: Optional[Relation] = None, seq: Optional[int] = None,
               key=None):
        """Ingest a delta batch.  With streaming configured, the batch lands
        in the DeltaLog (``seq`` orders out-of-order producers, ``key`` is
        an optional producer idempotency key for at-least-once replay
        dedupe) and refresh happens on watermarks; otherwise it goes
        straight into the pending set and the caller refreshes manually."""
        if self.stream is not None:
            return self.stream.offer(base, inserts=inserts, deletes=deletes,
                                     seq=seq, key=key)
        return self._ingest_pending(base, inserts=inserts, deletes=deletes)

    def _ingest_pending(self, base: str, inserts: Optional[Relation] = None,
                        deletes: Optional[Relation] = None):
        seg = DeltaSet()
        n_rows = 0
        if inserts is not None:
            seg.inserts[base] = inserts
            n_rows += int(np.asarray(inserts.valid).sum())
        if deletes is not None:
            seg.deletes[base] = deletes
            n_rows += int(np.asarray(deletes.valid).sum())
        if not seg.is_empty():
            self.pending_segments.append(seg)
            self._merged_cache.clear()
            self.ingested_rows[base] = self.ingested_rows.get(base, 0) + n_rows
        for mv in self.views.values():
            if base in mv.delta_bases:
                mv.stale_since_ivm = True
            if mv.outlier_index is not None and mv.outlier_index.base == base and inserts is not None:
                # deferred: the window's offers merge as ONE incremental
                # update at the next refresh (_flush_outlier_offers)
                mv.outlier_offers.append(inserts)
        if self.cost_model is not None and n_rows:
            self.cost_model.observe_ingest(base, n_rows)

    def _pending_from(self, lo: int) -> DeltaSet:
        """Segments [lo:] merged per base (memoized per refresh window)."""
        hi = len(self.pending_segments)
        key = (lo, hi)
        merged = self._merged_cache.get(key)
        if merged is None:
            with obs_trace.span("concat", segments=hi - lo,
                                **self.obs_attrs) as sp:
                ins: Dict[str, List[Relation]] = {}
                dels: Dict[str, List[Relation]] = {}
                for seg in self.pending_segments[lo:]:
                    for b, r in seg.inserts.items():
                        ins.setdefault(b, []).append(r)
                    for b, r in seg.deletes.items():
                        dels.setdefault(b, []).append(r)
                merged = DeltaSet()
                rows = cap = uploaded = 0
                for src, side in ((ins, merged.inserts), (dels, merged.deletes)):
                    for b, rs in src.items():
                        side[b], n, up = _concat_many(rs)
                        rows, uploaded = rows + n, uploaded + up
                        cap = max(cap, side[b].capacity)
                sp.set(rows=rows, cap=cap, bytes=uploaded)
            self._merged_cache[key] = merged
        return merged

    def drift_rows(self, view_name: str, since: str = "ivm") -> int:
        """Delta rows a view has not yet absorbed.

        ``since="ivm"``: rows not folded by full maintenance (the correction
        the clean sample must carry); ``since="clean"``: rows not yet
        reflected in the clean sample (the staleness bias of serving without
        a refresh).  Both are O(#bases) counter reads — the planner's drift
        signal costs no scans."""
        mv = self.views[view_name]
        snap = mv.applied_rows if since == "ivm" else mv.cleaned_rows
        return sum(
            max(self.ingested_rows.get(b, 0) - snap.get(b, 0), 0)
            for b in mv.delta_bases
        )

    def _deltas_for(self, mv: ManagedView) -> DeltaSet:
        """Pending deltas beyond the view's applied cursor, with EMPTY
        stand-ins for quiet delta bases so the cleaning/maintenance plans
        always find their Scan leaves.

        Insert AND delete leaves are both back-filled (a ``with_deletes``
        strategy has ``base__del`` Scans that must resolve even on an
        insert-only refresh window — previously a KeyError)."""
        merged = self._pending_from(mv.applied_seg)
        out = DeltaSet(inserts=dict(merged.inserts),
                       deletes=dict(merged.deletes))
        leaves = {leaf.name for leaf in plan_leaves(mv.strategy)}
        for b in mv.delta_bases:
            base = self.base[b]
            dtypes = {c: base.col(c).dtype for c in base.schema.columns}
            if b not in out.inserts:
                out.inserts[b] = empty_relation(dtypes, base.schema.pk, capacity=8)
            if b + DEL in leaves and b not in out.deletes:
                out.deletes[b] = empty_relation(dtypes, base.schema.pk, capacity=8)
        return out

    def _flush_outlier_offers(self, mv: ManagedView) -> None:
        """Merge the window's buffered index offers in ONE incremental
        update (threshold gate + bounded merge) instead of one per
        micro-batch; concat order is offer order, so the result is
        bit-equal to the per-batch path (stable survivor sort)."""
        offers, mv.outlier_offers = mv.outlier_offers, []
        if not offers or mv.outlier_index is None:
            return
        if len(offers) == 1:
            delta = offers[0]
        else:
            schema = offers[0].schema
            cols = {
                c: jnp.concatenate([r.col(c) for r in offers])
                for c in schema.columns
            }
            valid = jnp.concatenate([r.valid for r in offers])
            delta = Relation(cols, valid, schema)
        mv.outlier_index = update_outlier_index(mv.outlier_index, delta)

    # -- SVC: clean the samples only (cheap, between maintenance periods) ----
    def svc_refresh(self, view_name: str, fused: Optional[bool] = None,
                    _precomputed=None, _extra_s: float = 0.0,
                    _retuned: bool = False) -> float:
        """Clean the view's sample from the pending deltas (Problem 1).

        ``fused`` routes the delta aggregation through the single-pass
        kernels/fused_clean op (None = module default; it falls back to the
        plan executor when the plan shape does not qualify).  With the
        opt-in ``adaptive_m`` flag, a planner-recommended sampling ratio
        (``ManagedView.recommended_m``) is applied first.  ``_precomputed``/
        ``_extra_s``/``_retuned`` are the ``svc_refresh_many`` internals:
        already-batched fused delta aggregations, this view's share of the
        batched dispatch wall time, and whether the batched path already
        retuned the ratio (so the cost model files the wall time under
        retune, not refresh).

        The clean is TRANSACTIONAL per view: any failure (including an
        injected chaos fault) restores the view's pre-clean state —
        samples, caches, counters — records the failure in ``health``
        (quarantine + backoff), and re-raises.  A later successful clean
        folds everything the failed one missed (§4.5 recompute-from-full-
        pending), bit-equal to a run that never failed."""
        mv = self.views[view_name]
        snap = _view_snapshot(mv)
        with obs_trace.span("clean", view=view_name, **self.obs_attrs) as sp:
            try:
                dt = self._svc_refresh_inner(
                    mv, view_name, fused, _precomputed, _extra_s, _retuned
                )
            except Exception as e:
                _restore_view(mv, snap)
                if self._panel is not None:
                    self._panel.invalidate(view_name)
                self.health.record_failure(view_name, e)
                raise
            self.health.record_success(view_name)
            sp.set(wall_s=dt, sample_version=mv.sample_version)
        return dt

    def _svc_refresh_inner(self, mv: ManagedView, view_name: str,
                           fused: Optional[bool], _precomputed,
                           _extra_s: float, _retuned: bool) -> float:
        retuned = bool(_retuned)
        lat_s = self._inject_fault("refresh", view_name)
        t0 = self.clock()  # a retune below is part of the clean's cost
        if (self.adaptive_m and mv.recommended_m is not None
                and abs(mv.recommended_m - mv.m) > 1e-9):
            self._retune_sample_ratio(mv, mv.recommended_m)
            retuned = True
        if mv.outlier_index is not None:
            self._flush_outlier_offers(mv)
            self._refresh_pin_keys_only(mv)
        extra = dict(self.base)
        pin_name = None
        if mv.outlier_pin is not None:
            pin_name = "__pin__" + view_name
            extra[pin_name] = mv.outlier_pin
        mv.clean_sample = clean_sample(
            mv.sampled_strategy,
            mv.view.name,
            mv.view.pk,
            mv.stale_sample,
            self._deltas_for(mv),
            mv.m,
            mv.seed,
            extra_env=extra,
            out_capacity=mv.sample_capacity,
            pin_name=pin_name,
            fused=fused,
            precomputed=_precomputed,
        )
        mv.clean_sample = flag_outliers(mv.clean_sample, mv.outlier_pin)
        mv.stale_sample = flag_outliers(mv.stale_sample, mv.outlier_pin)
        mv.corr_cache = None  # samples moved: new correspondence window
        with obs_trace.span("sync", view=view_name, **self.obs_attrs):
            jnp.asarray(mv.clean_sample.valid).block_until_ready()
        dt = self.clock() - t0 + float(_extra_s) + lat_s
        mv.maintenance_s = dt
        mv.refresh_s = dt
        self._bump_sample_version(mv)
        for b in mv.delta_bases:  # the clean sample now reflects all deltas
            mv.cleaned_rows[b] = self.ingested_rows.get(b, 0)
        if self.cost_model is not None:
            if retuned:
                self.cost_model.observe_retune(view_name, dt)
            else:
                self.cost_model.observe_refresh(view_name, dt)
        return dt

    def _retune_sample_ratio(self, mv: ManagedView, new_m: float) -> None:
        """Planner-driven m adaptation (opt-in via ``adaptive_m``): re-derive
        the sample pair from the materialized view at the new ratio.

        The stale sample's invariant — Ŝ = η(S) for the CURRENT materialized
        view — is preserved (η is re-applied to ``materialized``, not to the
        old sample, so stepping m UP recovers rows the old sample dropped);
        the following clean folds every pending delta beyond the view's
        segment cursor into the new sample.  Sample arenas and the m-scaled
        group capacities are re-bucketed for the new ratio — the sample
        arena SCALES from its current size (preserving any explicit
        ``sample_capacity`` override's slack policy, never shrinking below
        the registration-time default formula)."""
        new_m = float(new_m)
        old_m = mv.m
        mv.m = new_m
        mv.sample_capacity = _next_pow2(max(
            64,
            int(mv.sample_capacity * (new_m / old_m)),
            int(mv.materialized.capacity * new_m * 4),
        ))
        mv.sampled_strategy = _replace_groupby_capacity(
            mv.strategy,
            _next_pow2(max(64, int(mv.delta_group_capacity * new_m * 4))),
        )
        mv.stale_sample = compact(
            hashing.apply_hash(
                mv.materialized, mv.view.pk, new_m, mv.seed, pin=mv.outlier_pin
            ),
            mv.sample_capacity,
        )
        mv.clean_sample = mv.stale_sample
        mv.corr_cache = None
        mv.recommended_m = None
        mv.stale_version += 1
        self._bump_sample_version(mv)

    def svc_refresh_many(self, names: Sequence[str],
                         fused: Optional[bool] = None,
                         isolate: bool = True) -> Dict[str, float]:
        """Refresh several views' samples as ONE compiled epoch pass.

        Every qualifying clean runs end-to-end through two fleet
        dispatches: the η-filtered delta group-bys batch across views in
        ONE kernels/fused_clean fleet pass (per-view seeds/ratios), and
        the merge remainders — upserting those dense deltas into the
        panel-backed stale samples with delete-cancellation — batch into
        ONE kernels/fleet_merge dispatch via
        ``core.maintenance.fleet_clean_merge``.  No per-view merge plan
        executes; per-view work after the dispatch is slicing the sorted
        rows into each view's sample arena.  A view qualifies when it is
        pin-free with a single int group key and its cleaning plan reduces
        to 1–2 canonical fused specs (insert side, plus the delete side
        for ``with_deletes`` strategies).  Views that do not qualify
        (outlier pins, composite keys, non-canonical plans, unbounded key
        domains, ``fused=False``) fall back to per-view ``svc_refresh``,
        reusing any side that did aggregate on the batched path.  Returns
        per-view wall seconds (each member carries its share of the
        batched dispatches).

        Failure isolation (``isolate=True``, the default): a failed
        per-view clean is quarantined into ``health`` and reported as 0.0
        wall seconds while every other view's clean commits — one bad view
        cannot abort the epoch.  A failure of the batched fleet dispatch
        itself falls the WHOLE epoch back to per-view cleans (counted in
        ``fleet_merge_failures``), so a kernel-level fault degrades to the
        slow path, never to an error.  ``isolate=False`` restores
        fail-fast propagation for debugging."""
        from repro.core.maintenance import (
            _FUSED_DEFAULT,
            _MergeJob,
            cleaning_plan,
            collect_fused_specs,
            delta_env,
            fleet_clean_merge,
        )

        names = list(names)
        out: Dict[str, float] = {}
        do_fused = _FUSED_DEFAULT if fused is None else bool(fused)
        jobs: List[object] = []
        retune_s: Dict[str, float] = {}
        retuned: set = set()
        if do_fused and len(names) > 1:
            panel = self.fleet_panel()
            for name in names:
                mv = self.views[name]
                if mv.outlier_index is not None or mv.outlier_pin is not None:
                    continue
                if (self.adaptive_m and mv.recommended_m is not None
                        and abs(mv.recommended_m - mv.m) > 1e-9):
                    tr = self.clock()  # charge the retune to this view
                    self._retune_sample_ratio(mv, mv.recommended_m)
                    retune_s[name] = self.clock() - tr
                    retuned.add(name)
                if len(mv.view.pk) != 1:
                    continue
                plan = cleaning_plan(
                    mv.sampled_strategy, mv.view.pk, mv.m, mv.seed
                )
                env = delta_env(mv.view.name, mv.stale_sample, self._deltas_for(mv))
                env.update(self.base)
                specs = collect_fused_specs(plan, env)
                # the merge remainder is bypassed wholesale, so EVERY delta
                # layer of the strategy must have fused: insert-only plans
                # yield exactly [ins]; with_deletes plans exactly [ins, del]
                # (collect order is the OuterJoin nesting order)
                has_del = any(
                    leaf.name.endswith(DEL) for leaf in plan_leaves(mv.strategy)
                )
                want = 2 if has_del else 1
                if len(specs) != want:
                    continue
                if any(s.dim_name is not None or s.pin_name is not None
                       or s.key != mv.view.pk[0] for s in specs):
                    continue
                if not specs[0].fact_name.endswith(INS):
                    continue
                if has_del and not specs[1].fact_name.endswith(DEL):
                    continue
                agg_cols = tuple(o for o, _fn, _v in specs[0].node.aggs)
                skeys, svalid, svals = panel.merge_slot(
                    name, mv.view.pk[0], agg_cols
                )
                jobs.append(_MergeJob(
                    name=name,
                    key=mv.view.pk[0],
                    agg_cols=agg_cols,
                    col_dtypes={
                        c: mv.stale_sample.col(c).dtype
                        for c in mv.stale_sample.schema.columns
                    },
                    stale_keys=skeys,
                    stale_valid=svalid,
                    stale_vals=svals,
                    ins=(env[specs[0].fact_name], specs[0]),
                    dele=(env[specs[1].fact_name], specs[1]) if has_del else None,
                    out_capacity=mv.sample_capacity,
                ))
        merged, precomputed = {}, {}
        with obs_trace.span("merge", jobs=len(jobs),
                            **self.obs_attrs) as sp:
            t0 = self.clock()
            if jobs:
                try:
                    self._inject_fault("kernel", None)
                    merged, precomputed = fleet_clean_merge(jobs)
                    for name, rel in merged.items():
                        with obs_trace.span("sync", view=name,
                                            **self.obs_attrs):
                            jnp.asarray(rel.valid).block_until_ready()
                except Exception:
                    if not isolate:
                        raise
                    # the batched dispatch failed as a unit: degrade the
                    # whole epoch to per-view cleans (slow but correct) —
                    # panel slots were only read, never written, so no
                    # restore is needed
                    self.fleet_merge_failures += 1
                    merged, precomputed = {}, {}
            share = (
                (self.clock() - t0) / max(len(merged), 1)
                if merged else 0.0
            )
            sp.set(merged=len(merged), fell_back=len(names) - len(merged))
        for name in names:
            try:
                if name in merged:
                    out[name] = self._finish_batched_refresh(
                        name, merged[name],
                        share + retune_s.get(name, 0.0), name in retuned,
                    )
                else:
                    out[name] = self.svc_refresh(
                        name, fused=fused,
                        _precomputed=precomputed.get(name),
                        _extra_s=retune_s.get(name, 0.0),
                        _retuned=name in retuned,
                    )
            except Exception:
                if not isolate:
                    raise
                # quarantined (health recorded by the per-view guard); the
                # view keeps serving its last good sample, the epoch commits
                out[name] = 0.0
        return out

    def _finish_batched_refresh(self, view_name: str, rel: Relation,
                                dt: float, retuned: bool) -> float:
        """Install one fleet-merged clean sample: the same bookkeeping tail
        ``svc_refresh`` runs (flag, cache drop, version bump, watermarks,
        cost-model observation), minus the plan execution the fleet
        dispatch already did.  Guarded like ``svc_refresh``: a failure
        restores the view and quarantines it."""
        mv = self.views[view_name]
        snap = _view_snapshot(mv)
        with obs_trace.span("clean", view=view_name, batched=True,
                            **self.obs_attrs) as sp:
            try:
                dt = self._finish_batched_inner(mv, view_name, rel, dt, retuned)
            except Exception as e:
                _restore_view(mv, snap)
                if self._panel is not None:
                    self._panel.invalidate(view_name)
                self.health.record_failure(view_name, e)
                raise
            self.health.record_success(view_name)
            sp.set(wall_s=dt, sample_version=mv.sample_version)
        return dt

    def _finish_batched_inner(self, mv: ManagedView, view_name: str,
                              rel: Relation, dt: float, retuned: bool) -> float:
        dt = dt + self._inject_fault("refresh", view_name)
        mv.clean_sample = flag_outliers(rel, mv.outlier_pin)
        mv.stale_sample = flag_outliers(mv.stale_sample, mv.outlier_pin)
        mv.corr_cache = None  # samples moved: new correspondence window
        mv.maintenance_s = dt
        mv.refresh_s = dt
        self._bump_sample_version(mv)
        for b in mv.delta_bases:  # the clean sample now reflects all deltas
            mv.cleaned_rows[b] = self.ingested_rows.get(b, 0)
        if self.cost_model is not None:
            if retuned:
                self.cost_model.observe_retune(view_name, dt)
            else:
                self.cost_model.observe_refresh(view_name, dt)
        return dt

    def _refresh_pin_keys_only(self, mv: ManagedView) -> None:
        idx = mv.outlier_index
        env = dict(self.base)
        # include pending inserts so new outliers pin their groups too
        keys = propagate_outlier_keys(mv.view.plan, env, idx)
        pin_cols = {c: keys[i] for i, c in enumerate(mv.view.pk)}
        mv.outlier_pin = from_columns(
            pin_cols, pk=mv.view.pk, valid=keys[0] != np.iinfo(np.int32).max
        )

    # -- full IVM (the expensive path; runs at maintenance periods) ----------
    def maintain(self, view_name: str, consume: bool = True) -> float:
        """Full IVM for ONE view at its own pace: fold the pending segments
        beyond this view's cursor into the materialized view, advance the
        cursor, and let the shared floor (min cursor over dependent views)
        apply fully-absorbed segments to the base relations — the planner
        can maintain hot views every epoch without double-applying deltas
        to views it deferred.

        ``consume=False`` is the timing probe for benchmarks: the same
        maintenance work runs into a scratch result and NO state moves, so
        repeated calls measure the full per-maintenance cost (a consuming
        call leaves nothing pending for the next repeat to fold)."""
        mv = self.views[view_name]
        if not consume:
            t0 = self.clock()
            scratch = full_maintenance(
                mv.strategy, mv.view.name, mv.materialized,
                self._deltas_for(mv), extra_env=self.base,
                out_capacity=mv.materialized.capacity,
            )
            with obs_trace.span("sync", view=view_name, **self.obs_attrs):
                jnp.asarray(scratch.valid).block_until_ready()
            return self.clock() - t0
        snap = _view_snapshot(mv)
        with obs_trace.span("maintain", view=view_name,
                            **self.obs_attrs) as sp:
            try:
                dt = self._maintain_inner(mv, view_name)
            except Exception as e:
                _restore_view(mv, snap)
                if self._panel is not None:
                    self._panel.invalidate(view_name)
                self.health.record_failure(view_name, e)
                raise
            self.health.record_success(view_name)
            sp.set(wall_s=dt, sample_version=mv.sample_version)
        return dt

    def _maintain_inner(self, mv: ManagedView, view_name: str) -> float:
        lat_s = self._inject_fault("maintain", view_name)
        self._flush_outlier_offers(mv)
        t0 = self.clock()
        hi = len(self.pending_segments)
        mv.materialized = full_maintenance(
            mv.strategy,
            mv.view.name,
            mv.materialized,
            self._deltas_for(mv),
            extra_env=self.base,
            out_capacity=mv.materialized.capacity,
        )
        with obs_trace.span("sync", view=view_name, **self.obs_attrs):
            jnp.asarray(mv.materialized.valid).block_until_ready()
        dt = self.clock() - t0 + lat_s
        mv.stale_sample = compact(
            hashing.apply_hash(mv.materialized, mv.view.pk, mv.m, mv.seed, pin=mv.outlier_pin),
            mv.sample_capacity,
        )
        mv.clean_sample = mv.stale_sample
        mv.corr_cache = None
        mv.stale_since_ivm = False
        mv.maintenance_s = dt
        mv.ivm_s = dt
        mv.stale_version += 1
        self._bump_sample_version(mv)
        mv.applied_seg = hi
        for b in mv.delta_bases:
            mv.applied_rows[b] = self.ingested_rows.get(b, 0)
            mv.cleaned_rows[b] = self.ingested_rows.get(b, 0)
        self._advance_pending_floor()
        if self.cost_model is not None:
            self.cost_model.observe_maintain(view_name, dt)
        return dt

    def maintain_all(self) -> float:
        if self.stream is not None:  # fold still-buffered micro-batches in
            for base, log in self.stream.logs.items():
                ins, dels = log.drain()
                if ins is not None or dels is not None:
                    self._ingest_pending(base, inserts=ins, deletes=dels)
        total = 0.0
        for name in self.views:
            total += self.maintain(name)
        self._advance_pending_floor()  # no views registered: drain anyway
        return total

    def _advance_pending_floor(self) -> None:
        """Apply and pop every leading segment that all dependent views have
        already folded in (their cursors are past it); cursors shift with
        the pop so pending memory stays bounded by the slowest view — which
        the planner's starvation guard forces to maintain eventually."""
        popped = False
        while self.pending_segments:
            seg = self.pending_segments[0]
            bases = set(seg.inserts) | set(seg.deletes)
            gating = [mv for mv in self.views.values()
                      if bases & set(mv.delta_bases)]
            if any(mv.applied_seg < 1 for mv in gating):
                break
            self._apply_segment_to_base(seg)
            self.pending_segments.pop(0)
            for mv in self.views.values():
                mv.applied_seg = max(0, mv.applied_seg - 1)
            popped = True
        if popped:
            self._merged_cache.clear()

    def _apply_segment_to_base(self, seg: DeltaSet) -> None:
        for b, rel in seg.inserts.items():
            grown = max(self.base[b].capacity, _next_pow2(int(np.asarray(self.base[b].valid.sum())) + rel.capacity))
            self.base[b] = upsert(self.base[b], rel, capacity=grown)
            self._base_applied_rows[b] = (
                self._base_applied_rows.get(b, 0) + int(np.asarray(rel.valid).sum())
            )
        for b, rel in seg.deletes.items():
            self.base[b] = delete_keys(self.base[b], rel)
            self._base_applied_rows[b] = (
                self._base_applied_rows.get(b, 0) + int(np.asarray(rel.valid).sum())
            )

    # -- query API ------------------------------------------------------------
    def query(
        self,
        view_name: str,
        q: Query,
        confidence: float = 0.95,
        prefer: Optional[str] = None,  # "corr" | "aqp" | None (auto, §5.2.2)
        rng=None,
        record_traffic: bool = True,
    ) -> Estimate:
        """Estimate one query — a batch-of-1 through the compiled engine.

        Sample-mean queries (sum/count/avg with encodable predicates) go
        through ``query_batch``'s fused pass and reuse the per-window
        correspondence cache; everything else (median/percentile/min/max,
        exotic predicates) falls back to the per-query estimators."""
        return self.query_batch(
            view_name, [q], confidence=confidence, prefer=prefer, rng=rng,
            record_traffic=record_traffic,
        )[0]

    def query_batch(
        self,
        view_name: str,
        queries: Sequence[Query],
        confidence: float = 0.95,
        prefer: Optional[str] = None,
        rng=None,
        fused: Optional[bool] = None,
        record_traffic: bool = True,
    ) -> List[Estimate]:
        """Answer N queries in one fused pass (multi-query optimization).

        Encodable sample-mean queries share: one correspondence-cache
        lookup, one kernels/multi_agg moment scan, and (only if some query
        resolves to SVC+CORR) one batched exact scan of the materialized
        view.  Non-encodable queries fall back per query; result order
        matches ``queries``.  ``fused=False`` keeps the batch machinery but
        computes moments query-by-query (benchmark A/B).

        ``record_traffic=False`` answers without feeding the planner's
        per-view traffic counter (evaluation/ground-truth probes must not
        masquerade as user demand)."""
        if self.cost_model is not None and record_traffic:
            self.cost_model.observe_traffic(view_name, len(queries))
        mv = self.views[view_name]
        with obs_trace.span("estimate", view=view_name, n=len(queries),
                            sample_version=mv.sample_version,
                            **self.obs_attrs):
            results: List[Optional[Estimate]] = [None] * len(queries)
            with obs_trace.span("encode"):
                cols = sample_columns(mv.clean_sample)
                batched = [i for i, q in enumerate(queries)
                           if is_encodable(q, cols)]
                if batched:
                    batch = QueryBatch.encode([queries[i] for i in batched],
                                              cols)
            fast = set(batched)
            for i, q in enumerate(queries):
                if i not in fast:
                    results[i] = self._query_fallback(mv, q, confidence,
                                                      prefer, rng)
            if batched:
                if prefer == "aqp":
                    # AQP never needs the stale side: skip the correspondence
                    # join entirely and scan only the clean sample
                    ests = run_batch_aqp(
                        mv.clean_sample, batch, mv.m, confidence=confidence,
                        fused=True if fused is None else fused,
                    )
                else:
                    cache = self._corr_cache(mv)
                    ests = run_batch(
                        cache, batch, confidence=confidence, prefer=prefer,
                        materialized=mv.materialized,
                        fused=True if fused is None else fused,
                    )
                for i, e in zip(batched, ests):
                    results[i] = e
        return results

    def _corr_cache(self, mv: ManagedView):
        if mv.corr_cache is None:
            with obs_trace.span("corr_build", view=mv.view.name,
                                **self.obs_attrs) as sp:
                cache = build_correspondence_cache(
                    mv.clean_sample, mv.stale_sample, mv.m
                )
                sp.set(rows=int(cache.x_new.shape[0]))
                # traced only; the batch's first fetch waits on these anyway
                sp.wait(cache.x_new, cache.x_old)
            mv.corr_cache = cache
        return mv.corr_cache

    def _query_fallback(
        self, mv: ManagedView, q: Query, confidence: float,
        prefer: Optional[str], rng,
    ) -> Estimate:
        """Per-query estimator path for queries outside the engine's class.

        q(S) — a full materialized-view scan — is computed lazily: AQP-side
        estimators never touch it."""
        stale_result = None

        def stale():
            nonlocal stale_result
            if stale_result is None:
                stale_result = exact(mv.materialized, q)
            return stale_result

        if q.agg in ("sum", "count", "avg"):
            if prefer is None:
                cmp = variance_comparison(mv.clean_sample, mv.stale_sample, q, mv.m)
                prefer = "corr" if bool(cmp["corr_wins"]) else "aqp"
            if prefer == "corr":
                return svc_corr(stale(), mv.clean_sample, mv.stale_sample, q, mv.m, confidence)
            return svc_aqp(mv.clean_sample, q, mv.m, confidence)
        if q.agg in ("median", "percentile"):
            import jax

            rng = rng if rng is not None else jax.random.PRNGKey(0)
            if prefer == "aqp":
                return bootstrap_aqp(mv.clean_sample, q, rng, confidence=confidence)
            return bootstrap_corr(stale(), mv.clean_sample, mv.stale_sample, q, rng, confidence=confidence)
        if q.agg in ("min", "max"):
            mm = svc_minmax(stale(), mv.clean_sample, mv.stale_sample, q, mv.m)
            return Estimate(mm.value, mm.exceed_prob, mm.value, mm.value, mm.method, confidence)
        raise ValueError(q.agg)

    def query_stale(self, view_name: str, q: Query) -> jnp.ndarray:
        """No-maintenance baseline answer."""
        return exact(self.views[view_name].materialized, q)

    def query_exact_fresh(self, view_name: str, q: Query) -> jnp.ndarray:
        """Ground truth: full IVM into a scratch copy (test/benchmark helper)."""
        mv = self.views[view_name]
        fresh = full_maintenance(
            mv.strategy, mv.view.name, mv.materialized, self._deltas_for(mv),
            extra_env=self.base, out_capacity=mv.materialized.capacity,
        )
        return exact(fresh, q)


def _view_snapshot(mv: ManagedView) -> dict:
    """Shallow snapshot of every ManagedView field so a failed refresh /
    maintenance can roll the view back to its pre-attempt state.  Relation
    arenas are immutable (every mutation rebinds the field), so a
    field-level copy is a full transactional checkpoint; the only mutable
    containers are the per-base row-watermark dicts and the outlier offer
    queue, which get container copies."""
    snap = {}
    for f in dataclasses.fields(mv):
        v = getattr(mv, f.name)
        if f.name in ("applied_rows", "cleaned_rows"):
            v = dict(v)
        elif f.name == "outlier_offers":
            v = list(v)
        snap[f.name] = v
    return snap


def _restore_view(mv: ManagedView, snap: dict) -> None:
    for k, v in snap.items():
        setattr(mv, k, v)


def _concat_many(rels: List[Relation]) -> Tuple[Relation, int, int]:
    """Concatenate delta segments into one size-bucketed arena; returns the
    arena, its valid row count and the bytes uploaded to build it.

    Capacity is sized by the VALID row count (next pow2, ≥4096), so a
    steady ingest stream keeps one stable shape → the compiled cleaning
    plan is reused across refreshes instead of retracing every step.
    Single segments ride the SAME arena: passing them through at their
    raw ingest shape used to hand the per-view jitted plans a second
    shape family (raw segment vs merged arena), doubling the compile
    churn the bucket exists to avoid.

    The merge itself runs on HOST numpy: segment row counts vary batch
    to batch, and eagerly concatenating/compacting them with jnp ops
    compiled a fresh set of tiny executables for every new raw shape —
    hundreds of milliseconds of XLA churn per epoch for a few hundred
    rows of actual data.  Selecting valid rows, sorting by key
    (``compact``'s stable valid-first lexsort, reproduced with
    ``np.lexsort``), and padding to the arena are all O(rows) host work
    with zero compile footprint; one ``jnp.asarray`` per column ships
    the finished arena to the device."""
    from repro.relational.relation import SENTINEL_KEY

    schema = rels[0].schema
    masks = [np.asarray(r.valid) for r in rels]
    n_valid = int(sum(m.sum() for m in masks))
    cap = _next_pow2(max(n_valid, 4096))
    if len(rels) == 1 and rels[0].valid.shape[0] == cap:
        return rels[0], n_valid, 0
    bodies = {
        c: np.concatenate([np.asarray(r.col(c))[m] for r, m in zip(rels, masks)])
        for c in schema.columns
    }
    # stable sort by composite pk (primary key first) — the same order
    # compact() yields, so batched and per-view consumers see identical
    # row order (float accumulation order is part of the bit-equality
    # contract between the fleet and sequential clean paths)
    order = np.lexsort(tuple(reversed([bodies[k] for k in schema.pk])))
    cols = {}
    for c in schema.columns:
        fill = SENTINEL_KEY if c in schema.pk else 0
        arena = np.full((cap,), fill, dtype=bodies[c].dtype)
        arena[:n_valid] = bodies[c][order]
        cols[c] = jnp.asarray(arena)
    valid = np.zeros((cap,), dtype=bool)
    valid[:n_valid] = True
    uploaded = valid.nbytes + sum(c.nbytes for c in cols.values())
    return Relation(cols, jnp.asarray(valid), schema), n_valid, uploaded


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p
