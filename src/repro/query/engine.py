"""Compiled batched query engine: one fused pass answers N queries.

Multi-query optimization for the §5 estimators.  Three pieces make N
concurrent dashboard queries cost ~one query:

  * **Correspondence cache** — the clean-vs-stale outer join behind
    ``correspondence_diff`` (Def. 4) is query-independent, so it is built
    once per refresh window: the join's row alignment is materialized as a
    pair of row-aligned f32 column panels (x_new ∥ x_old) plus per-row
    validity/weight/1−π vectors.  ``ViewManager`` invalidates it on
    ``svc_refresh`` / ``maintain`` and every query in the window reuses it.
  * **Encoded batches** — queries become arrays (repro.query.batch), so
    evaluation is one jitted, shape-cached call instead of dozens of small
    dispatches per query.
  * **Fused moments** — kernels/multi_agg tiles the aligned panel once and
    accumulates every sufficient statistic (counts, Σt, Σt², HT terms per
    side, Σd, Σd² and the pin-aware HT_D of the diff) for all Q queries
    simultaneously; estimate assembly is then O(Q) host arithmetic.  Views
    with an active §6 outlier index stay on this path: the deterministic
    stratum rides the per-row weight/1−π vectors, so skewed workloads get
    the same one-fused-pass serving as uniform ones.

``run_batch`` also keeps the stale full-view answer **lazy**: q(S) is only
scanned (one batched one-sided pass) when at least one query resolves to
SVC+CORR, so pure-AQP batches never touch the materialized view.
"""

from __future__ import annotations

import dataclasses
import math
from typing import List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core.estimators import OUTLIER_COL, Estimate, _gamma, _masked_moments
from repro.kernels.multi_agg import (
    HT_D,
    HT_NEW,
    K_D,
    K_NEW,
    K_OLD,
    S_D,
    S_NEW,
    S_OLD,
    SS_D,
    SS_NEW,
    SS_OLD,
    multi_agg_moments,
)
from repro.obs import trace as obs_trace
from repro.query.batch import QueryBatch
from repro.relational import ops
from repro.relational.relation import Relation, Schema

__all__ = [
    "CorrespondenceCache",
    "build_correspondence_cache",
    "sample_columns",
    "sample_panel",
    "run_batch",
    "run_batch_aqp",
    "variance_report",
]


def sample_columns(rel: Relation) -> Tuple[str, ...]:
    """The encodable column panel of a sample: all columns but the flag."""
    return tuple(c for c in rel.schema.columns if c != OUTLIER_COL)


@dataclasses.dataclass
class CorrespondenceCache:
    """Query-independent clean↔stale row alignment for one refresh window."""

    columns: Tuple[str, ...]
    x_new: jnp.ndarray  # (RJ, C) f32 clean-sample panel on the joined row space
    x_old: jnp.ndarray  # (RJ, C) f32 stale-sample panel, row-aligned
    valid_new: jnp.ndarray  # (RJ,) bool
    valid_old: jnp.ndarray
    w_new: jnp.ndarray  # (RJ,) f32 per-row 1/π weights (§6.3: pinned rows 1)
    w_old: jnp.ndarray
    ompi_new: jnp.ndarray  # (RJ,) f32 1−π HT factors (pinned rows 0)
    ompi_old: jnp.ndarray
    m: float


def _rows_only(rel: Relation) -> Relation:
    """Project a relation to pk + a ``__row`` source-index column."""
    cols = {k: rel.col(k) for k in rel.schema.pk}
    cols["__row"] = jnp.arange(rel.capacity, dtype=jnp.int32)
    schema = Schema(pk=rel.schema.pk, columns=tuple(sorted(cols)))
    return Relation(cols, rel.valid, schema)


def _gather_side(rel: Relation, idx: jnp.ndarray, present: jnp.ndarray,
                 columns: Sequence[str], m: float):
    idx = jnp.clip(idx, 0, rel.capacity - 1)
    x = jnp.stack(
        [jnp.asarray(rel.col(c), jnp.float32)[idx] for c in columns], axis=1
    )
    x = jnp.where(present[:, None], x, 0.0)
    if OUTLIER_COL in rel.columns:
        pin = rel.col(OUTLIER_COL).astype(bool)[idx] & present
    else:
        pin = jnp.zeros_like(present)
    w = jnp.where(pin, 1.0, 1.0 / m)
    ompi = jnp.where(pin, 0.0, 1.0 - m)
    return x, present, w, ompi


def build_correspondence_cache(
    clean_sample: Relation, stale_sample: Relation, m: float
) -> CorrespondenceCache:
    """One outer join (Def. 4 row space) → reusable aligned panels.

    RJ = |clean| + |stale| capacities, so the shape is stable across
    refresh windows and the downstream jitted moment pass never retraces.
    """
    columns = sample_columns(clean_sample)
    pk = clean_sample.schema.pk
    joined = ops.outer_join_unique(
        _rows_only(clean_sample), _rows_only(stale_sample),
        on=pk, how="outer", suffixes=("_new", "_old"),
    )
    lp = joined.col("__left_present").astype(bool) & joined.valid
    rp = joined.col("__right_present").astype(bool) & joined.valid
    x_new, valid_new, w_new, ompi_new = _gather_side(
        clean_sample, joined.col("__row_new"), lp, columns, m
    )
    x_old, valid_old, w_old, ompi_old = _gather_side(
        stale_sample, joined.col("__row_old"), rp, columns, m
    )
    return CorrespondenceCache(
        columns=columns,
        x_new=x_new, x_old=x_old,
        valid_new=valid_new, valid_old=valid_old,
        w_new=w_new, w_old=w_old,
        ompi_new=ompi_new, ompi_old=ompi_old,
        m=float(m),
    )


def sample_panel(rel: Relation, columns: Sequence[str], m: float):
    """One-sided (x, valid, w, ompi) panel straight from a sample relation
    — the AQP-only path, which needs no correspondence join at all."""
    x = jnp.stack(
        [jnp.asarray(rel.col(c), jnp.float32) for c in columns], axis=1
    )
    if OUTLIER_COL in rel.columns:
        pin = rel.col(OUTLIER_COL).astype(bool) & rel.valid
    else:
        pin = jnp.zeros_like(rel.valid)
    w = jnp.where(pin, 1.0, 1.0 / m)
    ompi = jnp.where(pin, 0.0, 1.0 - m)
    return x, rel.valid, w, ompi


# ---------------------------------------------------------------------------
# Moment passes
# ---------------------------------------------------------------------------

def _fetch(x) -> np.ndarray:
    """Device-to-host read under a ``fetch`` span: it waits for the device
    work that produces ``x``, so the span separates device waits from the
    host work around them."""
    with obs_trace.span("fetch", bytes=int(x.nbytes)):
        return np.asarray(x)


def panel_moments(cache: CorrespondenceCache, batch: QueryBatch,
                  fused: bool = True, use_pallas: Optional[bool] = None) -> np.ndarray:
    """(12, Q) host moments for a batch over the cached panel."""
    if fused:
        mom = multi_agg_moments(
            cache.x_new, cache.valid_new, cache.w_new, cache.ompi_new,
            batch.sel, batch.meta,
            cache.x_old, cache.valid_old, cache.w_old, cache.ompi_old,
            use_pallas=use_pallas,
        )
        return _fetch(mom)[:, :len(batch)]
    return _moments_per_query(cache, batch)


def _moments_per_query(cache: CorrespondenceCache, batch: QueryBatch) -> np.ndarray:
    """Unfused baseline: one full panel scan PER query instead of one for
    the whole batch.  Each scan goes through the same jitted Q=1 op (the
    (·, 1) shape compiles once and is reused), so the fused-vs-unfused
    benchmark A/B isolates the fusion win, not jit-vs-eager dispatch."""
    Q = len(batch)
    out = np.zeros((12, Q), np.float32)
    for qi in range(Q):
        mom = multi_agg_moments(
            cache.x_new, cache.valid_new, cache.w_new, cache.ompi_new,
            batch.sel[:, qi:qi + 1], batch.meta[:, qi:qi + 1],
            cache.x_old, cache.valid_old, cache.w_old, cache.ompi_old,
            use_pallas=False,
        )
        out[:, qi] = _fetch(mom)[:, 0]
    return out


def exact_batch(view: Relation, batch: QueryBatch,
                use_pallas: Optional[bool] = None) -> np.ndarray:
    """One batched scan of a full view → (Q,) exact sum/count/avg answers."""
    x = jnp.stack(
        [jnp.asarray(view.col(c), jnp.float32) for c in batch.columns], axis=1
    )
    ones = jnp.ones(view.valid.shape, jnp.float32)
    mom = _fetch(
        multi_agg_moments(x, view.valid, ones, jnp.zeros_like(ones),
                          batch.sel, batch.meta, use_pallas=use_pallas)
    )[:, :len(batch)]
    s, k = mom[S_NEW], mom[K_NEW]
    return np.where(batch.is_avg, s / np.maximum(k, 1.0), s)


# ---------------------------------------------------------------------------
# Estimate assembly (§5.1/§5.2 from the sufficient statistics)
# ---------------------------------------------------------------------------

def _var(ss: float, s: float, k: float) -> float:
    """Sample variance from moments: Σ(t−mean)² = Σt² − s²/k (k ≥ 1)."""
    return max(ss - s * s / max(k, 1.0), 0.0) / max(k - 1.0, 1.0)


# When less than this fraction of Σt² survives the mean subtraction, the
# f32 moment-form variance has cancelled away its significant digits (a
# large-mean small-spread column) — fall back to a two-pass Σ(t−mean)²
# over the panel for that query only, matching the per-query estimators.
_CANCEL_EPS = 1e-2


def _ill_conditioned(ss: float, s: float, k: float) -> bool:
    return ss > 0.0 and (ss - s * s / max(k, 1.0)) < _CANCEL_EPS * ss


def _trans_single_side(x, valid, w, batch: QueryBatch, qi: int):
    """(t, mask) of one query on one panel side (the two-pass fallback)."""
    from repro.kernels.multi_agg.ref import _trans_table

    t, mask = _trans_table(
        x, jnp.asarray(valid, bool), w,
        batch.sel[:, qi:qi + 1], batch.meta[:, qi:qi + 1],
    )
    return t[:, 0], mask[:, 0]


def _avg_var_new(cache_or_panel, batch: QueryBatch, qi: int) -> float:
    x, valid, w = cache_or_panel
    t, mask = _trans_single_side(x, valid, w, batch, qi)
    return float(_fetch(_masked_moments(t, mask)[3]))


def _avg_var_diff(cache: CorrespondenceCache, batch: QueryBatch, qi: int) -> float:
    tn, _ = _trans_single_side(cache.x_new, cache.valid_new, cache.w_new, batch, qi)
    to, _ = _trans_single_side(cache.x_old, cache.valid_old, cache.w_old, batch, qi)
    maskd = cache.valid_new | cache.valid_old
    return float(_fetch(_masked_moments(tn - to, maskd)[3]))


def run_batch(
    cache: CorrespondenceCache,
    batch: QueryBatch,
    confidence: float = 0.95,
    prefer: Optional[str] = None,
    materialized: Optional[Relation] = None,
    fused: bool = True,
    use_pallas: Optional[bool] = None,
) -> List[Estimate]:
    """Answer an encoded batch: moments → per-query AQP/CORR estimates.

    ``prefer`` forces the estimator ("corr"/"aqp"); None auto-selects per
    query by the §5.2.2 HT-variance break-even.  ``materialized`` is only
    scanned (one batched pass) when at least one query resolves to CORR.
    """
    with obs_trace.span("moments"):
        mom = panel_moments(cache, batch, fused=fused, use_pallas=use_pallas)
    kn, sn, ssn, htn = mom[K_NEW], mom[S_NEW], mom[SS_NEW], mom[HT_NEW]
    ko, so = mom[K_OLD], mom[S_OLD]
    kd, sd, ssd = mom[K_D], mom[S_D], mom[SS_D]
    # HT_D already excludes the deterministic outlier stratum (§6.3): rows
    # pinned on either side carry ompi = 0 in the cache panels, so the
    # same single scan serves skewed (indexed) views with no fallback
    ht_corr = mom[HT_D]
    if prefer == "corr":
        use_corr = np.ones(len(batch), bool)
    elif prefer == "aqp":
        use_corr = np.zeros(len(batch), bool)
    else:
        use_corr = ht_corr <= htn
    stale = None
    if use_corr.any():
        if materialized is None:
            raise ValueError("CORR queries need the materialized view for q(S)")
        with obs_trace.span("exact_scan"):
            stale = exact_batch(materialized, batch, use_pallas=use_pallas)
    g = _gamma(confidence)
    out: List[Estimate] = []
    with obs_trace.span("assemble") as sp:
        refits = 0
        for i in range(len(batch)):
            if batch.is_avg[i]:
                mean_n = sn[i] / max(kn[i], 1.0)
                if use_corr[i]:
                    mean_o = so[i] / max(ko[i], 1.0)
                    # paired mean-difference variance over the diff table,
                    # scaled by the clean-side predicate count (estimators.py)
                    var_d = _var(ssd[i], sd[i], kd[i])
                    if _ill_conditioned(ssd[i], sd[i], kd[i]):
                        var_d = _avg_var_diff(cache, batch, i)
                        refits += 1
                    stderr = math.sqrt(var_d / max(kn[i], 1.0))
                    value = float(stale[i]) + (mean_n - mean_o)
                    method = "SVC+CORR"
                else:
                    var_n = _var(ssn[i], sn[i], kn[i])
                    if _ill_conditioned(ssn[i], sn[i], kn[i]):
                        var_n = _avg_var_new(
                            (cache.x_new, cache.valid_new, cache.w_new), batch, i
                        )
                        refits += 1
                    stderr = math.sqrt(var_n / max(kn[i], 1.0))
                    value = mean_n
                    method = "SVC+AQP"
            else:
                if use_corr[i]:
                    value = float(stale[i]) + sd[i]
                    stderr = math.sqrt(max(ht_corr[i], 0.0))
                    method = "SVC+CORR"
                else:
                    value = sn[i]
                    stderr = math.sqrt(max(htn[i], 0.0))
                    method = "SVC+AQP"
            value = float(value)
            out.append(
                Estimate(value, float(stderr), value - g * stderr,
                         value + g * stderr, method, confidence)
            )
        sp.set(refits=refits)
    return out


def run_batch_aqp(
    clean_sample: Relation,
    batch: QueryBatch,
    m: float,
    confidence: float = 0.95,
    fused: bool = True,
    use_pallas: Optional[bool] = None,
) -> List[Estimate]:
    """AQP-only batch: one one-sided scan of the clean sample, no
    correspondence join, no stale-view access — the cheapest batch path,
    used by ``ViewManager.query_batch(prefer="aqp")``."""
    with obs_trace.span("moments"):
        x, valid, w, ompi = sample_panel(clean_sample, batch.columns, m)
        if fused:
            mom = _fetch(
                multi_agg_moments(x, valid, w, ompi, batch.sel, batch.meta,
                                  use_pallas=use_pallas)
            )[:, :len(batch)]
        else:
            mom = np.zeros((12, len(batch)), np.float32)
            for qi in range(len(batch)):
                one = multi_agg_moments(
                    x, valid, w, ompi,
                    batch.sel[:, qi:qi + 1], batch.meta[:, qi:qi + 1],
                    use_pallas=use_pallas,
                )
                mom[:, qi] = _fetch(one)[:, 0]
    kn, sn, ssn, htn = mom[K_NEW], mom[S_NEW], mom[SS_NEW], mom[HT_NEW]
    g = _gamma(confidence)
    out: List[Estimate] = []
    with obs_trace.span("assemble") as sp:
        refits = 0
        for i in range(len(batch)):
            if batch.is_avg[i]:
                var_n = _var(ssn[i], sn[i], kn[i])
                if _ill_conditioned(ssn[i], sn[i], kn[i]):
                    var_n = _avg_var_new((x, valid, w), batch, i)
                    refits += 1
                value = sn[i] / max(kn[i], 1.0)
                stderr = math.sqrt(var_n / max(kn[i], 1.0))
            else:
                value = sn[i]
                stderr = math.sqrt(max(htn[i], 0.0))
            value = float(value)
            out.append(
                Estimate(value, float(stderr), value - g * stderr,
                         value + g * stderr, "SVC+AQP", confidence)
            )
        sp.set(refits=refits)
    return out


def variance_report(cache: CorrespondenceCache, batch: QueryBatch,
                    fused: bool = True, use_pallas: Optional[bool] = None) -> dict:
    """Batched §5.2.2 break-even report (variance_comparison's keys, (Q,))."""
    mom = panel_moments(cache, batch, fused=fused, use_pallas=use_pallas)

    def stable(ss, s, k, two_pass):
        return two_pass() if _ill_conditioned(ss, s, k) else _var(ss, s, k)

    var_new = np.array([
        stable(mom[SS_NEW][i], mom[S_NEW][i], mom[K_NEW][i],
               lambda i=i: _avg_var_new((cache.x_new, cache.valid_new, cache.w_new), batch, i))
        for i in range(len(batch))
    ])
    var_old = np.array([
        stable(mom[SS_OLD][i], mom[S_OLD][i], mom[K_OLD][i],
               lambda i=i: _avg_var_new((cache.x_old, cache.valid_old, cache.w_old), batch, i))
        for i in range(len(batch))
    ])
    var_d = np.array([
        stable(mom[SS_D][i], mom[S_D][i], mom[K_D][i],
               lambda i=i: _avg_var_diff(cache, batch, i))
        for i in range(len(batch))
    ])
    ht_aqp = mom[HT_NEW]
    ht_corr = mom[HT_D]
    return {
        "var_aqp": ht_aqp,
        "var_corr": ht_corr,
        "cov": 0.5 * (var_old + var_new - var_d),
        "corr_wins": ht_corr <= ht_aqp,
    }
