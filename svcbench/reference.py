"""The plain reference: SVC's semantics in numpy, independent of the program.

It imports nothing of the system under test and reads only the benchmark's
own data (the base tables and the delta batches it generated) plus the
schedule of which epochs cleaned which view.  From those it rebuilds, for
any point of the run:

* the up-to-date view S' (group-by sums over base + drained deltas) and
  the stale view S (the view at registration, or at its last maintain);
* the hash samples η(S) and η(S'): a group is sampled when the splitmix32
  hash of its key, seeded per view, maps below m (§4.4), or when it is an
  outlier group — a group touched by the top-k base rows of the indexed
  attribute (§6: stable descending order, incumbents win ties);
* every dashboard answer: the SVC+AQP and SVC+CORR estimates with their
  confidence intervals (§5.1–5.2, HT variances; pinned rows carry weight 1
  and no sampling variance, §6.3).

``cast="bf16"`` is the control: every value and predicate column rounded
to bfloat16, the sample moments accumulated in float32 (the stale view's
exact range sums stay float64 prefix sums), the precision step below the
configuration's float32.
"""

from __future__ import annotations

import dataclasses
from statistics import NormalDist
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

from svcbench.datasets import ViewSpec

SEED_GAMMA = 0x9E3779B9
U32 = np.uint32


def _mix(x: np.ndarray) -> np.ndarray:
    """splitmix32 finalizer on uint32 (wrapping arithmetic)."""
    x = x.astype(U32)
    x = x ^ (x >> U32(16))
    x = (x * U32(0x7FEB352D)).astype(U32)
    x = x ^ (x >> U32(15))
    x = (x * U32(0x846CA68B)).astype(U32)
    return x ^ (x >> U32(16))


def eta(keys: np.ndarray, m: float, seed: int) -> np.ndarray:
    """η_{key,m}: True where the key's hash maps below m in float32."""
    h0 = U32((SEED_GAMMA * (int(seed) + 1)) & 0xFFFFFFFF)
    with np.errstate(over="ignore"):
        h = _mix(h0 ^ _mix(keys.astype(np.int64).astype(U32)))
    u = h.astype(np.float32) * np.float32(1.0 / 4294967296.0)
    return u < np.float32(m)


def stable_topk(values: np.ndarray, k: int) -> np.ndarray:
    """Positions of the k largest values, earlier positions first on ties."""
    order = np.argsort(-values.astype(np.float64), kind="stable")
    return order[:k]


def gamma(confidence: float) -> float:
    return NormalDist().inv_cdf((1.0 + confidence) / 2.0)


@dataclasses.dataclass
class Snapshot:
    """One view at one point: group aggregates over its key domain."""

    present: np.ndarray            # (G,) bool: group has rows
    aggs: Dict[str, np.ndarray]    # out name → (G,) float64


class ViewState:
    """Incremental group-by of one view over base + deltas in order."""

    def __init__(self, spec: ViewSpec, base: Dict[str, np.ndarray],
                 dim_keys: Optional[np.ndarray]):
        self.spec = spec
        G = spec.groups
        self.member = None
        if dim_keys is not None:
            self.member = np.zeros(G, bool)
            self.member[dim_keys[dim_keys < G]] = True
        self.count = np.zeros(G, np.float64)
        self.sums = {out: np.zeros(G, np.float64)
                     for out, fn, col in spec.aggs if fn == "sum"}
        self.add(base)

    def add(self, cols: Dict[str, np.ndarray]) -> None:
        k = cols[self.spec.key].astype(np.int64)
        keep = k < self.spec.groups
        if self.member is not None:
            keep &= self.member[np.minimum(k, self.spec.groups - 1)]
        k = k[keep]
        G = self.spec.groups
        self.count += np.bincount(k, minlength=G)
        for out, fn, col in self.spec.aggs:
            if fn == "sum":
                self.sums[out] += np.bincount(
                    k, weights=cols[col][keep].astype(np.float64), minlength=G)

    def snapshot(self) -> Snapshot:
        aggs = {}
        for out, fn, _col in self.spec.aggs:
            aggs[out] = (self.count.copy() if fn == "count"
                         else self.sums[out].copy())
        return Snapshot(self.count > 0, aggs)


class OutlierPins:
    """The top-k index of one base attribute and the groups it pins."""

    def __init__(self, spec: ViewSpec, base: Dict[str, np.ndarray]):
        attr, k = spec.outlier
        self.attr, self.k, self.key = attr, int(k), spec.key
        top = stable_topk(base[attr], self.k)
        self.vals = base[attr][top].astype(np.float32)
        self.keys = base[self.key][top].astype(np.int64)

    def merge(self, batches: Sequence[Dict[str, np.ndarray]]) -> None:
        if not batches:
            return
        vals = np.concatenate([self.vals] + [b[self.attr] for b in batches])
        keys = np.concatenate([self.keys] + [b[self.key] for b in batches])
        top = stable_topk(vals.astype(np.float32), self.k)
        self.vals, self.keys = vals[top].astype(np.float32), keys[top]

    def groups(self) -> np.ndarray:
        return np.unique(self.keys)


@dataclasses.dataclass
class Sample:
    """A hash sample: sampled group keys, their aggregates and pin flags."""

    keys: np.ndarray
    aggs: Dict[str, np.ndarray]
    pinned: np.ndarray


def sample_of(snap: Snapshot, sampled: np.ndarray, pins: np.ndarray,
              flag_pins: np.ndarray) -> Sample:
    """Rows of ``snap`` whose key is η-sampled or in ``pins``; the flag
    marks membership in ``flag_pins`` (the current pin set)."""
    G = snap.present.shape[0]
    pin_mask = np.zeros(G, bool)
    pin_mask[pins[pins < G]] = True
    flag = np.zeros(G, bool)
    flag[flag_pins[flag_pins < G]] = True
    keys = np.flatnonzero(snap.present & (sampled | pin_mask))
    return Sample(keys, {c: v[keys] for c, v in snap.aggs.items()}, flag[keys])


@dataclasses.dataclass(frozen=True)
class QuerySpec:
    agg: str                 # sum | count | avg
    col: Optional[str]       # value column (None for count)
    lo: float                # pred_col ≥ lo
    hi: float                # pred_col ≤ hi


def _cast(x: np.ndarray, cast: Optional[str]) -> np.ndarray:
    if cast is None:
        return x.astype(np.float64)
    import ml_dtypes

    return x.astype(ml_dtypes.bfloat16).astype(np.float32)


def _acc(cast: Optional[str]):
    return np.float64 if cast is None else np.float32


def estimates(clean: Sample, stale: Sample, stale_view: Snapshot,
              pred_col: str, queries: Sequence[QuerySpec], m: float,
              confidence: float, cast: Optional[str] = None,
              chunk: int = 16) -> Dict[str, np.ndarray]:
    """SVC+AQP and SVC+CORR estimates of every query, float64 (or the
    bf16 control).  Returns arrays of length Q: ``aqp``/``corr`` values,
    ``aqp_hw``/``corr_hw`` CI half-widths, ``ht_aqp``/``ht_corr`` (the
    §5.2.2 break-even inputs)."""
    acc = _acc(cast)
    keys = np.union1d(clean.keys, stale.keys)
    pos_n = np.searchsorted(keys, clean.keys)
    pos_o = np.searchsorted(keys, stale.keys)
    R = keys.shape[0]

    def side(s: Sample, pos):
        present = np.zeros(R, bool)
        present[pos] = True
        w = np.full(R, 1.0 / m, acc)
        ompi = np.full(R, 1.0 - m, acc)
        w[pos[s.pinned]] = 1.0
        ompi[pos[s.pinned]] = 0.0
        cols = {}
        for c, v in s.aggs.items():
            x = np.zeros(R, acc)
            x[pos] = _cast(v, cast)
            cols[c] = x
        return present, w, ompi, cols

    pn, wn, on, xn = side(clean, pos_n)
    po, wo, oo, xo = side(stale, pos_o)
    od = np.minimum(on, oo)
    joined = (pn | po).sum()
    # the stale view's exact answers: its present groups sorted by the
    # predicate column, with float64 prefix sums, so a range is two
    # binary searches (ties and bounds as ``lo <= pred <= hi``)
    sv = {c: _cast(v, cast)[stale_view.present] for c, v in stale_view.aggs.items()}
    order = np.argsort(sv[pred_col], kind="stable")
    spred = sv[pred_col][order]
    prefix = {c: np.concatenate([[0.0], np.cumsum(v[order], dtype=np.float64)])
              for c, v in sv.items()}
    g = gamma(confidence)

    Q = len(queries)
    out = {k: np.zeros(Q) for k in ("aqp", "corr", "aqp_hw", "corr_hw",
                                    "ht_aqp", "ht_corr")}
    for q0 in range(0, Q, chunk):
        qs = queries[q0:q0 + chunk]
        lo = np.array([q.lo for q in qs], np.float32).astype(acc)
        hi = np.array([q.hi for q in qs], np.float32).astype(acc)
        is_avg = np.array([q.agg == "avg" for q in qs])
        is_cnt = np.array([q.agg == "count" for q in qs])

        def trans(present, w, cols):
            pv = cols[pred_col][:, None]
            cond = present[:, None] & (pv >= lo) & (pv <= hi)
            v = np.stack([np.ones(R, acc) if q.col is None else cols[q.col]
                          for q in qs], axis=1)
            v = np.where(is_cnt, 1.0, v)
            weff = np.where(is_avg, 1.0, w[:, None])
            t = np.where(cond, v, 0.0).astype(acc) * weff
            mask = np.where(is_avg, cond, present[:, None])
            return t.astype(acc), mask

        tn, mn = trans(pn, wn, xn)
        to, mo = trans(po, wo, xo)
        kn, sn = mn.sum(0), tn.sum(0, dtype=acc)
        ssn = (tn * tn).sum(0, dtype=acc)
        htn = (on[:, None] * tn * tn).sum(0, dtype=acc)
        ko, so = mo.sum(0), to.sum(0, dtype=acc)
        d = tn - to
        sd = d.sum(0, dtype=acc)
        ssd = (d * d).sum(0, dtype=acc)
        htd = (od[:, None] * d * d).sum(0, dtype=acc)

        first = np.searchsorted(spred, lo, side="left")
        last = np.searchsorted(spred, hi, side="right")
        for j, q in enumerate(qs):
            i = q0 + j
            a, b = first[j], last[j]
            if q.agg == "count":
                exact = float(b - a)
            else:
                s = float(prefix[q.col][b] - prefix[q.col][a])
                exact = s / max(float(b - a), 1.0) if q.agg == "avg" else s
            if q.agg == "avg":
                mean_n = sn[j] / max(kn[j], 1.0)
                mean_o = so[j] / max(ko[j], 1.0)
                var_n = max(ssn[j] - sn[j] ** 2 / max(kn[j], 1.0), 0.0) / max(kn[j] - 1.0, 1.0)
                var_d = max(ssd[j] - sd[j] ** 2 / max(joined, 1), 0.0) / max(joined - 1.0, 1.0)
                out["aqp"][i] = mean_n
                out["aqp_hw"][i] = g * np.sqrt(var_n / max(kn[j], 1.0))
                out["corr"][i] = exact + (mean_n - mean_o)
                out["corr_hw"][i] = g * np.sqrt(var_d / max(kn[j], 1.0))
            else:
                out["aqp"][i] = sn[j]
                out["aqp_hw"][i] = g * np.sqrt(max(htn[j], 0.0))
                out["corr"][i] = exact + sd[j]
                out["corr_hw"][i] = g * np.sqrt(max(htd[j], 0.0))
            out["ht_aqp"][i] = htn[j]
            out["ht_corr"][i] = htd[j]
    return out


def value_gap(got: float, ref: float) -> float:
    return abs(got - ref) / max(abs(ref), 1.0)


def hw_gap(got: float, ref: float, value: float) -> float:
    return abs(got - ref) / max(ref, 1e-9 * max(abs(value), 1.0))


def near_tie(ht_aqp: float, ht_corr: float, rtol: float = 1e-3) -> bool:
    """The §5.2.2 choice sits within rounding of its break-even."""
    return abs(ht_aqp - ht_corr) <= rtol * max(abs(ht_aqp), abs(ht_corr), 1e-30)


def sample_as_columns(s: Sample, key: str, cast: Optional[str] = None
                      ) -> Dict[str, np.ndarray]:
    """A reference sample in the layout of the program's ``to_host`` rows
    (the control puts this, rounded to bf16, in the program's place)."""
    cols = {key: s.keys.copy(), "__outlier": s.pinned.astype(np.int8)}
    for c, v in s.aggs.items():
        cols[c] = _cast(v, cast)
    return cols


def compare_sample(got: Dict[str, np.ndarray], key: str, ref: Sample
                   ) -> Tuple[int, float]:
    """(mismatched rows, max relative value gap) of a clean sample against
    the reference's: rows whose key is on one side only, or whose outlier
    flag differs, count as mismatched; values are compared on the rest."""
    gk = got[key].astype(np.int64)
    order = np.argsort(gk)
    gk = gk[order]
    common, gi, ri = np.intersect1d(gk, ref.keys, assume_unique=True,
                                    return_indices=True)
    mismatched = int(gk.shape[0] + ref.keys.shape[0] - 2 * common.shape[0])
    flags = got.get("__outlier")
    if flags is not None:
        mismatched += int(np.sum(flags[order][gi].astype(bool) != ref.pinned[ri]))
    elif ref.pinned.any():
        mismatched += int(ref.pinned[ri].sum())
    gap = 0.0
    for c, rv in ref.aggs.items():
        gv = got[c][order][gi].astype(np.float64)
        if gv.size:
            gap = max(gap, float(np.max(np.abs(gv - rv[ri]) /
                                        np.maximum(np.abs(rv[ri]), 1.0))))
    return mismatched, gap
