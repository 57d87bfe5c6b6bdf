"""Outlier indexing (§6): reduce sampling sensitivity to skew.

An outlier index is a top-k / threshold index over an attribute of a *base*
relation.  It is eligible only if the sampling operator pushes down to that
relation (§6.2).  The index is pushed **up** the expression tree (Def. 5) by
evaluating the view plan with the base relation restricted to the indexed
records; the touched view keys identify the groups that must be maintained
exactly (the γ rule of Def. 5: outlier groups are replaced by their
full-data rows).

Operationally the sample predicate becomes ``hash(key) ≤ m  OR  key ∈
outlier_groups``; rows from outlier groups carry weight 1 and an
``__outlier`` flag, giving precedence to the index so nothing double counts
(§6.2), and the estimators (estimators.py) merge the deterministic stratum
with the sampled stratum exactly as §6.3 prescribes.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.relational.execute import execute_jit
from repro.relational.plan import Plan, plan_pk
from repro.relational.relation import SENTINEL_KEY, Relation, compact, next_pow2


@dataclasses.dataclass
class OutlierIndex:
    """Top-k index over ``attr`` of base relation ``base`` (threshold t).

    Invariant: ``records`` rows are sorted DESCENDING by ``attr`` with
    invalid slots at the end (build and the incremental merge both preserve
    it) — the incremental ``update_outlier_index`` merge relies on it.
    """

    base: str
    attr: str
    capacity: int
    records: Relation  # the indexed base records (≤ capacity valid rows)
    threshold: jnp.ndarray


def build_outlier_index(rel: Relation, base: str, attr: str, k: int) -> OutlierIndex:
    """Single-pass top-k selection (§6.1): keep the k largest by ``attr``."""
    vals = jnp.where(rel.valid, jnp.asarray(rel.col(attr), jnp.float32), -jnp.inf)
    order = jnp.argsort(-vals)  # descending
    take = order[:k]
    cols = {c: v[take] for c, v in rel.columns.items()}
    valid = rel.valid[take]
    records = Relation(cols, valid, rel.schema)
    threshold = jnp.where(jnp.any(valid), jnp.min(jnp.where(valid, vals[take], jnp.inf)), jnp.inf)
    return OutlierIndex(base=base, attr=attr, capacity=k, records=records, threshold=threshold)


def update_outlier_index(
    index: OutlierIndex, delta: Relation, incremental: bool = True
) -> OutlierIndex:
    """Streaming maintenance (§6.1): threshold-gated incremental top-k.

    Deltas are gated against the current top-k threshold first, in
    O(|∂D|): when the index is full, only rows with ``attr`` strictly above
    the threshold can displace a member (an equal value loses the tie to
    the incumbent, matching the rebuild's stable argsort), so a
    sub-threshold micro-batch returns the index unchanged without touching
    it.  Threshold-crossing survivors are sorted (|∂D| log |∂D|, the
    micro-batch — not the index) and merged with the already-descending
    ``records`` by a searchsorted position merge — no full argsort over
    capacity + delta per micro-batch.  ``incremental=False`` runs the seed
    concat-and-rebuild path (benchmark baseline / equivalence oracle).
    """
    if not incremental:
        merged_cols = {
            c: jnp.concatenate([index.records.col(c), delta.col(c)])
            for c in index.records.schema.columns
        }
        merged_valid = jnp.concatenate([index.records.valid, delta.valid])
        merged = Relation(merged_cols, merged_valid, index.records.schema)
        return build_outlier_index(merged, index.base, index.attr, index.capacity)

    gated, n_surv = _topk_gate(
        index.records.valid, delta.valid, delta.col(index.attr),
        index.threshold, index.capacity,
    )
    # one host sync for the early-out, mirroring the row count ingest
    # already pays per micro-batch (DeltaLog.offer)
    if int(n_surv) == 0:
        return index
    merge = _topk_merge_fn(index.attr, index.records.schema.columns, index.capacity)
    cols, valid, threshold = merge(
        dict(index.records.columns), index.records.valid,
        dict(delta.columns), gated,
    )
    records = Relation(cols, valid, index.records.schema)
    return OutlierIndex(
        base=index.base, attr=index.attr, capacity=index.capacity,
        records=records, threshold=threshold,
    )


@functools.partial(jax.jit, static_argnames=("capacity",))
def _topk_gate(rec_valid, delta_valid, delta_vals, threshold, capacity: int):
    """O(|∂D|) threshold gate: (gated vals, survivor count) in ONE compiled
    call — a sub-threshold micro-batch costs this and nothing else."""
    vals = jnp.where(delta_valid, jnp.asarray(delta_vals, jnp.float32), -jnp.inf)
    full = jnp.sum(rec_valid) >= capacity
    gate = delta_valid & jnp.where(full, vals > threshold, True)
    return jnp.where(gate, vals, -jnp.inf), jnp.sum(gate)


@functools.lru_cache(maxsize=256)
def _topk_merge_fn(attr: str, columns: Tuple[str, ...], capacity: int):
    """Compiled bounded merge for one (attr, schema, k): the survivor sort,
    the position merge, the column scatters, and the threshold recompute
    all live in ONE jitted computation (steady micro-batch shapes reuse
    it — the streaming analogue of maintenance's _fused_eval_fn)."""

    def fn(rec_cols, rec_valid, delta_cols, gated_vals):
        K = rec_valid.shape[0]
        S = min(capacity, gated_vals.shape[0])  # over-capacity survivors never place
        T = min(capacity, K + S)  # records may still be growing toward k
        sorder = jnp.argsort(-gated_vals)[:S]
        svals = gated_vals[sorder]
        rvals = jnp.where(rec_valid, jnp.asarray(rec_cols[attr], jnp.float32), -jnp.inf)

        # merge positions of two DESCENDING runs; records win ties (they
        # precede survivors, exactly the rebuild's concatenation order)
        pos_r = jnp.arange(K) + jnp.searchsorted(-svals, -rvals, side="left")
        pos_s = jnp.arange(S) + jnp.searchsorted(-rvals, -svals, side="right")
        out_cols = {}
        for c in columns:
            arena = jnp.zeros((K + S,), rec_cols[c].dtype)
            arena = arena.at[pos_r].set(rec_cols[c])
            arena = arena.at[pos_s].set(
                jnp.asarray(delta_cols[c], rec_cols[c].dtype)[sorder]
            )
            out_cols[c] = arena[:T]
        varena = jnp.zeros((K + S,), bool)
        varena = varena.at[pos_r].set(rec_valid)
        varena = varena.at[pos_s].set(svals > -jnp.inf)
        valid = varena[:T]
        nvals = jnp.where(valid, jnp.asarray(out_cols[attr], jnp.float32), -jnp.inf)
        threshold = jnp.where(
            jnp.any(valid), jnp.min(jnp.where(valid, nvals, jnp.inf)), jnp.inf
        )
        return out_cols, valid, threshold

    return jax.jit(fn)


def propagate_outlier_keys(
    view_plan: Plan, base_env, index: OutlierIndex
) -> Tuple[jnp.ndarray, ...]:
    """Def. 5 push-up: view pk values of rows derived from indexed records.

    Evaluates the view plan with the indexed base relation substituted for
    ``index.base``; returns the touched view keys (the groups that must be
    maintained exactly).
    """
    env = dict(base_env)
    env[index.base] = index.records
    touched = execute_jit(view_plan, env)
    # the touched groups are few but sit in the view's whole group arena;
    # compacted to a pow2 bucket of their count (one host sync), the pin
    # key table stays small enough for kernels/outlier_member's VMEM path
    cap = next_pow2(max(int(np.asarray(touched.valid).sum()), 64))
    touched = compact(touched, min(cap, touched.capacity))
    keys = []
    for kcol in plan_pk(view_plan):
        v = touched.col(kcol)
        keys.append(jnp.where(touched.valid, v, jnp.asarray(SENTINEL_KEY, v.dtype)))
    return tuple(keys)


def member_keys(probe: Tuple[jnp.ndarray, ...], keys: Tuple[jnp.ndarray, ...]) -> jnp.ndarray:
    """probe[i] ∈ keys.

    Single-column keys keep the exact sorted-search fast path (no hashing
    at all).  Composite keys go through kernels/outlier_member: both tuples
    are folded into 64-bit digests with the shared splitmix32 mixer and
    membership resolves by sorted-digest binary search — one fused pass,
    replacing the seed's O(N·K) loop unrolled over the index capacity
    (``member_keys_loop`` below, kept as the A/B baseline and oracle).
    """
    if len(keys) == 1:
        sk = jnp.sort(keys[0])
        pos = jnp.clip(jnp.searchsorted(sk, probe[0]), 0, sk.shape[0] - 1)
        return (sk[pos] == probe[0]) & (probe[0] != SENTINEL_KEY)
    from repro.kernels.outlier_member import ops as _om

    return _om.outlier_member(probe, keys)


def member_keys_loop(probe: Tuple[jnp.ndarray, ...], keys: Tuple[jnp.ndarray, ...]) -> jnp.ndarray:
    """Seed reference path: O(N·K) compare unrolled over the index capacity
    (one dispatch chain per indexed key).  Kept for parity tests and the
    fig8 outlier benchmark baseline — never called on the hot path."""
    hit = jnp.zeros(probe[0].shape, bool)
    for i in range(keys[0].shape[0]):
        row = jnp.ones(probe[0].shape, bool)
        for p, k in zip(probe, keys):
            row = row & (p == k[i])
        hit = hit | row & (probe[0] != SENTINEL_KEY)
    return hit


def flag_outliers(rel: Relation, pin: Relation | None) -> Relation:
    """(Re)compute the view-level ``__outlier`` flag: pk ∈ pin.

    The η push-down applies pin membership at the *base* relations; the flag
    column does not survive aggregation, so samples are re-flagged at the
    view level after cleaning (weights in estimators.py read this column).
    """
    if pin is None:
        return rel
    pin_keys = tuple(
        jnp.where(pin.valid, pin.col(c), jnp.asarray(SENTINEL_KEY, pin.col(c).dtype))
        for c in pin.schema.pk
    )
    probe = tuple(
        jnp.where(rel.valid, rel.col(c), jnp.asarray(SENTINEL_KEY, rel.col(c).dtype))
        for c in rel.schema.pk
    )
    omask = member_keys(probe, pin_keys)
    new_cols = dict(rel.columns)
    new_cols["__outlier"] = (omask & rel.valid).astype(np.int8)
    return Relation(new_cols, rel.valid, rel.schema.with_columns(tuple(new_cols)))


def apply_hash_with_outliers(
    rel: Relation,
    cols: Tuple[str, ...],
    m: float,
    seed: int,
    outlier_keys: Tuple[jnp.ndarray, ...],
) -> Relation:
    """η ∨ outlier-membership; flags pinned rows with __outlier (weight 1).

    One fused scan through kernels/outlier_member: the η hash, the 64-bit
    membership digest, the ``__outlier`` flag, and the validity narrowing
    all come out of a single pass over the key columns — no materialized
    membership intermediate, no per-key dispatch chain.
    """
    from repro.kernels.outlier_member import ops as _om

    probe = tuple(
        jnp.where(rel.valid, rel.col(c), jnp.asarray(SENTINEL_KEY, rel.col(c).dtype))
        for c in cols
    )
    keep, omask = _om.fused_hash_member(probe, m, seed, outlier_keys)
    new_cols = dict(rel.columns)
    new_cols["__outlier"] = (omask & rel.valid).astype(np.int8)
    schema = rel.schema.with_columns(tuple(new_cols))
    return Relation(new_cols, rel.valid & keep, schema)
