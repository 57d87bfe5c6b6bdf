"""Maintenance strategies M and sample cleaning C (§3, §4.5).

A *maintenance strategy* is a relational plan whose leaves are the stale
view and the delta relations; executing it yields the up-to-date view
S' = M(S, D, ∂D).  ``cleaning_plan`` derives the optimized expression
C = pushdown(η_pk,m(M)) that materializes the up-to-date *sample*
Ŝ' = C(Ŝ, D, ∂D) — Problem 1.

The concrete strategy implemented is the change-table / delta-table method
of Gupta & Mumick [22,23] used by the paper's experiments: apply the view
definition to the deltas, full-outer-join the delta view onto the stale
view on the group key, and merge aggregates with generalized projection
(Example 1).  Insertions add, deletions subtract; sum/count (and avg via
sum/count) are fully maintainable, min/max only under insert-only deltas.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.pushdown import push_down
from repro.relational import ops
from repro.relational.expr import Bin, Col, Lit
from repro.relational.plan import (
    FKJoin,
    GroupByNode,
    HashNode,
    OuterJoin,
    Plan,
    ProjectNode,
    Scan,
    plan_pk,
    substitute,
)
from repro.relational.execute import execute, execute_jit
from repro.relational.relation import Relation, compact, next_pow2


INS = "__ins"
DEL = "__del"


@dataclasses.dataclass(frozen=True)
class ViewDef:
    """A named materialized view: its defining plan over base relations."""

    name: str
    plan: Plan

    @property
    def pk(self) -> Tuple[str, ...]:
        return plan_pk(self.plan)


@dataclasses.dataclass
class DeltaSet:
    """∂D: per-base-relation insert and delete relations."""

    inserts: Dict[str, Relation] = dataclasses.field(default_factory=dict)
    deletes: Dict[str, Relation] = dataclasses.field(default_factory=dict)

    def is_empty(self) -> bool:
        return not self.inserts and not self.deletes


# ---------------------------------------------------------------------------
# Change-table strategy construction
# ---------------------------------------------------------------------------

def change_table_strategy(
    view: ViewDef,
    delta_bases: Tuple[str, ...],
    delta_group_capacity: int,
    with_deletes: bool = False,
) -> Plan:
    """Build M for a group-by-aggregate view (Example 1 generalized).

    ``delta_bases``: names of base relations receiving deltas (e.g. the fact
    table).  The returned plan's leaves are Scan(view.name) plus
    Scan(base + "__ins") / Scan(base + "__del").
    """
    g = _find_groupby(view.plan)
    if g is None:
        raise ValueError("change-table strategy requires a group-by aggregate view")
    keys = g.keys
    agg_names = tuple(out for out, _, _ in g.aggs)
    for _, fn, _ in g.aggs:
        if fn not in ("sum", "count") and with_deletes:
            raise ValueError(f"agg {fn!r} is not self-maintainable under deletes")

    def delta_view(suffix: str) -> Plan:
        mapping = {b: b + suffix for b in delta_bases}
        return _replace_groupby_capacity(substitute(view.plan, mapping), delta_group_capacity)

    plan: Plan = Scan(view.name, pk=keys)
    plan = _merge_delta(plan, delta_view(INS), keys, agg_names, sign=+1, tag="_ins")
    if with_deletes:
        plan = _merge_delta(plan, delta_view(DEL), keys, agg_names, sign=-1, tag="_del")
    return plan


def _merge_delta(
    stale: Plan, delta: Plan, keys: Tuple[str, ...], agg_names: Tuple[str, ...], sign: int, tag: str
) -> Plan:
    suffixes = ("", tag)
    joined = OuterJoin(left=stale, right=delta, on=keys, how="outer", suffixes=suffixes)
    outputs = [(k, k) for k in keys]
    for a in agg_names:
        d = Col(a + tag)
        if sign > 0:
            e = Bin("add", Col(a), d)
        else:
            e = Bin("sub", Col(a), d)
        outputs.append((a, e))
    return ProjectNode(child=joined, outputs=tuple(outputs), pk=keys)


def _find_groupby(p: Plan) -> Optional[GroupByNode]:
    if isinstance(p, GroupByNode):
        return p
    for f in dataclasses.fields(p):
        v = getattr(p, f.name)
        if isinstance(v, Plan):
            g = _find_groupby(v)
            if g is not None:
                return g
    return None


def _replace_groupby_capacity(p: Plan, cap: int) -> Plan:
    if isinstance(p, GroupByNode):
        return GroupByNode(
            child=_replace_groupby_capacity(p.child, cap),
            keys=p.keys,
            aggs=p.aggs,
            num_groups=cap,
        )
    if isinstance(p, Scan):
        return p
    kw = {}
    for f in dataclasses.fields(p):
        v = getattr(p, f.name)
        kw[f.name] = _replace_groupby_capacity(v, cap) if isinstance(v, Plan) else v
    return type(p)(**kw)


# ---------------------------------------------------------------------------
# Problem 1: stale sample view cleaning
# ---------------------------------------------------------------------------

def cleaning_plan(
    strategy: Plan, view_pk: Tuple[str, ...], m: float, seed: int = 0,
    pin_name: Optional[str] = None,
) -> Plan:
    """C = pushdown( η_{pk,m}(M) ) — Theorem 1 guarantees sample identity.

    ``pin_name`` threads the outlier-index pin set (Def. 5) through the η.
    """
    return push_down(
        HashNode(child=strategy, cols=tuple(view_pk), m=m, seed=seed, pin_name=pin_name)
    )


def delta_env(view_name: str, view_rel: Relation, deltas: DeltaSet) -> Dict[str, Relation]:
    env = {view_name: view_rel}
    for b, rel in deltas.inserts.items():
        env[b + INS] = rel
    for b, rel in deltas.deletes.items():
        env[b + DEL] = rel
    return env


def full_maintenance(
    strategy: Plan, view_name: str, stale_view: Relation, deltas: DeltaSet,
    extra_env: Optional[Mapping[str, Relation]] = None,
    out_capacity: Optional[int] = None,
) -> Relation:
    """IVM baseline: S' = M(S, D, ∂D), compacted to capacity."""
    env = delta_env(view_name, stale_view, deltas)
    if extra_env:
        env.update(extra_env)
    out = execute_jit(strategy, env, name=view_name)
    return compact(out, out_capacity or stale_view.capacity)


def _compact_eta_leaves(plan: Plan, env, m: float, slack: float = 4.0):
    """§Perf hillclimb C.3: materialize η(delta-leaf) COMPACTED.

    After push-down the η sits directly above the delta Scans; every
    downstream sort/join/γ still runs at the delta's full capacity.  Eagerly
    evaluating the η leaf and compacting to an m-scaled arena makes the
    expensive stages run at sample capacity — the paper's I/O saving
    realized as a capacity saving (the TPU-relevant resource)."""
    from repro.relational.plan import HashNode, Scan
    import dataclasses as _dc

    env = dict(env)

    def walk(p: Plan) -> Plan:
        if isinstance(p, HashNode) and isinstance(p.child, Scan):
            name = p.child.name
            if name.endswith(INS) or name.endswith(DEL):
                rel = env[name]
                filtered = execute_jit(p, env)
                cap = _next_pow2_int(max(64, int(rel.capacity * m * slack)))
                if cap < rel.capacity:
                    new_name = name + "__eta"
                    env[new_name] = compact(filtered, cap)
                    return Scan(new_name, pk=p.child.pk)
            return p
        if isinstance(p, Scan):
            return p
        kw = {}
        for f in _dc.fields(p):
            v = getattr(p, f.name)
            kw[f.name] = walk(v) if isinstance(v, Plan) else v
        return type(p)(**kw)

    return walk(plan), env


def _next_pow2_int(n: int) -> int:
    return next_pow2(n)


# ---------------------------------------------------------------------------
# Fused delta aggregation (kernels/fused_clean dispatch)
# ---------------------------------------------------------------------------

# Largest dense-key accumulator the fused path will allocate; sparse key
# domains beyond this fall back to the sort-based plan executor.
MAX_FUSED_GROUPS = 1 << 20

_FUSED_DEFAULT = True


def use_fused(flag: bool) -> None:
    """Toggle the fused clean_sample dispatch globally (benchmarks A/B it)."""
    global _FUSED_DEFAULT
    _FUSED_DEFAULT = bool(flag)


@dataclasses.dataclass(frozen=True)
class _FusedSpec:
    """A groupby-sum/count over η-filtered delta rows, fusable in one pass."""

    node: "GroupByNode"
    fact_name: str  # env name of the delta relation (η already below it)
    key: str  # single int group-key column (dense ids < num_groups)
    m: float
    seed: int
    pin_name: Optional[str]
    dim_name: Optional[str] = None  # FK dim relation filtering fact rows
    dim_key: Optional[str] = None
    fact_key: Optional[str] = None


def _match_fused_groupby(p: Plan, env: Mapping[str, Relation]) -> Optional[_FusedSpec]:
    """Does ``p`` have the canonical SVC delta-aggregation shape?

    GroupByNode(single int key; sum/count aggs over plain fact columns)
    over either η(Scan(delta)) or FKJoin(η(Scan(delta)), dim).  The dim-side
    η the push-down adds in the equality case is subsumed by the fact-side η
    (same cols/m/seed after the join-key rename), so the fused path probes
    the unfiltered dim.
    """
    if not isinstance(p, GroupByNode) or len(p.keys) != 1:
        return None
    key = p.keys[0]
    for _out, fn, val in p.aggs:
        if fn not in ("sum", "count"):
            return None
        if fn == "sum" and not isinstance(val, str):
            return None

    child = p.child
    dim_name = dim_key = fact_key = None
    if isinstance(child, FKJoin):
        fact_side, dim_side = child.fact, child.dim
        dim_inner = dim_side.child if isinstance(dim_side, HashNode) else dim_side
        if not isinstance(dim_inner, Scan):
            return None
        dim_key = child.dim_key or (dim_inner.pk[0] if len(dim_inner.pk) == 1 else None)
        if dim_key is None:
            return None
        if isinstance(dim_side, HashNode):
            # dropping the dim-side η is only sound in the push-down equality
            # case: the dim hash is on the join key, the group key IS the
            # join key, and both sides hash identically — then a kept fact
            # row's dim partner passes the same predicate on the same value.
            if not isinstance(fact_side, HashNode):
                return None
            if key != child.fact_key or dim_side.cols != (dim_key,):
                return None
            if (dim_side.m, dim_side.seed, dim_side.pin_name) != (
                fact_side.m, fact_side.seed, fact_side.pin_name
            ):
                return None
        dim_name = dim_inner.name
        fact_key = child.fact_key
        child = fact_side
    if not (isinstance(child, HashNode) and isinstance(child.child, Scan)
            and child.cols == (key,)):
        return None
    fact_name = child.child.name
    fact = env.get(fact_name)
    if fact is None:
        return None
    needed = {key} | {val for _o, fn, val in p.aggs if fn == "sum"}
    if fact_key is not None:
        needed.add(fact_key)
    if not needed <= set(fact.schema.columns):
        return None
    if fact.col(key).dtype != jnp.int32:
        return None
    return _FusedSpec(
        node=p, fact_name=fact_name, key=key, m=child.m, seed=child.seed,
        pin_name=child.pin_name, dim_name=dim_name, dim_key=dim_key,
        fact_key=fact_key,
    )


def _assemble_fused_output(spec: _FusedSpec, num_groups: int,
                           counts: jnp.ndarray, sums: jnp.ndarray) -> Relation:
    """(counts, sums) → the materialized delta-view relation.

    The ONE assembly both fused paths share (per-view ``_fused_eval_fn``
    and the fleet's ``_fleet_assemble_fn``), so batched and sequential
    refreshes emit identical relations by construction.  Compacts to the
    group-by's static capacity: stable shapes ⇒ the compiled merge
    remainder is reused across refreshes."""
    from repro.relational.relation import SENTINEL_KEY, from_columns

    group_valid = counts > 0
    key_vals = jnp.where(
        group_valid, jnp.arange(num_groups, dtype=jnp.int32), SENTINEL_KEY
    )
    out_cols = {spec.key: key_vals}
    i = 0
    for out, fn_name, _val in spec.node.aggs:
        if fn_name == "count":
            out_cols[out] = counts
        else:
            out_cols[out] = sums[:, i]
            i += 1
    rel = from_columns(out_cols, pk=(spec.key,), valid=group_valid)
    return compact(rel, spec.node.num_groups)


@functools.lru_cache(maxsize=256)
def _fused_eval_fn(spec: _FusedSpec, num_groups: int):
    """Compiled fused evaluation for one spec + key-domain bound: join-hit
    filter, pin membership, the fused η+γ pass, and output-relation assembly
    all live in ONE jitted computation (steady-state refreshes reuse it)."""
    from repro.core.outliers import member_keys
    from repro.kernels.fused_clean.ops import fused_clean_groupby
    from repro.relational.relation import SENTINEL_KEY

    sum_cols = tuple(val for _o, fn, val in spec.node.aggs if fn == "sum")

    def fn(fact: Relation, dim: Optional[Relation], pin: Optional[Relation]) -> Relation:
        keys = fact.col(spec.key)
        valid = fact.valid
        if dim is not None:
            probe = jnp.where(
                valid, fact.col(spec.fact_key),
                jnp.asarray(SENTINEL_KEY, fact.col(spec.fact_key).dtype),
            )
            _src, hit = ops.fk_hit(dim, spec.dim_key, probe)
            valid = valid & hit
        pin_mask = None
        if pin is not None:
            pin_keys = tuple(
                jnp.where(pin.valid, pin.col(c), jnp.asarray(SENTINEL_KEY, pin.col(c).dtype))
                for c in pin.schema.pk
            )
            probe = (jnp.where(valid, keys, jnp.asarray(SENTINEL_KEY, keys.dtype)),)
            pin_mask = member_keys(probe, pin_keys)

        vals = (
            jnp.stack([fact.col(c).astype(jnp.float32) for c in sum_cols], axis=1)
            if sum_cols else jnp.zeros((keys.shape[0], 0), jnp.float32)
        )
        counts, sums = fused_clean_groupby(
            keys, vals, valid, spec.m, spec.seed, num_groups, pin_mask=pin_mask
        )
        return _assemble_fused_output(spec, num_groups, counts, sums)

    return jax.jit(fn)


def _eval_fused_groupby(spec: _FusedSpec, env: Mapping[str, Relation]) -> Optional[Relation]:
    """One fused pass over the delta rows → the delta-view relation.

    Returns None when the key domain is unbounded (falls back to the plan
    executor); the single host sync for the bound mirrors the one ingest
    already pays for delta bucketing.
    """
    fact = env[spec.fact_name]
    keys = fact.col(spec.key)
    lo, hi = np.asarray(jnp.stack([
        jnp.min(jnp.where(fact.valid, keys, np.iinfo(np.int32).max)),
        jnp.max(jnp.where(fact.valid, keys, -1)),
    ]))  # one host sync for both bounds
    if int(lo) < 0:  # negative keys never land in the dense accumulator —
        return None  # the unfused executor handles them; fall back
    num_groups = _next_pow2_int(max(int(hi) + 1, 64))
    if num_groups > MAX_FUSED_GROUPS:
        return None
    dim = env[spec.dim_name] if spec.dim_name is not None else None
    pin = env.get(spec.pin_name) if spec.pin_name is not None else None
    return _fused_eval_fn(spec, num_groups)(fact, dim, pin)


def _fused_scan_name(spec: _FusedSpec) -> str:
    """Deterministic, collision-safe env name for a spliced delta view.

    Every field that shapes the fused result participates, so two fusable
    group-bys over the SAME delta leaf (different keys/aggs/dim/η) get
    distinct names instead of silently sharing one env slot; determinism
    per spec keeps the compiled merge remainder reusable across refreshes.
    """
    aggs = "_".join(f"{o}.{fn}.{val}" for o, fn, val in spec.node.aggs)
    parts = (
        spec.fact_name, spec.key, aggs, str(spec.node.num_groups),
        str(spec.dim_name), str(spec.fact_key),
        repr(spec.m), str(spec.seed), str(spec.pin_name),
    )
    return "__fused__" + "__".join(parts)


def collect_fused_specs(plan: Plan, env: Mapping[str, Relation]):
    """The fusable delta-aggregation sub-trees of a pushed cleaning plan.

    Same walk as ``fuse_delta_groupbys`` but evaluation-free: callers (the
    fleet refresh path) use the returned specs to batch the expensive η+γ
    stage across views before splicing the results back in via the
    ``precomputed`` argument."""
    out = []

    def walk(p: Plan) -> None:
        spec = _match_fused_groupby(p, env)
        if spec is not None:
            out.append(spec)
            return
        for f in dataclasses.fields(p):
            v = getattr(p, f.name)
            if isinstance(v, Plan):
                walk(v)

    walk(plan)
    return out


def fuse_delta_groupbys(plan: Plan, env: Mapping[str, Relation],
                        precomputed: Optional[Mapping["_FusedSpec", Relation]] = None):
    """Splice fused-kernel results in place of fusable delta aggregations.

    Walks the pushed cleaning plan; every sub-tree matching the canonical
    η+γ shape is evaluated by ``kernels/fused_clean`` and replaced with a
    Scan of the materialized delta view, leaving only the cheap outer-join
    merge for the plan executor.  Returns (plan, env) unchanged when nothing
    qualifies.  Replacement Scan names are a deterministic function of the
    fused spec (_fused_scan_name), so steady-state refreshes reuse the
    compiled merge remainder and distinct group-bys over one delta leaf
    never collide.

    ``precomputed`` maps specs to already-evaluated delta-view relations
    (the fleet refresh path batches many views' aggregations into one
    dispatch first); matching specs splice those instead of re-evaluating.
    """
    new_env = dict(env)
    fused_any = False

    def walk(p: Plan) -> Plan:
        nonlocal fused_any
        spec = _match_fused_groupby(p, new_env)
        if spec is not None:
            rel = None if precomputed is None else precomputed.get(spec)
            if rel is None:
                rel = _eval_fused_groupby(spec, new_env)
            if rel is not None:
                name = _fused_scan_name(spec)
                new_env[name] = rel
                fused_any = True
                return Scan(name, pk=(spec.key,))
            return p
        if isinstance(p, Scan):
            return p
        kw = {}
        for f in dataclasses.fields(p):
            v = getattr(p, f.name)
            kw[f.name] = walk(v) if isinstance(v, Plan) else v
        return type(p)(**kw)

    new_plan = walk(plan)
    return (new_plan, new_env) if fused_any else (plan, env)


# ---------------------------------------------------------------------------
# Fleet-batched delta aggregation (the epoch refresh path)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=256)
def _fleet_assemble_fn(spec: _FusedSpec, num_groups: int):
    """Compiled per-view slice assembly for the fleet path — the same
    ``_assemble_fused_output`` the per-view jit runs."""

    def fn(counts: jnp.ndarray, sums: jnp.ndarray) -> Relation:
        return _assemble_fused_output(spec, num_groups, counts, sums)

    return jax.jit(fn)


def _fleet_fused_counts(entries, min_group: int = 2):
    """Batched fused η+γ over many delta relations → raw dense accumulators.

    ``entries`` is a list of (entry_id, fact, spec).  Entries are grouped
    by the stacked dispatch shape — delta arena capacity × value-column
    count — and every group of ≥ ``min_group`` runs as ONE compiled
    ``kernels/fused_clean.fused_clean_groupby_fleet`` call with per-entry
    sampling thresholds and seeds.  Entries whose key domain is unbounded
    (negative keys, or past MAX_FUSED_GROUPS) are excluded — one wide-key
    entry must not knock its shape-mates off the batched path; survivors'
    shared pow2 bound is ≤ MAX_FUSED_GROUPS by construction.

    Returns {entry_id: (counts (num_groups,), sums (num_groups, n_sum),
    num_groups)} for entries that ran; callers fall back for the rest.
    """
    from repro.kernels.fused_clean.ops import fused_clean_groupby_fleet

    groups: Dict[Tuple[int, int], list] = {}
    for eid, fact, spec in entries:
        sum_cols = tuple(val for _o, fn, val in spec.node.aggs if fn == "sum")
        groups.setdefault((fact.capacity, len(sum_cols)), []).append(
            (eid, fact, spec, sum_cols)
        )

    out = {}
    for (_cap, n_sum), members in groups.items():
        if len(members) < min_group:
            continue
        # one host sync for every member's key bounds (the per-view path
        # pays one sync per view here)
        bounds = np.asarray(jnp.stack([
            jnp.stack([
                jnp.min(jnp.where(fact.valid, fact.col(spec.key),
                                  np.iinfo(np.int32).max)),
                jnp.max(jnp.where(fact.valid, fact.col(spec.key), -1)),
            ])
            for _n, fact, spec, _sc in members
        ]))
        keep = [
            i for i in range(len(members))
            if int(bounds[i, 0]) >= 0
            and _next_pow2_int(max(int(bounds[i, 1]) + 1, 64)) <= MAX_FUSED_GROUPS
        ]
        if len(keep) < min_group:
            continue
        hi = max(int(bounds[i, 1]) for i in keep)
        num_groups = _next_pow2_int(max(hi + 1, 64))
        sel = [members[i] for i in keep]
        gid = jnp.stack([fact.col(spec.key) for _n, fact, spec, _sc in sel])
        valid = jnp.stack([fact.valid for _n, fact, _s, _sc in sel])
        vals = jnp.stack([
            jnp.stack([fact.col(c).astype(jnp.float32) for c in sc], axis=1)
            if sc else jnp.zeros((fact.capacity, 0), jnp.float32)
            for _n, fact, _s, sc in sel
        ])
        counts, sums = fused_clean_groupby_fleet(
            gid, vals, valid,
            ms=tuple(spec.m for _n, _f, spec, _sc in sel),
            seeds=tuple(spec.seed for _n, _f, spec, _sc in sel),
            num_groups=num_groups,
        )
        for i, (eid, _fact, _spec, _sc) in enumerate(sel):
            out[eid] = (counts[i], sums[i], num_groups)
    return out


def fleet_eval_fused_groupbys(candidates) -> Dict[str, Dict[_FusedSpec, Relation]]:
    """Batch many views' η+γ delta aggregations into shared fused dispatches.

    ``candidates`` is a list of (view_name, env, spec) with exactly one
    pin-free, dim-free fused spec per view.  Thin wrapper over
    ``_fleet_fused_counts`` (≥2 per shape group; singletons and unbounded
    key domains take the per-view path) that assembles each member's
    delta-view relation the same way the per-view jit does.  Returns
    {view_name: {spec: delta-view Relation}} for the views that batched.
    """
    raw = _fleet_fused_counts(
        [(name, env[spec.fact_name], spec) for name, env, spec in candidates],
        min_group=2,
    )
    out: Dict[str, Dict[_FusedSpec, Relation]] = {}
    for name, _env, spec in candidates:
        got = raw.get(name)
        if got is None:
            continue
        counts, sums, num_groups = got
        out[name] = {spec: _fleet_assemble_fn(spec, num_groups)(counts, sums)}
    return out


# ---------------------------------------------------------------------------
# Fleet-batched merge remainder (kernels/fleet_merge dispatch)
# ---------------------------------------------------------------------------

def _cap_group_validity(counts: jnp.ndarray, cap: int) -> jnp.ndarray:
    """Which dense delta groups survive ``_assemble_fused_output``'s compact.

    The per-view path materializes the dense accumulator as a relation and
    compacts it to the group-by's static capacity; when more than ``cap``
    groups are live, compact's key-ascending truncation keeps the ``cap``
    LOWEST-keyed ones.  Reproducing that drop here keeps the batched merge
    bit-equal to the per-view path even in overflow."""
    nz = counts > 0
    rank = jnp.cumsum(nz.astype(jnp.int32))
    return nz & (rank <= cap)


@dataclasses.dataclass
class _MergeJob:
    """One view's inputs to the fleet-batched merge remainder.

    ``stale_*`` come from the view panel's merge slot (common padded Rp
    across the fleet, SENTINEL keys / zero values on invalid rows);
    ``ins``/``dele`` are (delta fact, fused spec) pairs whose aggregations
    ``fleet_clean_merge`` batches before the single merge dispatch."""

    name: str
    key: str                       # group-key column name
    agg_cols: Tuple[str, ...]      # aggregate output columns, spec order
    col_dtypes: Mapping[str, np.dtype]  # clean-sample column dtypes
    stale_keys: jnp.ndarray        # (Rp,) int32, SENTINEL on invalid rows
    stale_valid: jnp.ndarray       # (Rp,) bool
    stale_vals: jnp.ndarray        # (Rp, A) f32, agg_cols order
    ins: Tuple[Relation, _FusedSpec]
    dele: Optional[Tuple[Relation, _FusedSpec]]
    out_capacity: int              # the view's sample arena capacity


def _dense_side(spec: _FusedSpec, counts: jnp.ndarray, sums: jnp.ndarray,
                num_groups: int, g_pad: int):
    """Raw accumulators → (valid (g_pad,), vals (g_pad, A)) dense panels.

    Value columns follow ``spec.node.aggs`` order (counts for count aggs,
    sum columns in declaration order) — the same layout
    ``_assemble_fused_output`` writes, minus the relation materialization
    the fleet merge no longer needs."""
    gv = _cap_group_validity(counts, spec.node.num_groups)
    cols = []
    i = 0
    for _out, fn_name, _val in spec.node.aggs:
        if fn_name == "count":
            cols.append(counts.astype(jnp.float32))
        else:
            cols.append(sums[:, i].astype(jnp.float32))
            i += 1
    vals = jnp.stack(cols, axis=1)
    if g_pad > num_groups:
        gv = jnp.pad(gv, (0, g_pad - num_groups))
        vals = jnp.pad(vals, ((0, g_pad - num_groups), (0, 0)))
    return gv, vals


def fleet_clean_merge(jobs):
    """The whole epoch's merge remainders in one ``fleet_merge`` dispatch.

    For every job: batch the insert-side (and delete-side) fused delta
    aggregations across views (``_fleet_fused_counts`` with no minimum —
    a lone view still rides the batched kernel), then upsert all dense
    delta panels into the stacked stale-sample panels with
    ``kernels/fleet_merge`` — jobs sharing (Rp, aggregate count) merge in
    ONE dispatch, and the fleet panel's common merge bucket makes that the
    typical epoch shape.  Per-view work after the dispatch is slicing the
    sorted rows back into each view's sample arena — no per-view merge
    plan execution.

    Returns ``(merged, precomputed)``: ``merged`` maps view name → its
    cleaned sample relation (bit-equal to the per-view ``clean_sample``
    path on valid rows); ``precomputed`` maps view name → {spec: relation}
    for jobs whose key domain kept a side off the batched path — their
    aggregated sides still splice into the per-view fallback.
    """
    from repro.kernels.fleet_merge import fleet_merge
    from repro.relational.relation import from_columns

    entries = []
    for j in jobs:
        entries.append(((j.name, "ins"), j.ins[0], j.ins[1]))
        if j.dele is not None:
            entries.append(((j.name, "del"), j.dele[0], j.dele[1]))
    raw = _fleet_fused_counts(entries, min_group=1)

    merged: Dict[str, Relation] = {}
    precomputed: Dict[str, Dict[_FusedSpec, Relation]] = {}
    ready = []
    for j in jobs:
        ri = raw.get((j.name, "ins"))
        rd = raw.get((j.name, "del")) if j.dele is not None else None
        if ri is None or (j.dele is not None and rd is None):
            # a side fell off the dense path (unbounded key domain):
            # the view falls back to per-view cleaning, but any side that
            # DID aggregate still splices in as a precomputed delta view
            pre = {}
            if ri is not None:
                pre[j.ins[1]] = _fleet_assemble_fn(j.ins[1], ri[2])(ri[0], ri[1])
            if j.dele is not None and rd is not None:
                pre[j.dele[1]] = _fleet_assemble_fn(j.dele[1], rd[2])(rd[0], rd[1])
            if pre:
                precomputed[j.name] = pre
            continue
        ready.append((j, ri, rd))

    shape_groups: Dict[Tuple[int, int], list] = {}
    for item in ready:
        j = item[0]
        shape_groups.setdefault(
            (int(j.stale_keys.shape[0]), len(j.agg_cols)), []
        ).append(item)

    for (_rp, _n_agg), members in shape_groups.items():
        g_pad = max(
            max(ri[2], rd[2] if rd is not None else 0) for _j, ri, rd in members
        )
        sk = jnp.stack([j.stale_keys for j, _ri, _rd in members])
        sv = jnp.stack([j.stale_valid for j, _ri, _rd in members])
        sa = jnp.stack([j.stale_vals for j, _ri, _rd in members])
        ins_v, ins_x, del_v, del_x = [], [], [], []
        for j, ri, rd in members:
            gv, gx = _dense_side(j.ins[1], ri[0], ri[1], ri[2], g_pad)
            ins_v.append(gv)
            ins_x.append(gx)
            if rd is not None:
                gv, gx = _dense_side(j.dele[1], rd[0], rd[1], rd[2], g_pad)
            else:
                gv = jnp.zeros((g_pad,), bool)
                gx = jnp.zeros((g_pad, len(j.agg_cols)), jnp.float32)
            del_v.append(gv)
            del_x.append(gx)
        keys, vals, valid = fleet_merge(
            sk, sv, sa,
            jnp.stack(ins_v), jnp.stack(ins_x),
            jnp.stack(del_v), jnp.stack(del_x),
        )
        span = int(keys.shape[1])
        for idx, (j, _ri, _rd) in enumerate(members):
            n = min(j.out_capacity, span)
            # sorted valid-first ascending ⇒ truncation keeps the lowest-
            # keyed rows, exactly compact's overflow behavior
            cols = {j.key: keys[idx, :n].astype(j.col_dtypes[j.key])}
            for a_i, cname in enumerate(j.agg_cols):
                cols[cname] = vals[idx, :n, a_i].astype(j.col_dtypes[cname])
            merged[j.name] = from_columns(
                cols, pk=(j.key,), valid=valid[idx, :n], capacity=j.out_capacity
            )
    return merged, precomputed


def clean_sample(
    strategy: Plan,
    view_name: str,
    view_pk: Tuple[str, ...],
    stale_sample: Relation,
    deltas: DeltaSet,
    m: float,
    seed: int = 0,
    extra_env: Optional[Mapping[str, Relation]] = None,
    out_capacity: Optional[int] = None,
    pin_name: Optional[str] = None,
    compact_leaves: bool = False,  # §Perf C.3: REFUTED for single-join views
    # (the O(n log n) compaction sort costs more than the join it shrinks);
    # enable for deep multi-join/multi-agg pipelines where downstream >> sort.
    fused: Optional[bool] = None,  # None ⇒ module default (use_fused)
    precomputed: Optional[Mapping[_FusedSpec, Relation]] = None,
) -> Relation:
    """Ŝ' = C(Ŝ, D, ∂D) — the up-to-date sample at ratio m (Problem 1).

    ``stale_sample`` may be the full stale view (η will narrow it) or the
    already-hashed sample (η is idempotent on it, §4.6).

    When ``fused`` (default on), the η-filtered groupby-sum/count delta
    sub-aggregations of the cleaning plan are evaluated by the fused
    ``kernels/fused_clean`` Pallas op — hash-threshold + per-group
    accumulation in one pass, no materialized filtered intermediate — and
    only the small merge remainder runs through the plan executor.  Plans
    whose shape or key domain does not qualify fall back transparently.
    """
    plan = cleaning_plan(strategy, view_pk, m, seed, pin_name=pin_name)
    env = delta_env(view_name, stale_sample, deltas)
    if extra_env:
        env.update(extra_env)
    if fused if fused is not None else _FUSED_DEFAULT:
        plan, env = fuse_delta_groupbys(plan, env, precomputed=precomputed)
    if compact_leaves and pin_name is None:
        plan, env = _compact_eta_leaves(plan, env, m)
    out = execute_jit(plan, env, name=view_name)
    return compact(out, out_capacity or stale_sample.capacity)


# ---------------------------------------------------------------------------
# Base-relation update primitives
# ---------------------------------------------------------------------------

def upsert(rel: Relation, delta: Relation, capacity: Optional[int] = None) -> Relation:
    """Insert-or-replace by primary key (update = delete + insert, §3.1)."""
    merged = ops.union_keyed(delta, rel)  # left (delta) priority
    return compact(merged, capacity or rel.capacity)


def delete_keys(rel: Relation, gone: Relation) -> Relation:
    """Mask out rows of ``rel`` whose pk appears in ``gone``."""
    return ops.difference_keyed(rel, gone)


def staleness_report(stale: Relation, fresh: Relation) -> Dict[str, jnp.ndarray]:
    """Counts of incorrect / missing / superfluous rows (§3.1) — debugging."""
    inner = ops.outer_join_unique(stale, fresh, on=stale.schema.pk, how="outer",
                                  suffixes=("_stale", "_fresh"))
    lp = inner.col("__left_present").astype(bool) & inner.valid
    rp = inner.col("__right_present").astype(bool) & inner.valid
    both = lp & rp
    changed = jnp.zeros_like(both)
    for c in stale.schema.columns:
        if c in stale.schema.pk:
            continue
        a = inner.columns.get(c + "_stale", inner.columns.get(c))
        b = inner.columns.get(c + "_fresh")
        if a is None or b is None:
            continue
        changed = changed | (both & (a != b))
    return {
        "incorrect": jnp.sum(changed.astype(jnp.int32)),
        "missing": jnp.sum((rp & ~lp).astype(jnp.int32)),
        "superfluous": jnp.sum((lp & ~rp).astype(jnp.int32)),
    }
