"""Plan evaluator: interprets a Plan against an environment of Relations."""

from __future__ import annotations

import functools
from typing import Mapping, Optional

import jax

from repro.relational import ops
from repro.relational.plan import (
    DifferenceNode,
    FKJoin,
    GroupByNode,
    HashNode,
    IntersectNode,
    OuterJoin,
    Plan,
    ProjectNode,
    Scan,
    SelectNode,
    UnionNode,
)
from repro.relational.relation import Relation


def execute(p: Plan, env: Mapping[str, Relation]) -> Relation:
    if isinstance(p, Scan):
        rel = env[p.name]
        return rel
    if isinstance(p, SelectNode):
        return ops.select(execute(p.child, env), p.pred)
    if isinstance(p, ProjectNode):
        child = execute(p.child, env)
        return ops.project(child, dict(p.outputs), pk=p.pk)
    if isinstance(p, FKJoin):
        return ops.fk_join(
            execute(p.fact, env),
            execute(p.dim, env),
            fact_key=p.fact_key,
            dim_key=p.dim_key,
            suffix=p.suffix,
        )
    if isinstance(p, OuterJoin):
        return ops.outer_join_unique(
            execute(p.left, env),
            execute(p.right, env),
            on=p.on,
            how=p.how,
            suffixes=p.suffixes,
        )
    if isinstance(p, GroupByNode):
        child = execute(p.child, env)
        aggs = {out: (fn, val) for out, fn, val in p.aggs}
        return ops.groupby(child, p.keys, aggs, num_groups=p.num_groups)
    if isinstance(p, UnionNode):
        return ops.union_keyed(execute(p.left, env), execute(p.right, env))
    if isinstance(p, IntersectNode):
        return ops.intersect_keyed(execute(p.left, env), execute(p.right, env))
    if isinstance(p, DifferenceNode):
        return ops.difference_keyed(execute(p.left, env), execute(p.right, env))
    if isinstance(p, HashNode):
        from repro.core import hashing

        child = execute(p.child, env)
        pin = env.get(p.pin_name) if p.pin_name else None
        return hashing.apply_hash(child, p.cols, p.m, p.seed, pin=pin)
    raise TypeError(p)


@functools.lru_cache(maxsize=256)
def _jitted_executor(plan: Plan, name: Optional[str] = None):
    def run(env):
        return execute(plan, env)

    # the device program is named ``jit_plan_<name>`` (the view, else the
    # root node's type), so a profile can tell one plan from another
    run.__name__ = run.__qualname__ = f"plan_{name or type(plan).__name__}"
    return jax.jit(run)


def execute_jit(plan: Plan, env: Mapping[str, Relation],
                name: Optional[str] = None) -> Relation:
    """Compiled plan execution (plans are frozen/hashable; cached per plan).

    ``name`` labels the compiled program (``jit_plan_<name>``).  Retraces
    when relation capacities change; steady-state maintenance hits the
    cache.
    """
    return _jitted_executor(plan, name)(dict(env))
