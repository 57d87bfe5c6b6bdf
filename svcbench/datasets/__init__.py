"""Deployments, one module per data set, found by the ``dataset``
key of a configuration file (``svcbench/datasets/<dataset>.py``).

Each module defines ``build(config, rng) -> Deployment``: the base tables
as numpy columns, the views, the insert bases and a row generator for
their deltas.  Nothing here touches JAX; the harness uploads the columns.
"""

from __future__ import annotations

import dataclasses
import importlib
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from svcbench.data import Columns


@dataclasses.dataclass(frozen=True)
class ViewSpec:
    """A group-by view over one base relation (sum/count aggregates),
    optionally filtered by a foreign-key join to a dimension table."""

    name: str
    base: str
    key: str                                  # group key (a base column)
    aggs: Tuple[Tuple[str, str, Optional[str]], ...]  # (out, sum|count, col)
    groups: int                               # group-key domain bound
    join: Optional[Tuple[str, str]] = None    # (dimension, its pk)
    outlier: Optional[Tuple[str, int]] = None  # (base attr, k)
    seed: int = 0                             # η seed
    pred_col: str = ""                        # query predicate column
    pred_scale: float = 1.0                   # mean of pred_col per group


@dataclasses.dataclass
class Table:
    cols: Columns
    pk: str
    capacity: int


@dataclasses.dataclass
class Deployment:
    tables: Dict[str, Table]
    views: List[ViewSpec]
    insert_bases: List[str]
    new_rows: Callable[[np.random.Generator, str, int, int], Columns]


def load(name: str):
    return importlib.import_module(f"svcbench.datasets.{name}")
