"""Arithmetic on the obs spans of a traced window (``ReadContext.spans``:
records with ``id``, ``parent``, ``name``, ``dur_s`` and ``attrs``)."""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np


def walls_less(spans: Sequence[Dict], name: str, minus: Sequence[str],
               view: str) -> List[float]:
    """Seconds of each ``name`` span of ``view`` that the program split
    into child spans, less the walls of its descendants named in
    ``minus``; such a descendant is taken whole, with its own descendants.
    A span with no child is left out: a program without the child spans
    has nothing to subtract."""
    kids: Dict[int, List[Dict]] = {}
    for sp in spans:
        kids.setdefault(sp["parent"], []).append(sp)

    def taken(sp: Dict) -> float:
        return sum(c["dur_s"] if c["name"] in minus else taken(c)
                   for c in kids.get(sp["id"], ()))

    return [sp["dur_s"] - taken(sp) for sp in spans
            if sp["name"] == name and sp["id"] in kids
            and sp["attrs"].get("view") == view]


def p50_ms(seconds: Sequence[float]) -> Optional[float]:
    """Median in ms; None for no values."""
    return 1e3 * float(np.median(seconds)) if len(seconds) else None
