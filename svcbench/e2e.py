"""End-to-end arithmetic on the window's records (host clock, seconds).

Every function here takes plain numbers, so the tests can check it on a
synthetic schedule.  A time is an offset from the window's start.
"""

from __future__ import annotations

import bisect
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile (numpy's linear interpolation) of all values."""
    return float(np.percentile(np.asarray(values, np.float64), q))


def query_latencies(batches: Sequence[Tuple[float, Optional[float]]],
                    window_end: float) -> List[float]:
    """(due, done) per dashboard batch → seconds from due until its values
    were ready.  A batch never issued (done is None) counts until the
    window's end, a lower bound on what it would have waited."""
    return [(window_end if done is None else done) - due
            for due, done in batches]


def visibility(clean_ends: Sequence[Tuple[float, int]], drained: int
               ) -> Optional[float]:
    """When micro-batch number ``drained`` became visible in one view.

    ``clean_ends`` lists, in time order, (end time, micro-batches drained
    so far) for every epoch whose clean or maintain of this view
    committed.  The batch is visible at the end of the first such epoch
    that had drained it; None if none had."""
    counts = [n for _t, n in clean_ends]
    i = bisect.bisect_right(counts, drained)
    return clean_ends[i][0] if i < len(clean_ends) else None


def fresh_lags(pairs: Sequence[Tuple[float, Optional[float]]],
               window_end: float) -> List[float]:
    """(due, visible) per (micro-batch, view) pair → lag in seconds.
    A pair not visible by the window's end counts until the window's end,
    so a skipped clean cannot hide."""
    return [((window_end if vis is None or vis > window_end else vis) - due)
            for due, vis in pairs]


def span_p50_ms(spans: Sequence[Dict], names: Sequence[str]) -> Optional[float]:
    """Median wall of the named obs spans, in ms; None when no such span
    was recorded."""
    durs = [sp["dur_s"] for sp in spans if sp["name"] in names]
    if not durs:
        return None
    return 1e3 * float(np.median(durs))
