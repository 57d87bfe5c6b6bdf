"""The one traffic generator: a mix file's parameters → an open-loop schedule.

A mix (``svcbench/traffic/<mix>.json``) holds only numbers:

* ``queries``: ``rate_per_s`` (dashboard batches, each drawing fresh
  ranges), ``batch`` (queries per batch); batches split evenly over the
  views.
* ``inserts``: ``rate_rows_per_s``, ``micro_batch_rows`` (every micro-batch
  has this size), ``watermark_batches`` (the size watermark, a whole number
  of micro-batches, so every epoch drains one shape); micro-batches split
  evenly over the deployment's insert bases.
* ``backlog_rows``, ``warm_epochs``, ``warm_query_batches``,
  ``trace_subwindow_s``: the set-up and the traced run (harness).

Dashboard batches arrive as independent users send them: Poisson-shaped
(exponential gaps).  Insert micro-batches arrive as an upstream feed
flushes them, evenly spaced, so the watermark trips at a steady cadence.

The arrival trace — every event's due time and target — is drawn once,
from a fixed generator, for a given mix and window: every seed replays
the same trace, as a recorded trace is replayed.  The seed draws what the
requests hold (the inserted rows and the query ranges, in the harness),
so every seed gives the same work at the same times (PERF.md §6).
"""

from __future__ import annotations

import dataclasses
from typing import List, Sequence

import numpy as np

TRACE_SEED = 20150923  # the fixed draw of every arrival trace


@dataclasses.dataclass(frozen=True)
class Event:
    due_s: float      # offset from the window's start
    kind: str         # "query" | "insert"
    target: int       # view index (query) or insert-base index (insert)
    index: int        # query batch number, or micro-batch number


def poisson_gaps(n: int, horizon_s: float, rng: np.random.Generator) -> np.ndarray:
    """n exponential gaps, scaled so the n-th arrival falls at
    horizon·n/(n+1)."""
    if n <= 0:
        return np.zeros(0)
    g = rng.exponential(1.0, n)
    return g * (horizon_s * n / (n + 1)) / g.sum()


def target_counts(n: int, weights: Sequence[float]) -> np.ndarray:
    """Largest-remainder split of n events over targets by weight."""
    w = np.asarray(weights, np.float64)
    exact = n * w / w.sum()
    counts = np.floor(exact).astype(np.int64)
    short = n - int(counts.sum())
    if short:
        counts[np.argsort(-(exact - counts), kind="stable")[:short]] += 1
    return counts


def counts(mix: dict, seconds: float):
    """(query batches, micro-batches) the window offers."""
    q = int(round(mix["queries"]["rate_per_s"] * seconds))
    ins = mix["inserts"]
    i = int(round(ins["rate_rows_per_s"] * seconds / ins["micro_batch_rows"]))
    return q, i


def even_gaps(n: int, horizon_s: float) -> np.ndarray:
    """n equal gaps; the n-th arrival falls at horizon·n/(n+1)."""
    return np.full(n, horizon_s / (n + 1))


def _stream(gaps: np.ndarray, n_targets: int, rng):
    """Due times, and targets split evenly in a permuted order."""
    due = np.cumsum(gaps)
    tgt = np.repeat(np.arange(n_targets),
                    target_counts(len(gaps), np.ones(n_targets)))
    return due, rng.permutation(tgt)


def schedule(mix: dict, n_views: int, n_bases: int, seconds: float) -> List[Event]:
    """The window's arrival trace in due order (ties: inserts first)."""
    nq, ni = counts(mix, seconds)
    qrng = np.random.default_rng([TRACE_SEED, 1])
    irng = np.random.default_rng([TRACE_SEED, 2])
    qdue, qtgt = _stream(poisson_gaps(nq, seconds, qrng), n_views, qrng)
    idue, itgt = _stream(even_gaps(ni, seconds), n_bases, irng)
    events = [Event(float(t), "insert", int(b), k)
              for k, (t, b) in enumerate(zip(idue, itgt))]
    events += [Event(float(t), "query", int(v), k)
               for k, (t, v) in enumerate(zip(qdue, qtgt))]
    events.sort(key=lambda e: (e.due_s, e.kind != "insert", e.index))
    return events

