"""Reduce a JAX profiler trace to device busy time, kernel time and gaps.

``load`` reads the ``.xplane.pb`` a ``jax.profiler`` trace wrote and keeps
what the reduction needs as plain lists (so a small recorded trace can be
committed as JSON and tested): the device's op and module events, and the
host's ``svcbench.*`` annotations.  ``reduce`` then computes, inside the
traced window:

* busy: the union of the device's op intervals (averaged over devices);
* time per module (a jitted program or kernel) and per op name;
* idle gaps: the complement of busy, each labelled by the innermost host
  span around its middle — the program's own obs spans mapped onto the
  profile's clock, else the benchmark's annotation, else ``"no call"``.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]   # (start_ns, end_ns)

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ANNOTATION_PREFIX = "svcbench."


def _is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "TPU" in name.upper()


def load(prof_dir: str) -> Dict:
    """The device and host events of the newest trace under ``prof_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(prof_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {prof_dir}")
    data = ProfileData.from_file(paths[-1])
    out: Dict = {"devices": {}, "host": []}
    for plane in data.planes:
        if _is_device_plane(plane.name):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key:
                    dev[key] = [[e.name, e.start_ns, e.duration_ns]
                                for e in line.events]
            out["devices"][plane.name] = dev
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(ANNOTATION_PREFIX):
                        out["host"].append([e.name, e.start_ns, e.duration_ns])
    return out


def union(intervals: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """Merged intervals, clipped to [lo, hi]."""
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def _label(mid: float, spans: Sequence[Tuple[str, float, float]]) -> str:
    """Innermost (shortest) span around ``mid``."""
    best = None
    for name, s, e in spans:
        if s <= mid <= e and (best is None or e - s < best[2] - best[1]):
            best = (name, s, e)
    return best[0] if best else "no call"


def reduce(trace: Dict, window: Optional[Interval] = None,
           host_spans: Sequence[Tuple[str, float, float]] = ()) -> Dict:
    """Busy/idle, per-module and per-op device time, and labelled gaps.

    ``window`` is (start_ns, end_ns) on the profile's clock; by default
    the span of the benchmark's annotations.  ``host_spans`` are extra
    (name, start_ns, end_ns) host spans already on that clock."""
    ann = [(n, s, s + d) for n, s, d in trace["host"]]
    if window is None:
        if not ann:
            raise ValueError("no window given and no annotations in the trace")
        window = (min(s for _n, s, _e in ann), max(e for _n, _s, e in ann))
    lo, hi = window
    devices = trace["devices"]
    if not devices:
        raise ValueError("the trace holds no TPU device plane")
    busy_total = 0.0
    modules: Dict[str, float] = {}
    ops: Dict[str, float] = {}
    all_gaps: Dict[str, float] = {}
    longest: List[Tuple[float, str]] = []
    labels = list(host_spans) + ann
    for dev in devices.values():
        iv = [(s, s + d) for _n, s, d in dev["ops"]]
        busy = union(iv, lo, hi)
        busy_total += sum(e - s for s, e in busy)
        for name, s, d in dev["modules"]:
            if lo <= s < hi:
                modules[name] = modules.get(name, 0.0) + d * 1e-9
        for name, s, d in dev["ops"]:
            if lo <= s < hi:
                ops[name] = ops.get(name, 0.0) + d * 1e-9
        for s, e in gaps(busy, lo, hi):
            lab = _label(0.5 * (s + e), labels)
            all_gaps[lab] = all_gaps.get(lab, 0.0) + (e - s) * 1e-9
            longest.append(((e - s) * 1e-9, lab))
    n = len(devices)
    busy_s = busy_total * 1e-9 / n
    window_s = (hi - lo) * 1e-9
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_pct": 100.0 * (1.0 - busy_s / window_s),
        "modules": {k: v / n for k, v in modules.items()},
        "ops": {k: v / n for k, v in ops.items()},
        "gaps_by_label": {k: v / n for k, v in all_gaps.items()},
        "longest_gaps": sorted(longest, reverse=True)[:10],
    }


def module_time(red: Dict, names: Sequence[str]) -> float:
    """Device seconds of every module whose name contains one of ``names``."""
    return sum(t for m, t in red["modules"].items()
               if any(n in m for n in names))


def breakdown(red: Dict) -> Dict:
    """The result line's ``breakdown``: top device modules (or ops) and the
    idle seconds by what the host was doing, at most 10 each."""
    src = red["modules"] or red["ops"]
    top = sorted(src.items(), key=lambda kv: -kv[1])[:10]
    idle = sorted(red["gaps_by_label"].items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[k, v] for k, v in top],
            "idle_gaps": [[k, v] for k, v in idle]}
