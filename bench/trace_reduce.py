"""From a ``jax.profiler`` trace to the benchmark's device numbers.

:func:`load` reads the ``.xplane.pb`` a traced run wrote into plain
lists of ``(name, start_s, end_s)`` on the trace's clock: the operations
and the programs (XLA modules) run on each device, and the harness's own
host spans. :func:`reduce` turns those lists into the busy time of the
devices inside the traced window, each program's device time, and the
device's idle time split by the host spans it fell in. The lists can also
be read from JSON, which is how the tests check the reduction on a small
recorded trace.
"""

from __future__ import annotations

import glob
import json
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[str, float, float]

#: the harness's span around the whole measured window
WINDOW_SPAN = "bench.window"


def load(trace_dir: str, span_names: Iterable[str]) -> Dict:
    """Events of the newest trace under ``trace_dir``: ``{"devices":
    {plane: {"ops": [...], "modules": [...]}}, "spans": [...]}``, with
    only the host events whose names are in ``span_names``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    names = set(span_names) | {WINDOW_SPAN}
    out: Dict = {"devices": {}, "spans": []}
    for plane in ProfileData.from_file(paths[-1]).planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name \
                and "SparseCore" not in plane.name:
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key:
                    dev[key] += [(e.name, e.start_ns * 1e-9,
                                  (e.start_ns + e.duration_ns) * 1e-9)
                                 for e in line.events]
            if dev["ops"] or dev["modules"]:
                out["devices"][plane.name] = dev
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                out["spans"] += [(e.name, e.start_ns * 1e-9,
                                  (e.start_ns + e.duration_ns) * 1e-9)
                                 for e in line.events if e.name in names]
    return out


def load_json(path: str) -> Dict:
    with open(path) as f:
        ev = json.load(f)
    ev["spans"] = [tuple(s) for s in ev["spans"]]
    for dev in ev["devices"].values():
        for k in ("ops", "modules"):
            dev[k] = [tuple(e) for e in dev.get(k, [])]
    return ev


def union(intervals: Iterable[Tuple[float, float]], lo: float,
          hi: float) -> List[Tuple[float, float]]:
    """Disjoint, sorted union of intervals clipped to [lo, hi]."""
    out: List[List[float]] = []
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def gaps(busy: Sequence[Tuple[float, float]], lo: float,
         hi: float) -> List[Tuple[float, float]]:
    """The complement of a disjoint sorted union inside [lo, hi]."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def host_span_at(spans: Sequence[Interval], t: float) -> str:
    """The innermost harness span (latest start) that covers t, or
    ``"none"``."""
    best: Optional[Interval] = None
    for s in spans:
        if s[0] != WINDOW_SPAN and s[1] <= t < s[2] and (
                best is None or s[1] > best[1]):
            best = s
    return best[0] if best else "none"


def split_by_span(spans: Sequence[Interval], a: float,
                  b: float) -> Dict[str, float]:
    """Seconds of [a, b] under each innermost harness span: the interval
    is cut at every span boundary inside it, and each piece goes to the
    span that covers it."""
    cuts = sorted({a, b} | {t for s in spans for t in s[1:]
                            if a < t < b})
    out: Dict[str, float] = {}
    for lo, hi in zip(cuts, cuts[1:]):
        name = host_span_at(spans, (lo + hi) / 2)
        out[name] = out.get(name, 0.0) + (hi - lo)
    return out


def window_of(events: Dict) -> Tuple[float, float]:
    """The traced window: the harness's window span."""
    w = [s for s in events["spans"] if s[0] == WINDOW_SPAN]
    if not w:
        raise ValueError("the trace holds no window span")
    return w[-1][1], w[-1][2]


def reduce(events: Dict, top: int = 10) -> Dict:
    """Busy seconds (averaged over the devices), each program's device
    seconds summed over devices, the top programs, and the idle seconds
    by harness span, all inside the window span."""
    lo, hi = window_of(events)
    devs = events["devices"]
    if not devs:
        raise ValueError("the trace holds no device events")
    busy_total = 0.0
    idle_by: Dict[str, float] = {}
    programs: Dict[str, float] = {}
    spans = events["spans"]
    for dev in devs.values():
        source = dev["ops"] or dev["modules"]
        busy = union(((a, b) for _, a, b in source), lo, hi)
        busy_total += sum(b - a for a, b in busy)
        for a, b in gaps(busy, lo, hi):
            for name, d in split_by_span(spans, a, b).items():
                idle_by[name] = idle_by.get(name, 0.0) + d
        for name, a, b in dev["modules"]:
            d = min(b, hi) - max(a, lo)
            if d > 0:
                programs[name] = programs.get(name, 0.0) + d
    n = len(devs)
    rank = lambda d: sorted(d.items(), key=lambda kv: -kv[1])  # noqa: E731
    return {"window_s": hi - lo, "busy_s": busy_total / n,
            "programs": programs,
            "device_ops": [[k, v] for k, v in rank(programs)[:top]],
            "idle_gaps": [[k, v / n] for k, v in rank(idle_by)[:top]]}


def program_seconds(reduced: Dict, pattern: str) -> float:
    """Device seconds of the programs whose name contains ``pattern``."""
    return sum(v for k, v in reduced["programs"].items() if pattern in k)
