"""The program's own spans and counters (``repro.tracing``) for the
per-layer readers. A checkout whose program has no tracer reads
nothing: every helper here then returns None or 0, and raises
nothing."""

from __future__ import annotations

from typing import Optional


def tracer():
    """The program's ``repro.tracing`` module, or None."""
    try:
        from repro import tracing
    except ImportError:
        return None
    return tracing


def per(value: Optional[float], n: int,
        scale: float = 1.0) -> Optional[float]:
    """``scale * value / n``; None where nothing was read or counted."""
    if value is None or value <= 0 or not n:
        return None
    return scale * value / n


def total(r, name: str) -> Optional[float]:
    """Seconds in the program's spans ``name`` inside the window of the
    readings ``r``."""
    t = tracer()
    return None if t is None else t.total(name, *r.window)


def summed(r, name: str, attr: str) -> Optional[float]:
    """Sum of the counter ``attr`` over the program's spans that started
    in the window of ``r`` and are spans ``name`` or lie under one."""
    t = tracer()
    return None if t is None else t.summed(name, attr, *r.window)


def count(r, name: str) -> int:
    """How many of the program's spans ``name`` started in the window of
    ``r``: the steps or ticks a per-step or per-tick metric divides by
    (a tick that ends after the harness's last whole one still counts,
    as its spans do)."""
    t = tracer()
    recs = None if t is None else t.records(*r.window)
    return 0 if recs is None else sum(1 for x in recs if x[0] == name)


def us_per_event(r) -> Optional[float]:
    """Host microseconds in ``JcclWorld.wait_all`` per simulator event
    it ran, inside the window of ``r``."""
    secs = total(r, "jccl.wait_all")
    return None if secs is None else per(
        secs, summed(r, "jccl.wait_all", "events"), 1e6)
