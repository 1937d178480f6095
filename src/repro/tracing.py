"""Spans and counters inside the program, on the host clock that the
profiler's trace and the benchmark's window share.

``span(name, **attrs)`` times a block; ``begin``/``end`` time an
interval that opens and closes in different calls (a request's wait in
the queue); ``add(**counts)`` adds counts, such as ``d2h_bytes`` and
``h2d_bytes``, to the innermost open span of the calling thread. A span
opened under one that carries a ``rid`` carries it too, so every span of
a request's admission names the request.

Each closed span is one record ``(name, id, parent_id, t0, t1, attrs)``
with ``t0``/``t1`` on ``time.perf_counter``, kept in a ring of
:data:`RING` records that is always on. While a ``jax.profiler`` trace
runs, every ``span`` also lands in it as a ``TraceAnnotation`` of its
name, beside the device's operations; ``begin``/``end`` intervals
overlap each other and stay in memory only. JAX is never imported here:
where the process has not imported it, nothing is mirrored.

The readers take a window ``[lo, hi]`` on the same clock and return
``None`` where the ring has dropped a record that ended after ``lo``:
they never undercount. ``docs/tracing.md`` has the span names.
"""

from __future__ import annotations

import itertools
import math
import sys
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Set, Tuple

#: records the ring keeps; the oldest go first
RING = 1 << 17

Record = Tuple[str, int, Optional[int], float, Optional[float], Dict]

_clock = time.perf_counter


_annotation_cls = None


def _annotation(name: str):
    """A profiler annotation of ``name``, where the process has JAX."""
    global _annotation_cls
    if _annotation_cls is None:
        prof = sys.modules.get("jax.profiler")
        if prof is None:
            return None
        _annotation_cls = prof.TraceAnnotation
    return _annotation_cls(name)


class _Span:
    """One open stack span: a context manager that records on exit."""

    __slots__ = ("tracer", "name", "id", "parent", "attrs", "t0", "t1",
                 "_stack", "_ann")

    def __init__(self, tracer: "Tracer", name: str, attrs: Dict) -> None:
        self.tracer, self.name, self.attrs = tracer, name, attrs
        self.t1 = None

    def __enter__(self) -> "_Span":
        tr = self.tracer
        stack = self._stack = tr._stack()
        up = stack[-1] if stack else None
        self.parent = up.id if up is not None else None
        if up is not None and "rid" in up.attrs:
            self.attrs.setdefault("rid", up.attrs["rid"])
        self.id = next(tr._ids)
        tr._names.add(self.name)
        stack.append(self)
        self._ann = _annotation(self.name)
        if self._ann is not None:
            self._ann.__enter__()
        self.t0 = _clock()
        return self

    def __exit__(self, *exc) -> bool:
        self.t1 = _clock()
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        self._stack.pop()
        self.tracer._record((self.name, self.id, self.parent, self.t0,
                             self.t1, self.attrs))
        return False

    @property
    def seconds(self) -> float:
        """Duration of the closed span."""
        return self.t1 - self.t0


class Tracer:
    """A ring of span records and the per-thread stacks of open spans;
    the module-level functions use one shared instance."""

    def __init__(self, ring: int = RING) -> None:
        self._cap = ring
        self._ring: deque = deque()
        self._open: Dict[int, list] = {}
        self._lost_t1 = -math.inf    # latest end of a dropped record
        self._lock = threading.Lock()
        self._ids = itertools.count()
        self._local = threading.local()
        self._names: Set[str] = set()

    def _stack(self) -> List[_Span]:
        try:
            return self._local.stack
        except AttributeError:
            self._local.stack = []
            return self._local.stack

    def _record(self, rec: Record) -> None:
        ring = self._ring
        ring.append(rec)
        if len(ring) > self._cap:
            with self._lock:
                while len(ring) > self._cap:
                    self._lost_t1 = max(self._lost_t1, ring.popleft()[4])

    # -- writing ---------------------------------------------------------

    def span(self, name: str, **attrs) -> _Span:
        """A span around a ``with`` block, nested in the thread's open
        spans; its ``seconds`` holds the duration after the block."""
        return _Span(self, name, attrs)

    def begin(self, name: str, **attrs) -> list:
        """Open an interval that :meth:`end` closes, from any call or
        thread. It joins no stack and is never mirrored to the
        profiler."""
        stack = self._stack()
        h = [name, next(self._ids), stack[-1].id if stack else None,
             _clock(), attrs]
        self._open[h[1]] = h
        return h

    def end(self, handle: list, **attrs) -> None:
        """Close an interval :meth:`begin` opened, adding ``attrs``."""
        t1 = _clock()
        name, sid, parent, t0, a = handle
        if self._open.pop(sid, None) is None:
            raise ValueError(f"interval {name!r} ({sid}) is not open")
        a.update(attrs)
        self._record((name, sid, parent, t0, t1, a))

    def add(self, **counts) -> None:
        """Add ``counts`` to the innermost open span of this thread (no
        span open: dropped)."""
        stack = self._stack()
        if stack:
            a = stack[-1].attrs
            for k, v in counts.items():
                a[k] = a.get(k, 0) + v

    # -- reading ---------------------------------------------------------

    def stack_names(self) -> Set[str]:
        """Names of the spans opened with :meth:`span`: those mirrored
        to the profiler's trace."""
        return set(self._names)

    def _all(self, lo: float) -> Optional[List[Record]]:
        with self._lock:
            if self._lost_t1 > lo:
                return None
            recs = list(self._ring)
        return recs + [(n, i, p, t0, None, a)
                       for n, i, p, t0, a in list(self._open.values())]

    def records(self, lo: float = -math.inf,
                hi: float = math.inf) -> Optional[List[Record]]:
        """Records that started in ``[lo, hi]``, in the order they
        closed, then the ``begin`` intervals still open (``t1`` None)."""
        recs = self._all(lo)
        return None if recs is None else [r for r in recs
                                          if lo <= r[3] <= hi]

    def total(self, name: str, lo: float, hi: float) -> Optional[float]:
        """Seconds inside spans ``name``, clipped to ``[lo, hi]`` (an
        open interval runs to ``hi``)."""
        recs = self._all(lo)
        if recs is None:
            return None
        return sum(min(hi if t1 is None else t1, hi) - max(t0, lo)
                   for n, _, _, t0, t1, _ in recs
                   if n == name and t0 < hi and (t1 is None or t1 > lo))

    def self_time(self, name: str, lo: float,
                  hi: float) -> Optional[float]:
        """Seconds of the spans ``name`` that started in ``[lo, hi]``,
        less the time their child spans cover."""
        recs = self.records(lo, hi)
        if recs is None:
            return None
        own = {r[1]: r[4] - r[3] for r in recs
               if r[0] == name and r[4] is not None}
        kids = sum(r[4] - r[3] for r in recs
                   if r[2] in own and r[4] is not None)
        return sum(own.values()) - kids

    def summed(self, name: str, attr: str, lo: float,
               hi: float) -> Optional[float]:
        """Sum of ``attr`` over the records that started in ``[lo, hi]``
        and are spans ``name`` or lie under one."""
        recs = self._all(lo)
        if recs is None:
            return None
        up = {r[1]: (r[0], r[2]) for r in recs}

        def under(sid):
            while sid is not None and sid in up:
                n, sid_up = up[sid]
                if n == name:
                    return True
                sid = sid_up
            return False
        return float(sum(r[5].get(attr, 0) for r in recs
                         if lo <= r[3] <= hi and attr in r[5]
                         and under(r[1])))


_TRACER = Tracer()
span = _TRACER.span
begin = _TRACER.begin
end = _TRACER.end
add = _TRACER.add
stack_names = _TRACER.stack_names
records = _TRACER.records
total = _TRACER.total
self_time = _TRACER.self_time
summed = _TRACER.summed
