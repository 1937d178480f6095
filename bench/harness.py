"""Pieces every driver and reader shares: the cell a run measures, its
architecture's module, its seeds, the harness's host spans, the profiler
window, and the device.

Nothing here imports the program under test.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import time
from pathlib import Path
from types import ModuleType
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

class WindowClosed(Exception):
    """Raised from a driver's callback to end the program's loop once the
    measured window has closed."""


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    """One run of one cell: the configuration and traffic files it
    names, the module of the configuration's architecture, the limits of
    its correctness comparison, and the run's arguments."""

    name: str
    cfg: Dict[str, Any]
    arch: ModuleType
    mix: Dict[str, Any]
    limits: Dict[str, Any]
    seed: int
    seconds: float

    def sub_seed(self, what: str) -> int:
        """A 31-bit seed for one purpose (``"model"``, ``"traffic"``,
        ``"sample"``), drawn from ``--seed``: any whole number, also
        past 32 bits, gives the same sub-seeds every time."""
        tag = sum(ord(c) * 131 ** i for i, c in enumerate(what)) % (1 << 31)
        ss = np.random.SeedSequence([self.seed % (1 << 63), tag])
        return int(ss.generate_state(1)[0] & 0x7FFFFFFF)


def load_cell(root: Path, workload: str, seed: int,
              seconds: float) -> Tuple[Cell, Dict[str, Any]]:
    """Resolve a workload of ``<root>/BENCHMARK.json`` to its files by
    name. Returns the cell and the benchmark description."""
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = load_json(root / configs[w["config"]]["file"])
    mix = load_json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    limits = load_json(root / "bench" / "limits" / f"{workload}.json")
    return Cell(workload, cfg, arch(root, cfg), mix, limits, seed,
                seconds), bench


def arch(root: Path, cfg: Dict[str, Any]) -> ModuleType:
    """The module ``<root>/bench/archs/<class>.py`` of the configuration's
    architecture class, ``architectures[0]`` as in the model's published
    ``config.json``: the program's ``ModelConfig``, the float32
    reference and the counts of operations and bytes for that class."""
    cls = cfg["architectures"][0]
    path = root / "bench" / "archs" / f"{cls}.py"
    if not path.is_file():
        raise SystemExit(f"no module for architecture {cls!r} of "
                         f"configuration {cfg['name']!r}: add "
                         f"bench/archs/{cls}.py")
    return load_module(path, f"bench_arch_{cls}")


def load_module(path: Path, name: str) -> ModuleType:
    """The Python file ``path`` as a module named ``name``."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Spans:
    """Host spans of the harness, kept in memory as (name, start, end) on
    ``time.perf_counter``; while a profiler trace runs, each span is also
    a ``jax.profiler.TraceAnnotation`` so the trace reduction can name
    the device's idle gaps by them."""

    def __init__(self) -> None:
        self.records: List[Tuple[str, float, float]] = []
        self.tracing = False

    def begin(self, name: str):
        """Open a span; :meth:`end` closes it (for spans that open and
        close in different callbacks)."""
        ann = None
        if self.tracing:
            import jax
            ann = jax.profiler.TraceAnnotation(name)
            ann.__enter__()
        return name, time.perf_counter(), ann

    def end(self, handle) -> None:
        name, t0, ann = handle
        t1 = time.perf_counter()
        if ann is not None:
            ann.__exit__(None, None, None)
        self.records.append((name, t0, t1))

    @contextlib.contextmanager
    def __call__(self, name: str):
        handle = self.begin(name)
        try:
            yield
        finally:
            self.end(handle)

    def total(self, name: str, lo: float, hi: float) -> float:
        """Seconds inside spans ``name`` that lie in ``[lo, hi]``."""
        return sum(min(t1, hi) - max(t0, lo)
                   for n, t0, t1 in self.records
                   if n == name and t1 > lo and t0 < hi)


def wrap_wait_all(world, spans: Spans) -> None:
    """Time the fabric: every ``JcclWorld.wait_all`` of this world object
    runs inside a ``fabric.wait_all`` span."""
    inner = world.wait_all

    def wait_all(*args, **kwargs):
        with spans("fabric.wait_all"):
            return inner(*args, **kwargs)
    world.wait_all = wait_all


class Profiler:
    """The measured window. ``open`` marks its start and ``close`` its
    end (``t0``, ``t1`` on ``time.perf_counter``). With ``--trace 1``
    the window is also a ``jax.profiler`` trace, without the Python
    tracer (which would slow the host code it times), written under
    ``$TMPDIR``, with the whole window as the span
    :data:`bench.trace_reduce.WINDOW_SPAN`."""

    def __init__(self, spans: Spans, enabled: bool) -> None:
        self.spans = spans
        self.enabled = enabled
        self.dir: Optional[str] = None
        self.t0: Optional[float] = None
        self.t1: Optional[float] = None
        self._ann = None

    def open(self) -> None:
        if self.t0 is not None:
            return
        if self.enabled:
            import tempfile

            import jax

            from bench.trace_reduce import WINDOW_SPAN
            self.dir = tempfile.mkdtemp(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.spans.tracing = True
            self._ann = jax.profiler.TraceAnnotation(WINDOW_SPAN)
            self._ann.__enter__()
        self.t0 = time.perf_counter()

    def close(self) -> None:
        if self.t0 is None or self.t1 is not None:
            return
        self.t1 = time.perf_counter()
        if self._ann is not None:
            import jax
            self._ann.__exit__(None, None, None)
            self.spans.tracing = False
            jax.profiler.stop_trace()


class CompileLog:
    """When JAX traced or compiled a program (``time.perf_counter``), to
    count what the measured window compiled: nothing should be."""

    TRACE = "/jax/core/compile/jaxpr_trace_duration"
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        from jax import monitoring
        self.events: List[Tuple[str, float]] = []
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name: str, secs: float, **kwargs) -> None:
        if name in (self.TRACE, self.COMPILE):
            self.events.append((name, time.perf_counter()))

    def count(self, name: str, lo: float, hi: float) -> int:
        return sum(1 for n, t in self.events if n == name and lo <= t <= hi)


def percentile(values, q: float) -> float:
    """The q-th percentile of all samples (linear between order
    statistics)."""
    return float(np.percentile(np.asarray(values, np.float64), q))


def device_memory_peak() -> int:
    """Peak bytes in use on the fullest device of this process."""
    import jax
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices())


def leaf_paths(tree) -> List[str]:
    """'a/b/c' names of a pytree's leaves, in flattening order."""
    import jax
    out = []
    for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]:
        out.append("/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                            for p in path))
    return out
