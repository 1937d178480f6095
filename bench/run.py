"""Run one cell of the benchmark and print its result.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout. The cell's configuration, traffic mix,
driver, limits and per-layer readers are found by name from
``BENCHMARK.json`` (see ``bench/__init__.py``). The run exits non-zero,
printing no result, where JAX finds no TPU or fewer chips than the cell
asks for.

Output: a few lines about the run, then, as the last lines on standard
error, each compared number beside its limit, and as the last line on
standard output one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer ones), ``device``, with ``--trace 1`` ``breakdown``, and last
``checks``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Tuple  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# import the benchmark's modules as the package ``bench``, never by their
# bare names from this directory
sys.path = [p for p in sys.path
            if Path(p or ".").resolve() != ROOT / "bench"]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
if str(ROOT / "src") not in sys.path:
    sys.path.insert(1, str(ROOT / "src"))


def parse(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = p.parse_args(argv)
    if a.seconds <= 0:
        p.error("--seconds must be positive")
    return a


def require_chips(n: int):
    """The devices of this process; exits (code 3) where JAX finds no
    TPU or fewer than ``n`` chips."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < n:
        print(f"bench: JAX found {len(devs)} {devs[0].platform} device(s);"
              f" this cell needs {n} TPU chip(s)", file=sys.stderr)
        raise SystemExit(3)
    return devs


def enable_compile_cache(root: Path) -> None:
    """JAX's persistent compilation cache at a fixed directory inside the
    checkout, for every program however quick to compile."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def applies(metric: dict, workload: str, reported: set) -> bool:
    """Whether ``metric`` belongs in this cell's result: listed for it,
    or (with no list) moving an end-to-end metric the cell reports."""
    if "workloads" in metric:
        return workload in metric["workloads"]
    return metric.get("moves") is None or metric["moves"] in reported


class Readings:
    """What a per-layer reader reads: the cell, the driver's numbers, the
    reduced trace, the chip's peaks and the benchmark's counts (the
    module of the configuration's architecture, ``arch``)."""

    def __init__(self, cell, out, reduced, window, peaks, kind, chips):
        self.cfg, self.mix = cell.cfg, cell.mix
        self.chips = chips
        self.data = out["data"]
        self.trace = reduced
        self.window = window
        self.arch = cell.arch
        self._peaks, self._kind = peaks, kind

    def peak(self, what: str) -> float:
        if self._kind not in self._peaks:
            raise KeyError(f"no peaks for device kind {self._kind!r} in "
                           f"bench/peaks.json")
        return float(self._peaks[self._kind][what])


def fmt_check(name, value, limit, op) -> Tuple[bool, str]:
    """Whether a compared number keeps its limit, and a line saying so;
    a number that could not be read (None) fails."""
    ok = value is not None and {"<=": value <= limit, ">=": value >= limit,
                                "==": value == limit}[op]
    return ok, f"check {name} = {value!r} (limit {op} {limit!r}): " \
               f"{'ok' if ok else 'FAILED'}"


def main(argv=None) -> int:
    args = parse(argv)
    from bench import trace_reduce
    from bench.harness import (CompileLog, Profiler, Spans, load_cell,
                               load_module)

    cell, bench = load_cell(ROOT, args.workload, args.seed, args.seconds)
    chips = next(w["chips"] for w in bench["workloads"]
                 if w["name"] == cell.name)
    devs = require_chips(chips)
    enable_compile_cache(ROOT)
    driver = load_module(ROOT / "bench" / "drivers" /
                         f"{cell.mix['driver']}.py",
                         f"bench_driver_{cell.mix['driver']}")
    spans = Spans()
    prof = Profiler(spans, bool(args.trace))
    compiles = CompileLog()
    out = driver.run(cell, spans, prof)
    setup_s = out["data"]["window_start"] - T_START

    e2e = dict(out["e2e"], setup_s=setup_s)
    reported = {m["name"] for m in bench["end_to_end"]
                if applies(m, cell.name, set())}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    metrics, breakdown = {}, None
    unread = []
    if not args.trace:
        for m in bench["end_to_end"]:
            v = e2e[m["name"]] if m["name"] in reported else None
            if v is not None and math.isfinite(v):
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
            elif m["name"] in reported:
                unread.append(m["name"])
    else:
        names = {n for n, _, _ in spans.records}
        events = trace_reduce.load(prof.dir, names)
        shutil.rmtree(prof.dir, ignore_errors=True)
        reduced = trace_reduce.reduce(events)
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        breakdown = {"device_ops": reduced["device_ops"],
                     "idle_gaps": reduced["idle_gaps"]}
        peaks = json.loads((ROOT / "bench" / "peaks.json").read_text())
        r = Readings(cell, out, reduced, (prof.t0, prof.t1), peaks,
                     devs[0].device_kind, len(devs))
        for m in bench["per_layer"]:
            if not applies(m, cell.name, reported):
                continue
            reader = load_module(ROOT / "bench" / "metrics" /
                                 f"{m['name']}.py", f"bench_metric_{m['name']}")
            v = reader.read(r)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    checks, correct = {}, out["failed"] == 0 and not unread
    lines = list(out["info"])
    if unread:
        lines.append(f"no value for {', '.join(unread)}")
    lines.append(f"setup_s {setup_s!r}; memory peak "
                 f"{out['memory_peak_bytes']} bytes; inside the window JAX "
                 f"traced {compiles.count(CompileLog.TRACE, prof.t0, prof.t1)}"
                 f" and compiled "
                 f"{compiles.count(CompileLog.COMPILE, prof.t0, prof.t1)} "
                 f"programs")
    for name, value, limit, op in out["checks"]:
        ok, line = fmt_check(name, value, limit, op)
        correct = correct and ok
        lines.append(line)
        checks[name] = {"value": value, "limit": limit, "op": op}
    print("\n".join(lines), file=sys.stderr, flush=True)
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
