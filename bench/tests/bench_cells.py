"""Run a cell of a test checkout in this process, with the look for a
chip replaced by JAX's CPU devices and no persistent compile cache."""

from __future__ import annotations

import json
from pathlib import Path

#: past 32 bits, as the driver's seeds are
SEED = (1 << 33) + 12345


def add_cpu_peaks(root: Path) -> None:
    """The test checkout reads the v5e's peaks under JAX's CPU kind, so
    the per-layer readers have numbers to divide by."""
    p = root / "bench" / "peaks.json"
    peaks = json.loads(p.read_text())
    peaks["cpu"] = dict(peaks["TPU v5 lite"], source="test stand-in")
    p.write_text(json.dumps(peaks))


def run_cell(root: Path, workload: str, monkeypatch, capsys, trace=0,
             seed=SEED, seconds=1.0):
    """(exit code, last stdout line as JSON, stderr) of one run."""
    import jax

    from bench import run, trace_reduce
    from repro.core import verbs

    monkeypatch.setattr(run, "ROOT", root)
    monkeypatch.setattr(run, "require_chips", lambda n: jax.devices())
    monkeypatch.setattr(run, "enable_compile_cache", lambda root: None)
    real_load = trace_reduce.load

    def load_with_device(trace_dir, names):
        # a CPU trace has no device plane: stand one op in for it
        ev = real_load(trace_dir, names)
        lo, hi = trace_reduce.window_of(ev)
        mid = lo + (hi - lo) / 4
        ev["devices"] = {"/device:TPU:0": {
            "ops": [("fusion", lo, mid)],
            "modules": [("jit_decode_step", lo, mid)]}}
        return ev
    monkeypatch.setattr(trace_reduce, "load", load_with_device)
    verbs.reset_registries()
    rc = run.main(["--workload", workload, "--seed", str(seed), "--seconds",
                   str(seconds), "--trace", str(trace)])
    out = capsys.readouterr()
    return rc, json.loads(out.out.strip().splitlines()[-1]), out.err
