"""Every file BENCHMARK.json names loads by name, and the description
keeps the benchmark's rules on names, units, keys and sizes."""

from __future__ import annotations

import importlib.util
import json
import re

import pytest

from bench.harness import arch, load_cell
from bench.tests.tiny_root import ARCH_FUNCTIONS, ROOT

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_resolves_by_name(cell):
    c, _ = load_cell(ROOT, cell, seed=1, seconds=1.0)
    w = next(w for w in BENCH["workloads"] if w["name"] == cell)
    assert c.cfg["name"] == w["config"]
    assert (ROOT / "bench" / "drivers" / f"{c.mix['driver']}.py").exists()
    assert c.limits


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_metric_reader_loads(metric):
    path = ROOT / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(f"m_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert callable(mod.read) and mod.__doc__


@pytest.mark.parametrize("config", [c["name"] for c in BENCH["configs"]])
def test_architecture_module_is_complete(config):
    c = next(c for c in BENCH["configs"] if c["name"] == config)
    cfg = json.loads((ROOT / c["file"]).read_text())
    cls = cfg["architectures"][0]
    assert (ROOT / "bench" / "archs" / f"{cls}.py").is_file()
    mod = arch(ROOT, cfg)
    assert all(callable(getattr(mod, f, None)) for f in ARCH_FUNCTIONS), [
        f for f in ARCH_FUNCTIONS if not callable(getattr(mod, f, None))]


def test_configs_state_their_cuts():
    for c in BENCH["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        for key, cut in cfg["reduced"].items():
            assert cfg[key] < cut["published"]
        assert not any(k.endswith(("_dim", "_rank", "_size")) and
                       k != "vocab_size" for k in c["reduced"])


def test_description_keeps_the_rules():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    assert all(UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
               for m in metrics)
    assert all(m["bound"] <= 0.25 for m in BENCH["end_to_end"])
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and "\n" not in m["layer"]
    cells = {w["name"] for w in BENCH["workloads"]}
    assert all(set(m.get("workloads", cells)) <= cells for m in metrics)
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    runs = 2 + 14 * 24
    assert runs * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert len(json.dumps(BENCH)) < 64 * 1024
