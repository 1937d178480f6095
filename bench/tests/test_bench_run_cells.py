"""The harness end to end at a tiny size on the CPU: the result line of
every kind of cell, traced and not, in a checkout whose tiny cells were
added as new files only."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

from bench.tests.bench_cells import add_cpu_peaks, run_cell
from bench.tests.tiny_root import ROOT, make_root

KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    r = make_root(tmp_path_factory.mktemp("checkout"))
    add_cpu_peaks(r)
    return r


def _cell_metrics(root, workload, kind):
    bench = json.loads((root / "BENCHMARK.json").read_text())
    return {m["name"] for m in bench[kind]
            if workload in m.get("workloads", [workload])}


@pytest.mark.parametrize("workload", ["tiny.train", "tiny.chat"])
def test_result_line(root, workload, monkeypatch, capsys):
    rc, line, err = run_cell(root, workload, monkeypatch, capsys)
    assert rc == 0
    assert list(line) == KEYS          # checks come last
    assert line["correct"] is True, err
    assert set(line["metrics"]) == _cell_metrics(root, workload,
                                                 "end_to_end")
    for m in line["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        line["device"])
    # each compared number is printed beside its limit, last on stderr
    last = err.strip().splitlines()[-len(line["checks"]):]
    for (name, c), text in zip(line["checks"].items(), last):
        assert text.startswith(f"check {name} = ") and "limit" in text


@pytest.mark.parametrize("workload", ["tiny.train", "tiny.chat"])
def test_traced_line(root, workload, monkeypatch, capsys):
    rc, line, err = run_cell(root, workload, monkeypatch, capsys, trace=1)
    assert rc == 0 and line["correct"] is True, err
    assert set(line["metrics"]) == _cell_metrics(root, workload,
                                                 "per_layer")
    assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    assert list(line)[-1] == "checks"


def test_added_metric_is_read(root, monkeypatch, capsys):
    """A per-layer metric added as one file and one entry is reported."""
    _, line, _ = run_cell(root, "tiny.train", monkeypatch, capsys, trace=1)
    assert line["metrics"]["tiny_steps.train"]["value"] >= 1


@pytest.mark.parametrize("workload", ["tiny.train_kill", "tiny.chat_kill"])
def test_nic_kill_cells_fall_back(root, workload, monkeypatch, capsys):
    rc, line, err = run_cell(root, workload, monkeypatch, capsys,
                             seconds=1.5)
    assert line["correct"] is True, err
    assert line["checks"]["fallbacks"]["value"] >= 1


def test_refuses_without_a_tpu(root, monkeypatch, capsys):
    from bench import run
    monkeypatch.setattr(run, "ROOT", root)
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", "tiny.train", "--seed", "1", "--seconds",
                  "1"])
    assert e.value.code != 0
    assert capsys.readouterr().out == ""


def test_benchmark_files_alone_do_not_run(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's paths
    exits non-zero and prints no result."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "train.yi-6b.healthy",
         "--seed", "1", "--seconds", "1"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
