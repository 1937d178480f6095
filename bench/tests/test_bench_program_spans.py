"""The per-layer metrics read from the program's own spans and counters
(``repro.tracing``), end to end at a tiny size on the CPU, in a checkout
whose tiny cells are listed for them."""

from __future__ import annotations

import json
import math

import jax
import numpy as np
import pytest

from bench.tests.bench_cells import add_cpu_peaks, run_cell
from bench.tests.tiny_root import ROOT, make_root

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
#: per-layer metrics the program's spans and counters feed
PROGRAM = {m["name"] for m in BENCH["per_layer"]
           if m["source"] in ("program_span", "program_counter")}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    r = make_root(tmp_path_factory.mktemp("checkout"))
    add_cpu_peaks(r)
    return r


def _listed(root, workload):
    bench = json.loads((root / "BENCHMARK.json").read_text())
    return {m["name"] for m in bench["per_layer"]
            if m["name"] in PROGRAM and workload in m.get("workloads", [])}


@pytest.mark.parametrize("workload", ["tiny.train", "tiny.chat"])
def test_traced_cell_prints_program_metrics(root, workload, monkeypatch,
                                            capsys):
    rc, line, err = run_cell(root, workload, monkeypatch, capsys, trace=1)
    assert rc == 0 and line["correct"] is True, err
    listed = _listed(root, workload)
    assert len(listed) >= 5 and listed <= set(line["metrics"])
    for name in listed:
        v = line["metrics"][name]["value"]
        # nothing is saved in a healthy window: 0 ms of checkpoint
        ok = v == 0 if name == "ckpt_save_ms.train" else v > 0
        assert math.isfinite(v) and ok, (name, v)


def test_idle_gaps_can_be_named_by_program_spans(root, monkeypatch,
                                                 capsys):
    """The program's spans sit in the profiler's trace: a reduction
    given their names labels the device's idle time by them."""
    from bench import trace_reduce
    from repro import tracing

    real_load = trace_reduce.load
    monkeypatch.setattr(
        trace_reduce, "load",
        lambda d, names: real_load(d, set(names) | tracing.stack_names()))
    rc, line, err = run_cell(root, "tiny.train", monkeypatch, capsys,
                             trace=1)
    assert rc == 0, err
    labels = {k for k, _ in line["breakdown"]["idle_gaps"]}
    assert labels & tracing.stack_names(), labels


def test_train_d2h_is_the_ranks_gradients(root, monkeypatch, capsys):
    from bench.harness import arch
    from repro.models import build_model

    _, line, err = run_cell(root, "tiny.train", monkeypatch, capsys,
                            trace=1)
    cfg = json.loads((root / "bench" / "configs" / "tiny.yi.json")
                     .read_text())
    model = build_model(arch(root, cfg).model_config(cfg))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))
    # two ranks, float32 gradients, plus each rank's 4-byte loss
    assert line["metrics"]["d2h_mb.train"]["value"] == pytest.approx(
        2 * (4 * n + 4) / 1e6, rel=1e-12), err
    assert line["metrics"]["h2d_mb.train"]["value"] > 4 * n / 1e6


def test_checkpoint_save_is_read_after_a_fallback(root, monkeypatch,
                                                  capsys):
    rc, line, err = run_cell(root, "tiny.train_kill", monkeypatch, capsys,
                             trace=1, seconds=1.5)
    assert rc == 0 and line["correct"] is True, err
    assert line["metrics"]["ckpt_save_ms.train"]["value"] > 0
