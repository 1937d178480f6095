"""An architecture added as one file: a configuration whose
``architectures[0]`` names ``TinyForCausalLM`` runs a train cell and a
chat cell end to end through ``bench/archs/TinyForCausalLM.py`` alone
(the Llama functions, each call counted), and a class with no module
stops the run with a message that names the file to add."""

from __future__ import annotations

import pytest

from bench import harness
from bench.tests.bench_cells import add_cpu_peaks, run_cell
from bench.tests.tiny_root import make_root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    r = make_root(tmp_path_factory.mktemp("checkout"))
    add_cpu_peaks(r)
    return r


#: what each kind of cell calls, in a traced run: the program's model
#: configuration, the reference, and the counts its per-layer readers use
CALLED = {
    "tiny.arch.train": {"model_config", "init_params", "sequence_loss",
                        "train_flops_per_token"},
    "tiny.arch.chat": {"model_config", "init_params", "sequence_logits",
                       "prefill_flops", "decode_flops", "decode_bytes"},
}


@pytest.mark.parametrize("workload", list(CALLED))
def test_added_architecture_runs(root, workload, monkeypatch, capsys):
    loaded = []
    load = harness.arch

    def spy(root, cfg):
        loaded.append(load(root, cfg))
        return loaded[-1]
    monkeypatch.setattr(harness, "arch", spy)
    rc, line, err = run_cell(root, workload, monkeypatch, capsys, trace=1)
    assert rc == 0 and line["correct"] is True, err
    (mod,) = loaded
    assert mod.__file__.endswith("TinyForCausalLM.py")
    assert {f for f, n in mod.CALLS.items() if n} >= CALLED[workload], \
        dict(mod.CALLS)


def test_architecture_without_a_module(root, monkeypatch, capsys):
    with pytest.raises(SystemExit) as e:
        run_cell(root, "tiny.noarch.chat", monkeypatch, capsys)
    assert "bench/archs/NoSuchForCausalLM.py" in str(e.value.code)
    assert capsys.readouterr().out == ""
