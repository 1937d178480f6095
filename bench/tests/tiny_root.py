"""A checkout for tests: a copy of ``bench/`` plus tiny cells added as
new files and new ``BENCHMARK.json`` entries only, the way a later
change adds a configuration, an architecture, a traffic mix or a
per-layer metric."""

from __future__ import annotations

import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent

#: limits of the tiny cells, set from CPU readings of sound runs at
#: these sizes (loss gap ~2e-4, change gap ~1e-3, the post-fault step's
#: change gap ~4e-4, logit gap 0) with room above, under what the faults
#: read (the exchange left out after the fallback: 0.026-0.061)
TINY_LIMITS = {
    "train": {"loss_gap": 2e-3, "change_gap": 2e-2, "moved_floor": 1e-3},
    "serve": {"logit_gap": 0.05},
}
TINY_LIMITS["train_kill"] = dict(TINY_LIMITS["train"],
                                 fault_change_gap=5e-3)


def tiny_config(src: str, name: str, layers: int) -> dict:
    cfg = json.loads((BENCH / "configs" / f"{src}.json").read_text())
    cfg.update(name=name, hidden_size=64, intermediate_size=128,
               num_attention_heads=4, num_key_value_heads=2, head_dim=16,
               num_hidden_layers=layers, vocab_size=256)
    return cfg


#: an architecture module's interface: the program's ``ModelConfig``, the
#: float32 reference, and the counts of operations and bytes
ARCH_FUNCTIONS = ("model_config", "init_params", "sequence_logits",
                  "sequence_loss", "train_flops_per_token", "prefill_flops",
                  "decode_flops", "decode_bytes")

#: an architecture added as one file: the Llama module's functions under
#: another class name, each call counted in ``CALLS``
TINY_ARCH = '''"""TinyForCausalLM: the Llama functions, each call counted."""

import collections
from pathlib import Path

from bench.harness import load_module

_llama = load_module(Path(__file__).with_name("LlamaForCausalLM.py"),
                     "tiny_llama")
CALLS = collections.Counter()


def _counted(name):
    inner = getattr(_llama, name)

    def counted(*args, **kwargs):
        CALLS[name] += 1
        return inner(*args, **kwargs)
    return counted


for _name in %r:
    globals()[_name] = _counted(_name)
''' % (ARCH_FUNCTIONS,)


def make_root(tmp: Path) -> Path:
    """``tmp`` as a checkout holding the benchmark and seven tiny cells:
    ``tiny.train``, ``tiny.train_kill``, ``tiny.chat`` and
    ``tiny.chat_kill``; ``tiny.arch.train`` and ``tiny.arch.chat`` on
    the architecture ``TinyForCausalLM``, added as one file;
    ``tiny.noarch.chat``, whose architecture has no module; and one
    added per-layer metric, ``tiny_steps.train``."""
    shutil.copytree(BENCH, tmp / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    b = tmp / "bench"
    (b / "archs" / "TinyForCausalLM.py").write_text(TINY_ARCH)
    for src, name, layers, cls in (
            ("yi-6b.l1v8k", "tiny.yi", 1, None),
            ("deepseek-67b.l2", "tiny.ds", 2, None),
            ("yi-6b.l1v8k", "tiny.arch", 1, "TinyForCausalLM"),
            ("yi-6b.l1v8k", "tiny.noarch", 1, "NoSuchForCausalLM")):
        cfg = tiny_config(src, name, layers)
        if cls:
            cfg["architectures"] = [cls]
        (b / "configs" / f"{name}.json").write_text(json.dumps(cfg))
    for mix in ("ddp_healthy", "ddp_nic_kill"):
        m = json.loads((b / "traffic" / f"{mix}.json").read_text())
        m.update(batch_per_rank=2, seq_len=32, max_chunk_bytes=1 << 14)
        (b / "traffic" / f"tiny_{mix}.json").write_text(json.dumps(m))
    for mix in ("chat_open_loop", "chat_open_loop_nic_kill"):
        m = json.loads((b / "traffic" / f"{mix}.json").read_text())
        m.update(slots=4, prefill_len=32, max_len=48, rate_per_s=4.0,
                 lead_in_s=0.5, lead_in_active=2,
                 prompt_len={"median": 8, "sigma": 1.0, "min": 2, "max": 32},
                 output_len={"median": 6, "sigma": 1.0, "min": 2, "max": 16},
                 check={"min_tokens": 30, "max_requests": 4})
        (b / "traffic" / f"tiny_{mix}.json").write_text(json.dumps(m))
    (b / "metrics" / "tiny_steps.train.py").write_text(
        '"""Steps in the window: a metric added as one new file."""\n\n\n'
        'def read(r):\n    return float(r.data["steps"])\n')
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"] += [
        {"name": name, "source": "tiny", "reduced": [], "why": "tests",
         "file": f"bench/configs/{name}.json"}
        for name in ("tiny.yi", "tiny.ds", "tiny.arch", "tiny.noarch")]
    cells = {"tiny.train": ("tiny.yi", "tiny_ddp_healthy", "train"),
             "tiny.train_kill": ("tiny.yi", "tiny_ddp_nic_kill", "train"),
             "tiny.chat": ("tiny.ds", "tiny_chat_open_loop", "serve"),
             "tiny.chat_kill": ("tiny.ds", "tiny_chat_open_loop_nic_kill",
                                "serve"),
             "tiny.arch.train": ("tiny.arch", "tiny_ddp_healthy", "train"),
             "tiny.arch.chat": ("tiny.arch", "tiny_chat_open_loop", "serve"),
             "tiny.noarch.chat": ("tiny.noarch", "tiny_chat_open_loop",
                                  "serve")}
    for name, (cfg, mix, kind) in cells.items():
        bench["workloads"].append({"name": name, "config": cfg,
                                   "traffic": mix, "chips": 1, "why": "tests"})
        limits = TINY_LIMITS["train_kill" if name == "tiny.train_kill"
                             else kind]
        (b / "limits" / f"{name}.json").write_text(json.dumps(limits))
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            kind = m["workloads"][0].split(".")[0]
            m["workloads"] += [n for n, c in cells.items() if c[2] == kind]
    bench["per_layer"].append(
        {"name": "tiny_steps.train", "unit": "steps", "better": "higher",
         "source": "program_counter", "layer": "trainer",
         "moves": "train_tokens_per_s", "workloads": ["tiny.train"]})
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return tmp
