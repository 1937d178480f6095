"""The comparison that decides ``correct`` fails what it must.

Through the harness at a tiny size, with the timed path broken
underneath: a training step that returns its state unchanged, half of
each rank's batch left out (the mean over the rest), the gradient
exchange left out, the exchange left out only once SHIFT has fallen
back (in the cell that kills a NIC), and a served token altered where
it is produced.
And the controls, the reference put in the program's place one
precision below the configured one: bfloat16 weights, gradients and
moments for training; float8 weights and matrix inputs for serving."""

from __future__ import annotations

import numpy as np
import pytest

from bench import reference
from bench.drivers import train_ddp
from bench.harness import arch
from bench.tests.bench_cells import run_cell
from bench.tests.tiny_root import ROOT, TINY_LIMITS, make_root, tiny_config


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("checkout"))


def _state_unchanged(mp):
    from repro.train import trainer
    mp.setattr(trainer, "adamw_update", lambda p, g, s, c: (p, s, {}))


def _half_batch(mp):
    from repro.train.trainer import DDPTrainer
    init = DDPTrainer.__init__

    def half(self, *a, **k):
        init(self, *a, **k)
        full = self._grad_fn
        self._grad_fn = lambda p, b: full(
            p, {"tokens": b["tokens"][: b["tokens"].shape[0] // 2]})
    mp.setattr(DDPTrainer, "__init__", half)


def _no_exchange(mp):
    from repro.train.trainer import DDPTrainer
    mp.setattr(DDPTrainer, "_allreduce_grads",
               lambda self, world, run, vecs: None)


def _no_exchange_after_fallback(mp):
    from repro.train.trainer import DDPTrainer
    inner = DDPTrainer._allreduce_grads

    def exchange(self, world, run, vecs):
        own = [v.copy() for v in vecs]
        inner(self, world, run, vecs)
        if sum(lib.stats.fallbacks for lib in self.libs):
            for v, o in zip(vecs, own):
                v[...] = o
    mp.setattr(DDPTrainer, "_allreduce_grads", exchange)


TRAIN_FAULTS = {
    "state_unchanged": (_state_unchanged, "tiny.train"),
    "half_batch": (_half_batch, "tiny.train"),
    "no_exchange": (_no_exchange, "tiny.train"),
    # the NIC-kill cell compares the step whose exchange ran over the
    # fallback
    "no_exchange_after_fallback": (_no_exchange_after_fallback,
                                   "tiny.train_kill"),
}


@pytest.mark.parametrize("fault", list(TRAIN_FAULTS))
def test_training_fault_is_not_correct(root, fault, monkeypatch, capsys):
    plant, cell = TRAIN_FAULTS[fault]
    plant(monkeypatch)
    rc, line, err = run_cell(root, cell, monkeypatch, capsys, seconds=1.5)
    assert rc == 0
    assert line["correct"] is False, err


def test_altered_token_is_not_correct(root, monkeypatch, capsys):
    from repro.serving import TPServeEngine
    decode = TPServeEngine.decode_batch

    def altered(self, feed):
        toks = decode(self, feed)
        toks[0] = (toks[0] + 1) % self.model.cfg.vocab
        return toks
    monkeypatch.setattr(TPServeEngine, "decode_batch", altered)
    rc, line, err = run_cell(root, "tiny.chat", monkeypatch, capsys)
    assert line["correct"] is False, err
    assert line["checks"]["logit_gap"]["value"] > \
        line["checks"]["logit_gap"]["limit"]


TRAIN_MIX = {"ranks": 2, "batch_per_rank": 2, "seq_len": 32, "steps": 10**6,
             "optimizer": {"lr": 1e-3, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
                           "weight_decay": 0.1, "clip_norm": 1.0,
                           "warmup_steps": 10}}


@pytest.mark.parametrize("variant,after", [
    ("bf16", 0), ("half_batch", 0), ("no_exchange", 0),
    ("no_exchange_last", 2)])
def test_training_control_and_faults_in_the_reference(variant, after):
    cfg = tiny_config("yi-6b.l1v8k", "tiny.yi", 1)
    limits = TINY_LIMITS["train_kill" if after else "train"]
    model = arch(ROOT, cfg)
    ref = reference.train_reference(model, cfg, TRAIN_MIX, 7, 3, after)
    bad = reference.train_reference(model, cfg, TRAIN_MIX, 7, 3, after,
                                    variant=variant)
    nums = train_ddp.compare(bad, ref, limits["moved_floor"])
    assert any(nums[k] > limits[k] for k in nums if k in limits), nums


def test_serving_control_in_the_reference():
    cfg = tiny_config("deepseek-67b.l2", "tiny.ds", 2)
    model = arch(ROOT, cfg)
    params = model.init_params(cfg, 11)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 256, size=n, dtype=np.int32)
               for n in (5, 17, 30)]
    # the reference's own greedy continuations: gap 0 by construction
    sample = []
    for p in prompts:
        toks = []
        for _ in range(12):
            lg = reference.served_logits(model, params, cfg,
                                         [(p, toks + [0])], 48)[0]
            toks.append(int(np.argmax(lg[-1])))
        sample.append((p, toks))
    ref = reference.served_logits(model, params, cfg, sample, 48)
    assert reference.widest_gap(ref, [t for _, t in sample]) == 0.0
    low = reference.served_logits(model, reference.fp8_weights(params),
                                  cfg, sample, 48, act=reference.fp8_round)
    picked = [list(np.argmax(lg, axis=-1)) for lg in low]
    gap = reference.widest_gap(ref, picked)
    assert gap > TINY_LIMITS["serve"]["logit_gap"], gap
