"""Drive the system's main path once on one TPU chip, at GPT-2 124M width.

Run from the repository root:  python chip_smoke.py

All phases run in this one process (a chip belongs to one process at a
time). The weights are random, made from a seed.

1. training -- the paper's section 5.2 job: data-parallel training over
   SHIFT on a 2-host fabric with 2 NICs per host, GPT-2 124M, 4
   sequences of 1024 tokens per rank, 3 steps. It runs once clean and
   once with host1/mlx5_0 killed after step 1. The faulted run must
   need no restart and fall back at least once, its losses must be
   byte-identical to the clean run's, and both must match a plain
   reference that averages the per-rank gradients with jax.tree_util
   instead of the fabric.
2. serving -- ServeEngine and TPServeEngine over a 2-rank world: batch
   4, prompt 128, 32 greedy tokens. The TP tokens must be
   byte-identical to the local engine's.
3. kernels -- the same model with use_kernels=True: one loss-and-grad
   at the training batch and a few decode steps must match the jnp path
   within bf16 tolerances, and the compiled train step and decode step
   must hold the Pallas kernels (tpu_custom_call in their HLO).

Each phase prints its result and its wall time, taken on the host clock
around work that ends in block_until_ready. The last line of standard
output is one JSON object naming the device. The script exits non-zero
on any failure, and at once, printing no result, where JAX finds no TPU.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import tempfile
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

ARCH = "gpt2-124m"
SEED = 0
N_RANKS, NICS_PER_HOST = 2, 2
FAILED_NIC = "host1/mlx5_0"
BATCH, SEQ_LEN, STEPS, FAIL_AFTER_STEP = 4, 1024, 3, 1
PROMPT_LEN, GEN_TOKENS = 128, 32
KERNEL_DECODE_STEPS = 4

# Fabric DDP vs the tree_util reference: both sum the two ranks'
# float32 gradients once and halve them, so the losses agree to
# float32 rounding; this bound leaves room only for that.
REF_LOSS_RTOL = 1e-5
# Pallas kernels vs the jnp path, bf16 activations: the kernels keep
# their softmax state in float32 where the jnp path rounds some
# operands to bf16 (about 3 significant digits) first.
KERNEL_LOSS_RTOL = 1e-2      # |loss_k - loss_j| / |loss_j|
KERNEL_GRAD_RTOL = 5e-2      # ||g_k - g_j|| / ||g_j|| over all leaves
KERNEL_LOGIT_RTOL = 5e-2     # max |logit_k - logit_j| / max |logit_j|


class PhaseFailed(Exception):
    """A phase ran but its result broke a stated check."""


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise PhaseFailed(what)


def _timed(fn):
    """(fn(), wall seconds) with the result ready on the device."""
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------


def ddp_run(cfg, batch, seq_len, steps, ckpt_dir, fault: bool):
    """One DDP-over-SHIFT run as ``examples/train_ddp_shift.py`` builds
    it. Returns (TrainRun, trainer, wall seconds of each step)."""
    from repro.collectives import JcclWorld
    from repro.core import shift as S
    from repro.core.fabric import build_cluster
    from repro.train.trainer import DDPTrainer, TrainerConfig

    cluster = build_cluster(n_hosts=N_RANKS, nics_per_host=NICS_PER_HOST)
    kv, libs = None, []
    for r in range(N_RANKS):
        lib = S.ShiftLib(cluster, f"host{r}", kv=kv)
        kv = lib.kv
        libs.append(lib)
    world = JcclWorld(cluster, libs, max_chunk_bytes=1 << 20)
    # ckpt_every past the last step: no scheduled save, so the only
    # save is the post-fallback one of a faulted run
    tcfg = TrainerConfig(steps=steps, ckpt_every=steps + 1,
                         ckpt_dir=ckpt_dir, seed=SEED)
    trainer = DDPTrainer(cluster, libs, cfg, tcfg, batch_per_rank=batch,
                         seq_len=seq_len)

    ends = [time.perf_counter()]

    def on_step(step, t, loss):
        # the step's loss is already on the host: its device work is done
        ends.append(time.perf_counter())
        if fault and step == FAIL_AFTER_STEP:
            cluster.fail_nic(FAILED_NIC)

    run = trainer.train(world, on_step=on_step)
    return run, trainer, np.diff(ends).tolist()


def reference_losses(cfg, batch, seq_len, steps, opt_cfg):
    """The same model and data for ``steps`` steps with no fabric: the
    per-rank gradients are averaged by jax.tree_util, then the same
    adamw_update. Returns the per-step mean losses."""
    from repro.data import SyntheticDataset
    from repro.models import build_model
    from repro.optim import adamw_init, adamw_update

    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(SEED))
    opt = adamw_init(params, opt_cfg)
    grad_fn = jax.jit(jax.value_and_grad(model.loss))
    data = [SyntheticDataset(cfg.vocab, seq_len, batch, rank=r,
                             world=N_RANKS, seed=SEED)
            for r in range(N_RANKS)]
    losses = []
    for step in range(steps):
        outs = [grad_fn(params, {"tokens": jnp.asarray(d.batch_at(step))})
                for d in data]
        losses.append(float(np.mean([float(loss) for loss, _ in outs])))
        mean = jax.tree_util.tree_map(lambda *g: sum(g) / N_RANKS,
                                      *[g for _, g in outs])
        params, opt, _ = adamw_update(params, mean, opt, opt_cfg)
    return losses


def train_phase(cfg, batch=BATCH, seq_len=SEQ_LEN, steps=STEPS) -> None:
    """Clean run, faulted run and reference; raises PhaseFailed on a
    broken check."""
    with tempfile.TemporaryDirectory() as ckpt_root:
        clean, trainer, t_clean = ddp_run(
            cfg, batch, seq_len, steps, os.path.join(ckpt_root, "clean"),
            fault=False)
        faulted, ftrainer, t_fault = ddp_run(
            cfg, batch, seq_len, steps, os.path.join(ckpt_root, "faulted"),
            fault=True)
        saves = len(ftrainer.store.list_steps())
    t0 = time.perf_counter()
    ref = reference_losses(cfg, batch, seq_len, steps, trainer.opt_cfg)
    t_ref = time.perf_counter() - t0
    clean_l = np.array([loss for _, _, loss in clean.timeline])
    fault_l = np.array([loss for _, _, loss in faulted.timeline])
    ref_l = np.array(ref)
    ref_err = float(np.max(np.abs(clean_l - ref_l) / np.abs(ref_l)))
    print(f"train: clean losses {clean_l.tolist()}, step walls "
          f"{t_clean} s (step 1 compiles)")
    print(f"train: faulted losses {fault_l.tolist()}, step walls "
          f"{t_fault} s, restarts={faulted.restarts} "
          f"fallbacks={faulted.fallbacks} saves={saves}")
    print(f"train: reference losses {ref_l.tolist()} ({t_ref:.3f} s), "
          f"max rel err {ref_err:.3e} (tolerance {REF_LOSS_RTOL:.0e})")
    _check(clean.final_step == steps and faulted.final_step == steps,
           "a run stopped before its last step")
    _check(faulted.restarts == 0, f"faulted run restarted "
                                  f"{faulted.restarts} time(s)")
    _check(faulted.fallbacks >= 1, "the NIC kill caused no fallback")
    _check(saves <= 1, f"{saves} checkpoint saves, expected at most 1")
    _check(clean_l.tobytes() == fault_l.tobytes(),
           "faulted losses differ from the clean run's")
    _check(bool(np.all(np.isfinite(clean_l))), "non-finite loss")
    _check(ref_err <= REF_LOSS_RTOL, "losses off the tree_util reference")


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------


def serve_phase(cfg, batch=BATCH, prompt_len=PROMPT_LEN,
                gen=GEN_TOKENS) -> None:
    """Local and tensor-parallel greedy generation; the TP tokens must be
    byte-identical to the local engine's."""
    from repro.collectives import build_world
    from repro.models import build_model
    from repro.serving import ServeEngine, TPServeEngine

    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(SEED))
    max_len = prompt_len + gen + 1
    engine = ServeEngine(model, params, max_len=max_len)
    prompts = np.random.RandomState(SEED).randint(
        0, cfg.vocab, size=(batch, prompt_len)).astype(np.int32)
    local, t_local = _timed(lambda: engine.generate(prompts, n_tokens=gen))
    _, _, world = build_world(n_ranks=N_RANKS, probe_interval=5e-4,
                              fast=True)
    tp = TPServeEngine(model, params, world=world, max_len=max_len,
                       local=engine)
    tp_out, t_tp = _timed(lambda: tp.generate(prompts, n_tokens=gen))
    print(f"serve: local {batch}x{gen} tokens in {t_local:.3f} s "
          f"(first call, compile included); TP over {N_RANKS} ranks in "
          f"{t_tp:.3f} s, {tp.sync_rounds} fabric sync rounds")
    _check(local.shape == (batch, prompt_len + gen), "wrong output shape")
    _check(np.array_equal(tp_out, local),
           "TP tokens differ from the local engine's")
    _check(tp.reconstruction_mismatches == 0,
           f"{tp.reconstruction_mismatches} fabric reconstructions "
           f"differ from the local bytes")
    print(f"serve: TP tokens byte-identical to local "
          f"(first row {local[0, prompt_len:prompt_len + 8].tolist()}...)")


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------


def _rel_l2(a_tree, b_tree) -> float:
    """||a - b|| / ||b|| over every leaf of two matching trees."""
    num = den = 0.0
    for a, b in zip(jax.tree_util.tree_leaves(a_tree),
                    jax.tree_util.tree_leaves(b_tree)):
        a = np.asarray(a, np.float64)
        b = np.asarray(b, np.float64)
        num += float(np.sum((a - b) ** 2))
        den += float(np.sum(b ** 2))
    return (num / max(den, 1e-300)) ** 0.5


def kernel_phase(cfg, batch=BATCH, seq_len=SEQ_LEN, prompt_len=PROMPT_LEN,
                 n_decode=KERNEL_DECODE_STEPS) -> None:
    """use_kernels=True against the jnp path: one loss-and-grad at the
    training batch and ``n_decode`` teacher-forced decode steps."""
    from repro.data import SyntheticDataset
    from repro.models import build_model

    mj = build_model(cfg)
    mk = build_model(dataclasses.replace(cfg, use_kernels=True))
    params = mj.init(jax.random.PRNGKey(SEED))
    tokens = SyntheticDataset(cfg.vocab, seq_len, batch, rank=0,
                              world=N_RANKS, seed=SEED).batch_at(0)
    b = {"tokens": jnp.asarray(tokens)}

    def compile_step(model):
        return jax.jit(jax.value_and_grad(model.loss)).lower(
            params, b).compile()

    (step_j, t_cj) = _timed(lambda: compile_step(mj))
    (step_k, t_ck) = _timed(lambda: compile_step(mk))
    n_train_calls = step_k.as_text().count("tpu_custom_call")
    (lj, gj), t_j = _timed(lambda: step_j(params, b))
    (lk, gk), t_k = _timed(lambda: step_k(params, b))
    loss_err = abs(float(lk) - float(lj)) / abs(float(lj))
    grad_err = _rel_l2(gk, gj)
    print(f"kernels: train step loss jnp {float(lj):.6f} pallas "
          f"{float(lk):.6f} (rel err {loss_err:.3e}, tolerance "
          f"{KERNEL_LOSS_RTOL:.0e}); grad rel L2 err {grad_err:.3e} "
          f"(tolerance {KERNEL_GRAD_RTOL:.0e})")
    print(f"kernels: train step {n_train_calls} tpu_custom_call in HLO; "
          f"step jnp {t_j:.4f} s, pallas {t_k:.4f} s (compile jnp "
          f"{t_cj:.1f} s, pallas {t_ck:.1f} s)")

    max_len = prompt_len + n_decode + 1
    prompts = jnp.asarray(tokens[:, :prompt_len])

    def engine(model):
        prefill = jax.jit(lambda p, t: model.prefill(p, t, max_len=max_len))
        return prefill, jax.jit(model.decode_step)

    (pre_j, dec_j), (pre_k, dec_k) = engine(mj), engine(mk)
    logit_j, cache_j = pre_j(params, prompts)
    logit_k, cache_k = pre_k(params, prompts)
    nxt = jnp.argmax(logit_j[:, -1], axis=-1).astype(jnp.int32)[:, None]
    n_decode_calls = dec_k.lower(params, cache_k, nxt).compile().as_text() \
        .count("tpu_custom_call")

    def rel_err(k, j):
        k, j = np.asarray(k, np.float32), np.asarray(j, np.float32)
        return float(np.max(np.abs(k - j)) / np.max(np.abs(j)))

    errs = [rel_err(logit_k, logit_j)]
    t0 = time.perf_counter()
    for _ in range(n_decode):
        # teacher-forced: both paths take the jnp path's greedy token
        nxt = jnp.argmax(logit_j[:, -1], axis=-1).astype(jnp.int32)[:, None]
        logit_j, cache_j = dec_j(params, cache_j, nxt)
        logit_k, cache_k = dec_k(params, cache_k, nxt)
        errs.append(rel_err(logit_k, logit_j))
    t_dec = time.perf_counter() - t0
    print(f"kernels: prefill + {n_decode} decode steps, max logit rel err "
          f"{max(errs):.3e} (tolerance {KERNEL_LOGIT_RTOL:.0e}); decode "
          f"step {n_decode_calls} tpu_custom_call in HLO; {t_dec:.3f} s")
    _check(loss_err <= KERNEL_LOSS_RTOL, "kernel loss off the jnp path")
    _check(grad_err <= KERNEL_GRAD_RTOL, "kernel gradient off the jnp path")
    _check(max(errs) <= KERNEL_LOGIT_RTOL, "kernel logits off the jnp path")
    _check(n_train_calls > 0, "train step holds no compiled kernel")
    _check(n_decode_calls > 0, "decode step holds no compiled kernel")


# ---------------------------------------------------------------------------


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found platform {dev.platform!r}, not a TPU; "
              f"refusing to run on it", file=sys.stderr)
        return 1
    from repro import configs as C
    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}")
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}")
    cfg = C.get_config(ARCH)
    for name, phase in (("train", train_phase), ("serve", serve_phase),
                        ("kernels", kernel_phase)):
        t0 = time.perf_counter()
        try:
            phase(cfg)
        except PhaseFailed as e:
            print(f"{name}: FAILED: {e}", file=sys.stderr)
            return 1
        print(f"{name}: ok, {time.perf_counter() - t0:.3f} s wall "
              f"(measured on {dev.device_kind})", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
