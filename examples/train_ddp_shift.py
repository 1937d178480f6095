"""End-to-end driver: data-parallel LM training with SHIFT-protected
gradient all-reduce, surviving a fatal NIC failure mid-run.

Default is a fast reduced model; ``--full`` trains the paper's GPT-2 124M
for ``--steps`` (a few hundred) steps.

Run:  PYTHONPATH=src python examples/train_ddp_shift.py [--full]
          [--steps N] [--fail-at K] [--baseline]
"""

import argparse
import shutil
import sys

sys.path.insert(0, "src")

from repro import configs as C
from repro.collectives import JcclWorld
from repro.core import shift as S
from repro.core.fabric import build_cluster
from repro.launch.compile_cache import enable_compile_cache
from repro.train.trainer import DDPTrainer, RestartNeeded, TrainerConfig, \
    resume_training


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="GPT-2 124M (slow on CPU) instead of the reduced model")
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--fail-at", type=int, default=None)
    ap.add_argument("--ranks", type=int, default=2)
    ap.add_argument("--channels", type=int, default=1,
                    help="stripe gradient collectives across N rails "
                         "(multi-rail channelized JCCL)")
    ap.add_argument("--baseline", action="store_true",
                    help="StandardLib (crash + checkpoint-restart) instead "
                         "of SHIFT")
    args = ap.parse_args()
    enable_compile_cache()
    steps = args.steps or (200 if args.full else 60)
    fail_at = args.fail_at or steps // 3

    cluster = build_cluster(n_hosts=args.ranks,
                            nics_per_host=max(2, args.channels))
    if args.baseline:
        libs = [S.StandardLib(cluster, f"host{r}") for r in range(args.ranks)]
    else:
        kv = None
        libs = []
        for r in range(args.ranks):
            lib = S.ShiftLib(cluster, f"host{r}", kv=kv,
                             config=S.ShiftConfig(
                                 data_rails=max(1, args.channels)))
            kv = lib.kv
            libs.append(lib)
    world = JcclWorld(cluster, libs, max_chunk_bytes=1 << 20,
                      channels=args.channels)

    model_cfg = (C.get_config("gpt2-124m") if args.full else
                 C.smoke_config("gpt2-124m", n_layers=4, d_model=256,
                                n_heads=8, n_kv_heads=8, d_ff=1024,
                                vocab=2048))
    tcfg = TrainerConfig(steps=steps, ckpt_every=max(steps // 5, 5),
                         ckpt_dir="/tmp/repro-train-ddp")
    shutil.rmtree(tcfg.ckpt_dir, ignore_errors=True)
    trainer = DDPTrainer(cluster, libs, model_cfg, tcfg,
                         batch_per_rank=4 if args.full else 2,
                         seq_len=512 if args.full else 64)

    def on_step(step, t, loss):
        if step == fail_at:
            print(f">>> step {step}: killing host1/mlx5_0")
            cluster.fail_nic("host1/mlx5_0")
        if step % 10 == 0 or step == 1:
            print(f"step {step:4d}  t={t:8.2f}s  loss={loss:.4f}")

    try:
        run = trainer.train(world, on_step=on_step)
    except RestartNeeded as rn:
        print(">>> job crashed (baseline); restarting from checkpoint "
              f"(step {rn.step}, +{tcfg.reschedule_time}s reschedule)")
        cluster.recover_nic("host1/mlx5_0")
        libs2 = [S.StandardLib(cluster, f"host{r}")
                 for r in range(args.ranks)]
        world2 = JcclWorld(cluster, libs2, max_chunk_bytes=1 << 20,
                           channels=args.channels)
        run = resume_training(trainer, world2, rn, on_step=on_step)

    t_final, final_step, final_loss = run.timeline[-1]
    print(f"\ndone: {final_step} steps in {t_final:.1f}s (combined "
          f"compute+network), final loss {final_loss:.4f}")
    print(f"restarts={run.restarts} fallbacks={run.fallbacks} "
          f"recoveries={run.recoveries} "
          f"slowdown={run.slowdown_reschedule + run.slowdown_retrain:.1f}s")


if __name__ == "__main__":
    main()
