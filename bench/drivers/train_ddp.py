"""Driver ``train_ddp``: data-parallel training over SHIFT.

Builds the job as ``examples/train_ddp_shift.py`` does -- one host per
rank, ``nics_per_host`` NICs each, a ``ShiftLib`` per host, a
``JcclWorld`` -- and drives it only through ``DDPTrainer.train(world,
on_step)``. The trainer's own step count is set far past the window;
``on_step`` reads the correctness numbers in the first steps, opens the
window after them, fails a NIC where the mix asks for it, and ends the
run with :class:`~bench.harness.WindowClosed` at the first step end past
``--seconds`` that is also past the last compared step.

Correctness: the first ``checked_steps`` steps are the reference's too
and, where the mix fails a NIC, the steps through the first one whose
gradient exchange runs after the fault. Compared: each of those steps'
mean loss; per leaf, the norm of the parameters' change over the
checked steps; with a fault, per leaf, the norm of the change that the
first step after it made alone. Each by its worst case, against
:func:`bench.reference.train_reference` run after the window on freed
memory. The norm of the first gradient as AdamW took it (read from its
first moment after step 1) is printed, not compared: no control or
fault moves it far enough from the sound runs' readings.
"""

from __future__ import annotations

import gc
import tempfile
import time
from typing import Any, Dict, List, Optional

import numpy as np

from bench import reference
from bench.harness import (Cell, WindowClosed, device_memory_peak,
                           leaf_paths, wrap_wait_all)


def worst_gap(prog: List[float], ref: List[float],
              keep: List[bool]) -> float:
    """Largest |prog - ref| over the kept leaves, each against the larger
    of its reference norm and the median kept leaf's."""
    ref_kept = [r for r, k in zip(ref, keep) if k]
    med = float(np.median(ref_kept))
    return max(abs(p - r) / max(r, med)
               for p, r, k in zip(prog, ref, keep) if k)


def compare(prog: Dict[str, Any], ref: Dict[str, Any],
            floor: float) -> Dict[str, Optional[float]]:
    """The gaps between the program's numbers and the reference's:
    ``loss_gap``, ``change_gap``, with a fault ``fault_change_gap``, and
    ``grad_gap`` (printed only). Leaves whose reference gradient is
    under ``floor`` times the median leaf's move under Adam by rounding
    alone and are left out of the changes (not of the gradient)."""
    med = float(np.median(ref["first_grad"]))
    moved = [g >= floor * med for g in ref["first_grad"]]
    loss = max(abs(a - b) / abs(b)
               for a, b in zip(prog["losses"], ref["losses"]))
    out = {"loss_gap": float(loss),
           "grad_gap": worst_gap(prog["first_grad"], ref["first_grad"],
                                 [True] * len(moved)),
           "change_gap": worst_gap(prog["change"], ref["change"], moved)}
    if "last_change" in ref:
        # None, and so not correct, where the run ended before that step
        out["fault_change_gap"] = worst_gap(
            prog["last_change"], ref["last_change"], moved) \
            if "last_change" in prog else None
    return out


def run(cell: Cell, spans, prof) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp

    from repro.collectives import JcclWorld
    from repro.core import shift as S
    from repro.core.fabric import build_cluster
    from repro.train.trainer import (DDPTrainer, RestartNeeded,
                                     TrainerConfig)

    mix = cell.mix
    R, B, L = mix["ranks"], mix["batch_per_rank"], mix["seq_len"]
    K = mix["checked_steps"]
    fault = mix.get("fault")
    # the NIC fails at the end of step fault_step; the next step is the
    # first whose exchange runs after the fault, and the last compared
    fault_step = K + fault["after_window_steps"] if fault else None
    last = fault_step + 1 if fault else K
    b1 = mix["optimizer"]["b1"]
    seed = cell.sub_seed("model")

    cluster = build_cluster(n_hosts=R, nics_per_host=mix["nics_per_host"])
    kv, libs = None, []
    for r in range(R):
        lib = S.ShiftLib(cluster, f"host{r}", kv=kv)
        kv = lib.kv
        libs.append(lib)
    world = JcclWorld(cluster, libs, max_chunk_bytes=mix["max_chunk_bytes"])
    wrap_wait_all(world, spans)
    ckpt = tempfile.TemporaryDirectory(prefix="bench-ckpt-")
    tcfg = TrainerConfig(steps=mix["steps"], ckpt_every=mix["steps"] + 1,
                         ckpt_dir=ckpt.name, seed=seed,
                         lr=mix["optimizer"]["lr"])
    trainer = DDPTrainer(cluster, libs, cell.arch.model_config(cell.cfg),
                         tcfg, batch_per_rank=B, seq_len=L)

    # keep a handle on the trainer's live state: it updates the dict that
    # _init_state returned in place, step by step
    live: Dict[str, Any] = {}
    init_state = trainer._init_state

    def capture_state():
        state = init_state()
        live["state"] = state
        live["p0"] = jax.device_get(state["params"])
        return state
    trainer._init_state = capture_state

    norms = jax.jit(lambda t: [jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) for x in jax.tree_util.tree_leaves(t)])
    diff = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.subtract, a, b))
    prog: Dict[str, Any] = {"losses": []}
    ends: List[float] = []
    win: Dict[str, Any] = {}

    def on_step(step, t, loss):
        now = time.perf_counter()
        if step <= last:
            prog["losses"].append(float(loss))
        if step == 1:
            mu = live["state"]["opt"]["mu"]
            prog["first_grad"] = [float(x) / (1 - b1) for x in norms(mu)]
            prog["leaves"] = leaf_paths(mu)
        if step == K:
            p0 = jax.device_put(live.pop("p0"))
            prog["change"] = [float(x) for x in norms(
                diff(live["state"]["params"], p0))]
            del p0
            prof.open()
            win["start"] = time.perf_counter()
            win["span"] = spans.begin("train.step")
            return
        if "start" not in win:
            return
        ends.append(now)
        spans.end(win["span"])
        if step == fault_step:
            # the trainer replaces its parameters each step: holding
            # these keeps them for the change the next step makes
            live["before"] = live["state"]["params"]
            with spans("bench.fail_nic"):
                cluster.fail_nic(fault["nic"])
        if fault and step == last:
            prog["last_change"] = [float(x) for x in norms(
                diff(live["state"]["params"], live.pop("before")))]
        if now - win["start"] >= cell.seconds and step >= last:
            prof.close()
            raise WindowClosed
        win["span"] = spans.begin("train.step")

    restarts = 0
    with spans("bench.train"):
        try:
            trainer.train(world, on_step=on_step)
            run_error = "the trainer stopped before the window closed"
        except WindowClosed:
            run_error = None
        except RestartNeeded:
            restarts, run_error = 1, "the fabric aborted a step (restart)"
    prof.close()
    memory_peak = device_memory_peak()
    steps = len(ends)
    tokens = steps * R * B * L
    window_s = ends[-1] - win["start"] if steps else float("nan")
    fallbacks = sum(l.stats.fallbacks for l in libs)
    recoveries = sum(l.stats.recoveries for l in libs)
    saves = len(trainer.store.list_steps())
    step_walls = np.diff([win["start"]] + ends).tolist()
    fabric_s = spans.total("fabric.wait_all", win["start"],
                           ends[-1] if steps else win["start"])

    # free the program's state before the reference takes the chip
    live.clear()
    del trainer, world, libs, cluster, init_state
    gc.collect()
    ckpt.cleanup()

    ref = reference.train_reference(cell.arch, cell.cfg, mix, seed, K,
                                    last - K)
    nums = compare(prog, ref, cell.limits["moved_floor"])
    lim = cell.limits
    checks = [("loss_gap", nums["loss_gap"], lim["loss_gap"], "<="),
              ("change_gap", nums["change_gap"], lim["change_gap"], "<=")]
    if fault:
        checks.append(("fault_change_gap", nums["fault_change_gap"],
                       lim["fault_change_gap"], "<="))
    checks += [("restarts", restarts, 0, "=="),
               ("fallbacks", fallbacks, 1, ">=") if fault
               else ("fallbacks", fallbacks, 0, "==")]
    info = [f"train: {steps} steps in the window, {tokens} tokens in "
            f"{window_s:.3f} s; step walls {step_walls}",
            f"train: fallbacks {fallbacks}, recoveries {recoveries}, "
            f"checkpoint saves {saves}",
            f"train: losses {prog['losses']} vs reference {ref['losses']}",
            f"train: grad_gap {nums['grad_gap']!r} (not compared)"]
    for what in ("first_grad", "change", "last_change"):
        if what not in ref or what not in prog:
            continue
        info.append(f"train: {what} norms, program/reference: " + ", ".join(
            f"{leaf} {p:.6g}/{r:.6g}" for leaf, p, r in zip(
                prog["leaves"], prog[what], ref[what])))
    if run_error:
        info.append(f"train: {run_error}")
    return {
        "e2e": {"train_tokens_per_s": tokens / window_s},
        "data": {"steps": steps, "tokens": tokens, "window_s": window_s,
                 "fabric_s": fabric_s, "window_start": win.get("start")},
        "checks": checks, "attempted": steps,
        "failed": 0 if run_error is None else 1,
        "memory_peak_bytes": memory_peak, "info": info,
        "compared": nums,
    }
