"""Read the two readings each correctness limit is set from, on the chip.

    python bench/calibrate.py --workload <name> --seeds 12 --variant-seeds 3 --seconds 20

One process, so the programs compile once: the cell's driver runs on
``--seeds`` seeds and prints the numbers it compares (the lower
readings, from sound runs of the program); then, on the first
``--variant-seeds`` of them, the control and the faults put in the
program's place print theirs (the upper readings). Training: the
reference in bfloat16 (the control), with half of each rank's batch,
without the gradient exchange, and (in a faulted mix) without it in
the first step after the fault alone. Serving: the reference computed in
float8 (the control) and a served token altered, both over the sample
of requests the sound run compared. The benchmark's own runs never run
this; ``PERF.md`` records what it printed and the limits set from it.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path = [p for p in sys.path if Path(p or ".").resolve() != ROOT / "bench"]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def variants_train(cell, driver):
    from bench import reference
    seed = cell.sub_seed("model")
    K = cell.mix["checked_steps"]
    # a faulted mix is compared through the first step after the fault
    fault = cell.mix.get("fault")
    after = fault["after_window_steps"] + 1 if fault else 0
    ref = reference.train_reference(cell.arch, cell.cfg, cell.mix, seed, K,
                                    after)
    out = {}
    for v in ("bf16", "half_batch", "no_exchange") + (
            ("no_exchange_last",) if fault else ()):
        bad = reference.train_reference(cell.arch, cell.cfg, cell.mix, seed,
                                        K, after, variant=v)
        out[v] = driver.compare(bad, ref, cell.limits["moved_floor"])
    return out


def variants_serve(cell, sample):
    import numpy as np

    from bench import reference
    seed, V = cell.sub_seed("model"), cell.cfg["vocab_size"]
    L = cell.mix["max_len"]
    params = cell.arch.init_params(cell.cfg, seed)
    ref = reference.served_logits(cell.arch, params, cell.cfg, sample, L)
    low = reference.served_logits(cell.arch, reference.fp8_weights(params),
                                  cell.cfg, sample, L,
                                  act=reference.fp8_round)
    del params
    picked = [list(np.argmax(lg, axis=-1)) for lg in low]
    altered = [[(t + 1) % V for t in toks] for _, toks in sample]
    return {"fp8": {"logit_gap": reference.widest_gap(ref, picked)},
            "altered_token": {"logit_gap": reference.widest_gap(
                ref, altered)}}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, default=12)
    p.add_argument("--variant-seeds", type=int, default=3)
    p.add_argument("--seconds", type=float, default=20.0)
    p.add_argument("--first-seed", type=int, default=(1 << 32) + 101)
    a = p.parse_args(argv)

    from bench import run
    from bench.harness import Profiler, Spans, load_cell, load_module
    run.require_chips(1)
    run.enable_compile_cache(ROOT)
    for i in range(a.seeds):
        seed = a.first_seed + 7919 * i
        cell, _ = load_cell(ROOT, a.workload, seed, a.seconds)
        driver = load_module(
            ROOT / "bench" / "drivers" / f"{cell.mix['driver']}.py",
            f"bench_driver_{cell.mix['driver']}")
        t0 = time.perf_counter()
        spans = Spans()
        out = driver.run(cell, spans, Profiler(spans, False))
        ok = all(run.fmt_check(*c)[0] for c in out["checks"])
        rec = {"seed": seed, "sound": out["compared"], "correct": ok,
               "wall_s": time.perf_counter() - t0, "info": out["info"]}
        if i < a.variant_seeds:
            rec["variants"] = (variants_train(cell, driver)
                               if cell.mix["driver"] == "train_ddp"
                               else variants_serve(cell, out["sample"]))
        print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
