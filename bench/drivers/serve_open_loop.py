"""Driver ``serve_open_loop``: tensor-parallel serving under open-loop
arrivals.

``RequestScheduler`` over ``TPServeEngine`` on ``build_world``: each
scheduler tick admits queued requests into free slots (one padded
prefill each) and then decodes every slot once, with the logits and the
new K/V rows all-gathered over the simulated fabric. The harness submits
each request when it is due (:mod:`bench.arrivals`), whether or not the
server keeps up, and stamps every token: the first when the engine's
``admit`` returns it (a wrapper on the engine object built here), later
ones at the end of the tick that produced them.

Set-up: weights from the seed in one jitted call, a warm-up that admits
a request into every slot (every program and slot the window uses), and
the lead-in traffic, so that the window opens at steady occupancy.

Correctness, once the window has closed and the program's state is
freed: a sample of the finished requests drawn from the seed, with the
longest among them, goes through the plain reference's full forward
pass over prompt and served tokens (the float32 model in the module of
the configuration's architecture, ``bench/archs/``); the compared number
is the widest gap by which a served token's reference logit lies below
the best one.
Also held: no request failed, every finished request has exactly its
token count, no fabric reconstruction differed from the local bytes,
and the fabric fell back as often as the mix's fault asks.
"""

from __future__ import annotations

import gc
import time
from typing import Any, Dict, List

import numpy as np

from bench import arrivals, reference
from bench.harness import Cell, device_memory_peak, percentile, wrap_wait_all


def choose_sample(done, seed: int, min_tokens: int, max_requests: int):
    """The longest finished request, then others in an order drawn from
    the seed, until ``min_tokens`` served tokens or ``max_requests``."""
    if not done:
        return []
    done = sorted(done, key=lambda r: (-len(r.tokens), r.rid))
    rest = list(np.random.default_rng(seed).permutation(len(done) - 1) + 1)
    pick, n = [done[0]], len(done[0].tokens)
    for i in rest:
        if n >= min_tokens or len(pick) >= max_requests:
            break
        pick.append(done[i])
        n += len(done[i].tokens)
    return pick


def run(cell: Cell, spans, prof) -> Dict[str, Any]:
    import jax

    from repro.collectives import CollectiveError, build_world
    from repro.models import build_model
    from repro.serving import RequestScheduler, TPServeEngine
    from repro.serving.scheduler import DONE, FAILED

    mix, cfg = cell.mix, cell.cfg
    seed = cell.sub_seed("model")
    model = build_model(cell.arch.model_config(cfg))
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    cluster, libs, world = build_world(
        n_ranks=mix["ranks"], nics_per_host=mix["nics_per_host"], fast=True)
    wrap_wait_all(world, spans)
    engine = TPServeEngine(model, params, world=world,
                           max_len=mix["max_len"])
    sched = RequestScheduler(engine, n_slots=mix["slots"],
                             prefill_len=mix["prefill_len"])

    stamps: Dict[int, Dict[str, Any]] = {}
    tick: Dict[str, List[int]] = {"prefill": [], "decode": []}
    admit_inner, decode_inner = engine.admit, engine.decode_batch

    def admit(slot, prompt):
        req = sched.slots[slot]
        rec = stamps[req.rid]
        rec["admit"] = time.perf_counter()
        with spans("serve.admit"):
            tok = admit_inner(slot, prompt)
        rec["times"].append(time.perf_counter())
        tick["prefill"].append(int(req.prompt.size))
        return tok

    def decode_batch(feed):
        tick["decode"] = [int(r.prompt.size) + len(r.tokens) - 1
                          for r in sched.slots if r is not None]
        with spans("serve.decode"):
            return decode_inner(feed)
    engine.admit, engine.decode_batch = admit, decode_batch

    def submit(prompt, n_out, due, counted):
        req = sched.submit(prompt, n_out)
        stamps[req.rid] = {"due": due, "counted": counted, "times": [],
                           "admit": None}
        return req

    def step():
        """One scheduler tick; stamps the tokens it produced."""
        t0 = time.perf_counter()
        tick["prefill"], tick["decode"] = [], []
        active = [r for r in sched.slots if r is not None] + list(sched.queue)
        with spans("serve.tick"):
            sched.step()
        t1 = time.perf_counter()
        for r in active:
            times = stamps[r.rid]["times"]
            times.extend([t1] * (len(r.tokens) - len(times)))
        return (t0, t1, tick["prefill"], tick["decode"])

    # warm-up: a short request in every slot compiles (or loads) every
    # program and touches every slot index the window will use
    rng = np.random.default_rng(cell.sub_seed("warmup"))
    now = time.perf_counter()
    for _ in range(mix["slots"]):
        submit(rng.integers(0, cfg["vocab_size"], size=mix["prefill_len"],
                            dtype=np.int32), 2, now, False)
    while sched.pending:
        step()

    plan = arrivals.open_loop(mix, cfg["vocab_size"],
                              cell.sub_seed("traffic"), cell.seconds)
    fault = mix.get("fault")
    t_gen = time.perf_counter()
    ws = t_gen + mix["lead_in_s"]
    we = ws + cell.seconds
    ticks, i, aborted, failed_at = [], 0, None, None
    while True:
        now = time.perf_counter()
        if prof.t0 is None and now >= ws:
            prof.open()
        if now >= we:
            break
        while i < len(plan) and t_gen + plan[i].due <= now:
            a = plan[i]
            submit(a.prompt, a.n_out, t_gen + a.due, a.counted)
            i += 1
        if sched.pending:
            if fault and failed_at is None and \
                    now >= ws + fault["at_window_fraction"] * cell.seconds:
                with spans("bench.fail_nic"):
                    cluster.fail_nic(fault["nic"])
                failed_at = now
            try:
                ticks.append(step())
            except CollectiveError as e:
                sched.fail_outstanding()
                aborted = str(e)
                break
        else:
            nxt = t_gen + plan[i].due if i < len(plan) else we
            if prof.t0 is None:
                nxt = min(nxt, ws)
            with spans("serve.wait"):
                time.sleep(max(0.0, min(nxt, we) - now))
    prof.close()
    memory_peak = device_memory_peak()

    # end-to-end numbers over all samples of the window
    ttft, itl, tokens_in = [], [], 0
    queue_wait = []
    for rid, rec in stamps.items():
        times = rec["times"]
        tokens_in += sum(1 for t in times if ws <= t <= we)
        itl += [b - a for a, b in zip(times, times[1:]) if ws <= b <= we]
        if rec["counted"]:
            first = times[0] if times and times[0] <= we else we
            ttft.append(first - rec["due"])
            adm = rec["admit"] if rec["admit"] is not None and \
                rec["admit"] <= we else we
            queue_wait.append(adm - rec["due"])
    reqs = sched.requests
    counted = [r for r in reqs if stamps[r.rid]["counted"]]
    done = [r for r in reqs if r.state == DONE]
    failed = sum(1 for r in reqs if r.state == FAILED)
    count_errors = sum(1 for r in done if len(r.tokens) != r.n_tokens)
    mismatches = engine.reconstruction_mismatches
    fallbacks = sum(l.stats.fallbacks for l in libs)
    win_ticks = [t for t in ticks if t[0] >= ws and t[1] <= we]
    sample = [(r.prompt.copy(), list(r.tokens)) for r in choose_sample(
        done, cell.sub_seed("sample"), mix["check"]["min_tokens"],
        mix["check"]["max_requests"])]
    info = [
        f"serve: {len(counted)} requests due in the window, {len(ttft)} "
        f"time-to-first-token samples, {len(itl)} inter-token gaps, "
        f"{tokens_in} tokens in {cell.seconds} s",
        f"serve: {len(win_ticks)} whole ticks in the window, mean "
        f"{np.mean([b - a for a, b, _, _ in win_ticks]) if win_ticks else 0:.4f} s, "
        f"mean active slots "
        f"{np.mean([len(d) for _, _, _, d in win_ticks]) if win_ticks else 0:.2f}, "
        f"{sum(len(p) for _, _, p, _ in win_ticks)} admissions; "
        f"queue at close {len(sched.queue)}",
        f"serve: fallbacks {fallbacks}, failed {failed}, token-count "
        f"errors {count_errors}, reconstruction mismatches {mismatches}"
        + (f"; aborted: {aborted}" if aborted else "")]

    # free the program's state before the reference takes the chip
    del engine, sched, params, world, libs, cluster, admit_inner, \
        decode_inner
    gc.collect()
    ref_params = cell.arch.init_params(cfg, seed)
    ref_logits = reference.served_logits(cell.arch, ref_params, cfg, sample,
                                         mix["max_len"])
    del ref_params
    gap = reference.widest_gap(ref_logits, [t for _, t in sample])
    info.append(f"serve: reference over {len(sample)} requests, "
                f"{sum(len(t) for _, t in sample)} served tokens")

    lim = cell.limits
    checks = [("logit_gap", gap if sample else None,
               lim["logit_gap"], "<="),
              ("failed", failed, 0, "=="),
              ("count_errors", count_errors, 0, "=="),
              ("mismatches", mismatches, 0, "==")]
    checks.append(("fallbacks", fallbacks, 1, ">=") if fault
                  else ("fallbacks", fallbacks, 0, "=="))
    return {
        "e2e": {"ttft_p90_ms": 1e3 * percentile(ttft, 90),
                "itl_p95_ms": 1e3 * percentile(itl, 95)},
        "data": {"window_start": ws, "ticks": win_ticks,
                 "queue_wait": queue_wait,
                 "fabric_s": spans.total("fabric.wait_all", ws, we),
                 "n_ticks": len(win_ticks)},
        "checks": checks, "attempted": len(counted), "failed": failed,
        "memory_peak_bytes": memory_peak, "info": info,
        "compared": {"logit_gap": gap},
        "sample": sample,
    }
