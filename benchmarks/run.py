"""Benchmark driver — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (quick-mode defaults so the
full suite completes in minutes; each module's ``main()`` runs the full
configuration standalone).

``--smoke`` runs a reduced deterministic subset — the fault-scenario
campaign (pingpong workload over the full library), the concurrent-
collective overlap smoke (overlap_allreduce + bucketed-overlapped DDP
with >= 4 works in flight), the fault-tolerant TP serving smoke
(request-level invariants under rail kills, both datapaths), the
mixed latency-class smoke (priority scheduling under faults), the
asymmetric-topology smoke (hierarchical allreduce on a 2-pod world
under DCN degradation/partition scenarios) and fig7 — and exits
non-zero on any invariant violation: the fast CI pass.

``--matrix-md PATH`` additionally appends the per-class completion-
latency p50/p99 table (the mixed workload's class histograms) to the
campaign-matrix markdown for the CI job summary.

``--bench-json PATH`` additionally runs the tracked perf suite
(``benchmarks/perf_suite.py``), writes its JSON to PATH, and exits
non-zero on a >20% regression vs the committed baseline at PATH (which
is read before being overwritten).

``--policy-matrix-md PATH`` runs the policy-comparison campaign (every
fixed fault policy + adaptive over the full 6-scenario policy matrix,
DESIGN.md §12), writes the recovered-throughput markdown table plus the
dominance summary to PATH for the CI job summary, and exits non-zero if
any cell violates invariants or the adaptive policy misses the
dominance floors (aggregate >= best fixed, >= 0.9x per cell).

``--fuzz-heavy`` runs the randomized fault-fuzz suite
(``tests/test_fault_fuzz.py``) at heavy example counts
(``REPRO_FUZZ_EXAMPLES``) — the scheduled/manual deep pass; PR CI runs
the same suite at its bounded defaults via pytest."""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))), "src"))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def fig5_throughput_rows():
    from benchmarks import fig5_throughput
    rows = fig5_throughput.main(quick=True)
    out = []
    for name, pre, dur, post, _ in rows:
        out.append((name, float("nan"),
                    f"pre={pre:.1f}Gbps|during={dur:.1f}|post={post:.1f}"))
    return out


def fig6_fallback_rows():
    from benchmarks import fig6_fallback_latency
    rows = fig6_fallback_latency.main(quick=True)
    return [(name, ms * 1e3, f"{ms:.3f}ms" + (f"|{status}" if status else ""))
            for name, ms, status in rows]


def fig7_verbs_rows():
    from benchmarks import fig7_verb_overhead
    rows = fig7_verb_overhead.main(quick=True)
    return [(name, sh, f"std={std:.2f}us|ratio={ratio:.2f}")
            for name, std, sh, ratio in rows]


def table2_latency_rows():
    from benchmarks import table2_write_latency
    rows = table2_write_latency.main(quick=True)
    return [(name, m, f"std={s:.2f}") for name, m, s in rows]


def fig8_training_rows():
    from benchmarks import fig8_training
    rows = fig8_training.main(quick=True)
    out = []
    for (name, t_final, restarts, fallbacks, recoveries,
         resched, retrain, loss) in rows:
        out.append((name, t_final * 1e6,
                    f"restarts={restarts}|fallbacks={fallbacks}|"
                    f"recov={recoveries}|slowdown={resched + retrain:.1f}s|"
                    f"loss={loss:.3f}"))
    return out


def _violation_status(violations):
    # the derived column is one CSV field: keep commas out of it
    return "VIOLATED:" + ";".join(v.replace(",", ";") for v in violations)


def campaign_rows(smoke: bool = False, fast: bool = True):
    """Scenario-campaign section: one row per (scenario, workload) cell.
    ``fast=False`` drives every workload on the legacy per-WQE datapath
    (CI runs the smoke in both modes)."""
    from repro.scenarios import SCENARIOS, Campaign

    workloads = ("pingpong",) if smoke else (
        "pingpong", "allreduce", "broadcast", "all_to_all")
    kw = {"max_rounds": 2000, "fast": fast}
    campaign = Campaign(list(SCENARIOS.values()), workloads=workloads,
                        workload_kw={"pingpong": {"fast": fast},
                                     "allreduce": dict(kw),
                                     "broadcast": dict(kw),
                                     "all_to_all": dict(kw)})
    results = campaign.run()
    out = []
    for r in results:
        lat_us = max(r.fallback_latencies) * 1e6 if r.fallback_latencies \
            else float("nan")
        status = "ok" if r.ok else _violation_status(r.violations)
        out.append((f"campaign/{r.scenario}/{r.workload}", lat_us,
                    f"{status}|fb={r.fallbacks}|rec={r.recoveries}|"
                    f"events={r.event_count}"))
    return out


def overlap_rows(fast: bool = True):
    """Concurrent-collective smoke: the overlap_allreduce workload (>= 4
    async works per round, faults landing mid-overlap) over a
    representative scenario subset, plus — fast mode only, the trainer
    is too heavy for the legacy event chain in a smoke pass — the
    bucketed-overlapped DDP workload with ``bucket_bytes`` small enough
    to force >= 4 concurrent gradient buckets per step. The invariants
    fail any run that never actually overlapped."""
    from repro.scenarios import SCENARIOS, run_scenario

    cells = [("overlap_allreduce", n, {"max_rounds": 400, "fast": fast})
             for n in ("baseline_clean", "sender_nic_down",
                       "link_flap_train", "rail_kill_striped",
                       "double_rail_outage")]
    if fast:
        # flap cells enabled by anchor-only fault rebasing (the outage
        # durations survive the rebase, so the flap actually bites)
        cells += [("ddp_bucketed", n, {"fast": fast})
                  for n in ("baseline_clean", "sender_nic_down",
                            "link_flap_train")]
    out = []
    for workload, name, kw in cells:
        r = run_scenario(SCENARIOS[name], workload=workload, **kw)
        lat_us = max(r.fallback_latencies) * 1e6 if r.fallback_latencies \
            else float("nan")
        status = "ok" if r.ok else _violation_status(r.violations)
        out.append((f"overlap/{r.scenario}/{r.workload}", lat_us,
                    f"{status}|fb={r.fallbacks}|peak={r.peak_concurrency}|"
                    f"events={r.event_count}"))
    return out


def hooked_rows(fast: bool = True):
    """Issue-as-produced DDP smoke: the ``ddp_hooked`` workload (each
    gradient bucket's allreduce fired the moment the modeled backward
    produces its last leaf, DESIGN.md §13) under a clean fabric, a NIC
    death and a striped rail kill landing mid-backward. Byte-identity
    vs the clean post-backward reference is checked inside the
    workload (any divergence counts as a payload mismatch and fails
    the invariants). Runs on BOTH datapaths — the workload rides
    JcclWorld, which honours ``fast`` — with a short step count so the
    legacy event chain stays affordable in a smoke pass."""
    from repro.scenarios import SCENARIOS, run_scenario

    names = ("baseline_clean", "sender_nic_down", "rail_kill_striped")
    out = []
    for name in names:
        r = run_scenario(SCENARIOS[name], workload="ddp_hooked",
                         steps=3, fast=fast)
        lat_us = max(r.fallback_latencies) * 1e6 if r.fallback_latencies \
            else float("nan")
        status = "ok" if r.ok else _violation_status(r.violations)
        peaks = "/".join(str(p) for p in r.step_peak_works)
        out.append((f"hooked/{r.scenario}", lat_us,
                    f"{status}|fb={r.fallbacks}|"
                    f"ovl={r.overlap_fraction:.3f}|peaks={peaks}|"
                    f"mismatch={r.payload_mismatches}|"
                    f"events={r.event_count}"))
    return out


def serving_rows(fast: bool = True):
    """Fault-tolerant TP serving smoke: the continuous-batching serving
    workload (per-step logits/activation gathers, request-level
    invariants) over the scenario subset the ISSUE-6
    acceptance names — including the unmaskable double outage, which
    must fail requests loudly rather than corrupt tokens. Runs on both
    datapaths (the workload rides JcclWorld, which honours ``fast``)."""
    from repro.scenarios import SCENARIOS, run_scenario

    names = ("baseline_clean", "sender_nic_down", "nic_down_permanent",
             "link_flap_train", "rail_kill_striped", "double_rail_outage")
    out = []
    for name in names:
        r = run_scenario(SCENARIOS[name], workload="serving", fast=fast)
        lat_us = max(r.fallback_latencies) * 1e6 if r.fallback_latencies \
            else float("nan")
        status = "ok" if r.ok else _violation_status(r.violations)
        out.append((f"serving/{r.scenario}", lat_us,
                    f"{status}|fb={r.fallbacks}|"
                    f"req={r.requests_done}/{r.requests_total}|"
                    f"tokmis={r.token_mismatches}|"
                    f"events={r.event_count}"))
    return out


def mixed_rows(fast: bool = True):
    """Mixed latency-class smoke: the ``mixed`` workload (bulk gradient
    buckets + a latency-critical gather issued last each round + a real
    CheckpointStore streaming background broadcasts) under clean, NIC-
    down and rail-kill scenarios. The invariants fail any run where
    priority broke byte-identity/exactly-once or starved a class."""
    from repro.scenarios import SCENARIOS, run_scenario

    names = ("baseline_clean", "sender_nic_down", "rail_kill_striped")
    out = []
    for name in names:
        r = run_scenario(SCENARIOS[name], workload="mixed", fast=fast)
        status = "ok" if r.ok else _violation_status(r.violations)
        cl = r.class_latency or {}
        crit_p99 = cl.get("latency_critical", {}).get("p99_virtual_ms", 0)
        counts = "/".join(f"{k}:{s['count']}" for k, s in sorted(cl.items()))
        out.append((f"mixed/{r.scenario}", float("nan"),
                    f"{status}|fb={r.fallbacks}|rounds={r.rounds}|"
                    f"crit_p99={crit_p99}ms|{counts}"))
    return out


def hierarchical_rows(fast: bool = True):
    """Asymmetric-topology smoke: the hierarchical_allreduce workload
    (two-tier reduce-scatter / compressed cross-pod exchange /
    all-gather on a 2-pod world, DESIGN.md §11) under a clean fabric,
    a 4x DCN bandwidth degradation (must ride it out with ZERO
    fallbacks) and a transient-blip-then-permanent DCN partition (must
    fail over dcn0 -> dcn1). Honours ``fast`` so CI covers both
    datapaths. The payload invariant is byte-identity across ranks
    plus closeness to the true sum within the int8 error-feedback
    bound."""
    from repro.scenarios import SCENARIOS, run_scenario

    names = ("baseline_clean", "dcn_degrade", "dcn_partition_transient")
    out = []
    for name in names:
        r = run_scenario(SCENARIOS[name],
                         workload="hierarchical_allreduce", fast=fast)
        lat_us = max(r.fallback_latencies) * 1e6 if r.fallback_latencies \
            else float("nan")
        status = "ok" if r.ok else _violation_status(r.violations)
        out.append((f"hierarchical/{r.scenario}", lat_us,
                    f"{status}|fb={r.fallbacks}|rounds={r.rounds}|"
                    f"events={r.event_count}"))
    return out


def class_latency_markdown(fast: bool = True):
    """Per-class completion-latency p50/p99 table for the CI job summary
    (published alongside the campaign matrix): the ``mixed`` workload on
    a clean fabric and under a striped rail kill, one row per latency
    class. Returns ``(markdown, n_violations)``."""
    from repro.scenarios import SCENARIOS, run_scenario

    names = ("baseline_clean", "rail_kill_striped")
    lines = [
        "## Per-class completion latency (mixed workload)",
        "",
        "| scenario | class | works | p50 (virtual ms) "
        "| p99 (virtual ms) |",
        "|---|---|---|---|---|",
    ]
    n_viol = 0
    for name in names:
        r = run_scenario(SCENARIOS[name], workload="mixed", fast=fast)
        n_viol += len(r.violations)
        for klass in ("latency_critical", "bulk", "background"):
            s = (r.class_latency or {}).get(klass, {})
            lines.append(
                f"| {name} | {klass} | {s.get('count', 0)} | "
                f"{s.get('p50_virtual_ms', '-')} | "
                f"{s.get('p99_virtual_ms', '-')} |")
    lines += ["",
              f"**{n_viol} invariant violations in mixed-class cells.**",
              ""]
    return "\n".join(lines), n_viol


def ddp_overlap_markdown(fast: bool = True):
    """Per-step peak-in-flight gradient works table for the CI job
    summary (published alongside the campaign matrix): the overlapped
    DDP workloads — post-backward ``ddp_bucketed`` and
    issue-as-produced ``ddp_hooked`` — under a clean fabric and two
    fault scenarios, one row per cell with ``TrainRun.step_peak_works``
    spelled out step by step, so an overlap regression (peaks
    collapsing toward 1) is visible in the summary, not just in the
    ``ddp_hook_overlap`` bench gate. Returns ``(markdown,
    n_violations)``."""
    from repro.scenarios import SCENARIOS, run_scenario

    names = ("baseline_clean", "sender_nic_down", "link_flap_train")
    lines = [
        "## DDP overlap (peak in-flight gradient works per step)",
        "",
        "| scenario | workload | peak works by step | overlap fraction "
        "| status |",
        "|---|---|---|---|---|",
    ]
    n_viol = 0
    for workload in ("ddp_bucketed", "ddp_hooked"):
        for name in names:
            r = run_scenario(SCENARIOS[name], workload=workload,
                             fast=fast)
            n_viol += len(r.violations)
            peaks = " ".join(str(p) for p in r.step_peak_works) or "-"
            ovl = (f"{r.overlap_fraction:.3f}"
                   if workload == "ddp_hooked" else "-")
            status = ("ok" if r.ok else "**VIOLATED**: "
                      + "; ".join(v.replace("|", "/")
                                  for v in r.violations[:2]))
            lines.append(f"| {name} | {workload} | {peaks} | {ovl} | "
                         f"{status} |")
    lines += ["",
              f"**{n_viol} invariant violations in DDP overlap cells.**",
              ""]
    return "\n".join(lines), n_viol


def matrix_markdown(fast: bool = True, max_rounds: int = 1200):
    """Run the FULL scenario x workload campaign matrix and render it as
    a GitHub-flavoured markdown table (one row per scenario, one column
    per workload). Returns ``(markdown, n_violations)`` — CI publishes
    the table as a job summary so the docs' "0 violations" claim is
    continuously re-verified, not aspirational."""
    from repro.scenarios import SCENARIOS, Campaign

    workloads = ("pingpong", "allreduce", "overlap_allreduce",
                 "broadcast", "all_to_all")
    campaign = Campaign(
        list(SCENARIOS.values()), workloads=workloads,
        workload_kw={w: ({"fast": fast} if w == "pingpong"
                         else {"fast": fast, "max_rounds": max_rounds})
                     for w in workloads})
    results = campaign.run()
    cells = {(r.scenario, r.workload): r for r in results}
    lines = [
        "## Campaign matrix "
        f"({len(SCENARIOS)} scenarios x {len(workloads)} workloads, "
        f"{'fast' if fast else 'legacy'} datapath)",
        "",
        "| scenario | " + " | ".join(workloads) + " |",
        "|---|" + "---|" * len(workloads),
    ]
    n_viol = 0
    for name in SCENARIOS:
        row = [name]
        for w in workloads:
            r = cells[(name, w)]
            if r.ok:
                row.append(f"ok (fb={r.fallbacks})")
            else:
                n_viol += len(r.violations)
                row.append("**VIOLATED**: "
                           + "; ".join(v.replace("|", "/")
                                       for v in r.violations[:2]))
        lines.append("| " + " | ".join(row) + " |")
    lines += ["",
              f"**{len(results)} cells, {n_viol} invariant violations.**",
              ""]
    return "\n".join(lines), n_viol


def policy_matrix_markdown(max_rounds: int = 800):
    """Run the FULL policy-comparison matrix (every fixed policy +
    adaptive x the 6-scenario policy set) and render the recovered-
    throughput table plus the dominance summary as GitHub-flavoured
    markdown. Returns ``(markdown, failed)`` — ``failed`` is True when
    any cell violated invariants or the adaptive policy missed a
    dominance floor (the same floors ``perf_suite`` gates in
    ``BENCH_core.json``, here over the full matrix)."""
    from benchmarks.perf_suite import (POLICY_MIN_AGGREGATE_RATIO,
                                       POLICY_MIN_CELL_RATIO)
    from repro.policy import POLICIES
    from repro.scenarios import (POLICY_SCENARIOS, policy_dominance,
                                 run_policy_matrix)

    matrix = run_policy_matrix(max_rounds=max_rounds)
    dom = policy_dominance(matrix)
    lines = [
        "## Policy-comparison matrix "
        f"({len(POLICY_SCENARIOS)} scenarios x {len(POLICIES)} policies, "
        "recovered rounds/virtual-s; violating cells score 0)",
        "",
        "| scenario | " + " | ".join(POLICIES) + " |",
        "|---|" + "---|" * len(POLICIES),
    ]
    n_viol = 0
    for name in POLICY_SCENARIOS:
        row = [name]
        for p in POLICIES:
            c = matrix[p][name]
            if c["ok"]:
                row.append(f"{c['tput']:.0f} (d={c['decisions']}, "
                           f"fb={c['fallbacks']})")
            else:
                n_viol += len(c["violations"])
                row.append("**VIOLATED**: "
                           + "; ".join(v.replace("|", "/")
                                       for v in c["violations"][:2]))
        lines.append("| " + " | ".join(row) + " |")
    agg = " | ".join(f"{dom['aggregate'][p]:.3f}" for p in POLICIES)
    lines += [
        "",
        "| aggregate (normalized) | " + agg + " |",
        "",
        f"**Dominance:** adaptive aggregate "
        f"{dom['adaptive_aggregate_ratio']:.3f}x best fixed "
        f"(`{dom['best_fixed']}`, floor {POLICY_MIN_AGGREGATE_RATIO}), "
        f"worst cell `{dom['worst_cell']}` at "
        f"{dom['min_cell_ratio']:.3f}x (floor {POLICY_MIN_CELL_RATIO}), "
        f"{n_viol} invariant violations.",
        "",
    ]
    failed = bool(
        n_viol
        or dom["adaptive_aggregate_ratio"] < POLICY_MIN_AGGREGATE_RATIO
        or dom["min_cell_ratio"] < POLICY_MIN_CELL_RATIO)
    return "\n".join(lines), failed


def fuzz_heavy(examples: int = 200) -> int:
    """Run the fault-fuzz suite at a heavy example count (the scheduled
    deep pass; PR CI runs the bounded default via plain pytest)."""
    import subprocess
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    # the child only runs tests: keep it off any accelerator this
    # process may hold
    env = dict(os.environ, REPRO_FUZZ_EXAMPLES=str(examples),
               JAX_PLATFORMS="cpu")
    return subprocess.call(
        [sys.executable, "-m", "pytest", "-q",
         os.path.join(root, "tests", "test_fault_fuzz.py")], env=env)


def main(smoke: bool = False, bench_json: str = None,
         fast: bool = True, matrix_md: str = None,
         policy_matrix_md: str = None, fuzz_examples: int = None) -> int:
    if fuzz_examples:
        return fuzz_heavy(fuzz_examples)
    if policy_matrix_md:
        md, failed = policy_matrix_markdown()
        with open(policy_matrix_md, "w") as f:
            f.write(md)
        print(md)
        print(f"# policy matrix written to {policy_matrix_md}", flush=True)
        return 1 if failed else 0
    if matrix_md:
        md, n_viol = matrix_markdown(fast=fast)
        cl_md, cl_viol = class_latency_markdown(fast=fast)
        dd_md, dd_viol = ddp_overlap_markdown(fast=fast)
        md = md + "\n" + cl_md + "\n" + dd_md
        n_viol += cl_viol + dd_viol
        with open(matrix_md, "w") as f:
            f.write(md)
        print(md)
        print(f"# campaign matrix written to {matrix_md}", flush=True)
        return 1 if n_viol else 0
    if smoke:
        # fig6's scenarios are a subset of the campaign's, so the campaign
        # section already covers them — no separate fig6 pass in smoke
        sections = [
            ("campaign (fault scenarios)",
             lambda: campaign_rows(smoke=True, fast=fast)),
            ("overlap (concurrent collectives + bucketed DDP)",
             lambda: overlap_rows(fast=fast)),
            ("hooked (issue-as-produced DDP)",
             lambda: hooked_rows(fast=fast)),
            ("serving (fault-tolerant TP inference)",
             lambda: serving_rows(fast=fast)),
            ("mixed (latency classes under faults)",
             lambda: mixed_rows(fast=fast)),
            ("hierarchical (asymmetric 2-pod topology)",
             lambda: hierarchical_rows(fast=fast)),
            ("fig7 (verb overhead)", fig7_verbs_rows),
        ]
    else:
        sections = [
            ("fig7 (verb overhead)", fig7_verbs_rows),
            ("table2 (write latency)", table2_latency_rows),
            ("fig6b (fallback latency)", fig6_fallback_rows),
            ("fig5 (throughput failover)", fig5_throughput_rows),
            ("campaign (fault scenarios)", lambda: campaign_rows(fast=fast)),
            ("fig8 (training progress)", fig8_training_rows),
        ]
    print("name,us_per_call,derived")
    violated = False
    for title, fn in sections:
        print(f"# --- {title} ---", flush=True)
        for name, us, derived in fn():
            us_s = f"{us:.3f}" if np.isfinite(us) else ""
            print(f"{name},{us_s},{derived}", flush=True)
            violated = violated or "VIOLATED" in derived
    if violated:
        print("# campaign invariant VIOLATIONS detected", flush=True)
        return 1
    if bench_json:
        from benchmarks import perf_suite
        print("# --- perf suite (tracked baseline) ---", flush=True)
        return perf_suite.emit(bench_json, quick=smoke)
    return 0


if __name__ == "__main__":
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--smoke", action="store_true",
                        help="fast deterministic CI subset (campaign + "
                             "concurrent-collective overlap + fig7)")
    parser.add_argument("--bench-json", default=None, metavar="PATH",
                        help="run the tracked perf suite, write JSON to "
                             "PATH, fail on >20%% regression vs the "
                             "committed baseline")
    parser.add_argument("--legacy-datapath", action="store_true",
                        help="drive campaign workloads on the legacy "
                             "per-WQE event datapath instead of the "
                             "coalescing fast path")
    parser.add_argument("--matrix-md", default=None, metavar="PATH",
                        help="run the FULL scenario x workload matrix "
                             "and write a markdown results table to "
                             "PATH (CI job-summary publication); exits "
                             "non-zero on any invariant violation")
    parser.add_argument("--policy-matrix-md", default=None, metavar="PATH",
                        help="run the policy-comparison campaign (fixed "
                             "policies + adaptive over the policy "
                             "scenario set) and write the recovered-"
                             "throughput markdown table to PATH; exits "
                             "non-zero on invariant violations or a "
                             "dominance-floor miss")
    parser.add_argument("--fuzz-heavy", nargs="?", const=200, default=None,
                        type=int, metavar="EXAMPLES",
                        help="run tests/test_fault_fuzz.py at a heavy "
                             "example count (default 200) instead of the "
                             "benchmark sections")
    args = parser.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(main(smoke=args.smoke, bench_json=args.bench_json,
                  fast=not args.legacy_datapath,
                  matrix_md=args.matrix_md,
                  policy_matrix_md=args.policy_matrix_md,
                  fuzz_examples=args.fuzz_heavy))
