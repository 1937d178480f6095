"""expert_hit_pct.serve: the share of the held experts a decode step
computed that some row was routed to, over the window's decode steps.

100 x the sum of ``experts_hit`` over the sum of ``experts_run`` in the
program's ``tp.expert_counts`` spans (one a decode step of a MoE model:
held experts with at least one row, and held experts computed, summed
over the MoE layers; free slots' rows count). The expert layer's useful
work over the work it set out to do. A configuration with no experts
(``moe_num_experts``) has a dense MLP that every row uses: 100. None
where a MoE program has no such span, as a program without the
counter."""

from bench.program_spans import summed


def read(r):
    if "moe_num_experts" not in r.cfg:
        return 100.0
    hit = summed(r, "tp.expert_counts", "experts_hit")
    run = summed(r, "tp.expert_counts", "experts_run")
    if hit is None or not run:
        return None
    return 100.0 * hit / run
