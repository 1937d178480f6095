"""mfu.train: model FLOP/s of the traced window over the chips' bf16 peak.

Tokens trained in the window's whole steps per second, times the model's
forward-and-backward operations per token (the counts of the
configuration's architecture, ``bench/archs/<class>.py``: six per
weight of every matrix product including the head, plus causal
attention, nothing recomputed), over the chips times the peak."""


def read(r):
    d = r.data
    if not d["steps"]:
        return None
    per_token = r.arch.train_flops_per_token(r.cfg, r.mix["seq_len"])
    rate = d["tokens"] / d["window_s"]
    return 100.0 * rate * per_token / (r.chips * r.peak("bf16_flops_per_s"))
