"""d2h_mb.serve: megabytes per scheduler tick copied from the device to
the host under the program's ``sched.tick`` spans: logits, the K/V
cache, lengths and sampled tokens."""

from bench.program_spans import count, per, summed


def read(r):
    return per(summed(r, "sched.tick", "d2h_bytes"),
               count(r, "sched.tick"), 1e-6)
