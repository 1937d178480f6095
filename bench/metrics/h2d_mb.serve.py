"""h2d_mb.serve: megabytes per scheduler tick copied from the host to
the device under the program's ``sched.tick`` spans: prompts, fed
tokens and the logits rebuilt from the fabric's bytes."""

from bench.program_spans import count, per, summed


def read(r):
    return per(summed(r, "sched.tick", "h2d_bytes"),
               count(r, "sched.tick"), 1e-6)
