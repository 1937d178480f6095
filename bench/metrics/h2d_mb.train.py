"""h2d_mb.train: megabytes per step copied from the host to the device
under the program's ``trainer.step`` spans: the ranks' batches and the
mean gradient."""

from bench.program_spans import count, per, summed


def read(r):
    return per(summed(r, "trainer.step", "h2d_bytes"),
               count(r, "trainer.step"), 1e-6)
