"""grad_host_ms.train: host milliseconds per step in the program's
``trainer.grads_to_host`` spans: each rank's gradient leaves copied to
the host and flattened into one vector."""

from bench.program_spans import count, per, total


def read(r):
    return per(total(r, "trainer.grads_to_host"),
               count(r, "trainer.step"), 1e3)
