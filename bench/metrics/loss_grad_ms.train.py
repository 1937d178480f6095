"""loss_grad_ms.train: host milliseconds per step in the program's
``trainer.loss_and_grad`` spans: each rank's batch to the device, the
jitted loss-and-grad and the wait for its loss."""

from bench.program_spans import count, per, total


def read(r):
    return per(total(r, "trainer.loss_and_grad"),
               count(r, "trainer.step"), 1e3)
