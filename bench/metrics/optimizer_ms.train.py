"""optimizer_ms.train: host milliseconds per step in the program's
``trainer.optimizer`` span: the mean gradient back to the device leaf by
leaf and the dispatch of the AdamW update."""

from bench.program_spans import count, per, total


def read(r):
    return per(total(r, "trainer.optimizer"), count(r, "trainer.step"), 1e3)
