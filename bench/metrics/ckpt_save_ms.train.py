"""ckpt_save_ms.train: host milliseconds in the window inside the
program's ``ckpt.save`` spans: the trainer's state snapshot to the host
and its write, on the trainer's thread (0 where nothing was saved)."""

from bench.program_spans import total


def read(r):
    secs = total(r, "ckpt.save")
    return None if secs is None else 1e3 * secs
