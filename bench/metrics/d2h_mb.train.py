"""d2h_mb.train: megabytes per step copied from the device to the host
under the program's ``trainer.step`` spans (the ranks' losses and
gradients), less those of a checkpoint's snapshot, which
``ckpt_save_ms.train`` reads."""

from bench.program_spans import count, per, summed


def read(r):
    step = summed(r, "trainer.step", "d2h_bytes")
    if step is None:
        return None
    ckpt = summed(r, "ckpt.save", "d2h_bytes") or 0.0
    return per(step - ckpt, count(r, "trainer.step"), 1e-6)
