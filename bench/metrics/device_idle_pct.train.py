"""device_idle_pct.train: share of the traced window in which no
operation ran on the device (1 - union of op intervals / window)."""


def read(r):
    return 100.0 * (1.0 - r.trace["busy_s"] / r.trace["window_s"])
