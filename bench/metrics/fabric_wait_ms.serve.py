"""fabric_wait_ms.serve: host milliseconds per scheduler tick inside
``JcclWorld.wait_all`` (the harness's ``fabric.wait_all`` span): the
simulated logits and K/V all-gathers."""


def read(r):
    d = r.data
    if not d["n_ticks"] or d["fabric_s"] <= 0:
        return None
    return 1e3 * d["fabric_s"] / d["n_ticks"]
