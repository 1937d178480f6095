"""fabric_wait_ms.train: host milliseconds per step inside
``JcclWorld.wait_all`` (the harness's ``fabric.wait_all`` span): the
simulated gradient all-reduce. None when the steps waited on none."""


def read(r):
    d = r.data
    if not d["steps"] or d["fabric_s"] <= 0:
        return None
    return 1e3 * d["fabric_s"] / d["steps"]
