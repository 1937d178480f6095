"""trainer_ms.train: host milliseconds per step outside the fabric
wait: the step's wall time minus its ``fabric.wait_all`` span, i.e. the
ranks' loss-and-grad calls, gradient copies and flattening, and the
optimizer."""


def read(r):
    d = r.data
    if not d["steps"]:
        return None
    return 1e3 * (d["window_s"] - d["fabric_s"]) / d["steps"]
