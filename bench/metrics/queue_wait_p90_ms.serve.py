"""queue_wait_p90_ms.serve: 90th percentile, over the requests due in
the window, of the time from a request's due time to the start of its
admission (or to the window's close, for one never admitted)."""

import numpy as np


def read(r):
    w = r.data["queue_wait"]
    return 1e3 * float(np.percentile(w, 90)) if w else None
