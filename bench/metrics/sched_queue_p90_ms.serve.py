"""sched_queue_p90_ms.serve: 90th percentile, over the requests
submitted in the window, of the program's own ``sched.queued``
interval: submit to the start of admission, or to the window's close
for one not admitted by then."""

import numpy as np

from bench.program_spans import tracer


def read(r):
    t = tracer()
    lo, hi = r.window
    recs = None if t is None else t.records(lo, hi)
    if not recs:
        return None
    waits = [min(hi if t1 is None else t1, hi) - t0
             for name, _, _, t0, t1, _ in recs if name == "sched.queued"]
    return 1e3 * float(np.percentile(waits, 90)) if waits else None
