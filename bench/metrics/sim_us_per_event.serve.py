"""sim_us_per_event.serve: host microseconds in the program's
``jccl.wait_all`` spans per event of the fabric simulator they ran: the
simulated logits and K/V all-gathers' cost per event."""

from bench.program_spans import us_per_event


def read(r):
    return us_per_event(r)
