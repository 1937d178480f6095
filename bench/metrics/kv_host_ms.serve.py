"""kv_host_ms.serve: host milliseconds per scheduler tick in the
program's ``tp.kv_rows`` spans: the K/V rows the decode step wrote, cut
from the cache on the device, copied to the host."""

from bench.program_spans import count, per, total


def read(r):
    return per(total(r, "tp.kv_rows"), count(r, "sched.tick"), 1e3)
