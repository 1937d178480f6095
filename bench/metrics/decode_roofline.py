"""decode_roofline: the decode program's share of its roofline.

For each decode step of the traced window, the least time the chip
could take: the larger of its operations over the bf16 peak and the
bytes it must read in bf16 (for a dense decoder the weights of every
layer and the head, and the live K/V rows) over the HBM bandwidth, both
counted by the configuration's architecture, ``bench/archs/<class>.py``.
Their sum over the device time of the decode program (``decode_step``)
in the trace. None where the trace holds no decode program."""

from bench.trace_reduce import program_seconds


def read(r):
    a, cfg = r.arch, r.cfg
    lo, hi = r.window
    dev = program_seconds(r.trace, "decode_step")
    if dev <= 0:
        return None
    peak_f, peak_b = r.peak("bf16_flops_per_s"), r.peak("hbm_bytes_per_s")
    t = 0.0
    for t0, t1, _, decode in r.data["ticks"]:
        if t0 < lo or t1 > hi or not decode:
            continue
        t += max(a.decode_flops(cfg, decode) / peak_f,
                 a.decode_bytes(cfg, decode, 2) / peak_b)
    return 100.0 * t / dev
