"""decode_roofline: the decode program's share of its roofline.

For each decode step of the traced window, the least time the chip
could take: the larger of its operations over the bf16 peak and the
bytes it must read (bf16 weights of every layer and the head, and the
live K/V rows) over the HBM bandwidth. Their sum over the device time of
the decode program (``decode_step``) in the trace. None where the trace
holds no decode program."""

from bench.trace_reduce import program_seconds


def read(r):
    f, cfg = r.flops, r.cfg
    lo, hi = r.window
    dev = program_seconds(r.trace, "decode_step")
    if dev <= 0:
        return None
    w_bytes = f.weight_bytes_read(cfg, 2)
    peak_f, peak_b = r.peak("bf16_flops_per_s"), r.peak("hbm_bytes_per_s")
    t = 0.0
    for t0, t1, _, decode in r.data["ticks"]:
        if t0 < lo or t1 > hi or not decode:
            continue
        nbytes = w_bytes + f.kv_bytes(cfg, sum(n + 1 for n in decode), 2)
        t += max(f.decode_flops(cfg, decode) / peak_f, nbytes / peak_b)
    return 100.0 * t / dev
