"""mfu.serve: the whole tick's share of the chip's roofline.

For each scheduler tick of the traced window: the least time the chip
could take for its model work, the larger of its operations over the
bf16 peak and the bytes it must read over the HBM bandwidth. Operations:
one decode token for every live slot (attending to its cache) plus each
admitted prompt's prefill at its true length. Bytes: what one decode
step over the live slots must read in bf16 (for a dense decoder the
weights of every layer and the head, the embedding only gathered, plus
the live rows of the K/V cache). Both counts are the configuration's
architecture's, ``bench/archs/<class>.py``. Summed over the ticks, over
the window's wall time."""


def read(r):
    a, cfg = r.arch, r.cfg
    lo, hi = r.window
    peak_f, peak_b = r.peak("bf16_flops_per_s"), r.peak("hbm_bytes_per_s")
    t = 0.0
    for t0, t1, prefill, decode in r.data["ticks"]:
        if t0 < lo or t1 > hi:
            continue
        ops = a.decode_flops(cfg, decode) + sum(
            a.prefill_flops(cfg, n) for n in prefill)
        t += max(ops / peak_f, a.decode_bytes(cfg, decode, 2) / peak_b)
    return 100.0 * t / (hi - lo) / r.chips
