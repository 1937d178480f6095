"""mfu.serve: the whole tick's share of the chip's roofline.

For each scheduler tick of the traced window: the least time the chip
could take for its model work, the larger of its operations over the
bf16 peak and the bytes it must read over the HBM bandwidth. Operations:
one decode token for every live slot (attending to its cache) plus each
admitted prompt's prefill at its true length. Bytes: the bf16 weights
read once (every layer and the head; the embedding is only gathered)
plus the live rows of the K/V cache. Summed over the ticks, over the
window's wall time."""


def read(r):
    f, cfg = r.flops, r.cfg
    lo, hi = r.window
    w_bytes = f.weight_bytes_read(cfg, 2)
    peak_f, peak_b = r.peak("bf16_flops_per_s"), r.peak("hbm_bytes_per_s")
    t = 0.0
    for t0, t1, prefill, decode in r.data["ticks"]:
        if t0 < lo or t1 > hi:
            continue
        ops = f.decode_flops(cfg, decode) + sum(
            f.prefill_flops(cfg, n) for n in prefill)
        nbytes = w_bytes + f.kv_bytes(cfg, sum(n + 1 for n in decode), 2)
        t += max(ops / peak_f, nbytes / peak_b)
    return 100.0 * t / (hi - lo) / r.chips
