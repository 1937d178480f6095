"""The counts of ``bench/archs/LlamaForCausalLM.py`` against counts made
by hand for both configurations, and the readers that divide by them
against the same numbers worked out by hand."""

from __future__ import annotations

import json
import types

import pytest

from bench.harness import arch, load_module
from bench.tests.tiny_root import BENCH, ROOT

llama = load_module(BENCH / "archs" / "LlamaForCausalLM.py", "llama")
PEAKS = json.loads((BENCH / "peaks.json").read_text())
BF16_FLOPS = 197e12
HBM_BYTES = 819e9


def cfg(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def test_yi_train_counts():
    c = cfg("yi-6b.l1v8k")
    # per layer: q 4096*4096, k and v 4096*512 each, o 4096*4096,
    # SwiGLU 3 * 4096*11008
    layer = 16_777_216 + 2 * 2_097_152 + 16_777_216 + 135_266_304
    assert llama.layer_matmul_params(c) == layer == 173_015_040
    head = 4096 * 8000
    assert llama.param_count(c) == 2 * head + layer + 2 * 4096 + 4096
    assert llama.param_count(c) == 238_563_328
    # 6 per weight of the matrix products, plus causal attention: each
    # of the 4096 positions sees (t + 1) keys, 4 * 32 * 128 operations
    # per key forward, three times that with the backward pass
    attn = 3 * 4 * 32 * 128 * (4096 * 4097 // 2) / 4096
    assert llama.train_flops_per_token(c, 4096) == pytest.approx(
        6 * (layer + head) + attn, rel=1e-12)
    assert llama.train_flops_per_token(c, 4096) == pytest.approx(
        1.335386112e9, rel=1e-9)


def test_deepseek_serve_counts():
    c = cfg("deepseek-67b.l2")
    layer = 8192 * 8192 * 2 + 2 * 8192 * 1024 + 3 * 8192 * 22016
    assert llama.layer_matmul_params(c) == layer == 692_060_160
    head = 8192 * 102_400
    # weights a forward step reads in bf16: two layers with their norms,
    # the final norm and the head; not the embedding table
    assert llama.weight_bytes_read(c, 2) == 2 * (
        2 * (layer + 2 * 8192) + 8192 + head)
    # a decode step over caches of 10 and 0 tokens: every row through
    # both layers and the head, attending to 11 and 1 keys in 2 layers
    assert llama.decode_flops(c, [10, 0]) == \
        2 * 2 * (2 * layer + head) + 4 * 64 * 128 * (11 + 1) * 2
    # a 5-token prompt: 15 (query, key) pairs per layer, head once
    assert llama.prefill_flops(c, 5) == \
        2 * 2 * layer * 5 + 4 * 64 * 128 * 15 * 2 + 2 * head
    # keys and values of 7 cached positions: 2 layers x 8 heads x 128
    assert llama.kv_bytes(c, 7, 2) == 2 * 2 * 8 * 128 * 7 * 2


# (layers, matrix weights of a layer, hidden, vocabulary, K/V heads):
# weights a decode step reads, itemsize x (layers x (matrices + two
# norms) + final norm + head), and per cached or new position in every
# layer a key and a value of K/V heads x 128
SIZES = {"yi-6b.l1v8k": (1, 173_015_040, 4096, 8000, 4),
         "deepseek-67b.l2": (2, 692_060_160, 8192, 102_400, 8)}


@pytest.mark.parametrize("name", list(SIZES))
@pytest.mark.parametrize("lens", [[], [0], [10, 0], [1407] * 16,
                                  [255, 3, 48, 1000]])
def test_decode_bytes_are_weights_and_kv(name, lens):
    L, layer, d, V, KV = SIZES[name]
    weights = 2 * (L * (layer + 2 * d) + d + d * V)
    kv = 2 * L * KV * 128 * sum(n + 1 for n in lens) * 2
    assert llama.decode_bytes(cfg(name), lens, 2) == weights + kv


def readings(name, data, window, trace=None, mix=None):
    """The readings a per-layer reader gets, for one fixed window."""
    from bench import run
    c = cfg(name)
    cell = types.SimpleNamespace(cfg=c, mix=mix or {}, arch=arch(ROOT, c))
    return run.Readings(cell, {"data": data}, trace, window, PEAKS,
                        "TPU v5 lite", 1)


def reader(metric):
    return load_module(BENCH / "metrics" / f"{metric}.py", f"m_{metric}")


def test_mfu_train_by_hand():
    data = {"steps": 3, "tokens": 3 * 2 * 4 * 4096, "window_s": 20.0}
    r = readings("yi-6b.l1v8k", data, (0.0, 20.0), mix={"seq_len": 4096})
    per_token = 3.0 * (2 * (1 * 173_015_040 + 4096 * 8000)
                       + 4 * 32 * 128 * (4096 * 4097 // 2) * 1 / 4096)
    want = 100.0 * (98_304 / 20.0) * per_token / (1 * BF16_FLOPS)
    assert reader("mfu.train").read(r) == pytest.approx(want, rel=1e-12)


# scheduler ticks (start, end, admitted prompt lengths, cache lengths of
# the decoded slots); the last ends after the window and is left out
TICKS = [(0.1, 0.2, [5], [10, 0]), (0.3, 0.4, [], [11, 1, 300]),
         (0.5, 0.6, [1024], []), (1.9, 2.1, [7], [3])]


def ds_tick_seconds(prefill, decode):
    """The least time of one tick of deepseek-67b.l2, counted by hand:
    operations of the decoded tokens and the prefills, bytes of the bf16
    weights and the live K/V rows."""
    layer, head = 692_060_160, 8192 * 102_400
    ops = sum(2 * (2 * layer + head) + 4 * 64 * 128 * (n + 1) * 2
              for n in decode)
    ops += sum(2 * 2 * layer * n + 4 * 64 * 128 * (n * (n + 1) // 2) * 2
               + 2 * head for n in prefill)
    nbytes = 2 * (2 * (layer + 2 * 8192) + 8192 + head) \
        + 2 * 2 * 8 * 128 * sum(n + 1 for n in decode) * 2
    return max(ops / BF16_FLOPS, nbytes / HBM_BYTES)


def test_mfu_serve_by_hand():
    r = readings("deepseek-67b.l2", {"ticks": TICKS}, (0.0, 2.0))
    want = 100.0 * sum(ds_tick_seconds(p, d) for _, _, p, d in TICKS[:3]) \
        / 2.0 / 1
    assert reader("mfu.serve").read(r) == pytest.approx(want, rel=1e-12)


def test_decode_roofline_by_hand():
    trace = {"programs": {"jit_decode_step_1": 0.004, "jit_admit_2": 9.0}}
    r = readings("deepseek-67b.l2", {"ticks": TICKS}, (0.0, 2.0),
                 trace=trace)
    want = 100.0 * sum(ds_tick_seconds([], d) for _, _, _, d in TICKS[:2]) \
        / 0.004
    assert reader("decode_roofline").read(r) == pytest.approx(want,
                                                              rel=1e-12)
