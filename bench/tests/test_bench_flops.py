"""bench/flops.py against counts made by hand for both configurations."""

from __future__ import annotations

import json

import pytest

from bench import flops
from bench.tests.tiny_root import BENCH


def cfg(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def test_yi_train_counts():
    c = cfg("yi-6b.l1v8k")
    # per layer: q 4096*4096, k and v 4096*512 each, o 4096*4096,
    # SwiGLU 3 * 4096*11008
    layer = 16_777_216 + 2 * 2_097_152 + 16_777_216 + 135_266_304
    assert flops.layer_matmul_params(c) == layer == 173_015_040
    head = 4096 * 8000
    assert flops.param_count(c) == 2 * head + layer + 2 * 4096 + 4096
    assert flops.param_count(c) == 238_563_328
    # 6 per weight of the matrix products, plus causal attention: each
    # of the 4096 positions sees (t + 1) keys, 4 * 32 * 128 operations
    # per key forward, three times that with the backward pass
    attn = 3 * 4 * 32 * 128 * (4096 * 4097 // 2) / 4096
    assert flops.train_flops_per_token(c, 4096) == pytest.approx(
        6 * (layer + head) + attn, rel=1e-12)
    assert flops.train_flops_per_token(c, 4096) == pytest.approx(
        1.335386112e9, rel=1e-9)


def test_deepseek_serve_counts():
    c = cfg("deepseek-67b.l2")
    layer = 8192 * 8192 * 2 + 2 * 8192 * 1024 + 3 * 8192 * 22016
    assert flops.layer_matmul_params(c) == layer == 692_060_160
    head = 8192 * 102_400
    # weights a forward step reads in bf16: two layers with their norms,
    # the final norm and the head; not the embedding table
    assert flops.weight_bytes_read(c, 2) == 2 * (
        2 * (layer + 2 * 8192) + 8192 + head)
    # a decode step over caches of 10 and 0 tokens: every row through
    # both layers and the head, attending to 11 and 1 keys in 2 layers
    assert flops.decode_flops(c, [10, 0]) == \
        2 * 2 * (2 * layer + head) + 4 * 64 * 128 * (11 + 1) * 2
    # a 5-token prompt: 15 (query, key) pairs per layer, head once
    assert flops.prefill_flops(c, 5) == \
        2 * 2 * layer * 5 + 4 * 64 * 128 * 15 * 2 + 2 * head
    # keys and values of 7 cached positions: 2 layers x 8 heads x 128
    assert flops.kv_bytes(c, 7, 2) == 2 * 2 * 8 * 128 * 7 * 2
