"""Operations and bytes the model's work needs, from the configuration
file's sizes alone (published key names). The benchmark's own count,
kept beside it so that no change to the program can move it.

Conventions: a multiply-add is two operations; the embedding lookup is
no matrix product; attention is causal, so position t of a sequence
attends to t + 1 keys; nothing recomputed is counted.
"""

from __future__ import annotations

from typing import Dict, Iterable


def _sizes(cfg: Dict) -> tuple:
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["intermediate_size"], cfg["vocab_size"],
            cfg["num_hidden_layers"])


def layer_matmul_params(cfg: Dict) -> int:
    """Weights of one decoder layer's matrix products: q, k, v and output
    projections and the three SwiGLU matrices."""
    d, H, KV, hd, F, _, _ = _sizes(cfg)
    return d * H * hd + 2 * d * KV * hd + H * hd * d + 3 * d * F


def head_params(cfg: Dict) -> int:
    d, _, _, _, _, V, _ = _sizes(cfg)
    return d * V


def param_count(cfg: Dict) -> int:
    """All weights: embedding, layers (with their two norms), final
    norm, and the head unless it is tied to the embedding."""
    d, _, _, _, _, V, L = _sizes(cfg)
    head = 0 if cfg["tie_word_embeddings"] else head_params(cfg)
    return V * d + L * (layer_matmul_params(cfg) + 2 * d) + d + head


def attention_flops(cfg: Dict, q_len: int, k_len: int) -> int:
    """Scores and weighted values of one sequence in all layers: q_len
    queries, the last of which sees k_len keys (causal)."""
    _, H, _, hd, _, _, L = _sizes(cfg)
    first = k_len - q_len + 1
    keys_seen = q_len * (first + k_len) // 2
    return 4 * H * hd * keys_seen * L


def train_flops_per_token(cfg: Dict, seq_len: int) -> float:
    """Forward and backward (three times the forward) per trained token
    of ``seq_len``-token sequences."""
    fwd = 2 * (cfg["num_hidden_layers"] * layer_matmul_params(cfg)
               + head_params(cfg)) \
        + attention_flops(cfg, seq_len, seq_len) / seq_len
    return 3.0 * fwd


def decode_flops(cfg: Dict, lens: Iterable[int]) -> int:
    """One decode step over rows whose caches hold ``lens`` tokens
    before the step: every row's new token through all layers and the
    head, attending to its cache and itself."""
    lens = list(lens)
    per_token = 2 * (cfg["num_hidden_layers"] * layer_matmul_params(cfg)
                     + head_params(cfg))
    return len(lens) * per_token + sum(
        attention_flops(cfg, 1, n + 1) for n in lens)


def prefill_flops(cfg: Dict, n: int) -> int:
    """One prompt of n tokens through all layers, and the head at its
    last position."""
    return 2 * cfg["num_hidden_layers"] * layer_matmul_params(cfg) * n \
        + attention_flops(cfg, n, n) + 2 * head_params(cfg)


def weight_bytes_read(cfg: Dict, itemsize: int) -> int:
    """Weight bytes one forward step must read: every layer (with its
    norms), the final norm and the head; the embedding table is only
    gathered from."""
    d, _, _, _, _, _, L = _sizes(cfg)
    return itemsize * (L * (layer_matmul_params(cfg) + 2 * d) + d
                       + head_params(cfg))


def kv_bytes(cfg: Dict, tokens: int, itemsize: int) -> int:
    """Keys and values of ``tokens`` cached positions in all layers."""
    _, _, KV, hd, _, _, L = _sizes(cfg)
    return 2 * L * KV * hd * tokens * itemsize
