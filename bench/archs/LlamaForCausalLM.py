"""``LlamaForCausalLM``: a dense Llama-style decoder (RMSNorm, rotary
positions on split halves, grouped-query causal attention, SwiGLU MLP,
untied head) as the configuration files describe it, in their published
``config.json`` key names.

One module per architecture class, found by the configuration's
``architectures[0]`` (``bench.harness.arch``). Its interface:

* :func:`model_config` -- the program's ``ModelConfig`` (the only place
  that imports the program);
* the float32 reference, which imports nothing of the program:
  :func:`init_params`, :func:`sequence_logits`, :func:`sequence_loss`;
* the benchmark's own counts of operations and bytes, from the sizes
  alone: :func:`train_flops_per_token`, :func:`prefill_flops`,
  :func:`decode_flops`, :func:`decode_bytes`.

Counting conventions: a multiply-add is two operations; the embedding
lookup is no matrix product; attention is causal, so position t of a
sequence attends to t + 1 keys; nothing recomputed is counted.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Iterable

import jax
import jax.numpy as jnp

from bench.reference import F32, HI, causal_attention, identity


def model_config(cfg: Dict[str, Any]):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro import configs as C

    return C.get_config(
        cfg["arch"], n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]), act=cfg["hidden_act"],
        tie_embeddings=cfg["tie_word_embeddings"],
        param_dtype=getattr(jnp, cfg["param_dtype"]),
        dtype=getattr(jnp, cfg["compute_dtype"]))


# ---------------------------------------------------------------------------
# the reference: weights drawn from the seed, the forward pass
# ---------------------------------------------------------------------------


def _dense(key, shape, dtype):
    """Normal weights with std 1/sqrt(shape[0]) (the first axis is the
    fan-in), drawn in float32 and stored in ``dtype``."""
    std = 1.0 / math.sqrt(shape[0])
    return (jax.random.normal(key, shape, dtype=F32) * std).astype(dtype)


def init_params(cfg: Dict[str, Any], seed: int, dtype=None):
    """The initial weights for ``cfg`` from ``seed`` as one jitted call,
    by the program's recipe: keys split eight ways (embedding, head,
    layers), per layer two ways (attention, MLP), then four and three
    ways."""
    dtype = dtype or getattr(jnp, cfg["param_dtype"])
    D, V = cfg["hidden_size"], cfg["vocab_size"]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, F, L = cfg["head_dim"], cfg["intermediate_size"], \
        cfg["num_hidden_layers"]

    def block(k):
        k1, k2 = jax.random.split(k)
        a = list(jax.random.split(k1, 4))
        m = list(jax.random.split(k2, 3))
        return {"attn": {"wq": _dense(a[0], (D, H, hd), dtype),
                         "wk": _dense(a[1], (D, KV, hd), dtype),
                         "wv": _dense(a[2], (D, KV, hd), dtype),
                         "wo": _dense(a[3], (H, hd, D), dtype)},
                "ln1": jnp.ones((D,), dtype), "ln2": jnp.ones((D,), dtype),
                "mlp": {"w_gate": _dense(m[0], (D, F), dtype),
                        "w_up": _dense(m[1], (D, F), dtype),
                        "w_down": _dense(m[2], (F, D), dtype)}}

    def init(key):
        keys = list(jax.random.split(key, 8))
        p = {"embed": _dense(keys[0], (V, D), dtype),
             "final_norm": jnp.ones((D,), dtype),
             "blocks": jax.vmap(block)(
                 jnp.stack(list(jax.random.split(keys[2], L))))}
        if not cfg["tie_word_embeddings"]:
            p["lm_head"] = _dense(keys[1], (D, V), dtype)
        return p

    return jax.jit(init)(jax.random.PRNGKey(seed))


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(F32)


def _rope(x, pos, theta):
    """Rotary positions on split halves; x (S, heads, hd)."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = pos[:, None].astype(F32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def hidden(params, tokens, cfg: Dict[str, Any], q_block: int = 512,
           act=identity):
    """Final-normed hidden states (S, D) of one sequence, in float32.
    ``act`` rounds the input of every weight matrix product (the
    serving control passes :func:`bench.reference.fp8_round`)."""
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    w = lambda a: a.astype(F32)  # noqa: E731
    x = params["embed"][tokens].astype(F32)
    pos = jnp.arange(tokens.shape[0])

    def layer(x, blk):
        a, m = blk["attn"], blk["mlp"]
        h = act(_rms(x, blk["ln1"], eps))
        q = _rope(jnp.einsum("sd,dhk->shk", h, w(a["wq"]), precision=HI),
                  pos, theta)
        k = _rope(jnp.einsum("sd,dhk->shk", h, w(a["wk"]), precision=HI),
                  pos, theta)
        v = jnp.einsum("sd,dhk->shk", h, w(a["wv"]), precision=HI)
        o = act(causal_attention(q, k, v, q_block))
        x = x + jnp.einsum("shk,hkd->sd", o, w(a["wo"]), precision=HI)
        h = act(_rms(x, blk["ln2"], eps))
        g = jnp.dot(h, w(m["w_gate"]), precision=HI)
        u = jnp.dot(h, w(m["w_up"]), precision=HI)
        return x + jnp.dot(act(jax.nn.silu(g) * u), w(m["w_down"]),
                           precision=HI), None

    x, _ = jax.lax.scan(layer, x, params["blocks"])
    return act(_rms(x, params["final_norm"], eps))


def _head(params):
    h = params.get("lm_head")
    return params["embed"].T if h is None else h


def sequence_loss(params, tokens, cfg: Dict[str, Any]):
    """Mean next-token cross-entropy of one (S + 1,) token row."""
    x = hidden(params, tokens[:-1], cfg)
    logits = jnp.dot(x, _head(params).astype(F32), precision=HI)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, tokens[1:, None], axis=-1)[:, 0]
    return jnp.mean(lse - picked)


def sequence_logits(params, tokens, cfg: Dict[str, Any], act=identity):
    """(S, V) float32 logits of one token row."""
    return jnp.dot(hidden(params, tokens, cfg, act=act),
                   _head(params).astype(F32), precision=HI)


# ---------------------------------------------------------------------------
# counts of operations and bytes
# ---------------------------------------------------------------------------


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


def decode_bytes(cfg: Dict, lens: Iterable[int], itemsize: int) -> int:
    """Bytes one decode step over rows whose caches hold ``lens`` tokens
    must read: the weights once, and every row's keys and values of its
    cache and its new token in all layers."""
    return weight_bytes_read(cfg, itemsize) + kv_bytes(
        cfg, sum(n + 1 for n in lens), itemsize)
