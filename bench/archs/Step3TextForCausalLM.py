"""``Step3TextForCausalLM``: the text model of Step-3 (StepFun,
https://huggingface.co/stepfun-ai/step3, ``model_type`` ``step3_text``)
as the configuration files describe it, in its published
``config.json`` key names, with this chip's share of each expert layer.

The layer equations, for one sequence of hidden states x (S, D):

* ``h = RMSNorm(x)``; ``[q_a | k | v] = h W_qkv``, widths
  ``share_q_dim`` | ``head_dim`` | ``head_dim``;
  ``q = RMSNorm_q(q_a) W_q``, ``num_attention_heads`` heads of
  ``head_dim``; rotary positions on split halves over the whole head on
  q and on the one k head; causal softmax attention at scale
  ``1/sqrt(head_dim)``, every query head reading the one K/V head
  (``num_attention_groups`` 1); ``x += o W_o``.
* ``h = RMSNorm(x)``, then a dense layer ``x += SwiGLU_F(h)``
  (``intermediate_size``), or a MoE layer: ``p = softmax(h W_router)``
  over all ``moe_router_experts``; the top ``moe_top_k`` with gates
  ``p`` as they are (renormalised only where ``norm_expert_weight``);
  ``x += sum over e in top-k held here of p_e SwiGLU_e(h) +
  SwiGLU_shared(h)`` (``moe_intermediate_size``, ``share_expert_dim``).

The stack: ``dense_layers_held`` dense layers, then MoE layers. The
held share: this chip holds experts ``moe_held_first`` to
``moe_held_first + moe_num_experts - 1`` of each MoE layer; the router
keeps its published width, and what the other chips' experts would add
is left out here as in the program. Untied head, no biases.

One module per architecture class, found by the configuration's
``architectures[0]`` (``bench.harness.arch``), with the interface of
``LlamaForCausalLM.py``: :func:`model_config` (the only place that
imports the program), the float32 reference :func:`init_params`,
:func:`sequence_logits`, :func:`sequence_loss`, and the counts
:func:`train_flops_per_token`, :func:`prefill_flops`,
:func:`decode_flops`, :func:`decode_bytes`.

Counting conventions: a multiply-add is two operations; the embedding
lookup is no matrix product; attention is causal, so position t
attends to t + 1 keys; nothing recomputed is counted. Experts: each
token's ``moe_top_k`` pairs fall on the held experts as uniform routing
puts them, ``moe_top_k * moe_num_experts / moe_router_experts`` pairs a
token a MoE layer, plus the shared expert and the router for every
token. Bytes a decode step must read: every weight of every layer (with
its norms), the final norm and the head once, but of each MoE layer's
held experts only those that T rows can be expected to hit under
uniform routing, ``held * (1 - (1 - top_k / router_experts) ** T)``,
and the live K/V rows. So a program that reads every held expert shows
that in ``decode_roofline``, and one that skips the experts no row hit
cannot read over 100%.
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
        n_kv_heads=cfg["num_attention_groups"], head_dim=cfg["head_dim"],
        q_rank=cfg["share_q_dim"], d_ff=cfg["moe_intermediate_size"],
        vocab=cfg["vocab_size"], rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]), act=cfg["hidden_act"],
        tie_embeddings=cfg["tie_word_embeddings"],
        n_experts=cfg["moe_router_experts"], top_k=cfg["moe_top_k"],
        held_experts=(cfg["moe_held_first"], cfg["moe_num_experts"]),
        renormalize_topk=cfg["norm_expert_weight"],
        shared_expert_ff=cfg["share_expert_dim"],
        n_dense_layers=cfg["dense_layers_held"],
        dense_ff=cfg["intermediate_size"],
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


def _swiglu_init(key, d, f, dtype):
    g, u, dn = jax.random.split(key, 3)
    return {"w_gate": _dense(g, (d, f), dtype),
            "w_up": _dense(u, (d, f), dtype),
            "w_down": _dense(dn, (f, d), dtype)}


def init_params(cfg: Dict[str, Any], seed: int, dtype=None):
    """The initial weights for ``cfg`` from ``seed`` as one jitted call,
    by the program's recipe: keys split eight ways (embedding, head, MoE
    layers, dense layers), per layer two ways (attention, MLP);
    attention four ways (``W_qkv``, ``W_q``, unused, ``W_o``); a MoE
    layer five ways (router, experts, two unused, shared expert), the
    experts' key split once per router expert, of which the held ones
    are drawn, each three ways like a dense SwiGLU."""
    dtype = dtype or getattr(jnp, cfg["param_dtype"])
    D, V = cfg["hidden_size"], cfg["vocab_size"]
    H, KV, hd = cfg["num_attention_heads"], cfg["num_attention_groups"], \
        cfg["head_dim"]
    r, E = cfg["share_q_dim"], cfg["moe_router_experts"]
    first, held = cfg["moe_held_first"], cfg["moe_num_experts"]
    nd = cfg["dense_layers_held"]
    nm = cfg["num_hidden_layers"] - nd

    def attn(k):
        a = list(jax.random.split(k, 4))
        return {"wqkv": _dense(a[0], (D, r + 2 * KV * hd), dtype),
                "q_norm": jnp.ones((r,), dtype),
                "wq": _dense(a[1], (r, H, hd), dtype),
                "wo": _dense(a[3], (H, hd, D), dtype)}

    def dense_block(k):
        k1, k2 = jax.random.split(k)
        return {"attn": attn(k1), "ln1": jnp.ones((D,), dtype),
                "ln2": jnp.ones((D,), dtype),
                "mlp": _swiglu_init(k2, D, cfg["intermediate_size"], dtype)}

    def moe_block(k):
        k1, k2 = jax.random.split(k)
        m = list(jax.random.split(k2, 5))
        ekeys = jax.random.split(m[1], E)[first:first + held]
        ex = jax.vmap(lambda e: _swiglu_init(
            e, D, cfg["moe_intermediate_size"], dtype))(ekeys)
        moe = {"router": _dense(m[0], (D, E), dtype), **ex,
               "shared": _swiglu_init(m[4], D, cfg["share_expert_dim"],
                                      dtype)}
        return {"attn": attn(k1), "ln1": jnp.ones((D,), dtype),
                "ln2": jnp.ones((D,), dtype), "moe": moe}

    def init(key):
        keys = list(jax.random.split(key, 8))
        p = {"embed": _dense(keys[0], (V, D), dtype),
             "final_norm": jnp.ones((D,), dtype),
             "blocks": jax.vmap(moe_block)(jax.random.split(keys[2], nm))}
        if nd:
            p["dense_blocks"] = jax.vmap(dense_block)(
                jax.random.split(keys[3], nd))
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


def _w(a):
    return a.astype(F32)


def swiglu(h, m, act=identity):
    """SwiGLU of float32 rows h (S, D) under one MLP's weights."""
    g = jnp.dot(h, _w(m["w_gate"]), precision=HI)
    u = jnp.dot(h, _w(m["w_up"]), precision=HI)
    return jnp.dot(act(jax.nn.silu(g) * u), _w(m["w_down"]), precision=HI)


def attention(h, a, cfg: Dict[str, Any], q_block: int = 512,
              act=identity):
    """The attention sublayer's output (S, D) for normed rows h."""
    S = h.shape[0]
    r, KV, hd = cfg["share_q_dim"], cfg["num_attention_groups"], \
        cfg["head_dim"]
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    pos = jnp.arange(S)
    qkv = jnp.dot(h, _w(a["wqkv"]), precision=HI)
    q_a, k, v = jnp.split(qkv, [r, r + KV * hd], axis=-1)
    q = jnp.einsum("sr,rhk->shk", act(_rms(q_a, a["q_norm"], eps)),
                   _w(a["wq"]), precision=HI)
    q = _rope(q, pos, theta)
    k = _rope(k.reshape(S, KV, hd), pos, theta)
    o = act(causal_attention(q, k, v.reshape(S, KV, hd), q_block))
    return jnp.einsum("shk,hkd->sd", o, _w(a["wo"]), precision=HI)


def expert_layer(h, m, cfg: Dict[str, Any], act=identity):
    """The MoE layer's output (S, D) for normed rows h: the held
    experts' gated SwiGLUs and the shared expert. The held experts are
    taken one at a time (a scan), so only one expert's matrices are in
    float32 at once; each runs on every row, weighted by its gate, which
    is 0 where the row did not choose it."""
    first, K = cfg["moe_held_first"], cfg["moe_top_k"]
    p = jax.nn.softmax(jnp.dot(h, _w(m["router"]), precision=HI), axis=-1)
    gates, idx = jax.lax.top_k(p, K)
    if cfg["norm_expert_weight"]:
        gates = gates / jnp.sum(gates, -1, keepdims=True)

    def one(y, xs):
        wg, wu, wd, e = xs
        g = jnp.sum(jnp.where(idx == e, gates, 0.0), axis=-1)
        out = swiglu(h, {"w_gate": wg, "w_up": wu, "w_down": wd}, act)
        return y + g[:, None] * out, None

    held = first + jnp.arange(m["w_gate"].shape[0])
    y, _ = jax.lax.scan(one, jnp.zeros_like(h),
                        (m["w_gate"], m["w_up"], m["w_down"], held))
    return y + swiglu(h, m["shared"], act)


def hidden(params, tokens, cfg: Dict[str, Any], q_block: int = 512,
           act=identity):
    """Final-normed hidden states (S, D) of one sequence, in float32.
    ``act`` rounds the input of every weight matrix product (the
    serving control passes :func:`bench.reference.fp8_round`)."""
    eps = float(cfg["rms_norm_eps"])
    x = params["embed"][tokens].astype(F32)

    def layer(x, blk):
        x = x + attention(act(_rms(x, blk["ln1"], eps)), blk["attn"], cfg,
                          q_block, act)
        h = act(_rms(x, blk["ln2"], eps))
        if "moe" in blk:
            return x + expert_layer(h, blk["moe"], cfg, act), None
        return x + swiglu(h, blk["mlp"], act), None

    if "dense_blocks" in params:
        x, _ = jax.lax.scan(layer, x, params["dense_blocks"])
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


def attention_params(cfg: Dict) -> int:
    """Weights of one layer's attention: ``W_qkv``, the query norm,
    ``W_q`` and ``W_o``."""
    D, H, KV, hd = cfg["hidden_size"], cfg["num_attention_heads"], \
        cfg["num_attention_groups"], cfg["head_dim"]
    r = cfg["share_q_dim"]
    return D * (r + 2 * KV * hd) + r + r * H * hd + H * hd * D


def expert_params(cfg: Dict) -> int:
    """Weights of one routed expert's SwiGLU."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def moe_shared_params(cfg: Dict) -> int:
    """Weights of a MoE layer every token reads: router and shared
    expert."""
    D = cfg["hidden_size"]
    return D * cfg["moe_router_experts"] + 3 * D * cfg["share_expert_dim"]


def _layers(cfg: Dict):
    nd = cfg["dense_layers_held"]
    return nd, cfg["num_hidden_layers"] - nd


def param_count(cfg: Dict) -> int:
    """All weights held here: embedding, layers (with their two norms),
    final norm, and the head unless tied."""
    D, V = cfg["hidden_size"], cfg["vocab_size"]
    nd, nm = _layers(cfg)
    layers = (nd + nm) * (attention_params(cfg) + 2 * D) \
        + nd * 3 * D * cfg["intermediate_size"] \
        + nm * (moe_shared_params(cfg)
                + cfg["moe_num_experts"] * expert_params(cfg))
    head = 0 if cfg["tie_word_embeddings"] else D * V
    return V * D + layers + D + head


def held_pairs_per_token(cfg: Dict) -> float:
    """A token's (token, expert) pairs on the held experts of one MoE
    layer under uniform routing."""
    return cfg["moe_top_k"] * cfg["moe_num_experts"] \
        / cfg["moe_router_experts"]


def token_matmul_params(cfg: Dict) -> float:
    """Weights one token multiplies through in all layers: attention,
    the dense MLPs, and in each MoE layer the router, the shared expert
    and its expected held pairs."""
    nd, nm = _layers(cfg)
    return (nd + nm) * (attention_params(cfg) - cfg["share_q_dim"]) \
        + nd * 3 * cfg["hidden_size"] * cfg["intermediate_size"] \
        + nm * (moe_shared_params(cfg)
                + held_pairs_per_token(cfg) * expert_params(cfg))


def head_params(cfg: Dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def attention_flops(cfg: Dict, q_len: int, k_len: int) -> int:
    """Scores and weighted values of one sequence in all layers: q_len
    queries, the last of which sees k_len keys (causal)."""
    H, hd = cfg["num_attention_heads"], cfg["head_dim"]
    first = k_len - q_len + 1
    keys_seen = q_len * (first + k_len) // 2
    return 4 * H * hd * keys_seen * cfg["num_hidden_layers"]


def train_flops_per_token(cfg: Dict, seq_len: int) -> float:
    """Forward and backward (three times the forward) per trained token
    of ``seq_len``-token sequences."""
    fwd = 2 * (token_matmul_params(cfg) + head_params(cfg)) \
        + attention_flops(cfg, seq_len, seq_len) / seq_len
    return 3.0 * fwd


def decode_flops(cfg: Dict, lens: Iterable[int]) -> float:
    """One decode step over rows whose caches hold ``lens`` tokens
    before the step: every row's new token through all layers and the
    head, attending to its cache and itself."""
    lens = list(lens)
    per_token = 2 * (token_matmul_params(cfg) + head_params(cfg))
    return len(lens) * per_token + sum(
        attention_flops(cfg, 1, n + 1) for n in lens)


def prefill_flops(cfg: Dict, n: int) -> float:
    """One prompt of n tokens through all layers, and the head at its
    last position."""
    return 2 * token_matmul_params(cfg) * n + attention_flops(cfg, n, n) \
        + 2 * head_params(cfg)


def experts_hit(cfg: Dict, rows: int) -> float:
    """Held experts of one MoE layer that ``rows`` rows can be expected
    to hit under uniform routing."""
    miss = 1.0 - cfg["moe_top_k"] / cfg["moe_router_experts"]
    return cfg["moe_num_experts"] * (1.0 - miss ** rows)


def weight_bytes_read(cfg: Dict, itemsize: int, rows: int) -> float:
    """Weight bytes a forward step over ``rows`` rows must read: every
    layer with its norms, the final norm and the head, of each MoE
    layer's held experts the expected hit; the embedding table is only
    gathered from."""
    D = cfg["hidden_size"]
    _, nm = _layers(cfg)
    every = param_count(cfg) - cfg["vocab_size"] * D \
        - nm * cfg["moe_num_experts"] * expert_params(cfg)
    return itemsize * (every + nm * experts_hit(cfg, rows)
                       * expert_params(cfg))


def kv_bytes(cfg: Dict, tokens: int, itemsize: int) -> int:
    """Keys and values of ``tokens`` cached positions in all layers."""
    return 2 * cfg["num_hidden_layers"] * cfg["num_attention_groups"] \
        * cfg["head_dim"] * tokens * itemsize


def decode_bytes(cfg: Dict, lens: Iterable[int], itemsize: int) -> float:
    """Bytes one decode step over rows whose caches hold ``lens`` tokens
    must read: the weights as :func:`weight_bytes_read` counts them for
    that many rows, and every row's keys and values of its cache and its
    new token in all layers."""
    lens = list(lens)
    return weight_bytes_read(cfg, itemsize, len(lens)) + kv_bytes(
        cfg, sum(n + 1 for n in lens), itemsize)
