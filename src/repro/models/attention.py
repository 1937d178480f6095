"""Attention: GQA with RoPE, optionally with a low-rank query
(``cfg.q_rank``: one product for the query's code and K/V, a norm, then
the query heads) — blockwise (flash-style) training/prefill path
with a memory-proper flash BACKWARD, single-token decode over a KV cache,
and cross-attention (VLM).

Sharding design (validated against per-device memory_analysis on the
production mesh — see EXPERIMENTS.md §Perf for the iteration log):

* K/V are repeated to H heads OUTSIDE the attention core (autodiff of the
  repeat gives the GQA group-sum for dK/dV automatically). The core then
  has a single head axis that shards cleanly over 'model' — the grouped
  (KV, G) reshape breaks GSPMD head propagation.
* When n_heads doesn't divide the model axis (starcoder2-3b, musicgen,
  llama4), the query SEQUENCE is sharded over 'model' instead (context
  parallelism) with q_block = Sq so blocking never splits a sharded dim.
* The custom VJP recomputes probability blocks (flash backward): without
  it, differentiating the streaming-softmax scan stores one (bq, bk)
  probability matrix per step and activation memory explodes.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .common import (ModelConfig, apply_rope, constrain, dp_axis_names,
                     model_axis_size, rms_norm)

NEG_INF = -1e30


def _block_layout(x, axis, block):
    """Pad axis to a multiple of block and reshape into (n_blocks, block)."""
    n = x.shape[axis]
    nb = (n + block - 1) // block
    pad = nb * block - n
    if pad:
        widths = [(0, 0)] * x.ndim
        widths[axis] = (0, pad)
        x = jnp.pad(x, widths)
    new_shape = x.shape[:axis] + (nb, block) + x.shape[axis + 1:]
    return x.reshape(new_shape), nb, pad


def _blockwise_impl(q, k, v, causal, k_block, q_block, scale):
    """Streaming-softmax fwd. q,k,v: (B,S,H,hd) (k/v pre-repeated to H).
    Returns (out (B,Sq,H,hd), lse (B,H,Sq))."""
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    qs = (q.astype(jnp.float32) * scale).astype(q.dtype)

    k_block = min(k_block, Sk)
    q_block = min(q_block, Sq)
    kb, n_kb, _ = _block_layout(k, 1, k_block)   # (B,nk,bk,H,hd)
    vb, _, _ = _block_layout(v, 1, k_block)
    qb, n_qb, pad_q = _block_layout(qs, 1, q_block)

    def per_q_block(args):
        q_blk, qb_idx = args                       # (B,qb,H,hd)
        q_pos = qb_idx * q_block + jnp.arange(q_block)

        def kv_step(carry, inputs):
            m, l, acc = carry
            k_blk, v_blk, kb_idx = inputs
            k_pos = kb_idx * k_block + jnp.arange(k_block)
            s = jnp.einsum("bqhd,bshd->bhqs", q_blk, k_blk,
                           preferred_element_type=jnp.float32)
            valid = (k_pos[None, :] < Sk)
            if causal:
                valid = valid & (k_pos[None, :] <= q_pos[:, None])
            s = jnp.where(valid, s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.where(valid, jnp.exp(s - m_new[..., None]), 0.0)
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhqs,bshd->bhqd", p, v_blk.astype(jnp.float32))
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, H, q_block), NEG_INF, dtype=jnp.float32)
        l0 = jnp.zeros((B, H, q_block), dtype=jnp.float32)
        a0 = jnp.zeros((B, H, q_block, hd), dtype=jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0),
            (jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0),
             jnp.arange(n_kb)))
        l_safe = jnp.maximum(l, 1e-30)
        out = acc / l_safe[..., None]              # (B,H,qb,hd)
        lse = m + jnp.log(l_safe)                  # (B,H,qb)
        return jnp.moveaxis(out, 2, 1), lse        # (B,qb,H,hd)

    if n_qb == 1:
        out, lse = per_q_block((qb[:, 0], jnp.asarray(0)))
        out = out[:, None]
        lse = lse[:, :, None]
    else:
        out, lse = jax.lax.map(
            per_q_block, (jnp.moveaxis(qb, 1, 0), jnp.arange(n_qb)))
        out = jnp.moveaxis(out, 0, 1)              # (B,nq,qb,H,hd)
        lse = jnp.moveaxis(lse, 0, 2)              # (B,H,nq,qb)
    out = out.reshape(B, n_qb * q_block, H, hd)
    lse = lse.reshape(B, H, n_qb * q_block)
    if pad_q:
        out = out[:, :Sq]
        lse = lse[..., :Sq]
    return out.astype(q.dtype), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _blockwise_core(q, k, v, causal, k_block, q_block, scale):
    out, _ = _blockwise_impl(q, k, v, causal, k_block, q_block, scale)
    return out


def _blockwise_core_fwd(q, k, v, causal, k_block, q_block, scale):
    out, lse = _blockwise_impl(q, k, v, causal, k_block, q_block, scale)
    return out, (q, k, v, out, lse)


def _blockwise_core_bwd(causal, k_block, q_block, scale, res, do):
    """Flash backward: recompute probability blocks per (k, q) tile pair;
    dq accumulates as a scan carry, dk/dv emit as stacked scan outputs."""
    q, k, v, out, lse = res
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    k_block = min(k_block, Sk)
    q_block = min(q_block, Sq)

    delta = jnp.sum(out.astype(jnp.float32) * do.astype(jnp.float32),
                    axis=-1)                            # (B,Sq,H)
    delta = jnp.moveaxis(delta, 1, 2)                   # (B,H,Sq)

    qb, n_qb, _ = _block_layout(q, 1, q_block)
    dob, _, _ = _block_layout(do, 1, q_block)
    kb, n_kb, _ = _block_layout(k, 1, k_block)
    vb, _, _ = _block_layout(v, 1, k_block)
    lse_b, _, _ = _block_layout(lse, 2, q_block)        # (B,H,nq,qb)
    del_b, _, _ = _block_layout(delta, 2, q_block)

    def k_step(dq_acc, kin):
        k_blk, v_blk, kb_idx = kin
        k_pos = kb_idx * k_block + jnp.arange(k_block)

        def q_step(carry, qin):
            dk_blk, dv_blk = carry
            q_blk, do_blk, lse_blk, del_blk, qb_idx = qin
            q_pos = qb_idx * q_block + jnp.arange(q_block)
            s = jnp.einsum("bqhd,bshd->bhqs",
                           (q_blk.astype(jnp.float32) * scale
                            ).astype(q_blk.dtype), k_blk,
                           preferred_element_type=jnp.float32)
            valid = (k_pos[None, :] < Sk) & (q_pos[:, None] < Sq)
            if causal:
                valid = valid & (k_pos[None, :] <= q_pos[:, None])
            p = jnp.where(valid, jnp.exp(s - lse_blk[..., None]), 0.0)
            do32 = do_blk.astype(jnp.float32)
            dv_blk = dv_blk + jnp.einsum("bhqs,bqhd->bshd", p, do32)
            dp = jnp.einsum("bqhd,bshd->bhqs", do32,
                            v_blk.astype(jnp.float32))
            ds = p * (dp - del_blk[..., None]) * scale
            dk_blk = dk_blk + jnp.einsum("bhqs,bqhd->bshd", ds,
                                         q_blk.astype(jnp.float32))
            dq_blk = jnp.einsum("bhqs,bshd->bqhd", ds,
                                k_blk.astype(jnp.float32))
            return (dk_blk, dv_blk), dq_blk

        dk0 = jnp.zeros((B, k_block, H, hd), jnp.float32)
        dv0 = jnp.zeros((B, k_block, H, hd), jnp.float32)
        qs = (jnp.moveaxis(qb, 1, 0), jnp.moveaxis(dob, 1, 0),
              jnp.moveaxis(lse_b, 2, 0), jnp.moveaxis(del_b, 2, 0),
              jnp.arange(n_qb))
        (dk_blk, dv_blk), dq_parts = jax.lax.scan(q_step, (dk0, dv0), qs)
        dq_acc = dq_acc + jnp.moveaxis(dq_parts, 0, 1)
        return dq_acc, (dk_blk, dv_blk)

    dq0 = jnp.zeros((B, n_qb, q_block, H, hd), jnp.float32)
    ks = (jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0), jnp.arange(n_kb))
    dq_acc, (dk_all, dv_all) = jax.lax.scan(k_step, dq0, ks)
    dq = dq_acc.reshape(B, n_qb * q_block, H, hd)[:, :Sq]
    dk = jnp.moveaxis(dk_all, 0, 1).reshape(B, n_kb * k_block, H, hd)[:, :Sk]
    dv = jnp.moveaxis(dv_all, 0, 1).reshape(B, n_kb * k_block, H, hd)[:, :Sk]
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype))


_blockwise_core.defvjp(_blockwise_core_fwd, _blockwise_core_bwd)


def blockwise_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        *, causal: bool, k_block: int = 1024,
                        q_block: int = 2048,
                        scale: Optional[float] = None) -> jnp.ndarray:
    """Flash-style attention, memory-proper fwd AND bwd.

    q: (B, Sq, H, hd); k, v: (B, Sk, KV, hd). K/V are repeated to H heads
    here (differentiably — the repeat's VJP performs the GQA group-sum).
    """
    H = q.shape[2]
    KV = k.shape[2]
    if KV != H:
        k = jnp.repeat(k, H // KV, axis=2)
        v = jnp.repeat(v, H // KV, axis=2)
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    return _blockwise_core(q, k, v, causal, k_block, q_block, scale)


def decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                     v_cache: jnp.ndarray, cache_len: jnp.ndarray,
                     scale: Optional[float] = None) -> jnp.ndarray:
    """One-token attention over a (B, S, KV, hd) cache.

    q: (B, H, hd); cache_len: count of valid cache entries — a scalar
    (uniform batch) or a (B,) vector (ragged/continuous batching: each
    sequence masks its own prefix). The contraction runs in
    (B, S, KV, G) layout so the cache's sequence axis can stay sharded
    (sequence-parallel KV)."""
    B, H, hd = q.shape
    S, KV = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    scale = scale if scale is not None else hd ** -0.5
    qg = (q * scale).reshape(B, KV, G, hd)
    s = jnp.einsum("bcgd,bscd->bcgs", qg, k_cache,
                   preferred_element_type=jnp.float32)
    if cache_len.ndim == 1:
        cache_len = cache_len.reshape(B, 1, 1, 1)
    valid = jnp.arange(S)[None, None, None, :] < cache_len
    s = jnp.where(valid, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bcgs,bscd->bcgd", p, v_cache.astype(p.dtype))
    return out.reshape(B, H, hd).astype(q.dtype)


def _low_rank_qkv(x: jnp.ndarray, p: dict, cfg: ModelConfig) -> Tuple:
    """The low-rank query: one product gives the query's rank-r code and
    the K and V heads, the code is normed and lifted to the H query
    heads. x (B, S, D) -> q (B, S, H, hd), k and v (B, S, KV, hd)."""
    B, S, _ = x.shape
    r, KVh, hd = cfg.q_rank, cfg.n_kv_heads, cfg.hd
    qkv = jnp.einsum("bsd,de->bse", x, p["wqkv"].astype(x.dtype))
    q_a, k, v = jnp.split(qkv, [r, r + KVh * hd], axis=-1)
    q = jnp.einsum("bsr,rhk->bshk", rms_norm(q_a, p["q_norm"], cfg.norm_eps),
                   p["wq"].astype(x.dtype))
    return q, k.reshape(B, S, KVh, hd), v.reshape(B, S, KVh, hd)


def attention_sublayer(x: jnp.ndarray, p: dict, cfg: ModelConfig,
                       positions: jnp.ndarray,
                       cache: Optional[dict] = None,
                       kv_override: Optional[Tuple] = None) -> Tuple:
    """Full self-attention sublayer (no residual/norm; caller handles).

    Training/prefill: x (B,S,D) -> (out, new_cache_kv)
    Decode: x (B,1,D) with cache dict {"k","v","len"} -> (out, updated kv)
    kv_override: (k, v) for cross-attention (keys from image tokens).
    """
    B, S, D = x.shape
    H, KVh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    dp = dp_axis_names()
    tp = model_axis_size()
    heads_shard = tp > 1 and H % tp == 0
    if cfg.q_rank and kv_override is None:
        q, k, v = _low_rank_qkv(x, p, cfg)
    else:
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
        if kv_override is None:
            k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(x.dtype))
            v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(x.dtype))
    if kv_override is None:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    else:
        k, v = kv_override

    if cache is None:
        causal = kv_override is None
        if heads_shard:
            # TP over heads; batch over DP axes
            q = constrain(q, dp, None, "model", None)
        else:
            # context parallelism: shard the query sequence instead
            q = constrain(q, dp, "model", None, None)
        k = constrain(k, dp, None, None, None)
        v = constrain(v, dp, None, None, None)
        if cfg.use_kernels:
            # Pallas flash attention (TPU target; interpret-mode on CPU)
            from repro.kernels.flash_attention.ops import flash_attention
            out = flash_attention(
                q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                v.transpose(0, 2, 1, 3), causal, 128).transpose(0, 2, 1, 3)
        else:
            q_block = 2048 if heads_shard else q.shape[1]
            out = blockwise_attention(q, k, v, causal=causal,
                                      q_block=q_block)
        new_kv = (k, v)
    else:
        # decode: append the new K/V then attend over the whole cache.
        # A scalar cache["len"] appends at one shared position (uniform
        # batch — the historical path); a (B,) vector appends each row at
        # its OWN length (ragged prompts / continuous batching), so a
        # short sequence overwrites its pad slots and its mask never
        # admits them.
        idx = cache["len"]
        if idx.ndim == 1:
            def _row_update(c, n, i):
                return jax.lax.dynamic_update_slice_in_dim(c, n, i, axis=0)
            k_cache = jax.vmap(_row_update)(
                cache["k"], k.astype(cache["k"].dtype), idx)
            v_cache = jax.vmap(_row_update)(
                cache["v"], v.astype(cache["v"].dtype), idx)
            # per-row masking needs the pure-jax core (the pallas decode
            # kernel takes a scalar length)
            out = decode_attention(q[:, 0], k_cache, v_cache, idx + 1)
        else:
            k_cache = jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k.astype(cache["k"].dtype), idx, axis=1)
            v_cache = jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v.astype(cache["v"].dtype), idx, axis=1)
            if cfg.use_kernels:
                from repro.kernels.decode_attention.ops import \
                    decode_attention as decode_kernel
                out = decode_kernel(q[:, 0],               # (B,H,hd)
                                    jnp.swapaxes(k_cache, 1, 2),  # (B,KV,S,hd)
                                    jnp.swapaxes(v_cache, 1, 2), idx + 1)
            else:
                out = decode_attention(q[:, 0], k_cache, v_cache, idx + 1)
        out = out[:, None]
        new_kv = (k_cache, v_cache)
    o = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    if cache is None:
        o = constrain(o, dp, None, None)
    return o, new_kv


def init_attention(key, cfg: ModelConfig, dtype) -> dict:
    from .common import init_dense, split_keys
    D, H, KVh, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    k1, k2, k3, k4 = split_keys(key, 4)
    if cfg.q_rank:
        r = cfg.q_rank
        return {"wqkv": init_dense(k1, (D, r + 2 * KVh * hd), dtype=dtype),
                "q_norm": jnp.ones((r,), dtype=dtype),
                "wq": init_dense(k2, (r, H, hd), dtype=dtype),
                "wo": init_dense(k4, (H, hd, D), dtype=dtype)}
    return {
        "wq": init_dense(k1, (D, H, hd), dtype=dtype),
        "wk": init_dense(k2, (D, KVh, hd), dtype=dtype),
        "wv": init_dense(k3, (D, KVh, hd), dtype=dtype),
        "wo": init_dense(k4, (H, hd, D), dtype=dtype),
    }
