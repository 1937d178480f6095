"""Pallas TPU decode attention: one query token vs a long KV cache.

Grid ``(B, H, nk)`` with the cache axis innermost; the running softmax state
persists in VMEM scratch. Block shape (bk, hd) keeps the VMEM working set
small for 500k-token caches; memory-bound by design (the roofline term the
serving configs stress).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_default

NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
                   m_scr, l_scr, acc_scr,
                   *, bk: int, scale: float):
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32) * scale           # (1, hd)
    k = k_ref[0, 0].astype(jnp.float32)                   # (bk, hd)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (1, bk)
    k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
    valid = k_pos < len_ref[0]
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    p = jnp.where(valid, jnp.exp(s - m_new[:, None]), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + p.sum(axis=1)
    v = v_ref[0, 0].astype(jnp.float32)
    acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        o_ref[0, 0] = (acc_scr[...] /
                       jnp.maximum(l_scr[...], 1e-30)[:, None]).astype(o_ref.dtype)


def decode_attention(q, k_cache, v_cache, cache_len, *, scale=None,
                     bk: int = 512, interpret=None):
    """q: (B,H,hd); caches: (B,KV,S,hd); cache_len: scalar int32."""
    interpret = interpret_default(interpret)
    B, H, hd = q.shape
    KV, S0 = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    scale = hd ** -0.5 if scale is None else scale
    bk = min(bk, S0)
    pad = (-S0) % bk
    if pad:
        widths = ((0, 0), (0, 0), (0, pad), (0, 0))
        k_cache = jnp.pad(k_cache, widths)
        v_cache = jnp.pad(v_cache, widths)
    S = k_cache.shape[2]
    nk = pl.cdiv(S, bk)
    q4 = q[:, :, None, :]  # (B,H,1,hd)
    cache_len = jnp.asarray(cache_len, jnp.int32).reshape(1)
    out = pl.pallas_call(
        functools.partial(_decode_kernel, bk=bk, scale=scale),
        grid=(B, H, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, 1, hd), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, j, G=G: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, j, G=G: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, hd), lambda b, h, j: (b, h, 0, 0)),
        scratch_shapes=[pltpu.VMEM((1,), jnp.float32),
                        pltpu.VMEM((1,), jnp.float32),
                        pltpu.VMEM((1, hd), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((B, H, 1, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(cache_len, q4, k_cache, v_cache)
    return out[:, :, 0, :]
