"""Pallas TPU flash-attention: forward + backward kernels.

Layout: q (B, H, Sq, hd); k, v (B, KV, Sk, hd), GQA via H = KV * G.

Grid design (TPU): ``(B, H, nq, nk)`` with the KV axis innermost and
"arbitrary" semantics — the running softmax state (m, l, acc) lives in VMEM
scratch that persists across the innermost grid steps (the canonical TPU
flash pattern). Block shapes are the VMEM working set: (bq, hd) for Q/acc
and (bk, hd) for K/V; MXU-aligned when bq/bk/hd are multiples of 128 on
real hardware (tests use smaller interpret-mode blocks). Per-row values
(the softmax max/sum, ``lse`` and ``delta``) are (rows, 1) columns, in
scratch and in HBM alike: the TPU lowering takes a block whose last two
dims tile as (8, 128) or span the array, which a trailing unit dim does.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_default

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr,
                *, bq: int, bk: int, sk: int, causal: bool, scale: float):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32) * scale          # (bq, hd)
    k = k_ref[0, 0].astype(jnp.float32)                  # (bk, hd)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (bq, bk)

    q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    valid = k_pos < sk
    if causal:
        valid = valid & (k_pos <= q_pos)
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_scr[...]                                   # (bq, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    p = jnp.where(valid, p, 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
    v = v_ref[0, 0].astype(jnp.float32)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_scr[...] + jnp.log(l)).astype(lse_ref.dtype)


def _pad_to(x, axis: int, mult: int):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def flash_fwd(q, k, v, *, causal: bool = True, scale=None,
              bq: int = 128, bk: int = 128, interpret=None):
    """q: (B,H,Sq,hd); k, v: (B,KV,Sk,hd) -> (o (B,H,Sq,hd),
    lse (B,H,Sq) float32)."""
    interpret = interpret_default(interpret)
    B, H, Sq0, hd = q.shape
    KV, Sk0 = k.shape[1], k.shape[2]
    G = H // KV
    scale = hd ** -0.5 if scale is None else scale
    bq = min(bq, Sq0)
    bk = min(bk, Sk0)
    # pad to whole blocks; the kernel masks with the true Sk
    q = _pad_to(q, 2, bq)
    k = _pad_to(k, 2, bk)
    v = _pad_to(v, 2, bk)
    Sq, Sk = q.shape[2], k.shape[2]
    nq = pl.cdiv(Sq, bq)
    nk = pl.cdiv(Sk, bk)
    kernel = functools.partial(_fwd_kernel, bq=bq, bk=bk, sk=Sk0,
                               causal=causal, scale=scale)
    out_shapes = (
        jax.ShapeDtypeStruct((B, H, Sq, hd), q.dtype),
        jax.ShapeDtypeStruct((B, H, Sq, 1), jnp.float32),
    )
    o, lse = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j, G=G: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j, G=G: (b, h // G, j, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, i, j: (b, h, i, 0)),
        ),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        out_shape=out_shapes,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return o[:, :, :Sq0], lse[:, :, :Sq0, 0]


# ---------------------------------------------------------------------------
# backward: dQ kernel  (grid B, H, nq, nk — kv innermost, dq in scratch)
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_scr,
                   *, bq: int, bk: int, sk: int, causal: bool, scale: float):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    q = q_ref[0, 0].astype(jnp.float32) * scale
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0].astype(jnp.float32)
    delta = delta_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    valid = k_pos < sk
    if causal:
        valid = valid & (k_pos <= q_pos)
    p = jnp.exp(jnp.where(valid, s, NEG_INF) - lse)
    p = jnp.where(valid, p, 0.0)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta) * scale
    dq_scr[...] += jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _finalize():
        dq_ref[0, 0] = dq_scr[...].astype(dq_ref.dtype)


# ---------------------------------------------------------------------------
# backward: dK/dV kernel (grid B, KV, nk, G*nq — q/[group] innermost)
# ---------------------------------------------------------------------------


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr,
                    *, bq: int, bk: int, sk: int, nq: int, G: int,
                    causal: bool, scale: float):
    ik = pl.program_id(2)
    inner = pl.program_id(3)
    n_inner = pl.num_programs(3)
    iq = inner % nq

    @pl.when(inner == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    q = q_ref[0, 0].astype(jnp.float32) * scale
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0].astype(jnp.float32)
    delta = delta_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (bq,bk)
    q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    valid = k_pos < sk
    if causal:
        valid = valid & (k_pos <= q_pos)
    p = jnp.exp(jnp.where(valid, s, NEG_INF) - lse)
    p = jnp.where(valid, p, 0.0)
    # dV += P^T dO
    dv_scr[...] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta) * scale
    # dK += dS^T Q  (note Q already carries `scale`; dK needs raw Q)
    dk_scr[...] += jax.lax.dot_general(ds, q / scale,
                                       (((0,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32)

    @pl.when(inner == n_inner - 1)
    def _finalize():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def flash_bwd(q, k, v, o, lse, do, *, causal: bool = True, scale=None,
              bq: int = 128, bk: int = 128, interpret=None):
    """Gradients (dq, dk, dv) of :func:`flash_fwd` for output
    cotangent ``do``; ``o`` and ``lse`` (B,H,Sq) are its outputs."""
    interpret = interpret_default(interpret)
    B, H, Sq0, hd = q.shape
    KV, Sk0 = k.shape[1], k.shape[2]
    G = H // KV
    scale = hd ** -0.5 if scale is None else scale
    bq = min(bq, Sq0)
    bk = min(bk, Sk0)
    q, o, do = (_pad_to(t, 2, bq) for t in (q, o, do))
    k, v = (_pad_to(t, 2, bk) for t in (k, v))
    # padded q rows: lse pads must be huge so p = exp(s - lse) == 0 there
    pad_q = q.shape[2] - Sq0
    lse = jnp.pad(lse, ((0, 0), (0, 0), (0, pad_q)),
                  constant_values=-NEG_INF)[..., None]
    Sq, Sk = q.shape[2], k.shape[2]
    nq = pl.cdiv(Sq, bq)
    nk = pl.cdiv(Sk, bk)
    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32),
                    axis=-1, keepdims=True)  # (B, H, Sq, 1)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, bq=bq, bk=bk, sk=Sk0,
                          causal=causal, scale=scale),
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j, G=G: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j, G=G: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, i, j: (b, h, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
        scratch_shapes=[pltpu.VMEM((bq, hd), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    def qh_index(b, c, j, inner, G=G, nq=nq):
        # inner enumerates (g, iq); q head = c * G + g
        return (b, c * G + inner // nq, inner % nq, 0)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, bq=bq, bk=bk, sk=Sk0, nq=nq, G=G,
                          causal=causal, scale=scale),
        grid=(B, KV, nk, G * nq),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), qh_index),
            pl.BlockSpec((1, 1, bk, hd), lambda b, c, j, inner: (b, c, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, c, j, inner: (b, c, j, 0)),
            pl.BlockSpec((1, 1, bq, hd), qh_index),
            pl.BlockSpec((1, 1, bq, 1), qh_index),
            pl.BlockSpec((1, 1, bq, 1), qh_index),
        ],
        out_specs=(
            pl.BlockSpec((1, 1, bk, hd), lambda b, c, j, inner: (b, c, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, c, j, inner: (b, c, j, 0)),
        ),
        scratch_shapes=[pltpu.VMEM((bk, hd), jnp.float32),
                        pltpu.VMEM((bk, hd), jnp.float32)],
        out_shape=(jax.ShapeDtypeStruct((B, KV, Sk, hd), k.dtype),
                   jax.ShapeDtypeStruct((B, KV, Sk, hd), v.dtype)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq[:, :, :Sq0], dk[:, :, :Sk0], dv[:, :, :Sk0]
