"""Pallas TPU RWKV6 (Finch) recurrence: chunked time scan.

State S[h] is an (N, N) matrix per head; the time axis is the innermost
grid dimension and the state persists in VMEM scratch across chunks —
adapting the GPU's sequential wkv CUDA kernel to the TPU model: each chunk
is dense (N,N)-matrix work for the MXU, the carried state never leaves
VMEM (HBM traffic is only r/k/v/w streaming).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_default


def _column(row, n: int):
    """(1, n) row -> (n, 1) column through a masked sublane reduction
    (a vector transpose the TPU lowering accepts at any n)."""
    eye = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0) ==
           jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _rwkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, s_scr,
                 r_scr, k_scr, v_scr, w_scr, *, bt: int, n: int):
    it = pl.program_id(2)

    @pl.when(it == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    u = u_ref[0].astype(jnp.float32)                    # (N, 1)
    # the chunk in float32 VMEM, so each step loads one row at any t
    for ref, scr in ((r_ref, r_scr), (k_ref, k_scr), (v_ref, v_scr),
                     (w_ref, w_scr)):
        scr[...] = ref[0, 0].astype(jnp.float32)

    def row(scr, t):
        return scr[pl.ds(t, 1), :]                      # (1, N)

    def step(t, S):
        kv = _column(row(k_scr, t), n) * row(v_scr, t)  # (N, N)
        y = jax.lax.dot_general(
            row(r_scr, t), S + u * kv,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)         # (1, N)
        o_ref[0, 0, pl.ds(t, 1), :] = y.astype(o_ref.dtype)
        return _column(row(w_scr, t), n) * S + kv

    s_scr[...] = jax.lax.fori_loop(0, bt, step, s_scr[...])


def rwkv6_scan(r, k, v, w, u, *, bt: int = 64, interpret=None):
    """r,k,v,w: (B,T,H,N); u: (H,N). Returns (B,T,H,N) float32."""
    B, T0, H, N = r.shape
    bt = min(bt, T0)
    pad = (-T0) % bt
    if pad:
        widths = ((0, 0), (0, pad), (0, 0), (0, 0))
        r, k, v, w = (jnp.pad(x, widths) for x in (r, k, v, w))
    T = r.shape[1]
    nt = pl.cdiv(T, bt)
    # layout: (B,H,T,N) so the time axis tiles cleanly; the bonus u
    # enters as one (N, 1) column per head
    rt, kt, vt, wt = (jnp.moveaxis(x, 1, 2) for x in (r, k, v, w))
    out = pl.pallas_call(
        functools.partial(_rwkv_kernel, bt=bt, n=N),
        grid=(B, H, nt),
        in_specs=[
            pl.BlockSpec((1, 1, bt, N), lambda b, h, t: (b, h, t, 0)),
            pl.BlockSpec((1, 1, bt, N), lambda b, h, t: (b, h, t, 0)),
            pl.BlockSpec((1, 1, bt, N), lambda b, h, t: (b, h, t, 0)),
            pl.BlockSpec((1, 1, bt, N), lambda b, h, t: (b, h, t, 0)),
            pl.BlockSpec((1, N, 1), lambda b, h, t: (h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bt, N), lambda b, h, t: (b, h, t, 0)),
        scratch_shapes=[pltpu.VMEM((N, N), jnp.float32)]
        + [pltpu.VMEM((bt, N), jnp.float32)] * 4,
        out_shape=jax.ShapeDtypeStruct((B, H, T, N), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret_default(interpret),
    )(rt, kt, vt, wt, u[..., None])
    return jnp.moveaxis(out, 2, 1)[:, :T0]  # (B,T,H,N)
