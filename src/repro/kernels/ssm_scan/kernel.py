"""Pallas TPU Mamba2 SSD scan: chunked state-space recurrence.

Chunked SSD: within a chunk the output decomposes into an intra-chunk
(quadratic, MXU-friendly) term plus an inter-chunk term through the carried
state h (P x N per head), which persists in VMEM scratch across the
innermost (time-chunk) grid axis. This is the TPU-native restructuring of
the Mamba2 CUDA scan: sequential dependency only at chunk granularity.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_default


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, o_ref, h_scr,
                *, bt: int):
    it = pl.program_id(2)

    @pl.when(it == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    x = x_ref[0, 0].astype(jnp.float32)          # (bt, P)
    dt = dt_ref[0, 0].astype(jnp.float32)        # (bt, 1)
    A = a_ref[pl.program_id(1)]                  # scalar (SMEM)
    Bm = b_ref[0].astype(jnp.float32)            # (bt, N)
    Cm = c_ref[0].astype(jnp.float32)            # (bt, N)

    # every per-step vector stays 2-D: (bt, 1) columns, and (1, bt) rows
    # made by masked reductions, so the TPU lowering sees no 1-D values
    row_i = jax.lax.broadcasted_iota(jnp.int32, (bt, bt), 0)
    col_i = jax.lax.broadcasted_iota(jnp.int32, (bt, bt), 1)
    tri = row_i >= col_i                         # [t, s]: s <= t
    da = dt * A                                  # (bt, 1) log-decay per step
    da_row = jnp.sum(jnp.where(row_i == col_i, da, 0.0), axis=0,
                     keepdims=True)              # (1, bt)
    dt_row = jnp.sum(jnp.where(row_i == col_i, dt, 0.0), axis=0,
                     keepdims=True)              # (1, bt)
    # cumulative decay within the chunk, as a column and as a row
    cum = jnp.sum(jnp.where(tri, da_row, 0.0), axis=1, keepdims=True)
    cum_row = jnp.sum(jnp.where(row_i <= col_i, da, 0.0), axis=0,
                      keepdims=True)
    # L[t, s] = exp(cum[t] - cum[s]) for s <= t else 0  (segment-sum matrix)
    L = jnp.where(tri, jnp.exp(cum - cum_row), 0.0)

    # intra-chunk: y_intra[t] = sum_{s<=t} C[t]·B[s] L[t,s] dt[s] x[s]
    cb = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (bt,bt)
    gated = cb * L * dt_row
    y_intra = jax.lax.dot_general(gated, x, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    # inter-chunk: y_inter[t] = C[t] · h_in^T decayed to t
    h_in = h_scr[...]                            # (P, N)
    y_inter = jax.lax.dot_general(Cm, h_in, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    y_inter = y_inter * jnp.exp(cum)

    o_ref[0, 0] = (y_intra + y_inter).astype(o_ref.dtype)

    # carry the state: h_out = exp(sum da) h_in + sum_s exp(cum[-1]-cum[s]) dt[s] x[s] B[s]
    total = jnp.sum(da, axis=0, keepdims=True)   # (1, 1)
    w = jnp.exp(total - cum) * dt                # (bt, 1)
    h_new = jax.lax.dot_general(x * w, Bm, (((0,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (P,N)
    h_scr[...] = jnp.exp(total) * h_in + h_new


def ssd_scan(xh, dt, A, Bm, Cm, *, bt: int = 64, interpret=None):
    """xh: (B,T,H,P); dt: (B,T,H); A: (H,); Bm,Cm: (B,T,N).
    Returns (B,T,H,P) float32."""
    B, T0, H, P = xh.shape
    N = Bm.shape[-1]
    bt = min(bt, T0)
    pad = (-T0) % bt
    if pad:
        xh = jnp.pad(xh, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
    T = xh.shape[1]
    nt = pl.cdiv(T, bt)
    xt = jnp.moveaxis(xh, 1, 2)                  # (B,H,T,P)
    dtt = jnp.moveaxis(dt, 1, 2)[..., None]      # (B,H,T,1)
    out = pl.pallas_call(
        functools.partial(_ssd_kernel, bt=bt),
        grid=(B, H, nt),
        in_specs=[
            pl.BlockSpec((1, 1, bt, P), lambda b, h, t: (b, h, t, 0)),
            pl.BlockSpec((1, 1, bt, 1), lambda b, h, t: (b, h, t, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, bt, N), lambda b, h, t: (b, t, 0)),
            pl.BlockSpec((1, bt, N), lambda b, h, t: (b, t, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bt, P), lambda b, h, t: (b, h, t, 0)),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((B, H, T, P), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret_default(interpret),
    )(xt, dtt, A.astype(jnp.float32), Bm, Cm)
    return jnp.moveaxis(out, 2, 1)[:, :T0]
