"""Layer blocks: SwiGLU MLP, a dropless MoE over a held range of experts,
RWKV6 time/channel mix, Mamba2 SSD — pure JAX, kernel-routable."""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .common import (ModelConfig, act_fn, constrain, dp_axis_names,
                     init_dense, rms_norm, split_keys)


def _chunked_time_scan(step, carry0, xs, chunk: int = 64):
    """scan over time in checkpointed chunks.

    Differentiating a plain T-step scan stores the carried state for every
    step (T x state residuals — catastrophic for T=4096 recurrences).
    Chunking with jax.checkpoint on the chunk body bounds residuals to
    (T/chunk) boundary states + one chunk of recompute (~2*sqrt memory).
    """
    leaves = jax.tree_util.tree_leaves(xs)
    T = leaves[0].shape[0]
    chunk = min(chunk, T)
    nc = (T + chunk - 1) // chunk
    pad = nc * chunk - T

    def pad_leaf(a):
        if pad:
            widths = [(0, pad)] + [(0, 0)] * (a.ndim - 1)
            a = jnp.pad(a, widths)
        return a.reshape(nc, chunk, *a.shape[1:])

    xs_c = jax.tree_util.tree_map(pad_leaf, xs)

    @jax.checkpoint
    def chunk_fn(carry, xchunk):
        return jax.lax.scan(step, carry, xchunk)

    carry, ys = jax.lax.scan(chunk_fn, carry0, xs_c)
    ys = jax.tree_util.tree_map(
        lambda a: a.reshape(nc * chunk, *a.shape[2:])[:T], ys)
    return carry, ys


# ---------------------------------------------------------------------------
# dense MLP (SwiGLU)
# ---------------------------------------------------------------------------


def mlp(x: jnp.ndarray, p: dict, cfg: ModelConfig) -> jnp.ndarray:
    a = act_fn(cfg.act)
    h = a(jnp.einsum("bsd,df->bsf", x, p["w_gate"].astype(x.dtype)))
    h = h * jnp.einsum("bsd,df->bsf", x, p["w_up"].astype(x.dtype))
    return jnp.einsum("bsf,fd->bsd", h, p["w_down"].astype(x.dtype))


def init_mlp(key, d: int, f: int, dtype) -> dict:
    k1, k2, k3 = split_keys(key, 3)
    return {"w_gate": init_dense(k1, (d, f), dtype=dtype),
            "w_up": init_dense(k2, (d, f), dtype=dtype),
            "w_down": init_dense(k3, (f, d), dtype=dtype)}


# ---------------------------------------------------------------------------
# MoE: a dropless expert layer over the held range of experts. The router
# scores all ``n_experts``; the (token, expert) pairs that fall on the
# ``expert_range`` this model holds are sorted by expert and computed by
# one grouped product per matrix, with no capacity and no dropped pair.
# With every expert held this is the whole layer; with a range it is one
# chip's share of an expert-parallel layer, whose other shares would be
# added by the chips that hold them.
# ---------------------------------------------------------------------------


def moe_mlp(x: jnp.ndarray, p: dict, cfg: ModelConfig):
    """x (B, S, D) -> (the held experts' part of the layer plus the
    shared expert, (count,) rows routed to each held expert)."""
    B, S, D = x.shape
    K = cfg.top_k
    first, count = cfg.expert_range
    G = B * S
    xt = x.reshape(G, D)
    logits = jnp.einsum("gd,de->ge", xt, p["router"].astype(x.dtype),
                        preferred_element_type=jnp.float32)
    gates, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), K)  # (G,K)
    if cfg.renormalize_topk:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    local = idx.reshape(-1) - first                         # (G*K,)
    held = (local >= 0) & (local < count)
    # pairs on other chips' experts sort last, past every held group
    order = jnp.argsort(jnp.where(held, local, count))      # stable
    rows = jnp.bincount(jnp.where(held, local, count),
                        length=count + 1)[:count].astype(jnp.int32)
    xs = xt[order // K]                                     # (G*K, D)
    a = act_fn(cfg.act)
    h = a(jax.lax.ragged_dot(xs, p["w_gate"].astype(x.dtype), rows)) * \
        jax.lax.ragged_dot(xs, p["w_up"].astype(x.dtype), rows)
    out = jax.lax.ragged_dot(h, p["w_down"].astype(x.dtype), rows)
    # back to (token, k) order; rows past the held groups are zeroed
    out = jnp.where(held[order][:, None], out, 0)[jnp.argsort(order)]
    gw = jnp.where(held, gates.reshape(-1), 0.0).reshape(G, K)
    y = jnp.einsum("gkd,gk->gd", out.reshape(G, K, D), gw.astype(out.dtype))
    if cfg.shared_expert_ff:
        y = y + mlp(x, p["shared"], cfg).reshape(G, D)
    return y.reshape(B, S, D).astype(x.dtype), rows


def init_moe(key, cfg: ModelConfig, dtype) -> dict:
    """The router over all ``n_experts`` and the held experts' weights:
    expert e's are drawn from the e-th of ``n_experts`` keys, so a held
    range holds the same numbers as that range of the whole layer."""
    d, f = cfg.d_model, cfg.d_ff
    first, count = cfg.expert_range
    k1, k2, _, _, k5 = split_keys(key, 5)
    ekeys = jax.random.split(k2, cfg.n_experts)[first:first + count]
    experts = jax.vmap(lambda k: init_mlp(k, d, f, dtype))(ekeys)
    p = {"router": init_dense(k1, (d, cfg.n_experts), dtype=dtype),
         "w_gate": experts["w_gate"], "w_up": experts["w_up"],
         "w_down": experts["w_down"]}
    if cfg.shared_expert_ff:
        p["shared"] = init_mlp(k5, d, cfg.shared_expert_ff, dtype)
    return p


def moe_aux_loss(x: jnp.ndarray, p: dict, cfg: ModelConfig) -> jnp.ndarray:
    """Load-balancing auxiliary loss (Switch-style)."""
    B, S, D = x.shape
    logits = jnp.einsum("bsd,de->bse", x, p["router"].astype(x.dtype))
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top1 = jnp.argmax(probs, axis=-1)
    frac_tokens = jnp.mean(
        jax.nn.one_hot(top1, cfg.n_experts, dtype=jnp.float32), axis=(0, 1))
    frac_probs = jnp.mean(probs, axis=(0, 1))
    return cfg.n_experts * jnp.sum(frac_tokens * frac_probs)


# ---------------------------------------------------------------------------
# RWKV6 ("Finch"): data-dependent decay time-mix + channel-mix.
# Reference recurrence; cfg.use_kernels routes through the Pallas chunked
# scan kernel (repro.kernels.rwkv6_scan).
# ---------------------------------------------------------------------------


def _rwkv_scan_ref(r, k, v, w, u):
    """r,k,v: (B,T,H,N); w: (B,T,H,N) decay in (0,1); u: (H,N) bonus.
    Returns (B,T,H,N); state S: (B,H,N,N) with S[n_k, n_v]."""
    B, T, H, N = r.shape

    def step(S, inputs):
        r_t, k_t, v_t, w_t = inputs                       # (B,H,N)
        kv = k_t[..., :, None] * v_t[..., None, :]        # (B,H,N,N)
        out = jnp.einsum("bhn,bhnm->bhm", r_t, S + u[None, :, :, None] * kv)
        S_new = w_t[..., :, None] * S + kv
        return S_new, out

    S0 = jnp.zeros((B, H, N, N), dtype=jnp.float32)
    # §Perf hillclimb #2, iteration 2: r/k/v stream through the scan in
    # their native dtype (bf16 in production) instead of being upcast to
    # fp32 — state math still accumulates in fp32 inside the step; the
    # decay w keeps fp32 precision. Halves the streamed residuals.
    xs = (jnp.moveaxis(r, 1, 0), jnp.moveaxis(k, 1, 0),
          jnp.moveaxis(v, 1, 0), jnp.moveaxis(w.astype(jnp.float32), 1, 0))
    S_final, outs = _chunked_time_scan(step, S0, xs)
    return jnp.moveaxis(outs, 0, 1), S_final              # (B,T,H,N), (B,H,N,N)


def rwkv6_time_mix(x: jnp.ndarray, p: dict, cfg: ModelConfig,
                   state: Optional[dict] = None) -> Tuple:
    """x: (B,T,D). state (decode): {"shift": (B,D), "wkv": (B,H,N,N)}."""
    B, T, D = x.shape
    N = cfg.rwkv_head_dim
    H = D // N
    if state is None:
        x_prev = jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1]
    else:
        x_prev = state["shift"][:, None, :]
    # token-shift interpolation (per-projection mix coefficients)
    def mix(name):
        mu = p[f"mu_{name}"].astype(x.dtype)
        return x * mu + x_prev * (1 - mu)
    dp_ax = dp_axis_names()

    def proj(name, wname):
        y = jnp.einsum("btd,dhn->bthn", mix(name),
                       p[wname].astype(x.dtype)).reshape(B, T, H, N)
        # keep batch DP-sharded (and heads TP-sharded when divisible):
        # without the hint the partitioner replicates the batch here
        # (§Perf hillclimb #2: 234 -> ~20 GB/dev)
        return constrain(y, dp_ax, None, "model", None)
    r, k, v = proj("r", "wr"), proj("k", "wk"), proj("v", "wv")
    g = jax.nn.silu(proj("g", "wg"))
    # data-dependent decay (the Finch contribution)
    dw = proj("w", "ww")
    w = jnp.exp(-jnp.exp(p["w0"].astype(jnp.float32) + dw.astype(jnp.float32)))
    u = p["u"].astype(jnp.float32)

    if state is None:
        if cfg.use_kernels:
            from repro.kernels.rwkv6_scan import ops as rwkv_ops
            out = rwkv_ops.rwkv6_scan(r, k, v, w, u)
            new_state = None
        else:
            out, S_final = _rwkv_scan_ref(r, k, v, w, u)
            new_state = {"shift": x[:, -1, :], "wkv": S_final}
    else:
        S = state["wkv"]
        kv = k[:, 0, :, :, None].astype(jnp.float32) * \
            v[:, 0, :, None, :].astype(jnp.float32)
        out = jnp.einsum("bhn,bhnm->bhm", r[:, 0].astype(jnp.float32),
                         S + u[None, :, :, None] * kv)[:, None]
        S_new = w[:, 0, :, :, None] * S + kv
        new_state = {"shift": x[:, -1, :], "wkv": S_new}
    out = out.astype(x.dtype).reshape(B, T, H, N)
    out = out * g
    # per-head group norm
    outn = rms_norm(out.reshape(B, T, H * N).reshape(B, T, H, N),
                    p["ln_x"].reshape(H, N), cfg.norm_eps)
    y = jnp.einsum("bthn,hnd->btd", outn, p["wo"].astype(x.dtype))
    return y, new_state


def rwkv6_channel_mix(x: jnp.ndarray, p: dict, cfg: ModelConfig,
                      state: Optional[dict] = None) -> Tuple:
    B, T, D = x.shape
    if state is None:
        x_prev = jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1]
        new_state = {"shift_ffn": x[:, -1, :]}
    else:
        x_prev = state["shift_ffn"][:, None, :]
        new_state = {"shift_ffn": x[:, -1, :]}
    mu_k = p["mu_ck"].astype(x.dtype)
    mu_r = p["mu_cr"].astype(x.dtype)
    xk = x * mu_k + x_prev * (1 - mu_k)
    xr = x * mu_r + x_prev * (1 - mu_r)
    kx = jnp.square(jax.nn.relu(
        jnp.einsum("btd,df->btf", xk, p["w_k"].astype(x.dtype))))
    r = jax.nn.sigmoid(jnp.einsum("btd,de->bte", xr,
                                  p["w_r"].astype(x.dtype)))
    y = r * jnp.einsum("btf,fd->btd", kx, p["w_v"].astype(x.dtype))
    return y, new_state


def init_rwkv6(key, cfg: ModelConfig, dtype) -> dict:
    D, f = cfg.d_model, cfg.d_ff
    N = cfg.rwkv_head_dim
    H = D // N
    ks = split_keys(key, 12)
    p = {
        "wr": init_dense(ks[0], (D, H, N), dtype=dtype),
        "wk": init_dense(ks[1], (D, H, N), dtype=dtype),
        "wv": init_dense(ks[2], (D, H, N), dtype=dtype),
        "wg": init_dense(ks[3], (D, H, N), dtype=dtype),
        "ww": init_dense(ks[4], (D, H, N), dtype=dtype),
        "wo": init_dense(ks[5], (H, N, D), dtype=dtype),
        "w0": jnp.zeros((H, N), dtype=dtype) - 0.5,
        "u": init_dense(ks[6], (H, N), dtype=dtype),
        "ln_x": jnp.ones((D,), dtype=dtype),
        "w_k": init_dense(ks[7], (D, f), dtype=dtype),
        "w_v": init_dense(ks[8], (f, D), dtype=dtype),
        "w_r": init_dense(ks[9], (D, D), dtype=dtype),
    }
    for name in ("r", "k", "v", "g", "w"):
        p[f"mu_{name}"] = jnp.full((D,), 0.5, dtype=dtype)
    p["mu_ck"] = jnp.full((D,), 0.5, dtype=dtype)
    p["mu_cr"] = jnp.full((D,), 0.5, dtype=dtype)
    return p


# ---------------------------------------------------------------------------
# Mamba2 (SSD). Reference: sequential scan; kernel: chunked SSD.
# ---------------------------------------------------------------------------


def _ssd_scan_ref(xh, dt, A, Bm, Cm):
    """xh: (B,T,H,P) heads; dt: (B,T,H); A: (H,) <0; Bm,Cm: (B,T,N).
    h state: (B,H,P,N). Returns (B,T,H,P)."""
    B, T, H, P = xh.shape
    N = Bm.shape[-1]

    def step(h, inputs):
        x_t, dt_t, b_t, c_t = inputs     # (B,H,P),(B,H),(B,N),(B,N)
        da = jnp.exp(dt_t * A[None, :])                 # (B,H)
        dBx = (dt_t[..., None, None] * x_t[..., :, None] *
               b_t[:, None, None, :])                   # (B,H,P,N)
        h_new = da[..., None, None] * h + dBx
        y = jnp.einsum("bhpn,bn->bhp", h_new, c_t)
        return h_new, y

    h0 = jnp.zeros((B, H, P, N), dtype=jnp.float32)
    xs = (jnp.moveaxis(xh.astype(jnp.float32), 1, 0),
          jnp.moveaxis(dt.astype(jnp.float32), 1, 0),
          jnp.moveaxis(Bm.astype(jnp.float32), 1, 0),
          jnp.moveaxis(Cm.astype(jnp.float32), 1, 0))
    h_final, ys = _chunked_time_scan(step, h0, xs)
    return jnp.moveaxis(ys, 0, 1), h_final


def mamba2_mix(x: jnp.ndarray, p: dict, cfg: ModelConfig,
               state: Optional[dict] = None) -> Tuple:
    """Mamba2 block core. x: (B,T,D).
    decode state: {"conv": (B, d_in, K-1), "ssm": (B,H,P,N)}."""
    B, T, D = x.shape
    d_in = cfg.ssm_expand * D
    N = cfg.ssm_state
    P = cfg.ssm_head_dim
    H = d_in // P
    K = 4  # conv width

    zxbcdt = jnp.einsum("btd,de->bte", x, p["w_in"].astype(x.dtype))
    z, xc, Bm, Cm, dt = jnp.split(
        zxbcdt, [d_in, 2 * d_in, 2 * d_in + N, 2 * d_in + 2 * N], axis=-1)
    # depthwise causal conv over time for xc
    wconv = p["w_conv"].astype(x.dtype)                    # (K, d_in)
    if state is None:
        xpad = jnp.pad(xc, ((0, 0), (K - 1, 0), (0, 0)))
        conv = sum(xpad[:, i:i + T, :] * wconv[i][None, None, :]
                   for i in range(K))
        new_conv = xpad[:, -(K - 1):, :] if T >= K - 1 else None
    else:
        hist = state["conv"]                               # (B, K-1, d_in)
        xfull = jnp.concatenate([hist, xc], axis=1)        # (B, K-1+T, d_in)
        conv = sum(xfull[:, i:i + T, :] * wconv[i][None, None, :]
                   for i in range(K))
        new_conv = xfull[:, -(K - 1):, :]
    xc = jax.nn.silu(conv)
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(x.dtype))  # (B,T,H)
    A = -jnp.exp(p["a_log"].astype(jnp.float32))             # (H,)
    dp_ax = dp_axis_names()
    xh = constrain(xc.reshape(B, T, H, P), dp_ax, None, "model", None)
    dt = constrain(dt, dp_ax, None, "model")

    h_final = None
    if state is None:
        if cfg.use_kernels:
            from repro.kernels.ssm_scan import ops as ssd_ops
            y = ssd_ops.ssd_scan(xh, dt, A, Bm, Cm)
        else:
            y, h_final = _ssd_scan_ref(xh, dt, A, Bm, Cm)
        new_ssm = h_final
    else:
        h = state["ssm"]
        da = jnp.exp(dt[:, 0].astype(jnp.float32) * A[None, :])
        dBx = (dt[:, 0, :, None, None].astype(jnp.float32) *
               xh[:, 0, :, :, None].astype(jnp.float32) *
               Bm[:, 0, None, None, :].astype(jnp.float32))
        h_new = da[..., None, None] * h + dBx
        y = jnp.einsum("bhpn,bn->bhp", h_new,
                       Cm[:, 0].astype(jnp.float32))[:, None]
        new_ssm = h_new
    y = y.astype(x.dtype).reshape(B, T, d_in)
    y = y + xc * p["d_skip"].astype(x.dtype)
    y = y * jax.nn.silu(z)
    out = jnp.einsum("bte,ed->btd", y, p["w_out"].astype(x.dtype))
    if state is not None:
        new_state = {"conv": new_conv, "ssm": new_ssm}
    elif new_ssm is not None and new_conv is not None:
        new_state = {"conv": new_conv, "ssm": new_ssm}
    else:
        new_state = None
    return out, new_state


def init_mamba2(key, cfg: ModelConfig, dtype) -> dict:
    D = cfg.d_model
    d_in = cfg.ssm_expand * D
    N = cfg.ssm_state
    H = d_in // cfg.ssm_head_dim
    e = 2 * d_in + 2 * N + H
    ks = split_keys(key, 4)
    return {
        "w_in": init_dense(ks[0], (D, e), dtype=dtype),
        "w_out": init_dense(ks[1], (d_in, D), dtype=dtype),
        "w_conv": init_dense(ks[2], (4, d_in), dtype=dtype),
        "dt_bias": jnp.zeros((H,), dtype=dtype),
        "a_log": jnp.zeros((H,), dtype=dtype),
        "d_skip": jnp.ones((d_in,), dtype=dtype),
    }
