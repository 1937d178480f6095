"""Unified decoder LM covering all assigned architecture families.

Families:
  dense / audio   — GQA transformer (starcoder2, yi, deepseek, musicgen)
  moe             — GQA transformer with MoE FFN (kimi-k2, llama4-maverick)
  vlm             — dense + cross-attention image layers every Kth layer
                    (llama-3.2-vision); image patch embeddings are a stub
                    input per the assignment
  rwkv6           — attention-free RWKV6 (Finch)
  hybrid          — Mamba2 backbone + shared attention block (zamba2)

Layers are scanned (jax.lax.scan over stacked params) so the HLO stays
compact for 100-layer configs; remat policy is configurable.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import attention as A
from . import blocks as BL
from .common import ModelConfig, init_dense, rms_norm, split_keys


def _scan_blocks(fn, x, stacked, scan: bool):
    """lax.scan over stacked layer params, or an unrolled python loop.

    The unrolled path exists for the dry-run cost model: XLA's
    cost_analysis counts a while-loop body ONCE (not x trip-count), so
    roofline FLOPs extraction lowers with cfg.scan_layers=False.
    """
    if scan:
        return jax.lax.scan(fn, x, stacked)
    leaves = jax.tree_util.tree_leaves(stacked)
    L = leaves[0].shape[0]
    ys = []
    for i in range(L):
        blk = jax.tree_util.tree_map(lambda a: a[i], stacked)
        x, y = fn(x, blk)
        ys.append(y)
    if ys and ys[0] is not None:
        ys = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *ys)
    else:
        ys = None
    return x, ys


def _maybe_remat(fn, cfg: ModelConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
    return jax.checkpoint(fn)


class LM:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    # ------------------------------------------------------------------
    # init
    # ------------------------------------------------------------------
    def init(self, key) -> Dict[str, Any]:
        cfg = self.cfg
        dt = cfg.param_dtype
        keys = split_keys(key, 8)
        D, V = cfg.d_model, cfg.vocab
        params: Dict[str, Any] = {
            "embed": init_dense(keys[0], (V, D), dtype=dt),
            "final_norm": jnp.ones((D,), dtype=dt),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = init_dense(keys[1], (D, V), dtype=dt)

        if cfg.family in ("dense", "audio", "moe"):
            def init_block(k, moe, d_ff):
                k1, k2 = jax.random.split(k)
                blk = {"attn": A.init_attention(k1, cfg, dt),
                       "ln1": jnp.ones((D,), dtype=dt),
                       "ln2": jnp.ones((D,), dtype=dt)}
                if moe:
                    blk["moe"] = BL.init_moe(k2, cfg, dt)
                else:
                    blk["mlp"] = BL.init_mlp(k2, D, d_ff, dt)
                return blk
            nd = cfg.n_dense_layers
            bkeys = jnp.stack(split_keys(keys[2], cfg.n_layers - nd))
            params["blocks"] = jax.vmap(functools.partial(
                init_block, moe=cfg.family == "moe", d_ff=cfg.d_ff))(bkeys)
            if nd:
                dkeys = jnp.stack(split_keys(keys[3], nd))
                params["dense_blocks"] = jax.vmap(functools.partial(
                    init_block, moe=False, d_ff=cfg.dense_ff))(dkeys)

        elif cfg.family == "vlm":
            every = cfg.cross_attn_every
            n_groups = cfg.n_layers // every
            n_self = every - 1

            def init_self(k):
                k1, k2 = jax.random.split(k)
                return {"attn": A.init_attention(k1, cfg, dt),
                        "mlp": BL.init_mlp(k2, D, cfg.d_ff, dt),
                        "ln1": jnp.ones((D,), dtype=dt),
                        "ln2": jnp.ones((D,), dtype=dt)}

            def init_cross(k):
                k1, k2 = jax.random.split(k)
                return {"attn": A.init_attention(k1, cfg, dt),
                        "mlp": BL.init_mlp(k2, D, cfg.d_ff, dt),
                        "ln1": jnp.ones((D,), dtype=dt),
                        "ln2": jnp.ones((D,), dtype=dt),
                        "gate": jnp.zeros((1,), dtype=dt)}
            gkeys = jnp.stack(split_keys(keys[2], n_groups))
            skeys = jax.vmap(lambda k: jnp.stack(jax.random.split(k, n_self)))(gkeys)
            params["cross_blocks"] = jax.vmap(init_cross)(gkeys)
            params["self_blocks"] = jax.vmap(jax.vmap(init_self))(skeys)

        elif cfg.family == "rwkv6":
            def init_block(k):
                return {"tm": BL.init_rwkv6(k, cfg, dt),
                        "ln1": jnp.ones((D,), dtype=dt),
                        "ln2": jnp.ones((D,), dtype=dt)}
            bkeys = jnp.stack(split_keys(keys[2], cfg.n_layers))
            params["blocks"] = jax.vmap(init_block)(bkeys)

        elif cfg.family == "hybrid":
            every = max(cfg.attn_every, 1)
            n_groups = cfg.n_layers // every
            n_rem = cfg.n_layers - n_groups * every

            def init_mblock(k):
                return {"m": BL.init_mamba2(k, cfg, dt),
                        "ln": jnp.ones((D,), dtype=dt)}
            gkeys = jnp.stack(split_keys(keys[2], n_groups))
            inner = jax.vmap(lambda k: jnp.stack(jax.random.split(k, every)))(gkeys)
            params["groups"] = jax.vmap(jax.vmap(init_mblock))(inner)
            if n_rem:
                rkeys = jnp.stack(split_keys(keys[3], n_rem))
                params["rem"] = jax.vmap(init_mblock)(rkeys)
            # ONE shared transformer block (attn + MLP) — Zamba2 design
            params["shared_attn"] = {"attn": A.init_attention(keys[4], cfg, dt),
                                     "mlp": BL.init_mlp(keys[5], D, cfg.d_ff, dt),
                                     "ln": jnp.ones((D,), dtype=dt),
                                     "ln2": jnp.ones((D,), dtype=dt)}
        else:
            raise ValueError(cfg.family)
        return params

    # ------------------------------------------------------------------
    # forward pieces
    # ------------------------------------------------------------------
    def _stacks(self, params):
        """Each stacked group of decoder layers with its first and end
        layer, in forward order: a MoE model's leading dense layers, then
        the rest."""
        L, nd = self.cfg.n_layers, self.cfg.n_dense_layers
        if not nd:
            return [(params["blocks"], 0, L)]
        return [(params["dense_blocks"], 0, nd), (params["blocks"], nd, L)]

    def _dense_block(self, x, blk, positions, cache=None):
        """One decoder layer -> (x, new K/V, rows routed to each held
        expert, or None for a dense MLP)."""
        cfg = self.cfg
        h, kv = A.attention_sublayer(
            rms_norm(x, blk["ln1"], cfg.norm_eps), blk["attn"], cfg,
            positions, cache=cache)
        x = x + h
        y = rms_norm(x, blk["ln2"], cfg.norm_eps)
        rows = None
        if "moe" in blk:
            out, rows = BL.moe_mlp(y, blk["moe"], cfg)
            x = x + out
        else:
            x = x + BL.mlp(y, blk["mlp"], cfg)
        return x, kv, rows

    def _cross_block(self, x, blk, img_kv):
        cfg = self.cfg
        h, _ = A.attention_sublayer(
            rms_norm(x, blk["ln1"], cfg.norm_eps), blk["attn"], cfg,
            positions=jnp.zeros((x.shape[0], x.shape[1]), jnp.int32),
            kv_override=img_kv)
        x = x + jnp.tanh(blk["gate"].astype(x.dtype)) * h
        y = rms_norm(x, blk["ln2"], cfg.norm_eps)
        return x + BL.mlp(y, blk["mlp"], cfg)

    def _rwkv_block(self, x, blk, state=None):
        cfg = self.cfg
        h, st_t = BL.rwkv6_time_mix(
            rms_norm(x, blk["ln1"], cfg.norm_eps), blk["tm"], cfg,
            state=state)
        x = x + h
        h, st_c = BL.rwkv6_channel_mix(
            rms_norm(x, blk["ln2"], cfg.norm_eps), blk["tm"], cfg,
            state=state)
        x = x + h
        new_state = None
        if state is not None:
            new_state = {**st_t, **st_c}
        return x, new_state

    def _img_kv(self, cross_blk, img_embeds):
        """Precompute cross-attention K/V from (stub) image embeddings."""
        cfg = self.cfg
        k = jnp.einsum("bsd,dhk->bshk", img_embeds,
                       cross_blk["attn"]["wk"].astype(img_embeds.dtype))
        v = jnp.einsum("bsd,dhk->bshk", img_embeds,
                       cross_blk["attn"]["wv"].astype(img_embeds.dtype))
        return k, v

    # ------------------------------------------------------------------
    # full forward (training / prefill)
    # ------------------------------------------------------------------
    def forward(self, params, tokens, img_embeds=None):
        """tokens: (B,S) int32 -> logits (B,S,V)."""
        cfg = self.cfg
        B, S = tokens.shape
        x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))

        if cfg.family in ("dense", "audio", "moe"):
            def block(x, blk):
                out, _, _ = self._dense_block(x, blk, positions)
                return out, None
            block = _maybe_remat(block, cfg)
            for blocks, _, _ in self._stacks(params):
                x, _ = _scan_blocks(block, x, blocks, cfg.scan_layers)

        elif cfg.family == "vlm":
            if img_embeds is None:
                img_embeds = jnp.zeros((B, cfg.n_image_tokens, cfg.d_model),
                                       cfg.dtype)
            img_embeds = img_embeds.astype(cfg.dtype)

            def group(x, gp):
                cross, selfs = gp
                img_kv = self._img_kv(cross, img_embeds)
                x = self._cross_block(x, cross, img_kv)

                def sblock(x, blk):
                    out, _, _ = self._dense_block(x, blk, positions)
                    return out, None
                x, _ = jax.lax.scan(sblock, x, selfs)
                return x, None
            group = _maybe_remat(group, cfg)
            x, _ = _scan_blocks(group, x,
                                (params["cross_blocks"], params["self_blocks"]),
                                cfg.scan_layers)

        elif cfg.family == "rwkv6":
            def block(x, blk):
                out, _ = self._rwkv_block(x, blk)
                return out, None
            block = _maybe_remat(block, cfg)
            x, _ = _scan_blocks(block, x, params["blocks"], cfg.scan_layers)

        elif cfg.family == "hybrid":
            shared = params["shared_attn"]

            def group(x, gp):
                h, _ = A.attention_sublayer(
                    rms_norm(x, shared["ln"], cfg.norm_eps), shared["attn"],
                    cfg, positions)
                x = x + h
                x = x + BL.mlp(rms_norm(x, shared["ln2"], cfg.norm_eps),
                               shared["mlp"], cfg)

                def mblock(x, blk):
                    out, _ = BL.mamba2_mix(
                        rms_norm(x, blk["ln"], cfg.norm_eps), blk["m"], cfg)
                    return x + out, None
                x, _ = jax.lax.scan(mblock, x, gp)
                return x, None
            group = _maybe_remat(group, cfg)
            x, _ = _scan_blocks(group, x, params["groups"], cfg.scan_layers)
            if "rem" in params:
                def mblock(x, blk):
                    out, _ = BL.mamba2_mix(
                        rms_norm(x, blk["ln"], cfg.norm_eps), blk["m"], cfg)
                    return x + out, None
                x, _ = _scan_blocks(_maybe_remat(mblock, cfg), x,
                                    params["rem"], cfg.scan_layers)
        else:
            raise ValueError(cfg.family)

        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        head = params.get("lm_head")
        if head is None:
            head = params["embed"].T
        logits = jnp.einsum("bsd,dv->bsv", x, head.astype(x.dtype))
        return logits

    # ------------------------------------------------------------------
    # loss
    # ------------------------------------------------------------------
    def loss(self, params, batch) -> jnp.ndarray:
        cfg = self.cfg
        tokens = batch["tokens"]
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        logits = self.forward(params, inputs,
                              img_embeds=batch.get("image_embeds"))
        logits = logits.astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        # NB: per-target logit via an elementwise mask reduction, NOT
        # take_along_axis — a gather along the model-sharded vocab axis
        # makes the SPMD partitioner replicate the full logits per device.
        iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2)
        picked = jnp.sum(jnp.where(iota == targets[..., None], logits, 0.0),
                         axis=-1)
        nll = (lse - picked).mean()
        if cfg.family == "moe":
            # aux load-balance loss on the first block's router (cheap probe)
            first = jax.tree_util.tree_map(lambda a: a[0], params["blocks"])
            x = jnp.take(params["embed"], inputs, axis=0).astype(cfg.dtype)
            nll = nll + 0.01 * BL.moe_aux_loss(x, first["moe"], cfg)
        return nll

    # ------------------------------------------------------------------
    # serving: prefill + single-token decode over caches
    # ------------------------------------------------------------------
    def init_cache(self, batch_size: int, max_len: int) -> Dict[str, Any]:
        cfg = self.cfg
        B, KV, hd = batch_size, cfg.n_kv_heads, cfg.hd
        cdt = cfg.dtype
        if cfg.family in ("dense", "audio", "moe"):
            L = cfg.n_layers
            cache = {"k": jnp.zeros((L, B, max_len, KV, hd), cdt),
                     "v": jnp.zeros((L, B, max_len, KV, hd), cdt),
                     "len": jnp.zeros((), jnp.int32)}
            if cfg.family == "moe":
                # rows the last decode step routed to each held expert,
                # per MoE layer
                cache["expert_rows"] = jnp.zeros(
                    (L - cfg.n_dense_layers, cfg.expert_range[1]), jnp.int32)
            return cache
        if cfg.family == "vlm":
            n_groups = cfg.n_layers // cfg.cross_attn_every
            n_self = cfg.cross_attn_every - 1
            return {"k": jnp.zeros((n_groups, n_self, B, max_len, KV, hd), cdt),
                    "v": jnp.zeros((n_groups, n_self, B, max_len, KV, hd), cdt),
                    "img_k": jnp.zeros((n_groups, B, cfg.n_image_tokens, KV, hd), cdt),
                    "img_v": jnp.zeros((n_groups, B, cfg.n_image_tokens, KV, hd), cdt),
                    "len": jnp.zeros((), jnp.int32)}
        if cfg.family == "rwkv6":
            L, D = cfg.n_layers, cfg.d_model
            N = cfg.rwkv_head_dim
            H = D // N
            return {"shift": jnp.zeros((L, B, D), cdt),
                    "shift_ffn": jnp.zeros((L, B, D), cdt),
                    "wkv": jnp.zeros((L, B, H, N, N), jnp.float32),
                    "len": jnp.zeros((), jnp.int32)}
        if cfg.family == "hybrid":
            every = max(cfg.attn_every, 1)
            n_groups = cfg.n_layers // every
            n_rem = cfg.n_layers - n_groups * every
            d_in = cfg.ssm_expand * cfg.d_model
            H = d_in // cfg.ssm_head_dim
            P, N = cfg.ssm_head_dim, cfg.ssm_state
            cache = {"conv": jnp.zeros((n_groups, every, B, 3, d_in), cdt),
                     "ssm": jnp.zeros((n_groups, every, B, H, P, N), jnp.float32),
                     "attn_k": jnp.zeros((n_groups, B, max_len, KV, hd), cdt),
                     "attn_v": jnp.zeros((n_groups, B, max_len, KV, hd), cdt),
                     "len": jnp.zeros((), jnp.int32)}
            if n_rem:
                cache["rem_conv"] = jnp.zeros((n_rem, B, 3, d_in), cdt)
                cache["rem_ssm"] = jnp.zeros((n_rem, B, H, P, N), jnp.float32)
            return cache
        raise ValueError(cfg.family)

    def decode_step(self, params, cache, tokens):
        """tokens: (B,1) -> (logits (B,1,V), new cache). Caches donated.

        ``cache["len"]`` may be a scalar (uniform batch) or a (B,) vector
        (ragged prompts / continuous batching): with a vector, each row
        appends its K/V at — and takes its RoPE position from — its own
        length (supported for the dense/audio/moe families)."""
        cfg = self.cfg
        B = tokens.shape[0]
        x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
        ln = cache["len"]
        if ln.ndim == 1:
            if cfg.family not in ("dense", "audio", "moe"):
                raise ValueError(
                    f"per-sequence cache lengths are not supported for "
                    f"family {cfg.family!r} (recurrent/grouped state has "
                    f"no per-row append position)")
            pos = ln[:, None].astype(jnp.int32)
        else:
            pos = jnp.broadcast_to(ln[None, None], (B, 1)).astype(jnp.int32)

        if cfg.family in ("dense", "audio", "moe"):
            def block(x, xs):
                blk, kc, vc = xs
                out, kv, rows = self._dense_block(
                    x, blk, pos, cache={"k": kc, "v": vc, "len": cache["len"]})
                return out, (kv, rows)
            stacks = self._stacks(params)
            ks, vs = [], []
            new_cache = {"len": cache["len"] + 1}
            for blocks, lo, hi in stacks:
                kc, vc = cache["k"], cache["v"]
                if len(stacks) > 1:
                    kc, vc = kc[lo:hi], vc[lo:hi]
                x, ((k_all, v_all), rows) = _scan_blocks(
                    block, x, (blocks, kc, vc), cfg.scan_layers)
                ks.append(k_all)
                vs.append(v_all)
                if rows is not None:
                    new_cache["expert_rows"] = rows
            new_cache["k"] = ks[0] if len(ks) == 1 else jnp.concatenate(ks)
            new_cache["v"] = vs[0] if len(vs) == 1 else jnp.concatenate(vs)

        elif cfg.family == "vlm":
            def group(x, xs):
                cross, selfs, kc, vc, ik, iv = xs
                h, _ = A.attention_sublayer(
                    rms_norm(x, cross["ln1"], cfg.norm_eps), cross["attn"],
                    cfg, positions=pos, kv_override=(ik, iv))
                x = x + jnp.tanh(cross["gate"].astype(x.dtype)) * h
                y = rms_norm(x, cross["ln2"], cfg.norm_eps)
                x = x + BL.mlp(y, cross["mlp"], cfg)

                def sblock(x, xs2):
                    blk, kc2, vc2 = xs2
                    out, (kn, vn), _ = self._dense_block(
                        x, blk, pos, cache={"k": kc2, "v": vc2,
                                            "len": cache["len"]})
                    return out, (kn, vn)
                x, (k_new, v_new) = jax.lax.scan(sblock, x, (selfs, kc, vc))
                return x, (k_new, v_new)
            x, (k_all, v_all) = _scan_blocks(
                group, x, (params["cross_blocks"], params["self_blocks"],
                           cache["k"], cache["v"],
                           cache["img_k"], cache["img_v"]),
                cfg.scan_layers)
            new_cache = {**cache, "k": k_all, "v": v_all,
                         "len": cache["len"] + 1}

        elif cfg.family == "rwkv6":
            def block(x, xs):
                blk, sh, shf, wkv = xs
                out, st = self._rwkv_block(
                    x, blk, state={"shift": sh, "shift_ffn": shf, "wkv": wkv})
                return out, (st["shift"], st["shift_ffn"], st["wkv"])
            x, (sh, shf, wkv) = _scan_blocks(
                block, x, (params["blocks"], cache["shift"],
                           cache["shift_ffn"], cache["wkv"]),
                cfg.scan_layers)
            new_cache = {"shift": sh, "shift_ffn": shf, "wkv": wkv,
                         "len": cache["len"] + 1}

        elif cfg.family == "hybrid":
            shared = params["shared_attn"]

            def group(x, xs):
                gp, conv, ssm, kc, vc = xs
                h, (kn, vn) = A.attention_sublayer(
                    rms_norm(x, shared["ln"], cfg.norm_eps), shared["attn"],
                    cfg, pos, cache={"k": kc, "v": vc, "len": cache["len"]})
                x = x + h
                x = x + BL.mlp(rms_norm(x, shared["ln2"], cfg.norm_eps),
                               shared["mlp"], cfg)

                def mblock(x, xs2):
                    blk, cv, st = xs2
                    out, ns = BL.mamba2_mix(
                        rms_norm(x, blk["ln"], cfg.norm_eps), blk["m"], cfg,
                        state={"conv": cv, "ssm": st})
                    return x + out, (ns["conv"], ns["ssm"])
                x, (cv, st) = jax.lax.scan(mblock, x, (gp, conv, ssm))
                return x, (cv, st, kn, vn)
            x, (conv, ssm, k_all, v_all) = _scan_blocks(
                group, x, (params["groups"], cache["conv"], cache["ssm"],
                           cache["attn_k"], cache["attn_v"]),
                cfg.scan_layers)
            new_cache = dict(cache)
            new_cache.update(conv=conv, ssm=ssm, attn_k=k_all, attn_v=v_all,
                             len=cache["len"] + 1)
            if "rem" in params:
                def mblock(x, xs2):
                    blk, cv, st = xs2
                    out, ns = BL.mamba2_mix(
                        rms_norm(x, blk["ln"], cfg.norm_eps), blk["m"], cfg,
                        state={"conv": cv, "ssm": st})
                    return x + out, (ns["conv"], ns["ssm"])
                x, (rcv, rst) = _scan_blocks(
                    mblock, x, (params["rem"], cache["rem_conv"],
                                cache["rem_ssm"]), cfg.scan_layers)
                new_cache.update(rem_conv=rcv, rem_ssm=rst)
        else:
            raise ValueError(cfg.family)

        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        head = params.get("lm_head")
        if head is None:
            head = params["embed"].T
        logits = jnp.einsum("bsd,dv->bsv", x, head.astype(x.dtype))
        return logits, new_cache

    def prefill(self, params, tokens, img_embeds=None, max_len=None,
                last_pos=None):
        """Run the full prompt and build the decode cache (a forward pass
        whose layer scan also emits per-layer K/V / recurrent end-states).

        ``last_pos`` (optional, (B,) int32) names each sequence's TRUE
        last prompt position: the returned logits are gathered there
        instead of at column S-1, so right-padded ragged batches sample
        their next token from the real prompt end, not a pad slot. None
        keeps the historical uniform-batch behavior (column S-1)."""
        cfg = self.cfg
        B, S = tokens.shape
        max_len = max_len or S + 1
        x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
        positions = jnp.broadcast_to(
            jnp.arange(S, dtype=jnp.int32)[None], (B, S))
        cache = self.init_cache(B, max_len)

        def pad_kv(kv):
            """(..., S, KV, hd) -> (..., max_len, KV, hd)."""
            pad = [(0, 0)] * kv.ndim
            pad[-3] = (0, max_len - S)
            return jnp.pad(kv, pad)

        if cfg.family in ("dense", "audio", "moe"):
            def block(x, blk):
                out, (k, v), _ = self._dense_block(x, blk, positions)
                return out, (k, v)
            ks, vs = [], []
            for blocks, _, _ in self._stacks(params):
                x, (k, v) = _scan_blocks(_maybe_remat(block, cfg), x,
                                         blocks, cfg.scan_layers)
                ks.append(k)
                vs.append(v)
            ks = ks[0] if len(ks) == 1 else jnp.concatenate(ks)
            vs = vs[0] if len(vs) == 1 else jnp.concatenate(vs)
            cache["k"] = pad_kv(ks).astype(cfg.dtype)
            cache["v"] = pad_kv(vs).astype(cfg.dtype)

        elif cfg.family == "vlm":
            if img_embeds is None:
                img_embeds = jnp.zeros((B, cfg.n_image_tokens, cfg.d_model),
                                       cfg.dtype)
            img_embeds = img_embeds.astype(cfg.dtype)

            def group(x, gp):
                cross, selfs = gp
                img_k, img_v = self._img_kv(cross, img_embeds)
                x = self._cross_block(x, cross, (img_k, img_v))

                def sblock(x, blk):
                    out, (k, v), _ = self._dense_block(x, blk, positions)
                    return out, (k, v)
                x, (ks, vs) = jax.lax.scan(sblock, x, selfs)
                return x, (ks, vs, img_k, img_v)
            x, (ks, vs, iks, ivs) = _scan_blocks(
                _maybe_remat(group, cfg), x,
                (params["cross_blocks"], params["self_blocks"]),
                cfg.scan_layers)
            cache["k"] = pad_kv(ks).astype(cfg.dtype)
            cache["v"] = pad_kv(vs).astype(cfg.dtype)
            cache["img_k"] = iks.astype(cfg.dtype)
            cache["img_v"] = ivs.astype(cfg.dtype)

        elif cfg.family == "rwkv6":
            def block(x, blk):
                cfg_ = self.cfg
                h, st_t = BL.rwkv6_time_mix(
                    rms_norm(x, blk["ln1"], cfg_.norm_eps), blk["tm"], cfg_)
                x = x + h
                h, st_c = BL.rwkv6_channel_mix(
                    rms_norm(x, blk["ln2"], cfg_.norm_eps), blk["tm"], cfg_)
                x = x + h
                return x, (st_t["shift"], st_c["shift_ffn"], st_t["wkv"])
            x, (sh, shf, wkv) = _scan_blocks(_maybe_remat(block, cfg), x,
                                             params["blocks"], cfg.scan_layers)
            cache["shift"] = sh.astype(cfg.dtype)
            cache["shift_ffn"] = shf.astype(cfg.dtype)
            cache["wkv"] = wkv

        elif cfg.family == "hybrid":
            shared = params["shared_attn"]

            def group(x, gp):
                h, (k, v) = A.attention_sublayer(
                    rms_norm(x, shared["ln"], cfg.norm_eps), shared["attn"],
                    cfg, positions)
                x = x + h
                x = x + BL.mlp(rms_norm(x, shared["ln2"], cfg.norm_eps),
                               shared["mlp"], cfg)

                def mblock(x, blk):
                    out, st = BL.mamba2_mix(
                        rms_norm(x, blk["ln"], cfg.norm_eps), blk["m"], cfg)
                    return x + out, (st["conv"], st["ssm"])
                x, (cv, st) = jax.lax.scan(mblock, x, gp)
                return x, (cv, st, k, v)
            x, (cv, st, ks, vs) = _scan_blocks(_maybe_remat(group, cfg), x,
                                               params["groups"], cfg.scan_layers)
            cache["conv"] = cv.astype(cfg.dtype)
            cache["ssm"] = st
            cache["attn_k"] = pad_kv(ks).astype(cfg.dtype)
            cache["attn_v"] = pad_kv(vs).astype(cfg.dtype)
            if "rem" in params:
                def mblock(x, blk):
                    out, st2 = BL.mamba2_mix(
                        rms_norm(x, blk["ln"], cfg.norm_eps), blk["m"], cfg)
                    return x + out, (st2["conv"], st2["ssm"])
                x, (rcv, rst) = _scan_blocks(
                    _maybe_remat(mblock, cfg), x, params["rem"],
                    cfg.scan_layers)
                cache["rem_conv"] = rcv.astype(cfg.dtype)
                cache["rem_ssm"] = rst
        else:
            raise ValueError(cfg.family)

        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        head = params.get("lm_head")
        if head is None:
            head = params["embed"].T
        if last_pos is None:
            sel = x[:, -1:]
            cache["len"] = jnp.asarray(S, jnp.int32)
        else:
            sel = jnp.take_along_axis(
                x, last_pos.astype(jnp.int32)[:, None, None], axis=1)
            # ragged batches decode with per-sequence lengths: each row's
            # next token appends right after its true prompt end
            cache["len"] = last_pos.astype(jnp.int32) + 1
        logits = jnp.einsum("bsd,dv->bsv", sel, head.astype(x.dtype))
        return logits, cache


def build_model(cfg: ModelConfig) -> LM:
    return LM(cfg)
