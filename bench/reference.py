"""Plain references for the benchmark's correctness comparison.

Straightforward ``jax.numpy`` in float32 at ``Precision.HIGHEST``: a
Llama-style decoder (RMSNorm, rotary positions on split halves, grouped
-query causal attention, SwiGLU MLP, untied head) as the configuration
files describe it, its next-token loss and gradient, and AdamW with
global-norm clipping, warm-up and a cosine schedule. Nothing here
imports the program under test or takes anything it made: the initial
weights and the training data are drawn again from the seed, by the
same recipe the program follows, so both start from the same numbers.

Attention runs over blocks of queries under ``jax.checkpoint`` and the
loss one sequence at a time, so that the reference fits on one chip at
the timed sizes; that is all the blocking there is.

Variants put in the program's place, to show that the comparison fails
them (``bench/calibrate.py`` reads them on the chip, the tests at a
small size):

* ``bf16`` (training control) -- weights, gradients and Adam moments
  kept in bfloat16, the precision below the configured float32;
* ``half_batch`` -- each rank's gradient and loss over the first half of
  its rows only;
* ``no_exchange`` -- the gradient all-reduce left out: rank 0's own
  gradient divided by the rank count, as the trainer divides the sum;
* ``no_exchange_last`` -- the same in the last step alone, the first
  step whose exchange runs after a NIC fault in the faulted cells;
* ``fp8`` (serving control) -- every weight matrix and the input of
  every product with one rounded to float8_e4m3fn, one scale per
  tensor: computed in the precision below the configured bfloat16.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32


# ---------------------------------------------------------------------------
# weights and data, drawn from the seed
# ---------------------------------------------------------------------------


def _dense(key, shape, dtype):
    """Normal weights with std 1/sqrt(shape[0]) (the first axis is the
    fan-in), drawn in float32 and stored in ``dtype``."""
    std = 1.0 / math.sqrt(shape[0])
    return (jax.random.normal(key, shape, dtype=F32) * std).astype(dtype)


def init_params(cfg: Dict[str, Any], seed: int, dtype=None):
    """The initial weights for ``cfg`` from ``seed`` as one jitted call:
    keys split eight ways (embedding, head, layers), per layer two ways
    (attention, MLP), then four and three ways."""
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


def synthetic_batch(vocab: int, seq_len: int, batch: int, rank: int,
                    world: int, seed: int, step: int) -> np.ndarray:
    """(batch, seq_len + 1) training tokens of one rank at one step: an
    affine Markov chain over the vocabulary with 5% uniform noise, its
    multiplier and offset drawn from ``seed``."""
    rng = np.random.RandomState(seed)
    a = int(rng.randint(3, 23)) * 2 + 1
    c = int(rng.randint(1, vocab))
    rng = np.random.RandomState((seed * 1_000_003 + step * world + rank)
                                & 0x7FFFFFFF)
    toks = np.empty((batch, seq_len + 1), dtype=np.int32)
    toks[:, 0] = rng.randint(0, vocab, size=batch)
    noise_mask = rng.rand(batch, seq_len) < 0.05
    noise_vals = rng.randint(0, vocab, size=(batch, seq_len))
    for t in range(seq_len):
        nxt = (toks[:, t] * a + c) % vocab
        toks[:, t + 1] = np.where(noise_mask[:, t], noise_vals[:, t], nxt)
    return toks


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------


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


def _attention(q, k, v, q_block: int):
    """Causal softmax attention, queries in blocks; q (S, H, hd), k and
    v (S, KV, hd) shared by H / KV query heads each."""
    S, H, hd = q.shape
    rep = H // k.shape[1]
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    nb = -(-S // q_block)
    qb = -(-S // nb)
    q = jnp.pad(q, ((0, nb * qb - S), (0, 0), (0, 0)))

    @jax.checkpoint
    def one(args):
        q_blk, i = args
        s = jnp.einsum("qhd,khd->hqk", q_blk, k, precision=HI) * hd ** -0.5
        q_pos = i * qb + jnp.arange(qb)
        s = jnp.where(jnp.arange(S)[None, None, :] > q_pos[None, :, None],
                      -jnp.inf, s)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)

    out = jax.lax.map(one, (q.reshape(nb, qb, H, hd), jnp.arange(nb)))
    return out.reshape(nb * qb, H, hd)[:S]


def _same(x):
    return x


def fp8_round(a):
    """``a`` rounded to float8_e4m3fn under one scale per tensor (its
    largest magnitude maps to 448), back in its own dtype."""
    a32 = a.astype(F32)
    s = jnp.maximum(jnp.max(jnp.abs(a32)), 1e-30) / 448.0
    return ((a32 / s).astype(jnp.float8_e4m3fn).astype(F32) * s
            ).astype(a.dtype)


def hidden(params, tokens, cfg: Dict[str, Any], q_block: int = 512,
           act=_same):
    """Final-normed hidden states (S, D) of one sequence, in float32.
    ``act`` rounds the input of every weight matrix product (the
    serving control passes :func:`fp8_round`)."""
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
        o = act(_attention(q, k, v, q_block))
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


def sequence_logits(params, tokens, cfg: Dict[str, Any], act=_same):
    """(S, V) float32 logits of one token row."""
    return jnp.dot(hidden(params, tokens, cfg, act=act),
                   _head(params).astype(F32), precision=HI)


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------


def adamw(params, grads, mu, nu, step: int, hp: Dict[str, float]):
    """One AdamW step (step counts from 1): clip by the global norm,
    bias-corrected moments, decoupled weight decay, linear warm-up then a
    cosine from the peak rate down to a tenth of it. Leaves keep their
    dtypes; the arithmetic is float32."""
    leaves = jax.tree_util.tree_leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(F32))) for g in leaves))
    scale = jnp.minimum(1.0, hp["clip_norm"] / jnp.maximum(gnorm, 1e-9))
    warm = min(step / max(hp["warmup_steps"], 1), 1.0)
    prog = min(max((step - hp["warmup_steps"])
                   / max(hp["total_steps"] - hp["warmup_steps"], 1), 0), 1)
    lr = hp["lr"] * warm * (0.1 + 0.9 * 0.5 * (1 + math.cos(math.pi * prog)))
    b1, b2 = hp["b1"], hp["b2"]
    b1c, b2c = 1 - b1 ** step, 1 - b2 ** step

    def upd(p, g, m, n):
        g = g.astype(F32) * scale
        m32 = m.astype(F32) * b1 + (1 - b1) * g
        n32 = n.astype(F32) * b2 + (1 - b2) * g * g
        d = (m32 / b1c) / (jnp.sqrt(n32 / b2c) + hp["eps"]) \
            + hp["weight_decay"] * p.astype(F32)
        return ((p.astype(F32) - lr * d).astype(p.dtype),
                m32.astype(m.dtype), n32.astype(n.dtype))

    out = jax.tree_util.tree_map(upd, params, grads, mu, nu)
    pick = lambda i: jax.tree_util.tree_map(  # noqa: E731
        lambda o: o[i], out, is_leaf=lambda o: isinstance(o, tuple))
    return pick(0), pick(1), pick(2)


def leaf_norms(tree) -> List[float]:
    """Float32 L2 norm of each leaf, in flattening order."""
    return [float(x) for x in jax.jit(lambda t: [
        jnp.sqrt(jnp.sum(jnp.square(l.astype(F32))))
        for l in jax.tree_util.tree_leaves(t)])(tree)]


def train_reference(cfg: Dict[str, Any], mix: Dict[str, Any], seed: int,
                    checked: int, after: int = 0,
                    variant: Optional[str] = None) -> Dict[str, Any]:
    """``checked + after`` data-parallel steps of the plain reference (or
    of a ``variant``) from ``seed``. Returns the mean loss of each step,
    the per-leaf norms of the first step's gradient as AdamW takes it
    (after clipping), of each leaf's change over the first ``checked``
    steps (``change``) and, with ``after``, over the last step alone
    (``last_change``)."""
    hp = dict(mix["optimizer"], total_steps=mix["steps"])
    R, B, S = mix["ranks"], mix["batch_per_rank"], mix["seq_len"]
    steps = checked + after
    low = variant == "bf16"
    dt = jnp.bfloat16 if low else F32
    params = init_params(cfg, seed, dt)
    p0 = params
    mu = jax.tree_util.tree_map(jnp.zeros_like, params)
    nu = jax.tree_util.tree_map(jnp.zeros_like, params)
    vg = jax.jit(jax.value_and_grad(lambda p, t: sequence_loss(p, t, cfg)))
    add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b))
    scale = jax.jit(lambda a, s: jax.tree_util.tree_map(
        lambda x: (x * s).astype(dt), a))
    change = lambda a, b: leaf_norms(jax.tree_util.tree_map(  # noqa: E731
        lambda x, y: x.astype(F32) - y.astype(F32), a, b))
    rows = B // 2 if variant == "half_batch" else B
    out: Dict[str, Any] = {"losses": []}
    for step in range(steps):
        alone = variant == "no_exchange" or (
            variant == "no_exchange_last" and step == steps - 1)
        rank_losses, total = [], None
        for r in range(R):
            toks = synthetic_batch(cfg["vocab_size"], S, B, r, R, seed, step)
            for b in range(rows):
                loss, g = vg(params, jnp.asarray(toks[b]))
                rank_losses.append(float(loss))
                if alone and r > 0:
                    continue
                total = g if total is None else add(total, g)
        grads = scale(total, 1.0 / (rows * R))
        out["losses"].append(float(np.mean(rank_losses)))
        before = params
        params, mu, nu = adamw(params, grads, mu, nu, step + 1, hp)
        if step == 0:
            out["first_grad"] = [x / (1 - hp["b1"]) for x in leaf_norms(mu)]
        if step == checked - 1:
            out["change"] = change(params, p0)
        if after and step == steps - 1:
            out["last_change"] = change(params, before)
    return out


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------


def fp8_weights(params):
    """Every matrix of ``params`` through :func:`fp8_round`; vectors
    kept. Donates ``params``."""
    return jax.jit(lambda p: jax.tree_util.tree_map(
        lambda a: fp8_round(a) if a.ndim >= 2 else a, p),
        donate_argnums=0)(params)


def served_logits(params, cfg: Dict[str, Any], requests: Sequence[
        Tuple[np.ndarray, Sequence[int]]], max_len: int,
                  act=_same) -> List[np.ndarray]:
    """For each (prompt, served tokens): the (n, V) float32 logits at the
    positions that chose served tokens 0..n-1 (the prompt's last
    position, then each served token's), from one forward pass over the
    prompt and the tokens, padded to ``max_len``."""
    fwd = jax.jit(lambda p, t: sequence_logits(p, t, cfg, act=act))
    out = []
    for prompt, toks in requests:
        seq = np.zeros(max_len, np.int32)
        full = np.concatenate([np.asarray(prompt, np.int32),
                               np.asarray(toks, np.int32)])
        seq[:full.size - 1] = full[:-1]
        lo = len(prompt) - 1
        lg = fwd(params, jnp.asarray(seq))[lo:lo + len(toks)]
        out.append(np.asarray(lg))
    return out


def widest_gap(ref_logits: Sequence[np.ndarray],
               tokens: Sequence[Sequence[int]]) -> float:
    """Largest amount by which a chosen token's reference logit lies
    below the reference's best at its position."""
    gap = 0.0
    for lg, toks in zip(ref_logits, tokens):
        t = np.asarray(toks, np.int64)
        picked = lg[np.arange(t.size), t]
        gap = max(gap, float(np.max(lg.max(axis=-1) - picked)))
    return gap
