"""Plain references for the benchmark's correctness comparison: what no
architecture owns.

Straightforward ``jax.numpy`` in float32 at ``Precision.HIGHEST``. Each
architecture's module in ``bench/archs/`` holds its own model: initial
weights, forward pass and next-token loss. Here are the parts every
architecture shares: the training data, AdamW with global-norm
clipping, warm-up and a cosine schedule, blockwise causal attention,
and the two drivers of the comparison, :func:`train_reference` and
:func:`served_logits`, which take the architecture's module. Nothing
here imports the program under test or takes anything it made: the
initial weights and the training data are drawn again from the seed,
by the same recipe the program follows, so both start from the same
numbers.

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
from types import ModuleType
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32


# ---------------------------------------------------------------------------
# data, drawn from the seed
# ---------------------------------------------------------------------------


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
# shared by the architectures' models
# ---------------------------------------------------------------------------


def causal_attention(q, k, v, q_block: int):
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


def identity(x):
    """The ``act`` of a forward pass that rounds nothing."""
    return x


def fp8_round(a):
    """``a`` rounded to float8_e4m3fn under one scale per tensor (its
    largest magnitude maps to 448), back in its own dtype."""
    a32 = a.astype(F32)
    s = jnp.maximum(jnp.max(jnp.abs(a32)), 1e-30) / 448.0
    return ((a32 / s).astype(jnp.float8_e4m3fn).astype(F32) * s
            ).astype(a.dtype)


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


def train_reference(arch: ModuleType, cfg: Dict[str, Any],
                    mix: Dict[str, Any], seed: int, checked: int,
                    after: int = 0,
                    variant: Optional[str] = None) -> Dict[str, Any]:
    """``checked + after`` data-parallel steps of the plain reference of
    the architecture module ``arch`` (or of a ``variant``) from
    ``seed``. Returns the mean loss of each step, the per-leaf norms of
    the first step's gradient as AdamW takes it (after clipping), of
    each leaf's change over the first ``checked`` steps (``change``)
    and, with ``after``, over the last step alone (``last_change``)."""
    hp = dict(mix["optimizer"], total_steps=mix["steps"])
    R, B, S = mix["ranks"], mix["batch_per_rank"], mix["seq_len"]
    steps = checked + after
    low = variant == "bf16"
    dt = jnp.bfloat16 if low else F32
    params = arch.init_params(cfg, seed, dt)
    p0 = params
    mu = jax.tree_util.tree_map(jnp.zeros_like, params)
    nu = jax.tree_util.tree_map(jnp.zeros_like, params)
    vg = jax.jit(jax.value_and_grad(
        lambda p, t: arch.sequence_loss(p, t, cfg)))
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


def served_logits(arch: ModuleType, params, cfg: Dict[str, Any],
                  requests: Sequence[Tuple[np.ndarray, Sequence[int]]],
                  max_len: int, act=identity) -> List[np.ndarray]:
    """For each (prompt, served tokens): the (n, V) float32 logits at the
    positions that chose served tokens 0..n-1 (the prompt's last
    position, then each served token's), from one forward pass over the
    prompt and the tokens, padded to ``max_len``, by the architecture
    module ``arch``."""
    fwd = jax.jit(lambda p, t: arch.sequence_logits(p, t, cfg, act=act))
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
