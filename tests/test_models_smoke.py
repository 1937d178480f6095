"""Per-arch smoke tests: REDUCED configs, one forward + train-grad step +
prefill/decode on CPU; asserts shapes and no NaNs (assignment requirement)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs as C
from repro.models import build_model

ALL_ARCHS = C.list_archs()


def make_batch(cfg, batch=2, seq=16, key=0):
    rng = np.random.RandomState(key)
    toks = rng.randint(0, cfg.vocab, size=(batch, seq + 1)).astype(np.int32)
    batch_d = {"tokens": jnp.asarray(toks)}
    if cfg.family == "vlm":
        batch_d["image_embeds"] = jnp.asarray(
            rng.randn(batch, cfg.n_image_tokens, cfg.d_model),
            dtype=jnp.float32)
    return batch_d


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_forward_and_train_grad(arch):
    cfg = C.smoke_config(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = make_batch(cfg)
    loss, grads = jax.jit(jax.value_and_grad(model.loss))(params, batch)
    assert np.isfinite(float(loss)), f"{arch}: loss NaN/inf"
    leaves = jax.tree_util.tree_leaves(grads)
    assert all(np.all(np.isfinite(np.asarray(l))) for l in leaves), \
        f"{arch}: NaN grads"
    logits = jax.jit(model.forward)(params, batch["tokens"][:, :-1],
                                    img_embeds=batch.get("image_embeds"))
    assert logits.shape == (2, 16, cfg.vocab)


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_prefill_then_decode(arch):
    cfg = C.smoke_config(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    batch = make_batch(cfg, batch=2, seq=8)
    toks = batch["tokens"][:, :-1]
    logits, cache = jax.jit(lambda p, t: model.prefill(
        p, t, img_embeds=batch.get("image_embeds"), max_len=12))(params, toks)
    assert logits.shape == (2, 1, cfg.vocab)
    assert np.all(np.isfinite(np.asarray(logits, dtype=np.float32)))
    nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
    step = jax.jit(model.decode_step)
    for _ in range(3):
        logits, cache = step(params, cache, nxt)
        assert logits.shape == (2, 1, cfg.vocab)
        assert np.all(np.isfinite(np.asarray(logits, dtype=np.float32)))
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
    assert int(cache["len"]) == 8 + 3


@pytest.mark.parametrize("arch", ["gpt2-124m", "rwkv6-3b", "zamba2-1.2b",
                                  "kimi-k2-1t-a32b", "step3"])
def test_decode_matches_forward(arch):
    """Teacher-forced decode logits must match the parallel forward —
    validates cache correctness per family."""
    cfg = C.smoke_config(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(2))
    batch = make_batch(cfg, batch=1, seq=10)
    toks = batch["tokens"][:, :-1]  # (1, 10)
    ref = np.asarray(jax.jit(model.forward)(
        params, toks, img_embeds=batch.get("image_embeds")),
        dtype=np.float32)
    # prefill on the first 5, decode the next 5 teacher-forced
    _, cache = jax.jit(lambda p, t: model.prefill(p, t, max_len=12))(
        params, toks[:, :5])
    step = jax.jit(model.decode_step)
    for i in range(5, 10):
        logits, cache = step(params, cache, toks[:, i:i + 1])
        got = np.asarray(logits, dtype=np.float32)[0, 0]
        np.testing.assert_allclose(got, ref[0, i], rtol=0.1, atol=0.15)


def test_param_count_formulas_roughly_match():
    for arch in ALL_ARCHS:
        cfg = C.smoke_config(arch)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        actual = sum(np.prod(l.shape) for l in
                     jax.tree_util.tree_leaves(params))
        predicted = cfg.param_count()
        assert abs(actual - predicted) / actual < 0.35, \
            f"{arch}: count formula off ({predicted} vs {actual})"


def test_full_config_param_counts():
    """The FULL configs match their published sizes (order of magnitude +)."""
    expect = {
        "starcoder2-15b": 15e9, "yi-6b": 6e9, "starcoder2-3b": 3e9,
        "deepseek-67b": 67e9, "rwkv6-3b": 3e9, "zamba2-1.2b": 1.2e9,
        "kimi-k2-1t-a32b": 1.0e12, "llama4-maverick-400b-a17b": 400e9,
        "musicgen-medium": 1.5e9, "llama-3.2-vision-90b": 90e9,
        "step3": 316e9,
    }
    for arch, n in expect.items():
        cfg = C.get_config(arch)
        got = cfg.param_count()
        assert 0.5 * n < got < 2.1 * n, f"{arch}: {got:.3g} vs {n:.3g}"
    # MoE active params
    kimi = C.get_config("kimi-k2-1t-a32b")
    assert 15e9 < kimi.active_param_count() < 60e9
    mav = C.get_config("llama4-maverick-400b-a17b")
    assert 8e9 < mav.active_param_count() < 40e9
