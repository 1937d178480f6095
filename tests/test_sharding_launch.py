"""Launch machinery on a tiny (1,1) mesh: sharding-rule construction and
train/prefill/decode lowering for each family (the 512-device production
sweep runs via repro.launch.dryrun; this keeps the machinery covered by
the fast suite)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs as C
from repro.models import build_model
from repro.launch import sharding as SH
from repro.launch.mesh import make_debug_mesh
from repro.launch.steps import (make_decode_step, make_prefill_step,
                                make_train_step)
from repro.optim import AdamWConfig, adamw_init

FAMILY_ARCHS = ["gpt2-124m", "kimi-k2-1t-a32b", "rwkv6-3b", "zamba2-1.2b",
                "llama-3.2-vision-90b"]


def _setup(arch):
    cfg = C.smoke_config(arch)
    model = build_model(cfg)
    mesh = make_debug_mesh(1, 1)
    params_sds = jax.eval_shape(lambda k: model.init(k),
                                jax.ShapeDtypeStruct((2,), jnp.uint32))
    pspecs = SH.param_specs(cfg, params_sds, mesh)
    return cfg, model, mesh, params_sds, pspecs


@pytest.mark.parametrize("arch", FAMILY_ARCHS)
def test_param_specs_cover_tree(arch):
    cfg, model, mesh, params_sds, pspecs = _setup(arch)
    n_leaves = len(jax.tree_util.tree_leaves(params_sds))
    n_specs = len(jax.tree_util.tree_leaves(
        pspecs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec)))
    assert n_specs == n_leaves


@pytest.mark.parametrize("arch", ["gpt2-124m", "rwkv6-3b"])
def test_train_step_lowers_on_mesh(arch):
    cfg, model, mesh, params_sds, pspecs = _setup(arch)
    p_shard = SH.to_named(pspecs, mesh)
    opt_cfg = AdamWConfig()
    opt_sds = jax.eval_shape(lambda p: adamw_init(p, opt_cfg), params_sds)
    ospecs = SH.opt_specs(cfg, opt_sds, pspecs, mesh)
    o_shard = SH.to_named(ospecs, mesh)
    batch = {"tokens": jax.ShapeDtypeStruct((2, 17), jnp.int32)}
    bspecs = SH.batch_specs(cfg, mesh, 2)
    b_shard = SH.to_named({"tokens": bspecs["tokens"]}, mesh)
    with jax.set_mesh(mesh):
        step = make_train_step(model, opt_cfg)
        lowered = jax.jit(step, in_shardings=(p_shard, o_shard, b_shard),
                          out_shardings=(p_shard, o_shard, None)).lower(
            params_sds, opt_sds, batch)
        compiled = lowered.compile()
        assert compiled.cost_analysis() is not None


@pytest.mark.parametrize("arch", ["gpt2-124m", "zamba2-1.2b"])
def test_decode_step_lowers_with_cache_specs(arch):
    cfg, model, mesh, params_sds, pspecs = _setup(arch)
    p_shard = SH.to_named(pspecs, mesh)
    cache_sds = jax.eval_shape(lambda: model.init_cache(2, 32))
    cspecs = SH.cache_specs(cfg, cache_sds, mesh, 2)
    c_shard = SH.to_named(cspecs, mesh)
    toks = jax.ShapeDtypeStruct((2, 1), jnp.int32)
    with jax.set_mesh(mesh):
        step = make_decode_step(model)
        lowered = jax.jit(step, in_shardings=(p_shard, c_shard, None),
                          donate_argnums=(1,)).lower(
            params_sds, cache_sds, toks)
        assert lowered.compile() is not None


def test_collective_bytes_parser():
    from repro.launch.dryrun import collective_bytes
    hlo = """
  %ag = bf16[2048,512]{1,0} all-gather(bf16[128,512]{1,0} %p), dims={0}
  %ar.1 = f32[1024]{0} all-reduce(f32[1024]{0} %x), to_apply=%sum
  %rs = f32[64,8]{1,0} reduce-scatter(f32[512,8]{1,0} %y), dims={0}
  %other = f32[2,2]{1,0} add(f32[2,2]{1,0} %a, f32[2,2]{1,0} %b)
"""
    out = collective_bytes(hlo)
    assert out["all-gather"] == 128 * 512 * 2
    assert out["all-reduce"] == 1024 * 4
    assert out["reduce-scatter"] == 512 * 8 * 4
    assert out["all-to-all"] == 0


def test_serve_engine_generates():
    from repro.serving import ServeEngine
    cfg = C.smoke_config("gpt2-124m")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ServeEngine(model, params, max_len=32)
    prompts = np.random.RandomState(0).randint(
        0, cfg.vocab, size=(2, 8)).astype(np.int32)
    out = eng.generate(prompts, n_tokens=6)
    assert out.shape == (2, 14)
    np.testing.assert_array_equal(out[:, :8], prompts)
