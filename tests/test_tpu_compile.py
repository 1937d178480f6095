"""Compile each Pallas kernel for a described TPU v5e at real widths.

No chip is attached: the TPU compiler builds the kernels for a ``v5e:2x2``
topology that is only described. This catches what interpret mode
cannot (block shapes the TPU lowering refuses, vector ops Mosaic does not
legalize) and proves the kernels lower to ``tpu_custom_call``. Widths:
flash/decode attention at GPT-2 124M (B=4, H=12, hd=64), the RWKV6 scan
at rwkv6-3b (H=40, N=64), the SSD scan at zamba2-1.2b (H=64, P=64, N=64).
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention import kernel as DK
from repro.kernels.flash_attention import kernel as FK
from repro.kernels.rwkv6_scan import kernel as RK
from repro.kernels.ssm_scan import kernel as SK

B, H, S, HD = 4, 12, 512, 64       # GPT-2 124M attention
RWKV_H, RWKV_N = 40, 64            # rwkv6-3b: d_model 2560 / head 64
SSD_H, SSD_P, SSD_N = 64, 64, 64   # zamba2-1.2b: 2*2048 / 64, state 64
T = 256


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described (not attached) v5e:2x2 host."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A described-chip compile cannot be read back from the persistent
    cache, so keep it out of the cache entirely."""
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _sds(one_chip, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def test_flash_fwd_compiles_for_v5e(one_chip, no_persistent_cache):
    qkv = [_sds(one_chip, (B, H, S, HD))] * 3
    hlo = _compile_text(
        lambda q, k, v: FK.flash_fwd(q, k, v, interpret=False), *qkv)
    assert "tpu_custom_call" in hlo


def test_flash_bwd_compiles_for_v5e(one_chip, no_persistent_cache):
    x = _sds(one_chip, (B, H, S, HD))
    lse = _sds(one_chip, (B, H, S), jnp.float32)
    hlo = _compile_text(
        lambda q, k, v, o, l, do: FK.flash_bwd(q, k, v, o, l, do,
                                               interpret=False),
        x, x, x, x, lse, x)
    assert hlo.count("tpu_custom_call") >= 2  # dq and dk/dv kernels


def test_decode_attention_compiles_for_v5e(one_chip, no_persistent_cache):
    q = _sds(one_chip, (B, H, HD))
    cache = _sds(one_chip, (B, H, 1024, HD))
    hlo = _compile_text(
        lambda q, k, v: DK.decode_attention(q, k, v, 700, interpret=False),
        q, cache, cache)
    assert "tpu_custom_call" in hlo


def test_rwkv6_scan_compiles_for_v5e(one_chip, no_persistent_cache):
    x = _sds(one_chip, (1, T, RWKV_H, RWKV_N))
    w = _sds(one_chip, (1, T, RWKV_H, RWKV_N), jnp.float32)
    u = _sds(one_chip, (RWKV_H, RWKV_N), jnp.float32)
    hlo = _compile_text(
        lambda r, k, v, w, u: RK.rwkv6_scan(r, k, v, w, u, interpret=False),
        x, x, x, w, u)
    assert "tpu_custom_call" in hlo


def test_ssd_scan_compiles_for_v5e(one_chip, no_persistent_cache):
    xh = _sds(one_chip, (1, T, SSD_H, SSD_P))
    dt = _sds(one_chip, (1, T, SSD_H))
    a = _sds(one_chip, (SSD_H,), jnp.float32)
    bc = _sds(one_chip, (1, T, SSD_N))
    hlo = _compile_text(
        lambda x, d, a, b, c: SK.ssd_scan(x, d, a, b, c, interpret=False),
        xh, dt, a, bc, bc)
    assert "tpu_custom_call" in hlo
