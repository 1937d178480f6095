"""The dropless expert layer (``models/blocks.py`` ``moe_mlp``): no pair
is dropped however the router crowds the experts, and the rows routed
to each held expert are counted."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs as C
from repro.models import blocks


@pytest.mark.parametrize("arch,held", [("step3", None), ("step3", (1, 6)),
                                       ("kimi-k2-1t-a32b", None)])
def test_crowded_experts_drop_nothing(arch, held):
    """A router of zeros sends every token to the same top-k experts
    (ties go to the lowest index): the batch then gives each token what
    it gives alone, and every pair on a held expert is counted."""
    cfg = C.smoke_config(arch, held_experts=held, dtype=jnp.float32)
    p = blocks.init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
    p["router"] = jnp.zeros_like(p["router"])
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
    y, rows = blocks.moe_mlp(x, p, cfg)
    for b in range(2):
        for s in range(16):
            alone, _ = blocks.moe_mlp(x[b:b + 1, s:s + 1], p, cfg)
            np.testing.assert_allclose(np.asarray(y[b, s]),
                                       np.asarray(alone[0, 0]),
                                       rtol=1e-5, atol=1e-6)
    first, count = cfg.expert_range
    want = [32 if first + i < cfg.top_k else 0 for i in range(count)]
    assert np.asarray(rows).tolist() == want
