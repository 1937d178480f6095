"""Step-3 321B-A38B, text model (https://huggingface.co/stepfun-ai/step3,
``config.json`` text config, ``model_type`` ``step3_text``): MFA
attention (64 query heads of 256 through a rank-2048 query bottleneck
with a norm inside, one shared K head and one shared V head of 256),
leading dense layers with SwiGLU 18432, then MoE layers of 48 experts of
SwiGLU 5120, top-3 with gates not renormalised, and one shared expert.

Published layers 0-3 and 60 are dense; the stack holds its dense layers
first, so ``config()`` counts layer 60 among the leading five. Not
given by the config and assumed: softmax router scores,
``rms_norm_eps`` 1e-5, an untied head, no biases, rotary positions on
split halves over the whole head. The vision tower is left out."""

from repro.models.common import ModelConfig


def config(**overrides) -> ModelConfig:
    base = dict(
        name="step3", family="moe", n_layers=61, d_model=7168,
        n_heads=64, n_kv_heads=1, head_dim=256, q_rank=2048, d_ff=5120,
        vocab=128815, rope_theta=500000.0, norm_eps=1e-5,
        n_experts=48, top_k=3, renormalize_topk=False,
        shared_expert_ff=5120, n_dense_layers=5, dense_ff=18432,
    )
    base.update(overrides)
    return ModelConfig(**base)


def smoke_config(**overrides) -> ModelConfig:
    base = dict(
        name="step3-smoke", family="moe", n_layers=3, d_model=128,
        n_heads=4, n_kv_heads=1, head_dim=32, q_rank=64, d_ff=64,
        vocab=512, n_experts=12, top_k=3, renormalize_topk=False,
        shared_expert_ff=64, n_dense_layers=1, dense_ff=192,
    )
    base.update(overrides)
    return ModelConfig(**base)
