"""granite-4.0-h-micro [hybrid] — Mamba-2 layers with GQA layers between
them, each layer followed by a SwiGLU MLP. [hf: ibm-granite/granite-4.0-h-micro,
config.json, model_type granitemoehybrid]

Published: 40L d_model=2048, layer_types attention at 5, 15, 25, 35 and
Mamba-2 elsewhere (4 periods of [5 mamba, 1 attention, 4 mamba]);
attention 32H (kv=8) of 64, no positional encoding, scores scaled by
attention_multiplier 1/64; Mamba-2 64 heads x 64 (expand 2), d_state 128,
1 group, conv 4 with bias, chunk 256; MLP 8192 (shared_intermediate_size,
no experts); vocab 100352, tied embeddings; RMSNorm eps 1e-5; embeddings
x12, each sublayer's output x0.22, logits /8. bf16 parameters, as the
checkpoint is distributed. tp=8: the most ways the 8 KV heads split with
no query head padded and no KV head replicated.
"""
import jax.numpy as jnp
from repro.models.config import ModelConfig

PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4


def full() -> ModelConfig:
    return ModelConfig(
        name="granite-4.0-h-micro", family="hybrid", n_layers=40, d_model=2048,
        n_heads=32, n_kv_heads=8, d_ff=8192, vocab=100352,
        layer_types=PERIOD * 4, position="none", embedding_multiplier=12.0,
        residual_multiplier=0.22, attention_multiplier=0.015625,
        logits_scaling=8.0, norm_eps=1e-5,
        ssm_state=128, ssm_headdim=64, ssm_expand=2, ssm_chunk=256, conv_kernel=4,
        param_dtype=jnp.bfloat16, compute_dtype=jnp.bfloat16, tp=8, remat="full",
    )


def smoke() -> ModelConfig:
    """Both layer kinds, two periods of [mamba, attention, mamba]."""
    return full().with_(n_layers=6, layer_types=("mamba", "attention", "mamba"),
                        d_model=64, n_heads=4, n_kv_heads=2, d_ff=128, vocab=128,
                        ssm_state=16, ssm_headdim=16, ssm_chunk=8, tp=1,
                        remat="none", param_dtype=jnp.float32,
                        compute_dtype=jnp.float32)
