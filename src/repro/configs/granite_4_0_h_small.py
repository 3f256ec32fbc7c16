"""granite-4.0-h-small [hybrid_moe] — 40L d_model=4096: 36 Mamba-2 layers
(128 heads of 64, d_state 128, 1 group, conv 4 with bias, expand 2, gated
RMSNorm) and 4 GQA attention layers (32 query / 8 key-value heads of
128, no position embedding, softmax scale 1/128) at layers 5, 15, 25, 35;
every layer then runs 72 SwiGLU experts of width 768, top-10, plus a
shared SwiGLU expert of width 1536; muP multipliers (embedding 12,
residual 0.22, logits / 16); tied 100,352-token head
[hf:ibm-granite/granite-4.0-h-small config.json, model_type
granitemoehybrid]."""
from repro.configs.base import ModelConfig, tiny_variant

PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4

CONFIG = ModelConfig(
    name="granite-4.0-h-small", family="hybrid_moe",
    n_layers=40, d_model=4096, n_heads=32, n_kv_heads=8,
    d_ff=768, vocab_size=100352, tie_embeddings=True, norm_eps=1e-5,
    layer_types=PERIOD * 4, position_embedding="nope", attn_scale=0.0078125,
    n_experts=72, top_k=10, shared_d_ff=1536,
    ssm_state=128, ssm_conv=4, ssm_expand=2, ssm_headdim=64, ssm_chunk=256,
    ssm_conv_bias=True, ssm_gated_norm=True,
    embedding_multiplier=12.0, residual_multiplier=0.22, logits_scaling=16.0,
    rope_theta=1e4,
)
SMOKE_CONFIG = tiny_variant(CONFIG)
