"""whisper-large-v3 [audio] — 32L(+32L enc) d_model=1280 20H (MHA kv=20)
d_ff=5120 vocab=51866 — enc-dec, conv frontend STUB (batches provide
precomputed frame embeddings) [arXiv:2212.04356; unverified].
20 heads pad to 32 for TP=16; decoder self-attn uses RoPE (adaptation from
learned positions so the assigned 32k decode shape is well-defined)."""
from repro.configs.base import ModelConfig, tiny_variant

CONFIG = ModelConfig(
    name="whisper-large-v3", family="encdec",
    n_layers=32, d_model=1280, n_heads=20, n_kv_heads=20, d_head=64,
    d_ff=5120, vocab_size=51866, rope_theta=1e4,
    n_enc_layers=32, max_source_len=1500,
)
SMOKE_CONFIG = tiny_variant(CONFIG)
