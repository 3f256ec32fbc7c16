"""Whisper-style encoder-decoder backbone.

The audio conv frontend is a STUB per the assignment: batches provide
precomputed frame embeddings (B, S_enc, D) directly.
Encoder: bidirectional attention + sinusoidal positions. Decoder: causal
self-attention (RoPE — adaptation from whisper's learned embeddings so the
assigned 32k decode shapes are well-defined; recorded in DESIGN.md) +
cross-attention over encoder states + MLP.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from .layers import rms_norm, swiglu
from .transformer import (_decode_attn_one, _scan_blocks, attn_block,
                          decode_attention, embed_tokens, lm_logits, scan_xs)


def sinusoidal(S: int, D: int, dtype=jnp.float32):
    pos = np.arange(S)[:, None]
    dim = np.arange(D // 2)[None, :]
    ang = pos / np.power(10000.0, 2 * dim / D)
    emb = np.concatenate([np.sin(ang), np.cos(ang)], axis=-1)
    return jnp.asarray(emb, dtype)


def encode(params, cfg: ModelConfig, frames):
    """frames (B, S_enc, D) -> encoder states (B, S_enc, D)."""
    x = frames.astype(jnp.dtype(cfg.dtype))
    x = x + sinusoidal(x.shape[1], x.shape[2], x.dtype)

    def block(h, lp):
        a, _ = attn_block(cfg, lp, rms_norm(h, lp["ln1"], cfg.norm_eps),
                          positions=None, causal=False)
        h = h + a
        return h + swiglu(rms_norm(h, lp["ln2"], cfg.norm_eps),
                          lp["wi_gate"], lp["wi_up"], lp["wo_mlp"])

    x = _scan_blocks(cfg, params["enc_layers"], x, block)
    return rms_norm(x, params["enc_final_norm"], cfg.norm_eps)


def _dec_block(cfg, lp, h, enc_kv, positions, attn_impl, q_chunk):
    a, _ = attn_block(cfg, lp, rms_norm(h, lp["ln1"], cfg.norm_eps),
                      positions=positions, attn_impl=attn_impl,
                      q_chunk=q_chunk)
    h = h + a
    xa, _ = attn_block(cfg, lp, rms_norm(h, lp["ln_x"], cfg.norm_eps),
                       positions=None, prefix="x", causal=False,
                       kv_override=enc_kv, attn_impl=attn_impl,
                       q_chunk=q_chunk)
    h = h + xa
    return h + swiglu(rms_norm(h, lp["ln2"], cfg.norm_eps),
                      lp["wi_gate"], lp["wi_up"], lp["wo_mlp"])


def forward(params, cfg: ModelConfig, batch: dict, *, attn_impl="masked",
            q_chunk=512, return_hidden=False, **_):
    """batch: frames (B,S_enc,D) + tokens (B,S_dec) -> logits (B,S_dec,Vp)."""
    enc = encode(params, cfg, batch["frames"])
    tokens = batch["tokens"]
    x = embed_tokens(cfg, params, tokens)
    positions = jnp.arange(tokens.shape[1])

    def block(h, lp):
        # project encoder states with this layer's cross-attn K/V
        B, Se, D = enc.shape
        Kp, hd = cfg.padded_kv_heads, cfg.head_dim
        k = jnp.einsum("bsd,dh->bsh", enc, lp["wxk"]).reshape(B, Se, Kp, hd)
        v = jnp.einsum("bsd,dh->bsh", enc, lp["wxv"]).reshape(B, Se, Kp, hd)
        return _dec_block(cfg, lp, h, (k, v, None), positions,
                          attn_impl, q_chunk)

    x = _scan_blocks(cfg, params["dec_layers"], x, block)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if return_hidden:
        return x
    return lm_logits(cfg, params, x)


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=None,
               source_len: int | None = None):
    # int8 KV not plumbed for enc-dec (cross-attn cache is prefill-written);
    # self-attn cache stays in model dtype.
    dtype = dtype or jnp.dtype(cfg.dtype)
    L, Kp, hd = cfg.n_layers, cfg.padded_kv_heads, cfg.head_dim
    Se = source_len or cfg.max_source_len
    return {
        "k": jnp.zeros((L, batch, max_len, Kp, hd), dtype),
        "v": jnp.zeros((L, batch, max_len, Kp, hd), dtype),
        # cross-attn K/V precomputed from encoder states at prefill
        "xk": jnp.zeros((L, batch, Se, Kp, hd), dtype),
        "xv": jnp.zeros((L, batch, Se, Kp, hd), dtype),
        "pos": jnp.zeros((batch,), jnp.int32),   # per-lane (slot-resettable)
    }


def precompute_cross_kv(params, cfg: ModelConfig, frames):
    """Run the encoder once and cache every decoder layer's cross K/V."""
    enc = encode(params, cfg, frames)
    B, Se, D = enc.shape
    Kp, hd = cfg.padded_kv_heads, cfg.head_dim

    def per_layer(lp):
        k = jnp.einsum("bsd,dh->bsh", enc, lp["wxk"]).reshape(B, Se, Kp, hd)
        v = jnp.einsum("bsd,dh->bsh", enc, lp["wxv"]).reshape(B, Se, Kp, hd)
        return k, v

    k, v = jax.vmap(per_layer)(params["dec_layers"])
    return k, v


def decode_step(params, cfg: ModelConfig, cache, prev_tokens, **_):
    pos = cache["pos"]
    x = embed_tokens(cfg, params, prev_tokens[:, None])

    def body(carry, xs):
        h = carry
        lp, kc, vc, xk, xv = xs
        a, kc, vc = _decode_attn_one(
            cfg, lp, rms_norm(h, lp["ln1"], cfg.norm_eps), kc, vc, pos)
        h = h + a
        # cross attention: full (static) source, no causal mask
        B = h.shape[0]
        Hp, Kp, hd = cfg.padded_heads, cfg.padded_kv_heads, cfg.head_dim
        hq = rms_norm(h, lp["ln_x"], cfg.norm_eps)
        q = jnp.einsum("bsd,dh->bsh", hq, lp["wxq"]).reshape(B, 1, Hp, hd)
        o = decode_attention(q, xk, xv, jnp.asarray(xk.shape[1] - 1))
        h = h + jnp.einsum("bsh,hd->bsd", o.reshape(B, 1, Hp * hd), lp["wxo"])
        h = h + swiglu(rms_norm(h, lp["ln2"], cfg.norm_eps),
                       lp["wi_gate"], lp["wi_up"], lp["wo_mlp"])
        return h, (kc, vc)

    x, (k_new, v_new) = scan_xs(
        cfg, body, x, (params["dec_layers"], cache["k"], cache["v"],
                       cache["xk"], cache["xv"]))
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = lm_logits(cfg, params, x)[:, 0]
    return logits, {**cache, "k": k_new, "v": v_new, "pos": pos + 1}
