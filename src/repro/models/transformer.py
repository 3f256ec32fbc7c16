"""Dense decoder-only transformer (llama/qwen family) with scan-over-layers,
remat, GQA, RoPE, qk-norm, and sliding-window attention.

The attention + MLP block functions here are reused by the MoE, hybrid,
encoder-decoder and VLM families.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from .layers import (ATTN_IMPLS, decode_attention, rms_norm, rope, shard,
                     swiglu, NEG_INF)


# ------------------------------------------------------------ shared blocks
def attn_block(cfg: ModelConfig, lp: dict, x, *, positions,
               attn_impl="masked", prefix="", kv_override=None,
               causal=True, q_chunk=512):
    """Pre-norm attention block (residual applied by caller).
    kv_override: (k, v, kv_positions) for cross-attention. positions=None
    applies no rotary embedding (NoPE models, or absolute positions handled
    outside)."""
    B, S, D = x.shape
    hd, Hp, Kp = cfg.head_dim, cfg.padded_heads, cfg.padded_kv_heads
    q = jnp.einsum("bsd,dh->bsh", x, lp[f"w{prefix}q"]).reshape(B, S, Hp, hd)
    if kv_override is None:
        k = jnp.einsum("bsd,dh->bsh", x, lp[f"w{prefix}k"]).reshape(B, S, Kp, hd)
        v = jnp.einsum("bsd,dh->bsh", x, lp[f"w{prefix}v"]).reshape(B, S, Kp, hd)
        kv_positions = positions
    else:
        k, v, kv_positions = kv_override
    if cfg.qk_norm and not prefix:
        q = rms_norm(q, lp["q_norm"], cfg.norm_eps)
        k = rms_norm(k, lp["k_norm"], cfg.norm_eps)
    if positions is not None:  # rotary (None => absolute/sinusoidal handled outside)
        q = rope(q, positions, cfg.rope_theta)
        if kv_override is None:
            k = rope(k, kv_positions, cfg.rope_theta)
    q = shard(q, ("pod", "data"), None, "model", None)
    k = shard(k, ("pod", "data"), None, None, None)
    impl = ATTN_IMPLS[attn_impl]
    o = impl(q, k, v, causal=causal, window=cfg.sliding_window, q_chunk=q_chunk,
             scale=cfg.attn_scale)
    o = o.reshape(B, S, Hp * hd)
    return jnp.einsum("bsf,fd->bsd", o, lp[f"w{prefix}o"]), (k, v)


def dense_block(cfg: ModelConfig, lp: dict, x, *, positions,
                attn_impl="masked", q_chunk=512, causal=True):
    a, _ = attn_block(cfg, lp, rms_norm(x, lp["ln1"], cfg.norm_eps),
                      positions=positions, attn_impl=attn_impl,
                      q_chunk=q_chunk, causal=causal)
    x = x + a
    return x + swiglu(rms_norm(x, lp["ln2"], cfg.norm_eps),
                      lp["wi_gate"], lp["wi_up"], lp["wo_mlp"])


def embed_tokens(cfg: ModelConfig, params, tokens):
    x = jnp.take(params["embed"], tokens, axis=0)
    return shard(x, ("pod", "data"), None, None)


def lm_logits(cfg: ModelConfig, params, x):
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bsd,dv->bsv", x, head)
    logits = shard(logits, ("pod", "data"), None, "model")
    if cfg.padded_vocab != cfg.vocab_size:  # mask padding ids
        pad = jnp.arange(cfg.padded_vocab) >= cfg.vocab_size
        logits = jnp.where(pad, NEG_INF, logits.astype(jnp.float32)).astype(logits.dtype)
    return logits


def scan_xs(cfg: ModelConfig, body, carry, xs):
    """lax.scan when cfg.scan_layers else an unrolled Python loop
    (loop-free HLO, which XLA's cost analysis counts in full)."""
    if cfg.scan_layers:
        return jax.lax.scan(body, carry, xs)
    L = jax.tree_util.tree_leaves(xs)[0].shape[0]
    ys = []
    for i in range(L):
        x_i = jax.tree_util.tree_map(lambda a: a[i], xs)
        carry, y = body(carry, x_i)
        ys.append(y)
    if ys and ys[0] is not None:
        ys = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *ys)
    else:
        ys = None
    return carry, ys


def _scan_blocks(cfg: ModelConfig, layers_params, x, block_fn):
    """Scan `block_fn(x, layer_params) -> x` over stacked layers with remat."""
    def body(carry, lp):
        return block_fn(carry, lp), None
    if cfg.remat:
        body = jax.checkpoint(body, prevent_cse=False)
    if cfg.scan_layers:
        x, _ = jax.lax.scan(body, x, layers_params)
    else:
        L = jax.tree_util.tree_leaves(layers_params)[0].shape[0]
        for i in range(L):
            lp = jax.tree_util.tree_map(lambda a: a[i], layers_params)
            x, _ = body(x, lp)
    return x


# ------------------------------------------------------------------ forward
def forward(params, cfg: ModelConfig, batch: dict, *,
            attn_impl="masked", q_chunk=512, return_hidden=False):
    """Teacher-forced scoring: batch['tokens'] (B,S) -> logits (B,S,Vp).
    logits[:, t] predicts tokens[:, t+1] (standard causal LM convention;
    the compressor adapter handles the BOS shift)."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    x = embed_tokens(cfg, params, tokens)
    if cfg.family == "vlm" and "img_embeds" in batch:
        img = batch["img_embeds"].astype(x.dtype)   # (B, n_img, D) stub frontend
        x = jnp.concatenate([img, x], axis=1)
        S = x.shape[1]
    positions = jnp.arange(S)
    block = partial(dense_block, cfg, positions=positions,
                    attn_impl=attn_impl, q_chunk=q_chunk)
    x = _scan_blocks(cfg, params["layers"],
                     x, lambda h, lp: block(lp, h))
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if cfg.family == "vlm" and "img_embeds" in batch:
        x = x[:, batch["img_embeds"].shape[1]:]     # only text positions score
    if return_hidden:
        return x
    return lm_logits(cfg, params, x)


# -------------------------------------------------------------------- cache
def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=None):
    """KV cache. kv_cache_dtype="int8" stores quantized K/V with per-
    (position, head) fp16 scales — halves decode HBM traffic vs bf16
    (§Perf iteration; decompression is decode/memory-bound). Losslessness
    is unaffected: compressor and decompressor run the same program.

    ``pos`` is PER-LANE (B,): every batch lane carries its own decode
    position, so the continuous-batching scheduler (repro.service) can
    reset one slot to a fresh context while the rest keep stepping —
    lock-step callers simply see all lanes advance together."""
    dtype = dtype or jnp.dtype(cfg.dtype)
    S = min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len
    L, Kp, hd = cfg.n_layers, cfg.padded_kv_heads, cfg.head_dim
    if cfg.kv_cache_dtype == "int8":
        return {
            "k": jnp.zeros((L, batch, S, Kp, hd), jnp.int8),
            "v": jnp.zeros((L, batch, S, Kp, hd), jnp.int8),
            "k_scale": jnp.zeros((L, batch, S, Kp), jnp.float16),
            "v_scale": jnp.zeros((L, batch, S, Kp), jnp.float16),
            "pos": jnp.zeros((batch,), jnp.int32),
        }
    return {
        "k": jnp.zeros((L, batch, S, Kp, hd), dtype),
        "v": jnp.zeros((L, batch, S, Kp, hd), dtype),
        "pos": jnp.zeros((batch,), jnp.int32),
    }


def _quant_kv(x):
    """x (B,1,K,hd) -> (int8, fp16 scale (B,1,K))."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) + 1e-8
    scale = (amax / 127.0)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float16)


def _dequant_kv(q, scale):
    return q.astype(jnp.float32) * scale.astype(jnp.float32)[..., None]


def _cache_slot(cfg: ModelConfig, pos, cache_len):
    """Physical slot for absolute position `pos` (ring buffer under SWA)."""
    return pos % cache_len if cfg.sliding_window else pos


def _decode_qkv(cfg, lp, x, pos, prefix=""):
    """One token's q (B,1,Hp,hd) and k, v (B,1,Kp,hd): projected, qk-normed
    when the config says so, and rotated at each lane's ``pos`` unless
    ``cfg.position_embedding`` is "nope"."""
    B, _, D = x.shape
    hd, Hp, Kp = cfg.head_dim, cfg.padded_heads, cfg.padded_kv_heads
    q = jnp.einsum("bsd,dh->bsh", x, lp[f"w{prefix}q"]).reshape(B, 1, Hp, hd)
    k = jnp.einsum("bsd,dh->bsh", x, lp[f"w{prefix}k"]).reshape(B, 1, Kp, hd)
    v = jnp.einsum("bsd,dh->bsh", x, lp[f"w{prefix}v"]).reshape(B, 1, Kp, hd)
    if cfg.qk_norm and not prefix:
        q = rms_norm(q, lp["q_norm"], cfg.norm_eps)
        k = rms_norm(k, lp["k_norm"], cfg.norm_eps)
    if cfg.position_embedding == "rope":
        q = rope(q, pos[:, None], cfg.rope_theta)
        k = rope(k, pos[:, None], cfg.rope_theta)
    return q, k, v


def _kv_rows(k, v, int8):
    """The (B, 1, ...) rows one step writes into a K/V cache: k and v, or
    under an int8 cache k and v quantized with their scales."""
    if not int8:
        return k, v
    kq, k_sc = _quant_kv(k)
    vq, v_sc = _quant_kv(v)
    return kq, vq, k_sc, v_sc


def _kv_read(leaves, dtype):
    """The k and v attention reads from a layer's cache leaves (k, v) or
    (k, v, k_scale, v_scale)."""
    if len(leaves) == 2:
        return leaves
    kc, vc, ks, vs = leaves
    return (_dequant_kv(kc, ks).astype(dtype),
            _dequant_kv(vc, vs).astype(dtype))


def _decode_attend(cfg, lp, q, k_eff, v_eff, pos, prefix=""):
    """Attention of one token over a layer's (B,S,K,hd) cache that already
    holds the token's own row, then the output projection."""
    B, S = k_eff.shape[:2]
    if cfg.sliding_window:
        # ring buffer: slot s holds abs position pos - ((pos - s) mod S);
        # valid if >= 0 — computed per lane
        s_idx = jnp.arange(S)
        abs_pos = pos[:, None] - jnp.mod(pos[:, None] - s_idx[None, :], S)
        o = _ring_attention(q, k_eff, v_eff, abs_pos >= 0)
    else:
        o = decode_attention(q, k_eff, v_eff, pos, scale=cfg.attn_scale)
    o = o.reshape(B, 1, cfg.padded_heads * cfg.head_dim)
    return jnp.einsum("bsf,fd->bsd", o, lp[f"w{prefix}o"])


def _decode_attn_one(cfg, lp, x, kc, vc, pos, prefix="", scales=None):
    """One-token attention vs. a (B,S,K,hd) cache; returns out, new kc/vc
    (+ new scales when the cache is int8-quantized).

    ``pos`` is (B,): each lane reads/writes its own cache position
    (scatter update + per-lane causal mask), which is what lets the
    service scheduler hold lanes at different chunk offsets. With all
    lanes equal this computes exactly what the old scalar-pos path did.
    q and k are rotated unless ``cfg.position_embedding`` is "nope"; the
    softmax scale is ``cfg.attn_scale`` (default 1/sqrt(head_dim))."""
    B = x.shape[0]
    q, k, v = _decode_qkv(cfg, lp, x, pos, prefix)
    slot = _cache_slot(cfg, pos, kc.shape[1])           # (B,)
    lanes = jnp.arange(B)
    leaves = (kc, vc) if scales is None else (kc, vc, *scales)
    leaves = tuple(c.at[lanes, slot].set(r[:, 0].astype(c.dtype)) for c, r
                   in zip(leaves, _kv_rows(k, v, scales is not None)))
    out = _decode_attend(cfg, lp, q, *_kv_read(leaves, x.dtype), pos,
                         prefix)
    if scales is not None:
        return out, leaves[0], leaves[1], leaves[2:]
    return out, leaves[0], leaves[1]


def _decode_attn_stacked(cfg, lp, x, kv, layer, pos):
    """``_decode_attn_one`` for layer ``layer`` of the stacked cache ``kv``
    = (k, v) or, int8, (k, v, k_scale, v_scale), each (L, B, S, ...).
    The lanes' new rows are written into the stacked leaves at [layer,
    lane, slot] and attention reads the layer's slab back out of them, so
    no slab is copied and a donated cache is updated in place. Same values
    as ``_decode_attn_one``; returns (out, kv)."""
    B = x.shape[0]
    q, k, v = _decode_qkv(cfg, lp, x, pos)
    slot = _cache_slot(cfg, pos, kv[0].shape[2])        # (B,)
    lanes = jnp.arange(B)
    kv = tuple(c.at[layer, lanes, slot].set(r[:, 0].astype(c.dtype))
               for c, r in zip(kv, _kv_rows(k, v, len(kv) == 4)))
    k_eff, v_eff = _kv_read([c[layer] for c in kv], x.dtype)
    return _decode_attend(cfg, lp, q, k_eff, v_eff, pos), kv


def _ring_attention(q, kc, vc, valid):
    """valid (B, S) per-lane mask over the ring-buffer cache."""
    B, _, H, hd = q.shape
    _, S, K, _ = kc.shape
    G = H // K
    qg = q.reshape(B, K, G, hd)
    s = jnp.einsum("bkgh,bskh->bkgs", qg.astype(jnp.float32),
                   kc.astype(jnp.float32)) / jnp.sqrt(float(hd))
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bskh->bkgh", p, vc.astype(jnp.float32))
    return o.reshape(B, 1, H, hd).astype(q.dtype)


def decode_step(params, cfg: ModelConfig, cache, prev_tokens):
    """One autoregressive step: (cache, prev (B,)) -> (logits (B,Vp), cache).

    The stacked K/V leaves (and int8 scales) ride in the layer scan's
    carry, and each layer writes its lanes' new rows into them in place.
    Under a jit that donates ``cache`` (``ModelPredictor``'s, for the
    families in ``models.api.IN_PLACE_DECODE``) the returned cache reuses
    the argument's buffers, and the argument's arrays are consumed."""
    pos = cache["pos"]
    x = embed_tokens(cfg, params, prev_tokens[:, None])
    names = ("k", "v", "k_scale", "v_scale") \
        if cfg.kv_cache_dtype == "int8" else ("k", "v")

    def body(carry, xs):
        h, kv = carry
        lp, layer = xs
        a, kv = _decode_attn_stacked(
            cfg, lp, rms_norm(h, lp["ln1"], cfg.norm_eps), kv, layer, pos)
        h = h + a
        h = h + swiglu(rms_norm(h, lp["ln2"], cfg.norm_eps),
                       lp["wi_gate"], lp["wi_up"], lp["wo_mlp"])
        return (h, kv), None

    (x, kv), _ = scan_xs(cfg, body, (x, tuple(cache[n] for n in names)),
                         (params["layers"], jnp.arange(cfg.n_layers)))
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = lm_logits(cfg, params, x)[:, 0]
    return logits, {**dict(zip(names, kv)), "pos": pos + 1}
