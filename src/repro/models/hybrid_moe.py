"""Granite-4.0-H-style hybrid: each layer is a mixer, Mamba-2 or grouped-
query attention as ``cfg.layer_types`` says, followed by an expert FFN,
routed experts plus a shared SwiGLU expert (hf:ibm-granite/granite-4.0-h-
small, ``GraniteMoeHybridDecoderLayer``):

    h = h + m * mixer(norm1(h))
    h = h + m * (experts(norm2(h)) + shared(norm2(h)))

with ``m`` = ``cfg.residual_multiplier``, the input embedding times
``cfg.embedding_multiplier`` and the logits divided by
``cfg.logits_scaling``. Mamba layers gate their output through an RMSNorm
(``cfg.ssm_gated_norm``) and add a conv bias; attention takes no position
embedding when ``cfg.position_embedding`` is "nope" and softmaxes at
``cfg.attn_scale``.

The expert layer holds ``cfg.experts_held`` of the router's
``cfg.n_experts`` experts (the first ones: shard 0 of E / held): the
router scores all of them, and the layer adds its own experts' part for
the tokens routed to them (``moe.moe_ffn_local``).

Parameters are stacked by kind (``mamba``, ``attn``, one ``moe`` FFN per
layer). The decode cache holds one entry per run of consecutive layers
of one kind, in layer order: ``{"conv", "state"}`` (float32 SSM state)
for a Mamba run, ``{"k", "v"}`` for an attention run, each (layers, B,
...), plus the per-lane ``pos``. A run is one ``lax.scan`` that reads its
cache entry whole and writes a new one, and takes its layers' weights
from the stacked parameters by index.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from .layers import rms_norm, scale_residual, swiglu
from .mamba2 import init_ssm_cache, ssm_block, ssm_decode_step
from .moe import moe_ffn_local
from .transformer import _decode_attn_one, attn_block, embed_tokens, lm_logits


def runs(cfg: ModelConfig) -> list:
    """(kind, first layer, first index within its kind, length) of each
    run of consecutive layers with one mixer kind, in layer order."""
    kinds = cfg.layer_types[:cfg.n_layers]
    if len(kinds) != cfg.n_layers or set(kinds) - {"mamba", "attention"}:
        raise ValueError(f"layer_types {cfg.layer_types!r} does not give "
                         f"{cfg.n_layers} mamba/attention layers")
    out, seen = [], {"mamba": 0, "attention": 0}
    for i, kind in enumerate(kinds):
        if out and out[-1][0] == kind:
            out[-1][3] += 1
        else:
            out.append([kind, i, seen[kind], 1])
        seen[kind] += 1
    return [tuple(r) for r in out]


def _shards(cfg: ModelConfig) -> int:
    held = cfg.experts_held or cfg.n_experts
    if cfg.n_experts % held:
        raise ValueError(f"{held} experts held do not divide "
                         f"{cfg.n_experts}")
    return cfg.n_experts // held


def _at(tree, i):
    return jax.tree_util.tree_map(lambda a: a[i], tree)


def _ffn(cfg: ModelConfig, lp: dict, h, dropless: bool):
    """h + m * (held experts' part + shared expert) of norm2(h), and the
    (token, expert) pairs routed to the held experts."""
    B, S, D = h.shape
    x = rms_norm(h, lp["ln2"], cfg.norm_eps)
    y, n_local = moe_ffn_local(x.reshape(B * S, D), lp, cfg, shard_id=0,
                               n_shards=_shards(cfg), dropless=dropless,
                               count=True)
    y = y.reshape(B, S, D) + swiglu(x, lp["ws_gate"], lp["ws_up"],
                                    lp["ws_down"])
    return h + scale_residual(cfg, y), n_local


def _embed(cfg: ModelConfig, params, tokens):
    x = embed_tokens(cfg, params, tokens)
    m = cfg.embedding_multiplier
    return x if m == 1.0 else x * m


def _logits(cfg: ModelConfig, params, x):
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = lm_logits(cfg, params, x)
    s = cfg.logits_scaling
    return logits if s == 1.0 else logits / s


def forward(params, cfg: ModelConfig, batch: dict, *, attn_impl="masked",
            q_chunk=512, dropless=False):
    """Teacher-forced scoring: batch['tokens'] (B,S) -> logits (B,S,Vp)."""
    tokens = batch["tokens"]
    x = _embed(cfg, params, tokens)
    positions = (jnp.arange(tokens.shape[1])
                 if cfg.position_embedding == "rope" else None)
    for kind, i0, j0, n in runs(cfg):
        for i, j in zip(range(i0, i0 + n), range(j0, j0 + n)):
            if kind == "mamba":
                x = ssm_block(cfg, _at(params["mamba"], j), x)
            else:
                ap = _at(params["attn"], j)
                a, _ = attn_block(cfg, ap, rms_norm(x, ap["ln1"], cfg.norm_eps),
                                  positions=positions, attn_impl=attn_impl,
                                  q_chunk=q_chunk)
                x = x + scale_residual(cfg, a)
            x, _ = _ffn(cfg, _at(params["moe"], i), x, dropless)
    return _logits(cfg, params, x)


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=None):
    dtype = dtype or jnp.dtype(cfg.dtype)
    Kp, hd = cfg.padded_kv_heads, cfg.head_dim
    entries = []
    for kind, _, _, n in runs(cfg):
        if kind == "mamba":
            entries.append(init_ssm_cache(cfg, batch, n, dtype))
        else:
            z = jnp.zeros((n, batch, max_len, Kp, hd), dtype)
            entries.append({"k": z, "v": z})
    return {"runs": entries, "pos": jnp.zeros((batch,), jnp.int32)}


def _decode_mixer(cfg: ModelConfig, params, kind: str, j, h, a, b, pos):
    """Layer ``j`` of its kind on one token a lane, from its cache entry's
    two states (conv window and SSM state, or K and V)."""
    if kind == "mamba":
        return ssm_decode_step(cfg, _at(params["mamba"], j), h, a, b)
    ap = _at(params["attn"], j)
    o, a, b = _decode_attn_one(cfg, ap, rms_norm(h, ap["ln1"], cfg.norm_eps),
                               a, b, pos)
    return h + scale_residual(cfg, o), a, b


def decode_step(params, cfg: ModelConfig, cache, prev_tokens, *,
                dropless=True, stats=False):
    """One token per lane through every layer. Returns (logits (B,Vp),
    new cache), and with ``stats`` also the step's expert counters:
    ``moe.routed_local`` (token, expert) pairs routed to the held experts
    and ``moe.expert_rows`` rows the held experts computed, over all
    layers."""
    pos = cache["pos"]
    B = prev_tokens.shape[0]
    h = _embed(cfg, params, prev_tokens[:, None])
    n_local = jnp.zeros((), jnp.int32)
    new = []
    for (kind, i0, j0, n), entry in zip(runs(cfg), cache["runs"]):
        def body(carry, xs):
            h, n_local = carry
            i, j, a, b = xs
            h, a, b = _decode_mixer(cfg, params, kind, j, h, a, b, pos)
            h, nl = _ffn(cfg, _at(params["moe"], i), h, dropless)
            return (h, n_local + nl), (a, b)
        (h, n_local), state = jax.lax.scan(
            body, (h, n_local), (jnp.arange(i0, i0 + n),
                                 jnp.arange(j0, j0 + n), *entry.values()))
        new.append(dict(zip(entry, state)))
    logits = _logits(cfg, params, h)[:, 0]
    out = (logits, {"runs": new, "pos": pos + 1})
    if not stats:
        return out
    if not dropless:
        raise ValueError("expert rows are counted for dropless dispatch")
    # dropless: each held expert computes C = T = B rows in every layer
    rows = cfg.n_experts // _shards(cfg) * B * cfg.n_layers
    return out + ({"moe.routed_local": n_local,
                   "moe.expert_rows": jnp.asarray(rows, jnp.int32)},)
