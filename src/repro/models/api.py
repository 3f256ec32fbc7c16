"""Family dispatch: a single forward/init_cache/decode_step API over the
model families."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from . import encdec, hybrid, hybrid_moe, mamba2, moe, transformer
from .schema import abstract_params, count_params, init_params, param_axes

_FAMS = {
    "dense": transformer,
    "vlm": transformer,
    "moe": moe,
    "ssm": mamba2,
    "hybrid": hybrid,
    "hybrid_moe": hybrid_moe,
    "encdec": encdec,
}
# families whose serving paths route over experts: dropless dispatch, so
# scoring and decoding give the same distributions (models/moe.py)
DROPLESS = ("moe", "hybrid_moe")
# families whose decode_step(..., stats=True) also returns the step's
# counters (name -> device scalar)
STEP_STATS = ("hybrid_moe",)
# families whose decode_step writes its new K/V rows into the cache it is
# given (the layer scan carries the stacked cache): ModelPredictor donates
# the cache to their decode program. The others pass each layer's cache
# through their scans' inputs and outputs, where donation only adds a copy.
IN_PLACE_DECODE = ("dense", "vlm")


def module_for(cfg: ModelConfig):
    return _FAMS[cfg.family]


def forward(params, cfg: ModelConfig, batch: dict, **kw):
    """Teacher-forced scoring -> logits (B, S, padded_vocab)."""
    return module_for(cfg).forward(params, cfg, batch, **kw)


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=None, **kw):
    return module_for(cfg).init_cache(cfg, batch, max_len, dtype, **kw)


def decode_step(params, cfg: ModelConfig, cache, prev_tokens, **kw):
    """(logits (B, padded_vocab), new_cache)."""
    return module_for(cfg).decode_step(params, cfg, cache, prev_tokens, **kw)


def _ce_from_logits(logits, targets, vocab_size):
    """Cross entropy via one-hot einsum. take_along_axis/gather on a
    sharded vocab dim makes XLA replicate the full fp32 logits across the
    batch axis ("involuntary full rematerialization" — measured: a 2.4 GiB
    all-gather per microbatch on qwen3-1.7b); the one-hot contraction
    partitions cleanly (psum over the model axis)."""
    lg = logits.astype(jnp.float32)
    m = jax.lax.stop_gradient(jnp.max(lg, axis=-1, keepdims=True))
    lse = jnp.log(jnp.sum(jnp.exp(lg - m), axis=-1)) + m[..., 0]
    onehot = jax.nn.one_hot(targets, vocab_size, dtype=logits.dtype)
    tgt = jnp.einsum("bsv,bsv->bs", lg, onehot.astype(jnp.float32))
    return lse - tgt


def loss_fn(params, cfg: ModelConfig, batch: dict, *, loss_block: int = 0,
            **kw):
    """Next-token cross entropy (paper Eq. 16). batch['tokens'] (B,S):
    input tokens[:, :-1], target tokens[:, 1:].

    loss_block > 0 evaluates the LM head + CE per position-block
    (jax.lax.map + remat) so fp32 logits are materialized only per block —
    §Perf iteration; 0 keeps the single-shot head."""
    # Keep the full S tokens as input (token counts stay divisible by the
    # batch mesh axes — the MoE shard_map requires it); the final position
    # predicts a PAD target with zero mask.
    tokens = batch["tokens"]
    inp = dict(batch)
    inp["tokens"] = tokens
    targets = jnp.concatenate(
        [tokens[:, 1:], jnp.zeros_like(tokens[:, :1])], axis=1)
    mask = (jnp.ones(targets.shape, jnp.float32) if "mask" not in batch
            else batch["mask"].astype(jnp.float32))
    mask = mask.at[:, -1].set(0.0)
    if loss_block:
        from repro.models.transformer import lm_logits
        hidden = forward(params, cfg, inp, return_hidden=True, **kw)
        B, S, D = hidden.shape
        sb = loss_block
        pad = (-S) % sb
        if pad:
            hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
            targets = jnp.pad(targets, ((0, 0), (0, pad)))
            mask = jnp.pad(mask, ((0, 0), (0, pad)))
        nblk = hidden.shape[1] // sb
        hb = jnp.moveaxis(hidden.reshape(B, nblk, sb, D), 1, 0)
        tb = jnp.moveaxis(targets.reshape(B, nblk, sb), 1, 0)

        @jax.checkpoint
        def blk(args):
            h, t = args
            return _ce_from_logits(lm_logits(cfg, params, h), t,
                                   cfg.padded_vocab)

        nll = jax.lax.map(blk, (hb, tb))
        nll = jnp.moveaxis(nll, 0, 1).reshape(B, nblk * sb)
    else:
        logits = forward(params, cfg, inp, **kw)
        nll = _ce_from_logits(logits, targets, cfg.padded_vocab)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


__all__ = ["forward", "init_cache", "decode_step", "loss_fn", "module_for",
           "init_params", "abstract_params", "param_axes", "count_params"]
