"""Plain reference of the coder's symbol model: the code length of each
token under the top-K + escape quantized CDF.

The container format fixes the symbol model (paper §5, top-K variant):
the K most likely ids each get a slot and every other id shares one
ESCAPE slot; the K+1 probabilities (softmax over the whole vocabulary,
escape = 1 - sum of the top K) are quantized to integers summing to
2**precision by cumulative rounding,

    point_i = round(P(slot <= i) * (2**precision - (K+1))) + (i + 1),

so every slot keeps at least one quantum. A token in slot i costs
precision - log2(freq_i) bits; an escaped token costs the escape slot's
bits plus ceil(log2 V) bits for its id. The logits are those of the
reference module ``ref`` the configuration names (``forward`` with its
``mm``, or ``mm_int8`` for the control). Nothing here imports the
system under test.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def escape_bits(vocab: int) -> int:
    return max(1, (vocab - 1).bit_length())


def token_bits(logits, tokens, k: int, precision: int):
    """Bits per position (..., S) of ``tokens`` under ``logits`` (..., S, V)."""
    lg = logits.astype(jnp.float32)
    V = lg.shape[-1]
    vals, ids = jax.lax.top_k(lg, k)
    m = jnp.max(lg, axis=-1, keepdims=True)
    top_p = jnp.exp(vals - m) / jnp.sum(jnp.exp(lg - m), -1, keepdims=True)
    esc = jnp.clip(1.0 - jnp.sum(top_p, -1, keepdims=True), 0.0, 1.0)
    pmf = jnp.concatenate([top_p, esc], -1)
    cum = jnp.cumsum(pmf / jnp.sum(pmf, -1, keepdims=True), -1)
    cum = cum / cum[..., -1:]
    budget = float((1 << precision) - (k + 1))
    pts = jnp.floor(cum * budget + 0.5) + jnp.arange(1, k + 2)
    freq = jnp.diff(pts, axis=-1, prepend=jnp.zeros_like(pts[..., :1]))
    hit = ids == tokens[..., None]
    slot = jnp.where(hit.any(-1), jnp.argmax(hit, -1), k)
    f = jnp.take_along_axis(freq, slot[..., None], -1)[..., 0]
    return (precision - jnp.log2(f)
            + jnp.where(slot == k, escape_bits(V), 0)).astype(jnp.float32)


@partial(jax.jit, static_argnums=(0, 1, 4, 5, 6, 7))
def _block_bits(ref, mkey, params, chunks, k, precision, bos, int8):
    """chunks (b, C) tokens -> bits (b, C): position t is coded given
    [BOS, chunk[:t]], as the service codes a chunk from a fresh context."""
    m = dict(mkey)
    inp = jnp.concatenate([jnp.full((chunks.shape[0], 1), bos, chunks.dtype),
                           chunks[:, :-1]], axis=1)
    with jax.default_matmul_precision("highest"):
        logits = ref.forward(m, params, inp,
                             ref.mm_int8 if int8 else ref.mm)
    return token_bits(logits, chunks, k, precision)


def chunk_bits(ref, m: dict, params, chunks: np.ndarray,
               valid: np.ndarray, *, k: int, precision: int, bos: int,
               block: int, int8: bool = False) -> np.ndarray:
    """Reference code length in bits of each chunk's first ``valid``
    tokens, ``block`` chunks per call (float32, highest precision; with
    ``int8`` every weight product in int8, the control)."""
    mkey = tuple(sorted((a, b) for a, b in m.items()
                        if not isinstance(b, (dict, list))))
    n, C = chunks.shape
    pad = (-n) % block
    x = np.concatenate([chunks, np.zeros((pad, C), chunks.dtype)])
    out = []
    for i in range(0, len(x), block):
        out.append(np.asarray(_block_bits(ref, mkey, params,
                                          jnp.asarray(x[i:i + block]),
                                          k, precision, bos, int8)))
    bits = np.concatenate(out)[:n].astype(np.float64)
    live = np.arange(C)[None, :] < valid[:, None]
    return (bits * live).sum(axis=1)

