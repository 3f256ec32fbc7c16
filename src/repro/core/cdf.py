"""Model distribution -> quantized integer CDFs.

The bridge between the LLM (which emits logits) and the arithmetic coder
(which consumes integer CDFs). Two paths:

* ``quantize_pmf`` / ``logits_to_cdf`` — full-vocabulary CDF. Exact
  quantization with every-symbol-nonzero guarantee; the coder overhead vs
  true cross-entropy is O(V / 2^precision) bits/token.

* ``logits_to_topk_cdf`` — **top-K + escape** (beyond-paper optimization,
  still lossless): only the K most likely tokens get individual slots; all
  remaining mass goes to one ESCAPE symbol. If the actual token escapes, it
  is coded uniformly over the vocabulary (log2 V extra bits). For a
  well-matched predictor on LLM-generated text, escapes are rare, and the
  host coder now touches K+1 integers per token instead of V=151936.
  kernels/ac_cdf.py holds a fused Pallas form of this transform; no
  coding path dispatches it.

All jnp functions are jit-safe and vmap-able over leading axes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

DEFAULT_PRECISION = 16


def quantize_cdf_points(probs: jnp.ndarray,
                        precision: int = DEFAULT_PRECISION) -> jnp.ndarray:
    """Quantize a pmf (last axis, size V) into integer CDF interior points
    by **cumulative rounding**:

        cdf_i = round(P(x <= i) * (T - V)) + (i + 1),   i = 0..V-1

    Properties: strictly increasing (every symbol gets >= 1 quantum),
    cdf_{V-1} == T exactly, single streaming cumsum (no sort) — which is
    what makes the fused TPU kernel (kernels/ac_cdf.py) a one-pass
    prefix-scan. Returns int32 (..., V) = cdf[1:] (prepend 0 for the coder).
    """
    V = probs.shape[-1]
    T = 1 << precision
    if T <= V:
        raise ValueError(f"precision {precision} too small for vocab {V}")
    budget = jnp.float32(T - V)
    cum = jnp.cumsum(probs.astype(jnp.float32), axis=-1)
    cum = cum / cum[..., -1:]                       # exact 1.0 tail
    pts = jnp.floor(cum * budget + 0.5).astype(jnp.int32)
    return pts + (1 + jnp.arange(V, dtype=jnp.int32))


def quantize_pmf(probs: jnp.ndarray, precision: int = DEFAULT_PRECISION) -> jnp.ndarray:
    """Integer pmf (sums to 2**precision, every entry >= 1) via
    cumulative rounding — see quantize_cdf_points."""
    pts = quantize_cdf_points(probs, precision)
    return jnp.diff(pts, axis=-1, prepend=jnp.zeros_like(pts[..., :1]))


def pmf_to_cdf(q: np.ndarray) -> np.ndarray:
    """Integer pmf -> CDF array (numpy, host side)."""
    q = np.asarray(q, dtype=np.int64)
    cdf = np.zeros(q.shape[:-1] + (q.shape[-1] + 1,), dtype=np.int64)
    np.cumsum(q, axis=-1, out=cdf[..., 1:])
    return cdf


@jax.jit
def _full_pmf(logits: jnp.ndarray) -> jnp.ndarray:
    return jax.nn.softmax(logits.astype(jnp.float32), axis=-1)


def logits_to_cdf(logits, precision: int = DEFAULT_PRECISION) -> np.ndarray:
    """Full-vocab quantized CDF(s) from logits. Returns numpy int64 (..., V+1)."""
    probs = _full_pmf(jnp.asarray(logits))
    q = quantize_pmf(probs, precision)
    return pmf_to_cdf(np.asarray(q))


#: ids per block of the two-stage top-k selection (divides the 151,936,
#: 102,400 and 100,352 vocabularies; one TPU lane row)
TOPK_BLOCK = 128


def topk_blocks(V: int, k: int) -> int:
    """Blocks of ``TOPK_BLOCK`` ids that ``top_k`` splits a V-wide row
    into, or 0 where it takes one ``lax.top_k`` over the row: the two
    stages pay only when more than k blocks compete (V > k * 128)."""
    nb = -(-V // TOPK_BLOCK)
    return nb if nb > k else 0


def _total_order_max(x: jnp.ndarray) -> jnp.ndarray:
    """Max over the last axis in the float total order (-0.0 < +0.0), so a
    block's maximum is never a -0.0 that hides a +0.0 of the same block:
    each float maps to an int32 key that orders as the float does, and
    the same map takes the largest key back."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    key = jnp.max(bits ^ ((bits >> 31) & 0x7FFFFFFF), axis=-1)
    return jax.lax.bitcast_convert_type(key ^ ((key >> 31) & 0x7FFFFFFF),
                                        jnp.float32)


def top_k(x: jnp.ndarray, k: int):
    """``lax.top_k`` of a float32 ``x`` over its last axis: the same values
    and ids in the same order, ties to the lower id, without a TopK over
    the whole vocabulary.

    Where ``topk_blocks`` gives nb > 0 the row is viewed as nb blocks of
    128 ids (padded with -inf at ids above every real one), the k blocks
    with the largest maxima are kept, in ascending id order, and one TopK
    over their k * 128 candidates picks the result. Exact, ties included:
    every block that ranks above the block of a top-k element (by its
    maximum, then its index) holds an element that ranks above it, so its
    block is among the k kept; and candidates laid out in id order make
    the second TopK's lower-position tie rule the lower-id rule."""
    V = x.shape[-1]
    nb = topk_blocks(V, k)
    if not nb:
        return jax.lax.top_k(x, k)
    lead = x.shape[:-1]
    rows = x.reshape(-1, V)
    pad = nb * TOPK_BLOCK - V
    if pad:
        rows = jnp.pad(rows, ((0, 0), (0, pad)), constant_values=-jnp.inf)
    blocks = rows.reshape(rows.shape[0], nb, TOPK_BLOCK)
    _, bidx = jax.lax.top_k(_total_order_max(blocks), k)
    bidx = jnp.sort(bidx, axis=-1)                            # id order
    cand = jnp.take_along_axis(blocks, bidx[..., None], axis=1)
    vals, pos = jax.lax.top_k(cand.reshape(-1, k * TOPK_BLOCK), k)
    ids = (jnp.take_along_axis(bidx, pos // TOPK_BLOCK, axis=-1)
           * TOPK_BLOCK + pos % TOPK_BLOCK)
    return vals.reshape(lead + (k,)), ids.reshape(lead + (k,))


def topk_quantized(logits: jnp.ndarray, k: int,
                   precision: int = DEFAULT_PRECISION,
                   temperature: float = 1.0):
    """Top-K + escape quantization.

    Returns (ids, qpmf):
      ids  int32 (..., k)    — vocabulary ids of the top-k slots
      qpmf int32 (..., k+1)  — integer pmf over [k slots, ESCAPE], sums to 2**precision

    Escape slot always has >= 1 quantum, so out-of-top-K tokens stay codable.
    """
    logits = logits.astype(jnp.float32) / temperature
    top_vals, ids = top_k(logits, k)
    # Stable softmax over the full vocab, then renormalize the top-k slice.
    m = jnp.max(logits, axis=-1, keepdims=True)
    denom = jnp.sum(jnp.exp(logits - m), axis=-1, keepdims=True)
    top_p = jnp.exp(top_vals - m) / denom          # (..., k), sums to <= 1
    escape_p = jnp.clip(1.0 - jnp.sum(top_p, axis=-1, keepdims=True), 0.0, 1.0)
    pmf = jnp.concatenate([top_p, escape_p], axis=-1)
    pmf = pmf / jnp.sum(pmf, axis=-1, keepdims=True)
    q = quantize_pmf(pmf, precision)
    return ids, q


topk_quantized_jit = jax.jit(topk_quantized, static_argnums=(1, 2))


def topk_cdf(logits: jnp.ndarray, k: int,
             precision: int = DEFAULT_PRECISION):
    """Fused top-K selection + quantization + **integer CDF build** in one
    device computation: returns (ids (..., k) int32, cdf (..., k+2) int32)
    with cdf[..., 0] == 0 and cdf[..., -1] == 2**precision.

    The CDF rows are bit-identical to the host path
    ``pmf_to_cdf(topk_quantized(logits, k, precision)[1])``: the pmf is the
    same float computation and the cumsum is exact integer arithmetic
    (2**precision <= 2**23 fits int32), so golden containers are
    unaffected. This is what removes the per-step host-side
    ``pmf_to_cdf`` slicing from the decode loops."""
    ids, q = topk_quantized(logits, k, precision)
    zero = jnp.zeros_like(q[..., :1])
    cdf = jnp.concatenate([zero, jnp.cumsum(q, axis=-1)], axis=-1)
    return ids, cdf


topk_cdf_jit = jax.jit(topk_cdf, static_argnums=(1, 2))


def topk_cdf_lookup(logits: jnp.ndarray, slots: jnp.ndarray, k: int,
                    precision: int = DEFAULT_PRECISION):
    """Fused decode step: top-K + CDF build + **symbol-interval lookup**
    for the rANS decoder's peeked slot bits, all on device.

    ``slots`` (...,) int32 are the coder states' low ``precision`` bits
    (``BatchedRansDecoder.peek``). Returns (ids, cdf, syms, starts,
    freqs): syms[i] is the unique s with cdf[s] <= slot < cdf[s+1]
    (s == k means ESCAPE), and (starts, freqs) are that symbol's interval
    — exactly what ``BatchedRansDecoder.advance`` consumes."""
    ids, cdf = topk_cdf(logits, k, precision)
    syms = jnp.sum((cdf[..., 1:] <= slots[..., None]).astype(jnp.int32),
                   axis=-1)
    starts = jnp.take_along_axis(cdf, syms[..., None], axis=-1)[..., 0]
    ends = jnp.take_along_axis(cdf, syms[..., None] + 1, axis=-1)[..., 0]
    return ids, cdf, syms, starts, ends - starts


topk_cdf_lookup_jit = jax.jit(topk_cdf_lookup, static_argnums=(2, 3))


def full_cdf(logits: jnp.ndarray, precision: int = DEFAULT_PRECISION):
    """Full-vocabulary quantized CDF rows (..., V+1) int32 built entirely
    on device (leading 0 included) — bit-identical integers to the host
    ``logits_to_cdf`` (the interior points are the same cumulative-rounding
    values; no diff+recumsum detour)."""
    pts = quantize_cdf_points(_full_pmf(logits), precision)
    zero = jnp.zeros_like(pts[..., :1])
    return jnp.concatenate([zero, pts], axis=-1)


full_cdf_jit = jax.jit(full_cdf, static_argnums=(1,))


def full_cdf_lookup(logits: jnp.ndarray, slots: jnp.ndarray,
                    precision: int = DEFAULT_PRECISION):
    """Full-vocabulary analog of ``topk_cdf_lookup``: quantized-CDF build
    + symbol-interval lookup on device (no (B, V+1) host cumsum in the
    decode loop). Returns (syms, starts, freqs) — the decoded symbols ARE
    the token ids here. Bit-identical to searching the host
    ``logits_to_cdf`` rows: the interior points are the same integers."""
    pts = quantize_cdf_points(_full_pmf(logits), precision)   # (..., V)
    syms = jax.vmap(lambda p, s: jnp.searchsorted(p, s, side="right"))(
        pts.reshape(-1, pts.shape[-1]),
        slots.astype(pts.dtype).reshape(-1)).reshape(slots.shape)
    starts = jnp.where(
        syms > 0,
        jnp.take_along_axis(pts, jnp.maximum(syms - 1, 0)[..., None],
                            axis=-1)[..., 0], 0)
    ends = jnp.take_along_axis(pts, syms[..., None], axis=-1)[..., 0]
    return syms, starts, ends - starts


full_cdf_lookup_jit = jax.jit(full_cdf_lookup, static_argnums=(2,))


def build_topk_cdfs(ids: np.ndarray, qpmf: np.ndarray):
    """Host-side: (ids, qpmf) -> per-position (ids, cdf) pairs."""
    return np.asarray(ids), pmf_to_cdf(np.asarray(qpmf))


def coding_cost_bits(logits, tokens) -> float:
    """Ideal (un-quantized) coding cost of ``tokens`` under ``logits`` in bits.
    This is the paper's Eq. (4) summed over the sequence; the measured AC
    output should exceed it only by quantization + termination overhead."""
    logp = jax.nn.log_softmax(jnp.asarray(logits).astype(jnp.float32), axis=-1)
    tok = jnp.asarray(tokens)
    nll = -jnp.take_along_axis(logp, tok[..., None], axis=-1)[..., 0]
    return float(jnp.sum(nll) / jnp.log(2.0))
