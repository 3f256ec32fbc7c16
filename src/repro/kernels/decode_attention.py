"""Flash-decode: single-token attention over a long KV cache.

The decompression inner loop is decode-bound: one new token attends a KV
cache of up to 512k positions. The kernel streams KV blocks HBM->VMEM with
an online-softmax accumulator — purely memory-bound, so block size is
chosen to saturate HBM bandwidth (block_k=512 × hd=128 × 2B = 128 KiB per
stream; double-buffered by the pipeline).

Layout: q (B,H,hd), caches (B,K,S,hd), lengths (B,) valid prefix lengths
(ragged batch — streams decode in lock-step but may have unequal lengths).
Grid (B, H, nk), kv axis sequential with VMEM scratch carry.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                   acc_ref, *, scale, block_k, nk):
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[b]
    live = j * block_k < length

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # (1, hd)
        k = k_ref[0, 0].astype(jnp.float32)            # (block_k, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        k_pos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(k_pos < length, s, NEG_INF)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1)[:, None])
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=-1)[:, None]
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == nk - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def decode_attention(q, k_cache, v_cache, lengths, *, block_k=512,
                     interpret=False):
    """q (B,H,hd), caches (B,K,S,hd), lengths (B,) -> (B,H,hd)."""
    B, H, hd = q.shape
    K, S = k_cache.shape[1], k_cache.shape[2]
    G = H // K
    block_k = min(block_k, S)
    assert S % block_k == 0
    nk = S // block_k
    scale = 1.0 / math.sqrt(hd)
    q4 = q[:, :, None, :]                              # (B,H,1,hd)

    kernel = functools.partial(_decode_kernel, scale=scale,
                               block_k=block_k, nk=nk)
    out = pl.pallas_call(
        kernel,
        grid=(B, H, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),     # lengths
            pl.BlockSpec((1, 1, 1, hd), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b, h, j: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b, h, j: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, hd), lambda b, h, j: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, 1, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(lengths.astype(jnp.int32), q4, k_cache, v_cache)
    return out[:, :, 0, :]
