"""Flash attention (causal / sliding-window / GQA) as a Pallas TPU kernel.

Layout: q (B,H,Sq,hd), k/v (B,K,Sk,hd). Grid (B, H, nq, nk) with the kv
axis innermost/sequential; running max / denominator / accumulator live in
VMEM scratch across the kv iterations (standard online softmax).

VMEM budget per step (v5e ~16 MiB/core): q,k,v blocks (block_q + 2*block_k)
× hd × 2B plus fp32 scratch block_q×(hd+2)×4B — defaults (block_q=block_k=
256, hd=128) use ≈ 0.5 MiB, leaving room for the MXU pipeline's
double-buffering. Block sizes are multiples of 128 to align the MXU.

Causal/SWA blocks that are fully masked are skipped with pl.when — on TPU
the grid still visits them but the MXU work is predicated away; the FLOP
saving shows up in the §Perf iteration ("block_causal" XLA path is the
mesh-level equivalent).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale, block_q, block_k, nk, causal, window):
    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = i * block_q
    k_start = j * block_k
    # fully-masked block? (causal: kv strictly after the last q row;
    # SWA: kv block entirely before the window of the first q row)
    skip = False
    if causal:
        skip = k_start > q_start + block_q - 1
    live = jnp.logical_not(skip)
    if window is not None:
        live = jnp.logical_and(live, k_start + block_k - 1 >
                               q_start - window)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # (block_q, hd)
        k = k_ref[0, 0].astype(jnp.float32)          # (block_k, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = jnp.ones_like(s, dtype=jnp.bool_)
        if causal:
            mask &= k_pos <= q_pos
        if window is not None:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=-1)[:, None]
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1)[:, None]
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        l_ref[...] = l_new

    @pl.when(j == nk - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal=True, window=None,
                    block_q=256, block_k=256, interpret=False):
    """q (B,H,Sq,hd), k/v (B,K,Sk,hd) -> (B,H,Sq,hd)."""
    B, H, Sq, hd = q.shape
    K, Sk = k.shape[1], k.shape[2]
    G = H // K
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    assert Sq % block_q == 0 and Sk % block_k == 0
    nq, nk = Sq // block_q, Sk // block_k
    scale = 1.0 / math.sqrt(hd)

    kernel = functools.partial(
        _flash_kernel, scale=scale, block_q=block_q, block_k=block_k,
        nk=nk, causal=causal, window=window)
    return pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b, h, i, j: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b, h, i, j: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),    # running max
            pltpu.VMEM((block_q, 1), jnp.float32),    # running denom
            pltpu.VMEM((block_q, hd), jnp.float32),   # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
