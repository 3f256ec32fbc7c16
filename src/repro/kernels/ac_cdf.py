"""Fused quantized-CDF kernel — the paper-specific hot-spot.

Turning next-token logits into integer CDFs for the arithmetic coder is a
vocab-sized memory-bound chain (max -> exp -> cumsum -> normalize ->
round). Left to XLA these materialize V-sized fp32 intermediates per
token; this kernel streams vocab blocks through VMEM once, carrying
(running max, running scaled sum) in scratch, then a second sweep emits
the integer CDF points with a running prefix — two HBM passes total,
nothing materialized.

Quantization is **cumulative rounding** (see core/cdf.py): strictly
monotone, exact total, streaming. Grid (row blocks, 2, nv): pass 0
reduces, pass 1 emits; the pass axis is sequential so scratch carries
across. A row block is 8 rows (the TPU's sublane tile) or all of B.

Two kernels share the layout:

* ``cdf_points``      — full-vocabulary CDF interior points (B, V);
* ``topk_cdf_points`` — fused top-k selection -> (k+1)-symbol quantized
  CDF (+ escape), the device form of ``core.cdf.topk_cdf``: pass 0 also
  merges each block's candidates into a running top-k scratch, pass 1
  emits (ids, cdf) once — the decode loops stop paying a host-side
  ``top_k``/``pmf_to_cdf`` per step.

A vocabulary that ``block_v`` does not divide (151936 = 128 x 1187) ends
in a partial block, whose lanes past V are masked to -inf in the kernel.
For padded vocabularies the caller masks pad logits to -inf upstream;
exp(-inf - max) = 0 contributes nothing and pad symbols get exactly one
quantum each (they are never coded).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .prefix_sum import prefix_sum

NEG_INF = -1e30


def _row_block(B: int) -> int:
    return 8 if B % 8 == 0 else B


def _load_block(logits_ref, j, block_v, V):
    """This vocab block as f32, lanes past V (a partial last block)
    masked to NEG_INF."""
    x = logits_ref[...].astype(jnp.float32)            # (rb, block_v)
    if V % block_v:
        gid = j * block_v + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
        x = jnp.where(gid < V, x, NEG_INF)
    return x


def _cdf_kernel(logits_ref, out_ref, m_ref, s_ref, c_ref, p_ref, *,
                block_v, V, budget):
    p = pl.program_id(1)       # pass: 0 = reduce, 1 = emit
    j = pl.program_id(2)       # vocab block

    @pl.when((p == 0) & (j == 0))
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        s_ref[...] = jnp.zeros_like(s_ref)
        c_ref[...] = jnp.zeros_like(c_ref)
        p_ref[...] = jnp.zeros_like(p_ref)

    x = _load_block(logits_ref, j, block_v, V)

    @pl.when(p == 0)
    def _reduce():
        m_prev, s_prev = m_ref[...], s_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(x, axis=-1, keepdims=True))
        s_ref[...] = s_prev * jnp.exp(m_prev - m_new) + \
            jnp.sum(jnp.exp(x - m_new), axis=-1, keepdims=True)
        m_ref[...] = m_new

    @pl.when(p == 1)
    def _emit():
        m, s = m_ref[...], s_ref[...]
        probs = jnp.exp(x - m) / s                     # normalized block pmf
        cum = c_ref[...] + prefix_sum(probs)          # global prefix
        c_ref[...] = cum[:, -1:]
        local = jax.lax.broadcasted_iota(jnp.int32, cum.shape, 1)
        idx = j * block_v + local
        pts = jnp.floor(cum * budget + 0.5).astype(jnp.int32) + idx + 1
        # Exactness clamps. The float prefix can drift either way, and a
        # coder CDF must end at exactly 2**precision with strictly
        # increasing points — "off by one at the tail" corrupts streams:
        #   * upper: drift above 1.0 would overshoot the budget;
        #   * lower: drift DOWN across a block boundary would emit a point
        #     <= the previous block's last point (p_ref carries it), so
        #     force >= prev_last + 1 + local (strictly increasing, and
        #     never above the upper clamp: prev_last <= budget + j*block_v
        #     by the upper clamp of the previous block);
        #   * tail: the final point (index V - 1) is forced to exactly
        #     budget + V — clamping down never pulls a short tail UP.
        pts = jnp.minimum(pts, jnp.int32(budget) + idx + 1)
        pts = jnp.maximum(pts, p_ref[...] + 1 + local)
        pts = jnp.where(idx == V - 1, jnp.int32(budget) + jnp.int32(V), pts)
        p_ref[...] = pts[:, -1:]
        out_ref[...] = pts


def cdf_points(logits, precision: int, *, block_v=2048, interpret=False):
    """logits (B, V) -> int32 CDF interior points (B, V) (cdf[1:];
    prepend 0 on the host for the coder)."""
    B, V = logits.shape
    block_v = min(block_v, V)
    nv = pl.cdiv(V, block_v)
    rb = _row_block(B)
    budget = float((1 << precision) - V)

    kernel = functools.partial(_cdf_kernel, block_v=block_v, V=V,
                               budget=budget)
    return pl.pallas_call(
        kernel,
        grid=(B // rb, 2, nv),
        in_specs=[pl.BlockSpec((rb, block_v), lambda b, p, j: (b, j))],
        out_specs=pl.BlockSpec((rb, block_v), lambda b, p, j: (b, j)),
        out_shape=jax.ShapeDtypeStruct((B, V), jnp.int32),
        scratch_shapes=[
            pltpu.VMEM((rb, 1), jnp.float32),  # running max
            pltpu.VMEM((rb, 1), jnp.float32),  # running sum (scaled)
            pltpu.VMEM((rb, 1), jnp.float32),  # running prefix of cum prob
            pltpu.VMEM((rb, 1), jnp.int32),    # previous block's last point
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(logits)


def _topk_cdf_kernel(logits_ref, ids_ref, cdf_ref, m_ref, s_ref,
                     vals_ref, tids_ref, *, block_v, V, k, budget):
    p = pl.program_id(1)       # pass: 0 = reduce + top-k merge, 1 = emit
    j = pl.program_id(2)       # vocab block

    @pl.when((p == 0) & (j == 0))
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        s_ref[...] = jnp.zeros_like(s_ref)
        vals_ref[...] = jnp.full_like(vals_ref, NEG_INF)
        tids_ref[...] = jnp.zeros_like(tids_ref)

    x = _load_block(logits_ref, j, block_v, V)

    @pl.when(p == 0)
    def _reduce():
        m_prev, s_prev = m_ref[...], s_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(x, axis=-1, keepdims=True))
        s_ref[...] = s_prev * jnp.exp(m_prev - m_new) + \
            jnp.sum(jnp.exp(x - m_new), axis=-1, keepdims=True)
        m_ref[...] = m_new
        # merge this block's candidates into the running top-k scratch by
        # k extract-max rounds over [scratch | block]. Scratch-first order
        # + first-index argmax reproduce lax.top_k's tie rule (smallest
        # vocab id wins): scratch entries carry smaller global ids than
        # this block, and were themselves appended in id order.
        work = jnp.concatenate([vals_ref[...], x], axis=-1)  # (rb, k+bv)
        gid = j * block_v + jax.lax.broadcasted_iota(
            jnp.int32, x.shape, 1)
        wid = jnp.concatenate([tids_ref[...], gid], axis=-1)
        iota = jax.lax.broadcasted_iota(jnp.int32, work.shape, 1)
        n = jnp.int32(work.shape[-1])
        new_v, new_i = [], []
        for _ in range(k):
            mx = jnp.max(work, axis=-1, keepdims=True)
            pos = jnp.min(jnp.where(work == mx, iota, n), axis=-1,
                          keepdims=True)
            sel = iota == pos
            new_v.append(mx)
            new_i.append(jnp.sum(jnp.where(sel, wid, 0), axis=-1,
                                 keepdims=True))
            work = jnp.where(sel, NEG_INF, work)
        vals_ref[...] = jnp.concatenate(new_v, axis=-1)
        tids_ref[...] = jnp.concatenate(new_i, axis=-1)

    @pl.when((p == 1) & (j == 0))
    def _emit():
        # mirrors core.cdf.topk_quantized + quantize_cdf_points on the
        # (k+1)-symbol alphabet, term for term — with one vocab block the
        # scratch (m, s, top-k) equals the host's flat reduction and the
        # emitted integers are bit-identical to the host path
        m, s = m_ref[...], s_ref[...]
        top_p = jnp.exp(vals_ref[...] - m) / s                   # (rb, k)
        esc = jnp.clip(1.0 - jnp.sum(top_p, axis=-1, keepdims=True),
                       0.0, 1.0)
        pmf = jnp.concatenate([top_p, esc], axis=-1)             # (rb, k+1)
        pmf = pmf / jnp.sum(pmf, axis=-1, keepdims=True)
        cum = prefix_sum(pmf)
        cum = cum / cum[:, -1:]
        idx = jax.lax.broadcasted_iota(jnp.int32, cum.shape, 1)
        pts = jnp.floor(cum * budget + 0.5).astype(jnp.int32) + idx + 1
        ids_ref[...] = tids_ref[...]
        cdf_ref[...] = jnp.concatenate(
            [jnp.zeros_like(pts[:, :1]), pts], axis=-1)          # (rb, k+2)


def topk_cdf_points(logits, k: int, precision: int, *, block_v=2048,
                    interpret=False):
    """Fused top-k selection -> quantized (k+1)-symbol CDF: logits (B, V)
    -> (ids (B, k) int32, cdf (B, k+2) int32) with cdf[:, 0] == 0 and
    cdf[:, -1] == 2**precision — the device version of
    ``core.cdf.topk_cdf`` (one HBM pass over the logits; no V-sized
    intermediate, no host pmf cumsum per decode step).

    Caveat: ids match ``lax.top_k`` exactly when at least k logits exceed
    the NEG_INF sentinel; rows padded below that (all-(-inf) tails wider
    than V - k) may order their zero-probability slots differently.
    """
    B, V = logits.shape
    block_v = min(block_v, V)
    nv = pl.cdiv(V, block_v)
    rb = _row_block(B)
    budget = float((1 << precision) - (k + 1))

    kernel = functools.partial(_topk_cdf_kernel, block_v=block_v, V=V,
                               k=k, budget=budget)
    return pl.pallas_call(
        kernel,
        grid=(B // rb, 2, nv),
        in_specs=[pl.BlockSpec((rb, block_v), lambda b, p, j: (b, j))],
        out_specs=[
            pl.BlockSpec((rb, k), lambda b, p, j: (b, 0)),
            pl.BlockSpec((rb, k + 2), lambda b, p, j: (b, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, k), jnp.int32),
            jax.ShapeDtypeStruct((B, k + 2), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((rb, 1), jnp.float32),  # running max
            pltpu.VMEM((rb, 1), jnp.float32),  # running sum (scaled)
            pltpu.VMEM((rb, k), jnp.float32),  # running top-k values
            pltpu.VMEM((rb, k), jnp.int32),    # running top-k vocab ids
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(logits)
