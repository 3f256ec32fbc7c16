"""Mamba2 SSD intra-chunk kernel.

The SSD forward splits into (a) a quadratic *intra-chunk* part — the
compute hot-spot, O(Q^2) per chunk like attention — and (b) a cheap
inter-chunk state recurrence (done outside in lax.scan). This kernel
computes (a) plus each chunk's boundary-state contribution in one pass.

Grid (B, nc, H): one (batch, chunk, head) cell per step; everything for a
cell fits VMEM comfortably (Q=256, P=64, N=128 => ~0.4 MiB fp32).
The Q×Q decay matrix is built in-register from the cumulative log-decay.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .prefix_sum import prefix_sum


def _ssd_kernel(a_ref, x_ref, dt_ref, b_ref, c_ref, y_ref, s_ref):
    x = x_ref[0, 0].astype(jnp.float32)        # (Q, P)  (head-major layout)
    dt = dt_ref[0, 0].astype(jnp.float32)      # (Q, 1)
    A = a_ref[pl.program_id(2)]                # this head's decay rate (SMEM)
    Bm = b_ref[0].astype(jnp.float32)          # (Q, N)
    Cm = c_ref[0].astype(jnp.float32)          # (Q, N)
    Q = x.shape[0]

    a = dt * A                                 # (Q,1) log decay
    cum = prefix_sum(a, axis=0)                # (Q,1)
    seg = cum - cum.T                          # (Q,Q) cum_i - cum_j
    ii = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    L = jnp.exp(jnp.where(ii >= jj, seg, -jnp.inf))
    G = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (Q,Q)
    W = G * L * dt.T                           # fold dt_j into the weights
    y_ref[0, 0] = jax.lax.dot_general(
        W, x, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(y_ref.dtype)
    # chunk boundary state: sum_j exp(cum_Q - cum_j) dt_j x_j (X) B_j -> (P,N)
    end = jnp.exp(cum[-1:] - cum) * dt         # (Q,1)
    s_ref[0, 0] = jax.lax.dot_general(
        x, Bm * end, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(s_ref.dtype)


def ssd_intra(x, dt, A, Bm, Cm, *, interpret=False):
    """Intra-chunk SSD. x (B,Q,H,P), dt (B,Q,H), A (H,), Bm/Cm (B,Q,N)
    -> y (B,Q,H,P) fp32, state (B,H,P,N) fp32 (zero entering state)."""
    B, Q, H, P = x.shape
    N = Bm.shape[-1]
    # head-major layouts for clean BlockSpecs
    xh = jnp.moveaxis(x, 2, 1)                 # (B,H,Q,P)
    dth = jnp.moveaxis(dt, 2, 1)[..., None]    # (B,H,Q,1)

    out = pl.pallas_call(
        _ssd_kernel,
        grid=(B, 1, H),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),          # A (H,)
            pl.BlockSpec((1, 1, Q, P), lambda b, c, h: (b, h, c, 0)),
            pl.BlockSpec((1, 1, Q, 1), lambda b, c, h: (b, h, c, 0)),
            pl.BlockSpec((1, Q, N), lambda b, c, h: (b, c, 0)),
            pl.BlockSpec((1, Q, N), lambda b, c, h: (b, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, Q, P), lambda b, c, h: (b, h, c, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, c, h: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Q, P), jnp.float32),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
    )(A.astype(jnp.float32), xh, dth, Bm, Cm)
    y, state = out
    return jnp.moveaxis(y, 1, 2), state
