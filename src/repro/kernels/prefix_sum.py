"""Inclusive prefix sum for Pallas TPU kernel bodies.

Mosaic has no lowering for ``cumsum``. This builds it from rolls and
selects, which lower on the TPU and run under ``interpret=True``.

The additions follow the association order of XLA's CPU ``cumsum``: a scan
longer than 16 is cut into tiles of 16 (summed in sequence inside a tile),
the tile totals are scanned by the same rule, and each tile then adds the
inclusive prefix of the tiles before it. Float addition is not
associative, so matching that order is what lets the interpret-mode
kernels reproduce the host quantizer (``core.cdf``) bit for bit.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

_TILE = 16


def prefix_sum(x, axis: int = -1):
    """Inclusive prefix sum of ``x`` along ``axis`` (like jnp.cumsum)."""
    axis = axis % x.ndim
    pos = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
    return _scan(x, pos, x.shape[axis], axis, 1)


def _scan(x, pos, n, axis, s):
    """Scan the elements at positions p with (p + 1) % s == 0; element k
    sits at position (k + 1) * s - 1. Other positions come back unchanged
    or hold values no caller reads. ``pltpu.roll(x, d, axis)`` puts
    x[p - d] at p; every lane that reads it has p >= d."""
    at = (pos + 1) % s == 0
    k = (pos + 1) // s - 1
    t = k % _TILE
    inner = x
    for i in range(1, _TILE):       # in sequence inside each tile
        inner = jnp.where(at & (t == i), pltpu.roll(inner, s, axis) + inner,
                          inner)
    if n <= _TILE * s:              # one tile: nothing precedes it
        return inner
    outer = _scan(inner, pos, n, axis, _TILE * s)   # scan of tile totals
    # every element of tile i >= 1 adds the inclusive prefix ending at
    # tile i - 1's last element: fetch it into the tile's first element,
    # then copy it across the tile in doubling steps
    carry = pltpu.roll(outer, s, axis)
    d = 1
    while d < _TILE:
        carry = jnp.where((t >= d) & (t < 2 * d),
                          pltpu.roll(carry, d * s, axis), carry)
        d *= 2
    return jnp.where(at & (k >= _TILE), inner + carry, inner)
