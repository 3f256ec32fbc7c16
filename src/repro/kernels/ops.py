"""Jit'd public wrappers over the Pallas kernels.

``impl`` picks what runs:

* ``"pallas"``    — the compiled kernel; raises off a TPU backend;
* ``"interpret"`` — the kernel body under the Pallas interpreter, on any
  backend (correctness checks on the CPU);
* ``"ref"``       — the pure-jnp oracle (kernels/ref.py);
* ``"auto"``      — ``"pallas"`` on a TPU backend, ``"ref"`` elsewhere.

The model zoo's XLA paths (models/layers.py) implement the same
algorithms, so their HLO is structurally faithful to what the kernels do
on TPU.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from . import ref as _ref
from .ac_cdf import cdf_points as _cdf_points
from .ac_cdf import topk_cdf_points as _topk_cdf_points
from .decode_attention import decode_attention as _decode_attention
from .flash_attention import flash_attention as _flash_attention
from .ssd_scan import ssd_intra as _ssd_intra


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _resolve(impl: str) -> str:
    """"ref" | "interpret" | "pallas" for an ``impl`` request."""
    if impl == "auto":
        return "pallas" if _on_tpu() else "ref"
    if impl not in ("pallas", "interpret", "ref"):
        raise ValueError(f"unknown kernel impl {impl!r}")
    if impl == "pallas" and not _on_tpu():
        raise RuntimeError(
            f"impl='pallas' needs a TPU backend (this one is "
            f"{jax.default_backend()!r}); impl='interpret' runs the kernel "
            f"body on it")
    return impl


@partial(jax.jit, static_argnames=("causal", "window", "impl"))
def flash_attention(q, k, v, *, causal=True, window=None, impl="auto"):
    """q (B,H,Sq,hd), k/v (B,K,Sk,hd). impl: auto|pallas|interpret|ref."""
    impl = _resolve(impl)
    if impl == "ref":
        return _ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    return _flash_attention(q, k, v, causal=causal, window=window,
                            interpret=impl == "interpret")


@partial(jax.jit, static_argnames=("impl",))
def decode_attention(q, k_cache, v_cache, lengths, *, impl="auto"):
    impl = _resolve(impl)
    if impl == "ref":
        return _ref.decode_attention_ref(q, k_cache, v_cache, lengths)
    return _decode_attention(q, k_cache, v_cache, lengths,
                             interpret=impl == "interpret")


@partial(jax.jit, static_argnames=("impl",))
def ssd_intra(x, dt, A, Bm, Cm, *, impl="auto"):
    impl = _resolve(impl)
    if impl == "ref":
        return _ref.ssd_intra_ref(x, dt, A, Bm, Cm)
    return _ssd_intra(x, dt, A, Bm, Cm, interpret=impl == "interpret")


@partial(jax.jit, static_argnames=("precision", "impl"))
def cdf_points(logits, precision: int = 16, *, impl="auto"):
    impl = _resolve(impl)
    if impl == "ref":
        p = jnp.exp(logits - jnp.max(logits, axis=-1, keepdims=True))
        return _ref.cdf_quantize_ref(p, precision)
    return _cdf_points(logits, precision, interpret=impl == "interpret")


@partial(jax.jit, static_argnames=("k", "precision", "impl"))
def topk_cdf(logits, k: int, precision: int = 16, *, impl="auto"):
    """Fused top-k + escape quantized CDF: (ids (B,k), cdf (B,k+2))."""
    impl = _resolve(impl)
    if impl == "ref":
        return _ref.topk_cdf_ref(logits, k, precision)
    return _topk_cdf_points(logits, k, precision,
                            interpret=impl == "interpret")
