"""The CDF program's two-stage top-k selection (``core/cdf.py`` ``top_k``)
against one ``lax.top_k`` over the whole vocabulary: the same values (sign
bits included), ids and order, ties to the lower id; and the same CDF
integers from ``topk_cdf`` and ``topk_cdf_lookup`` as the one-stage
formula, which this file keeps as its oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.cdf import (TOPK_BLOCK, quantize_pmf, top_k, topk_blocks,
                            topk_cdf_jit, topk_cdf_lookup_jit)

K = 48
# the cells' vocabularies, one 128 does not divide, the smallest that
# takes two stages at k = 48, and two that take one
VOCABS = [151936, 102400, 100352, 50257, K * TOPK_BLOCK + 1,
          K * TOPK_BLOCK, 1000]
KINDS = ["bf16", "ties", "signed_zeros", "neg_inf", "lead_axes"]

_top_k = jax.jit(top_k, static_argnums=1)
_lax_top_k = jax.jit(jax.lax.top_k, static_argnums=1)


def _logits(kind, V, seed=0):
    rng = np.random.default_rng([seed, V])
    shape = (2, 3, V) if kind == "lead_axes" else (4, V)
    if kind == "bf16":
        x = rng.standard_normal(shape) * 4
    elif kind in ("ties", "lead_axes"):
        x = rng.integers(-6, 3, shape) * 0.5    # a few values, many ties
    elif kind == "signed_zeros":
        x = np.where(rng.random(shape) < 0.5, -0.0, 0.0)
        x[1, : V // 2] = -0.0                   # all -0.0 in the low half
        x[2] = -0.0
    else:                                       # mostly -inf
        x = np.where(rng.random(shape) < 0.999, -np.inf,
                     rng.integers(-2, 2, shape) * 1.0)
        x[0] = -np.inf                          # a row with no finite entry
        x[1, 20:] = -np.inf                     # one with fewer than K
    x = jnp.asarray(x.astype(np.float32), jnp.bfloat16)
    return np.asarray(x.astype(jnp.float32))


def _bits(a):
    return np.asarray(a, np.float32).view(np.int32)


@pytest.mark.parametrize("V,k,nb", [
    (151936, 48, 1187), (102400, 48, 800), (100352, 48, 784),
    (50257, 48, 393), (6145, 48, 49), (6144, 48, 0), (1000, 48, 0),
    (1030, 8, 9), (258, 8, 0)])
def test_topk_blocks(V, k, nb):
    """Two stages only where more than k blocks of 128 ids compete."""
    assert topk_blocks(V, k) == nb


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("V", VOCABS)
def test_top_k_equals_lax_top_k(V, kind):
    x = jnp.asarray(_logits(kind, V))
    vals, ids = _top_k(x, K)
    ref_vals, ref_ids = _lax_top_k(x, K)
    assert vals.shape == ids.shape == x.shape[:-1] + (K,)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(ref_ids))
    np.testing.assert_array_equal(_bits(vals), _bits(ref_vals))


def _onestage_topk_cdf(logits, k, precision):
    """The CDF formula with one ``lax.top_k`` over the whole row."""
    logits = logits.astype(jnp.float32)
    top_vals, ids = jax.lax.top_k(logits, k)
    m = jnp.max(logits, axis=-1, keepdims=True)
    denom = jnp.sum(jnp.exp(logits - m), axis=-1, keepdims=True)
    top_p = jnp.exp(top_vals - m) / denom
    escape_p = jnp.clip(1.0 - jnp.sum(top_p, axis=-1, keepdims=True),
                        0.0, 1.0)
    pmf = jnp.concatenate([top_p, escape_p], axis=-1)
    pmf = pmf / jnp.sum(pmf, axis=-1, keepdims=True)
    q = quantize_pmf(pmf, precision)
    zero = jnp.zeros_like(q[..., :1])
    return ids, jnp.concatenate([zero, jnp.cumsum(q, axis=-1)], axis=-1)


_oracle = jax.jit(_onestage_topk_cdf, static_argnums=(1, 2))


@pytest.mark.parametrize("kind", ["bf16", "ties", "lead_axes"])
@pytest.mark.parametrize("V", [151936, 100352, 50257, K * TOPK_BLOCK])
def test_cdf_integers_equal_one_stage(V, kind):
    """``topk_cdf`` and ``topk_cdf_lookup`` give the one-stage formula's
    ids and CDF integers, on the bfloat16 logits the model step emits;
    the lookup finds each slot's symbol interval in those integers."""
    prec = 16
    x = jnp.asarray(_logits(kind, V, seed=1), jnp.bfloat16)
    ref_ids, ref_cdf = (np.asarray(a) for a in _oracle(x, K, prec))
    ids, cdf = (np.asarray(a) for a in topk_cdf_jit(x, K, prec))
    np.testing.assert_array_equal(ids, ref_ids)
    np.testing.assert_array_equal(cdf, ref_cdf)

    slots = np.random.default_rng(V).integers(
        0, 1 << prec, x.shape[:-1]).astype(np.int32)
    l_ids, l_cdf, syms, starts, freqs = (
        np.asarray(a) for a in topk_cdf_lookup_jit(x, slots, K, prec))
    np.testing.assert_array_equal(l_ids, ref_ids)
    np.testing.assert_array_equal(l_cdf, ref_cdf)
    rows, sl = ref_cdf.reshape(-1, K + 2), slots.reshape(-1)
    want = np.array([np.searchsorted(r[1:], s, side="right")
                     for r, s in zip(rows, sl)])
    np.testing.assert_array_equal(syms.reshape(-1), want)
    np.testing.assert_array_equal(starts.reshape(-1),
                                  rows[np.arange(len(want)), want])
    np.testing.assert_array_equal(
        freqs.reshape(-1), rows[np.arange(len(want)), want + 1]
        - rows[np.arange(len(want)), want])


@pytest.mark.parametrize("vocab,blocked", [(1030, True), (258, False)])
def test_scheduler_counts_blocked_steps(vocab, blocked):
    """``cdf.topk_blocked`` counts the model steps whose top-8 CDF took
    the two stages: every step at V = 1,030 (9 blocks), none at 258."""
    from helpers import tiny
    from repro.models import init_params
    from repro.serve.engine import ModelPredictor
    from repro.service import CompressionService

    cfg = tiny("dense", vocab_size=vocab)
    pred = ModelPredictor(init_params(cfg, jax.random.PRNGKey(0)), cfg,
                          bos_id=257)
    svc = CompressionService(pred, slots=4, chunk_size=16, topk=8)
    toks = np.random.default_rng(3).integers(0, 256, 60).astype(np.int32)
    blob, _ = svc.submit_compress(toks).result()
    assert np.array_equal(svc.submit_decompress(blob).result(), toks)
    steps = svc.stats.model_steps
    assert steps > 0
    assert svc.registry.value("cdf.topk_blocked") == (steps if blocked
                                                      else 0)
