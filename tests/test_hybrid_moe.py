"""The Granite-4.0-H family (``models/hybrid_moe.py``) against its plain
float32 reference (``chipbench/reference/granite_hybrid.py``) at a tiny
size on seeded random weights: prefill and decoding through the mixed
cache, the expert share of one chip, the service round trip with reused
slots, and the dense and Granite decode programs pinned by hash."""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import granite_hybrid as ref
from helpers import tiny
from repro.models import api
from repro.models.moe import moe_ffn_local
from repro.models.layers import swiglu
from repro.models.schema import abstract_params
from repro.serve.engine import ModelPredictor
from repro.service import CompressionService

# the tiny model as a configuration file's ``model`` section states it:
# 3 layers (Mamba, attention, Mamba), 2 of 16 experts held, top-4
M = {
    "hidden_size": 64, "intermediate_size": 32,
    "shared_intermediate_size": 48, "num_hidden_layers": 3,
    "num_attention_heads": 4, "num_key_value_heads": 2, "vocab_size": 258,
    "rms_norm_eps": 1e-5, "tie_word_embeddings": True,
    "router_outputs": 16, "num_experts_per_tok": 4, "num_local_experts": 2,
    "mamba_d_state": 16, "mamba_d_conv": 4, "mamba_expand": 2,
    "mamba_d_head": 16, "mamba_chunk_size": 8, "mamba_conv_bias": True,
    "attention_multiplier": 1.0 / 16, "embedding_multiplier": 12.0,
    "residual_multiplier": 0.22, "logits_scaling": 16.0,
    "rope_theta": 10000.0, "layer_pattern": "MAM", "vocab_pad_multiple": 1,
}
INIT = {"embed_std": 0.05, "final_norm": 100.0, "norm_jitter": 0.1,
        "conv_bias_std": 0.1}
BOS = 257
# float32 on both sides, so the gaps are rounding: the program's chunked
# SSD scan and the reference's token-by-token recurrence, and XLA's and
# the einsums' summation orders (measured 2.6e-6). Logits here have std
# ~2.7; leaving out the conv bias, the gated norm or a residual
# multiplier moves them by more than 1 (test_tolerance_sees_each_granite_term).
ATOL = 1e-4


def _cfg(m=M):
    return tiny("hybrid_moe").with_(**{
        f: m[k] for k, f in ref.PROGRAM_FIELDS.items() if k in m})


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


@pytest.fixture(scope="module")
def model():
    params = ref.make_weights(M, INIT, 11)
    return _cfg(), _f32(params), params


def _tokens(n=2, s=12, seed=1):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (n, s),
                                         0, 256), np.int32)


def _ref_logits(params, toks, m=M):
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.forward(m, params, jnp.asarray(toks)))


def test_reference_weights_have_the_program_layout(model):
    cfg, params, _ = model
    want = jax.tree_util.tree_map(lambda a: a.shape, abstract_params(cfg))
    assert jax.tree_util.tree_map(lambda a: a.shape, params) == want


def test_prefill_and_decode_match_reference(model):
    """Scoring through ``forward`` and decoding token by token through the
    mixed cache (conv window, float32 SSM state, K/V) both give the
    reference's full-forward logits."""
    cfg, params, _ = model
    toks = _tokens()
    want = _ref_logits(params, toks)
    got = np.asarray(api.forward(params, cfg, {"tokens": jnp.asarray(toks)},
                                 dropless=True))[..., :cfg.vocab_size]
    np.testing.assert_allclose(got, want, atol=ATOL, rtol=0)
    cache = api.init_cache(cfg, toks.shape[0], toks.shape[1])
    step = jax.jit(lambda p, c, t: api.decode_step(p, cfg, c, t,
                                                   dropless=True))
    steps = []
    for t in range(toks.shape[1]):
        lg, cache = step(params, cache, toks[:, t])
        steps.append(np.asarray(lg)[:, :cfg.vocab_size])
    np.testing.assert_allclose(np.stack(steps, 1), want, atol=ATOL, rtol=0)
    assert want.std() > 1.0


@pytest.mark.parametrize("term", ["conv_b", "gate_norm", "residual"])
def test_tolerance_sees_each_granite_term(model, term):
    """The agreement above is not loose enough to hide a Granite term: the
    reference without it is farther than the tolerance from itself."""
    _, params, _ = model
    toks = _tokens()
    m, p = dict(M), dict(params, mamba=dict(params["mamba"]))
    if term == "residual":
        m["residual_multiplier"] = 1.0
    elif term == "conv_b":
        p["mamba"]["conv_b"] = jnp.zeros_like(p["mamba"]["conv_b"])
    else:
        p["mamba"]["gate_norm"] = 1.0 / p["mamba"]["gate_norm"]
    gap = np.abs(_ref_logits(p, toks, m) - _ref_logits(params, toks)).max()
    assert gap > 1000 * ATOL, gap


def test_expert_shares_sum_to_uncut_layer():
    """Eight chips of 2 experts each: their partial outputs, with the
    shared expert (computed by every chip alike) counted once, sum to the
    uncut reference's whole expert layer over all 16 experts."""
    m = dict(M, num_local_experts=16)
    cfg = _cfg(m)
    lp = ref._take(ref.make_weights(m, INIT, 5)["moe"], 0, True)
    x = jax.random.normal(jax.random.PRNGKey(2), (24, 64), jnp.float32)
    held = M["num_local_experts"]
    total = swiglu(x[None], lp["ws_gate"], lp["ws_up"], lp["ws_down"])[0]
    for s in range(m["router_outputs"] // held):
        part = dict(lp, **{k: lp[k][s * held:(s + 1) * held]
                           for k in ("we_gate", "we_up", "we_down")})
        total = total + moe_ffn_local(x, part, cfg.with_(experts_held=held),
                                      shard_id=s, n_shards=8, dropless=True)
    with jax.default_matmul_precision("highest"):
        want = ref._experts(m, lp, x[None], ref.mm)[0]
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_service_round_trip_and_reused_slot(model):
    """Containers of the service round-trip byte for byte; a chunk coded in
    a slot that held other chunks gives the same bytes as in a fresh slot
    (the refill zeroes the recurrent state); the decode program's expert
    counters reach the service registry."""
    cfg, params, _ = model
    pred = ModelPredictor(params, cfg, bos_id=BOS)
    rng = np.random.default_rng(3)
    first = rng.integers(0, 256, 7).astype(np.int32)
    others = [rng.integers(0, 256, n).astype(np.int32) for n in (21, 13)]

    fresh = CompressionService(pred, slots=2, chunk_size=8, topk=8)
    blob_fresh = fresh.submit_compress(first).result()[0]
    svc = CompressionService(pred, slots=2, chunk_size=8, topk=8)
    hs = [svc.submit_compress(x) for x in others + [first]]
    blobs = [h.result()[0] for h in hs]
    assert blobs[-1] == blob_fresh

    back = CompressionService(pred, slots=2, chunk_size=8, topk=8)
    for x, blob in zip(others + [first], blobs):
        assert np.array_equal(back.submit_decompress(blob).result(), x)

    reg = svc.registry
    steps = int(svc.stats.model_steps)
    held, L = cfg.experts_held, cfg.n_layers
    assert reg.counter("moe.expert_rows").value == steps * held * 2 * L
    routed = reg.counter("moe.routed_local").value
    assert 0 < routed < steps * 2 * L * cfg.top_k


# sha256 of ``_decode.lower(...).as_text()``. Qwen3's: the qwen3-1.7b smoke
# preset's decode program with its K/V cache carried through the layer
# scan and donated; a change to the dense decode program must re-pin it on
# purpose. Granite's: the tiny Granite model's decode program, which the
# dense in-place cache left as it was (neither restructured nor donated).
QWEN3_TINY_DECODE_SHA256 = \
    "f7dfa896a50648212f7dbb69e95c0e3e2e372dc2feab57bda69ee0d7bd70d198"
GRANITE_TINY_DECODE_SHA256 = \
    "7a6271d9b3a52b5f23d351de800d43befb8b78d3bf725d0bbc9a34f6a7520820"


def _decode_sha256(pred, params, cache, batch):
    text = pred._decode.lower(params, cache, jnp.zeros((batch,), jnp.int32),
                              {}).as_text()
    return hashlib.sha256(text.encode()).hexdigest()


def test_dense_decode_program_unchanged():
    from repro.configs.qwen3_1_7b import SMOKE_CONFIG as cfg
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    pred = ModelPredictor(params, cfg)
    cache = api.init_cache(cfg, 4, 32)
    assert _decode_sha256(pred, params, cache, 4) == QWEN3_TINY_DECODE_SHA256
    pred.decode_step(cache, np.zeros(4, np.int32))
    assert pred.step_stats is None


def test_granite_decode_program_unchanged(model):
    cfg, _, params = model
    pred = ModelPredictor(params, cfg)
    cache = api.init_cache(cfg, 4, 32)
    assert _decode_sha256(pred, params, cache, 4) == \
        GRANITE_TINY_DECODE_SHA256
