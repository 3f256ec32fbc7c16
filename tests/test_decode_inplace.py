"""The dense decode step writes its new K/V rows into the stacked cache it
carries through the layer scan, and ``ModelPredictor`` donates that cache.

Checked here against the loop it replaced, kept below as the reference:
that loop passed each layer's (B, S, K, hd) cache slab through the scan's
inputs and outputs. Both must give the same logits and caches bit for bit,
and the service must write the same containers over a donated predictor as
over one that runs the reference loop undonated."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from helpers import tiny
from repro.models import api, init_params
from repro.models.layers import rms_norm, swiglu
from repro.models.transformer import (_decode_attn_one, embed_tokens,
                                      lm_logits, scan_xs)
from repro.serve.engine import ModelPredictor
from repro.service import CompressionService

BOS = 257


def _reference_decode_step(params, cfg, cache, prev_tokens):
    """The dense decode step as it was: each layer's cache slab is a scan
    input, updated by ``_decode_attn_one`` and returned as a scan output."""
    pos = cache["pos"]
    x = embed_tokens(cfg, params, prev_tokens[:, None])
    int8 = cfg.kv_cache_dtype == "int8"

    def body(h, xs):
        if int8:
            lp, kc, vc, ks, vs = xs
            a, kc, vc, (ks, vs) = _decode_attn_one(
                cfg, lp, rms_norm(h, lp["ln1"], cfg.norm_eps), kc, vc, pos,
                scales=(ks, vs))
        else:
            lp, kc, vc = xs
            a, kc, vc = _decode_attn_one(
                cfg, lp, rms_norm(h, lp["ln1"], cfg.norm_eps), kc, vc, pos)
        h = h + a
        h = h + swiglu(rms_norm(h, lp["ln2"], cfg.norm_eps),
                       lp["wi_gate"], lp["wi_up"], lp["wo_mlp"])
        return h, (kc, vc, ks, vs) if int8 else (kc, vc)

    names = ("k", "v", "k_scale", "v_scale") if int8 else ("k", "v")
    x, new = scan_xs(cfg, body, x, (params["layers"],
                                    *(cache[n] for n in names)))
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = lm_logits(cfg, params, x)[:, 0]
    return logits, {**dict(zip(names, new)), "pos": pos + 1}


VARIANTS = {"plain": {}, "int8": {"kv_cache_dtype": "int8"},
            "window": {"sliding_window": 4}}


def _cfg(dtype, variant):
    return tiny("dense", vocab_size=258, dtype=dtype, **VARIANTS[variant])


def _bits(a):
    a = np.asarray(a)
    return a.view(np.uint16) if a.dtype == jnp.bfloat16 else a


@pytest.mark.parametrize("variant", sorted(VARIANTS))
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_inplace_decode_matches_slab_loop(dtype, variant):
    """14 steps, lanes entering at different positions and two of them
    reset to a fresh context after step 6: every logit and every cache leaf
    equal the reference loop's, bit for bit (the window variant's ring of 4
    slots wraps several times)."""
    cfg = _cfg(dtype, variant)
    params = init_params(cfg, jax.random.PRNGKey(3))
    pred = ModelPredictor(params, cfg, bos_id=BOS)
    new = jax.jit(lambda p, c, t: api.decode_step(p, cfg, c, t))
    ref = jax.jit(lambda p, c, t: _reference_decode_step(p, cfg, c, t))
    B = 4
    cache = api.init_cache(cfg, B, 24)
    cache["pos"] = jnp.array([0, 3, 7, 1], jnp.int32)
    c_new = c_ref = cache
    toks = np.random.default_rng(5).integers(0, 256, (14, B)).astype(np.int32)
    reset = np.array([False, True, False, True])
    for t in range(14):
        if t == 6:
            c_new = pred.reset_slots(c_new, reset)
            c_ref = pred.reset_slots(c_ref, reset)
        lg_new, c_new = new(params, c_new, toks[t])
        lg_ref, c_ref = ref(params, c_ref, toks[t])
        assert np.array_equal(_bits(lg_new), _bits(lg_ref)), t
        assert sorted(c_new) == sorted(c_ref)
        for name in c_ref:
            assert np.array_equal(_bits(c_new[name]), _bits(c_ref[name])), \
                (t, name)
    assert np.asarray(c_new["pos"]).tolist() == [14, 8, 21, 8]


@pytest.mark.parametrize("family", sorted(api._FAMS))
def test_cache_donated_only_where_decode_updates_in_place(family):
    """The predictor's decode step consumes its cache argument's buffers
    exactly for the families in ``IN_PLACE_DECODE``. Either way the state
    passed in still steps as it did: stepping it again, in place of the
    state the first step returned, gives the same logits bit for bit."""
    cfg = tiny(family)
    pred = ModelPredictor(init_params(cfg, jax.random.PRNGKey(0)), cfg,
                          bos_id=cfg.vocab_size - 1)
    pred.set_decode_len(8)
    state = pred.begin_decode(2)
    _, state = pred.decode_step(state, np.array([3, 4], np.int32))
    leaves = jax.tree_util.tree_leaves(state)
    tok = np.array([5, 6], np.int32)
    lg, _ = pred.decode_step(state, tok)
    donated = family in api.IN_PLACE_DECODE
    assert donated == (family in ("dense", "vlm"))
    assert all(a.is_deleted() == donated for a in leaves), family
    lg = np.asarray(lg)
    for _ in range(2):
        lg_again, _ = pred.decode_step(state, tok)
        assert np.array_equal(np.asarray(lg_again), lg)


class ReferencePredictor(ModelPredictor):
    """A predictor whose decode program is the reference slab loop, with
    no donation."""

    def __init__(self, params, cfg, **kw):
        super().__init__(params, cfg, **kw)
        V = cfg.vocab_size

        @jax.jit
        def _decode(params, cache, prev, extra):
            logits, cache = _reference_decode_step(params, cfg, cache, prev)
            return logits[..., :V], cache

        self._decode = _decode


@pytest.mark.parametrize("variant", ["plain", "int8"])
def test_donated_service_containers_byte_identical(variant):
    """The service over the donated in-place step writes the same bytes as
    over the undonated reference loop, across slot refills, and each
    container decodes back to its tokens."""
    cfg = _cfg("bfloat16", variant)
    params = init_params(cfg, jax.random.PRNGKey(0))
    blobs = {}
    for cls in (ModelPredictor, ReferencePredictor):
        svc = CompressionService(cls(params, cfg, bos_id=BOS), slots=4,
                                 chunk_size=16, topk=8)
        out = []
        for seed, n in ((41, 100), (42, 37)):
            toks = np.random.default_rng(seed).integers(0, 256, n) \
                .astype(np.int32)
            blob, _ = svc.submit_compress(toks).result()
            assert np.array_equal(svc.submit_decompress(blob).result(), toks)
            out.append(blob)
        blobs[cls] = out
    assert blobs[ModelPredictor] == blobs[ReferencePredictor]
