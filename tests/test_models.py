"""Model-zoo behaviour: forward/grads finite, decode == teacher forcing
(the lossless-compression invariant), SSD == naive recurrence, MoE
dispatch invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from helpers import rand_batch, tiny
from repro.models import (decode_step, forward, init_cache, init_params,
                          loss_fn)

FAMILIES = ["dense", "moe", "ssm", "hybrid", "encdec", "vlm"]


@pytest.mark.parametrize("family", FAMILIES)
def test_forward_and_grads_finite(family):
    cfg = tiny(family)
    p = init_params(cfg, jax.random.PRNGKey(0))
    batch = rand_batch(cfg)
    logits = forward(p, cfg, batch)
    assert logits.shape[-1] == cfg.padded_vocab
    assert np.isfinite(np.asarray(logits, np.float32)).all()
    g = jax.grad(lambda p: loss_fn(p, cfg, batch))(p)
    for leaf in jax.tree_util.tree_leaves(g):
        assert np.isfinite(np.asarray(leaf, np.float32)).all()


@pytest.mark.parametrize("family,kw", [
    ("dense", {}), ("dense", {"qk_norm": True, "sliding_window": 6}),
    ("moe", {"capacity_factor": 8.0}), ("ssm", {}), ("hybrid", {}),
    ("encdec", {}),
])
def test_decode_matches_teacher_forcing(family, kw):
    cfg = tiny(family, **kw)
    p = init_params(cfg, jax.random.PRNGKey(0))
    S = 12
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, S), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks}
    init_kw = {}
    if family == "encdec":
        frames = jax.random.normal(jax.random.PRNGKey(2), (2, 8, cfg.d_model))
        batch["frames"] = frames
        init_kw["source_len"] = 8
    want = forward(p, cfg, batch)
    cache = init_cache(cfg, 2, S, **init_kw)
    if family == "encdec":
        from repro.models.encdec import precompute_cross_kv
        cache["xk"], cache["xv"] = precompute_cross_kv(p, cfg, frames)
    outs = []
    for t in range(S):
        lg, cache = decode_step(p, cfg, cache, toks[:, t])
        outs.append(lg)
    got = jnp.stack(outs, 1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)


def test_ssd_chunked_equals_naive_recurrence():
    from repro.models.mamba2 import ssd_chunked
    rng = np.random.default_rng(1)
    B, S, H, P, N = 2, 24, 3, 4, 5
    x = jnp.asarray(rng.normal(size=(B, S, H, P)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.1, 1.0, (B, S, H)), jnp.float32)
    A = jnp.asarray(-rng.uniform(0.5, 2.0, (H,)), jnp.float32)
    Bm = jnp.asarray(rng.normal(size=(B, S, N)), jnp.float32)
    Cm = jnp.asarray(rng.normal(size=(B, S, N)), jnp.float32)
    y, fin = ssd_chunked(x, dt, A, Bm, Cm, chunk=8)
    h = np.zeros((B, H, P, N))
    ys = []
    for t in range(S):
        dA = np.exp(np.asarray(dt[:, t]) * np.asarray(A))
        h = h * dA[:, :, None, None] + \
            np.asarray(dt[:, t])[:, :, None, None] * \
            np.asarray(x[:, t])[..., None] * \
            np.asarray(Bm[:, t])[:, None, None, :]
        ys.append(np.einsum("bhpn,bn->bhp", h, np.asarray(Cm[:, t])))
    np.testing.assert_allclose(np.asarray(y), np.stack(ys, 1), atol=1e-4)
    np.testing.assert_allclose(np.asarray(fin), h, atol=1e-4)


def test_moe_dropless_group_invariance():
    """Dropless dispatch must not depend on the dispatch grouping — the
    lossless-serving requirement."""
    cfg = tiny("moe")
    p = init_params(cfg, jax.random.PRNGKey(0))
    t = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 257)
    a = forward(p, cfg, {"tokens": t}, dropless=True)
    b = forward(p, cfg, {"tokens": t}, dropless=True, dispatch_group=16)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_moe_capacity_drops_tokens():
    """With a tiny capacity factor some tokens must lose expert outputs
    (training path); the layer still runs and is finite."""
    cfg = tiny("moe", capacity_factor=0.05)
    p = init_params(cfg, jax.random.PRNGKey(0))
    t = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 257)
    drop = forward(p, cfg, {"tokens": t}, dropless=False)
    full = forward(p, cfg, {"tokens": t}, dropless=True)
    assert np.isfinite(np.asarray(drop, np.float32)).all()
    assert np.abs(np.asarray(drop) - np.asarray(full)).max() > 1e-6


def test_scan_vs_unrolled_equivalence():
    for family in ("dense", "ssm", "hybrid"):
        cfg = tiny(family)
        p = init_params(cfg, jax.random.PRNGKey(0))
        t = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 257)
        a = forward(p, cfg, {"tokens": t})
        b = forward(p, cfg.with_(scan_layers=False), {"tokens": t})
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_attention_impls_agree():
    from repro.models.layers import (attention_block_causal, attention_dense,
                                     attention_masked)
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(2, 33, 4, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 33, 2, 16)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 33, 2, 16)), jnp.float32)
    for window in (None, 7):
        a = attention_masked(q, k, v, causal=True, window=window, q_chunk=8)
        b = attention_block_causal(q, k, v, causal=True, window=window,
                                   q_chunk=8)
        c = attention_dense(q, k, v, causal=True, window=window)
        np.testing.assert_allclose(np.asarray(a), np.asarray(c), atol=2e-5)
        np.testing.assert_allclose(np.asarray(b), np.asarray(c), atol=2e-5)


def test_cache_leaves_follow_the_lane_convention():
    """ModelPredictor's lane programs (_reset, _snapshot, _restore,
    _rollback) take 'pos' as (B,), leave 'xk'/'xv' (per-job conditioning)
    whole, and index every other cache leaf's lanes on axis 1. Check that
    every configuration's cache keeps to it: between batch 16 and batch 3
    a leaf changes on exactly that axis."""
    from repro.configs.registry import ARCH_IDS, get_config

    def leaves(cfg, batch):
        cache = jax.eval_shape(lambda: init_cache(cfg, batch, 128))
        return jax.tree_util.tree_flatten_with_path(cache)[0]

    for arch in ARCH_IDS + ["granite_4_0_h_small"]:
        cfg = get_config(arch)
        names = []
        for (path, a), (_, b) in zip(leaves(cfg, 16), leaves(cfg, 3)):
            name = path[-1].key
            names.append(name)
            if name in ("xk", "xv"):
                assert cfg.family == "encdec", (arch, name)
                continue
            if name == "pos":
                assert (a.shape, b.shape) == ((16,), (3,)), arch
                continue
            differ = [i for i, (m, n) in enumerate(zip(a.shape, b.shape))
                      if m != n]
            assert differ == [1] and a.shape[1] == 16, \
                (arch, name, a.shape, b.shape)
        assert "pos" in names, arch
