"""Pallas kernels vs pure-jnp oracles (interpret=True), sweeping shapes
and dtypes per the deliverable spec."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypo import given, settings, st
from repro.kernels import ref
from repro.kernels.ac_cdf import cdf_points, topk_cdf_points
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ssd_scan import ssd_intra

RNG = np.random.default_rng(0)

# Bit-identity comparisons must run the oracle under jit: the Pallas
# interpreter executes inside a jitted program, and XLA fusion moves
# float rounding by an ulp vs eager op-by-op execution — enough to flip
# a floor(x + 0.5) at a half-integer boundary.
_blocked_cdf_ref = jax.jit(ref.cdf_quantize_blocked_ref,
                           static_argnums=(1, 2))
_topk_ref = jax.jit(ref.topk_cdf_ref, static_argnums=(1, 2))
_topk_blocked_ref = jax.jit(ref.topk_cdf_blocked_ref,
                            static_argnums=(1, 2, 3))


def _rand(shape, dtype):
    return jnp.asarray(RNG.normal(size=shape), dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,K,S,hd,causal,window,blk", [
    (2, 4, 2, 64, 16, True, None, 16),
    (1, 4, 4, 128, 32, True, None, 32),
    (2, 2, 1, 64, 16, False, None, 16),
    (1, 4, 2, 128, 16, True, 24, 32),
    (1, 8, 2, 256, 64, True, None, 64),
])
def test_flash_attention(B, H, K, S, hd, causal, window, blk, dtype):
    q, k, v = (_rand((B, H, S, hd), dtype), _rand((B, K, S, hd), dtype),
               _rand((B, K, S, hd), dtype))
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=blk, block_k=blk, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,K,S,hd,blk", [
    (2, 4, 2, 64, 16, 16), (3, 4, 4, 128, 32, 32), (2, 2, 1, 96, 16, 32),
    (1, 8, 8, 512, 64, 128),
])
def test_decode_attention(B, H, K, S, hd, blk, dtype):
    q = _rand((B, H, hd), dtype)
    kc, vc = _rand((B, K, S, hd), dtype), _rand((B, K, S, hd), dtype)
    lens = jnp.asarray(RNG.integers(1, S + 1, B), jnp.int32)
    out = decode_attention(q, kc, vc, lens, block_k=blk, interpret=True)
    want = ref.decode_attention_ref(q, kc, vc, lens)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol)


@pytest.mark.parametrize("B,Q,H,P,N", [
    (2, 16, 3, 8, 4), (1, 32, 2, 16, 8), (2, 64, 4, 8, 16),
    (1, 128, 2, 32, 32),
])
def test_ssd_intra(B, Q, H, P, N):
    x = _rand((B, Q, H, P), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.05, 0.8, (B, Q, H)), jnp.float32)
    A = jnp.asarray(-RNG.uniform(0.3, 2.0, (H,)), jnp.float32)
    Bm, Cm = _rand((B, Q, N), jnp.float32), _rand((B, Q, N), jnp.float32)
    y, s = ssd_intra(x, dt, A, Bm, Cm, interpret=True)
    yr, sr = ref.ssd_intra_ref(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-4)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), atol=1e-4)


@pytest.mark.parametrize("B,V,bv,prec", [
    (4, 256, 64, 16), (2, 1024, 256, 16), (1, 512, 512, 14),
    (3, 4096, 1024, 18),
    (16, 1000, 256, 16),     # two row blocks of 8, partial last vocab block
])
def test_cdf_points(B, V, bv, prec):
    lg = jnp.asarray(RNG.normal(size=(B, V)) * 3, jnp.float32)
    pts = np.asarray(cdf_points(lg, prec, block_v=bv, interpret=True))
    want = np.asarray(ref.cdf_quantize_ref(
        jnp.exp(lg - lg.max(-1, keepdims=True)), prec))
    # strict coder invariants hold exactly; vs-ref tolerance 1 quantum
    assert (np.diff(pts, axis=-1) >= 1).all()
    assert (pts[:, -1] == (1 << prec)).all()
    assert np.abs(pts - want).max() <= 1


@pytest.mark.parametrize("B,V,bv,prec", [
    (4, 256, 64, 16), (2, 1024, 256, 16), (1, 512, 512, 14),
    (3, 4096, 1024, 18),
    (16, 1000, 256, 16),     # two row blocks of 8, partial last vocab block
])
def test_cdf_points_bitwise_vs_blocked_oracle(B, V, bv, prec):
    """The kernel's blocked float accumulation is replayed term-for-term
    by ref.cdf_quantize_blocked_ref — equality must be BIT-exact, not
    within a quantum."""
    lg = jnp.asarray(RNG.normal(size=(B, V)) * 3, jnp.float32)
    pts = np.asarray(cdf_points(lg, prec, block_v=bv, interpret=True))
    want = np.asarray(_blocked_cdf_ref(lg, prec, bv))
    assert np.array_equal(pts, want)


@pytest.mark.parametrize("case", ["peaky", "flat", "ramp", "padded"])
def test_cdf_points_tail_exact_drift_prone(case):
    """Regression for the tail-exactness bug: the old kernel clamped
    drifted points DOWN but never UP, so a float prefix that drifted low
    left cdf[-1] < 2**precision (an invalid coder CDF). Drift-prone
    shapes: near-delta pmfs (peaky), near-uniform across many blocks
    (flat/ramp), and padded-vocab tails of exact zeros."""
    B, V, bv, prec = 3, 4096, 128, 16      # 32 blocks: maximal carry drift
    rng = np.random.default_rng(7)
    if case == "peaky":
        lg = rng.standard_normal((B, V)).astype(np.float32) * 40.0
    elif case == "flat":
        lg = rng.standard_normal((B, V)).astype(np.float32) * 1e-3
    elif case == "ramp":
        lg = np.tile(np.linspace(-5, 5, V, dtype=np.float32), (B, 1))
    else:
        lg = rng.standard_normal((B, V)).astype(np.float32) * 3.0
        lg[:, V // 2:] = ref.NEG_INF       # upstream pad masking
    pts = np.asarray(cdf_points(jnp.asarray(lg), prec, block_v=bv,
                                interpret=True))
    assert (pts[:, -1] == (1 << prec)).all(), "tail must be exact"
    assert (np.diff(pts, axis=-1) >= 1).all(), "strictly increasing"
    assert (pts[:, 0] >= 1).all()
    want = np.asarray(_blocked_cdf_ref(jnp.asarray(lg), prec, bv))
    assert np.array_equal(pts, want)


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 10_000), st.sampled_from([64, 128, 256]),
       st.sampled_from([12, 16, 20]))
def test_cdf_points_kernel_vs_host_property(seed, bv, prec):
    """Property: over randomized logits the kernel output is bit-identical
    to the blocked host oracle, and within one quantum of the flat host
    path (core.cdf cumulative rounding) with every coder invariant held
    absolutely."""
    rng = np.random.default_rng(seed)
    B, V = int(rng.integers(1, 5)), 1024
    scale = float(rng.uniform(0.01, 20.0))
    lg = jnp.asarray(rng.standard_normal((B, V)) * scale, jnp.float32)
    pts = np.asarray(cdf_points(lg, prec, block_v=bv, interpret=True))
    blocked = np.asarray(_blocked_cdf_ref(lg, prec, bv))
    assert np.array_equal(pts, blocked)
    flat = np.asarray(ref.cdf_quantize_ref(
        jnp.exp(lg - lg.max(-1, keepdims=True)), prec))
    assert (pts[:, -1] == (1 << prec)).all()
    assert (np.diff(pts, axis=-1) >= 1).all()
    assert np.abs(pts - flat).max() <= 1


@pytest.mark.parametrize("B,V,k,prec", [
    (4, 512, 16, 16), (2, 1024, 48, 16), (1, 256, 8, 14),
    (16, 1000, 48, 16),
])
def test_topk_cdf_single_block_bitwise_vs_host(B, V, k, prec):
    """With one vocab block the fused kernel's reductions are the host's
    flat reductions — (ids, cdf) must match lax.top_k + core-style
    quantization bit-for-bit (this is what keeps golden containers
    byte-stable when the decode loops move onto the kernel)."""
    lg = jnp.asarray(RNG.normal(size=(B, V)) * 3, jnp.float32)
    ids, cdf = (np.asarray(a) for a in
                topk_cdf_points(lg, k, prec, interpret=True))
    ids_r, cdf_r = (np.asarray(a) for a in _topk_ref(lg, k, prec))
    assert np.array_equal(ids, ids_r)
    assert np.array_equal(cdf, cdf_r)
    from repro.core.cdf import topk_cdf_jit
    ids_c, cdf_c = (np.asarray(a) for a in topk_cdf_jit(lg, k, prec))
    assert np.array_equal(ids, ids_c)
    assert np.array_equal(cdf, cdf_c.astype(np.int32))


@pytest.mark.parametrize("B,V,k,bv,prec", [
    (4, 512, 16, 128, 16), (2, 1024, 32, 256, 16), (3, 512, 8, 64, 14),
    (16, 1000, 32, 256, 16),
])
def test_topk_cdf_blocked_bitwise_and_invariants(B, V, k, bv, prec):
    lg = jnp.asarray(RNG.normal(size=(B, V)) * 3, jnp.float32)
    ids, cdf = (np.asarray(a) for a in
                topk_cdf_points(lg, k, prec, block_v=bv, interpret=True))
    ids_b, cdf_b = (np.asarray(a) for a in
                    _topk_blocked_ref(lg, k, prec, bv))
    assert np.array_equal(ids, ids_b)
    assert np.array_equal(cdf, cdf_b)
    # the id SET always equals lax.top_k's (order can differ only via
    # value ties); the CDF is a valid coder table regardless
    ids_r, _ = _topk_ref(lg, k, prec)
    assert np.array_equal(np.sort(ids), np.sort(np.asarray(ids_r)))
    assert (cdf[:, 0] == 0).all()
    assert (cdf[:, -1] == (1 << prec)).all()
    assert (np.diff(cdf, axis=-1) >= 1).all()


def test_topk_cdf_padded_vocab():
    """Pad logits masked to NEG_INF never enter the top-k, and the CDF
    invariants survive an exactly-zero probability tail."""
    B, V, k, prec = 2, 512, 16, 16
    lg = (RNG.normal(size=(B, V)) * 3).astype(np.float32)
    lg[:, 400:] = ref.NEG_INF
    ids, cdf = (np.asarray(a) for a in
                topk_cdf_points(jnp.asarray(lg), k, prec, block_v=128,
                                interpret=True))
    assert (ids < 400).all()
    ids_r, cdf_r = (np.asarray(a) for a in
                    _topk_ref(jnp.asarray(lg), k, prec))
    assert np.array_equal(np.sort(ids), np.sort(ids_r))
    assert (cdf[:, -1] == (1 << prec)).all()
    assert (np.diff(cdf, axis=-1) >= 1).all()


def test_ops_dispatch_cpu_uses_ref():
    from repro.kernels import ops
    q = jnp.ones((1, 2, 8, 4))
    out = ops.flash_attention(q, q, q)
    assert out.shape == (1, 2, 8, 4)


def test_ops_topk_cdf_dispatch():
    from repro.kernels import ops
    lg = jnp.asarray(RNG.normal(size=(2, 256)) * 3, jnp.float32)
    ids_r, cdf_r = (np.asarray(a) for a in _topk_ref(lg, 8, 16))
    for impl in ("ref", "interpret"):
        ids, cdf = (np.asarray(a) for a in
                    ops.topk_cdf(lg, 8, 16, impl=impl))
        assert np.array_equal(ids, ids_r), impl
        assert np.array_equal(cdf, cdf_r), impl
