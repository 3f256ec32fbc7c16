"""Pure-jnp oracles for every Pallas kernel. Deliberately naive and
readable — the kernel tests assert_allclose against these."""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def flash_attention_ref(q, k, v, *, causal=True, window=None, scale=None):
    """q (B,H,Sq,hd), k/v (B,K,Sk,hd) -> (B,H,Sq,hd). GQA by head grouping."""
    B, H, Sq, hd = q.shape
    K = k.shape[1]
    G = H // K
    scale = scale or hd ** -0.5
    kk = jnp.repeat(k, G, axis=1)
    vv = jnp.repeat(v, G, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   kk.astype(jnp.float32)) * scale
    qp = jnp.arange(Sq)[:, None]
    kp = jnp.arange(k.shape[2])[None, :]
    mask = jnp.ones((Sq, k.shape[2]), bool)
    if causal:
        mask &= kp <= qp
    if window:
        mask &= kp > qp - window
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, vv.astype(jnp.float32)).astype(q.dtype)


def decode_attention_ref(q, k_cache, v_cache, lengths):
    """q (B,H,hd); caches (B,K,S,hd); lengths (B,) valid prefix lengths.
    -> (B,H,hd)."""
    B, H, hd = q.shape
    K, S = k_cache.shape[1], k_cache.shape[2]
    G = H // K
    kk = jnp.repeat(k_cache, G, axis=1)
    vv = jnp.repeat(v_cache, G, axis=1)
    s = jnp.einsum("bhd,bhsd->bhs", q.astype(jnp.float32),
                   kk.astype(jnp.float32)) * hd ** -0.5
    valid = jnp.arange(S)[None, :] < lengths[:, None]
    s = jnp.where(valid[:, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhs,bhsd->bhd", p, vv.astype(jnp.float32)).astype(q.dtype)


def ssd_intra_ref(x, dt, A, Bm, Cm):
    """Intra-chunk SSD (one chunk, zero entering state) + chunk state.

    x (B,Q,H,P), dt (B,Q,H), A (H,), Bm/Cm (B,Q,N)
    -> y (B,Q,H,P), state_out (B,H,P,N)
    """
    a = dt * A                                   # (B,Q,H) log decays
    cum = jnp.cumsum(a, axis=1)
    seg = cum[:, :, None, :] - cum[:, None, :, :]
    Q = x.shape[1]
    ii, jj = jnp.meshgrid(jnp.arange(Q), jnp.arange(Q), indexing="ij")
    L = jnp.exp(jnp.where((ii >= jj)[None, :, :, None], seg, -jnp.inf))
    G = jnp.einsum("bin,bjn->bij", Cm, Bm)
    W = G[..., None] * L
    y = jnp.einsum("bijh,bjh,bjhp->bihp", W, dt, x)
    end = jnp.exp(cum[:, -1:, :] - cum)
    state = jnp.einsum("bjh,bjh,bjhp,bjn->bhpn", end, dt, x, Bm)
    return y, state


def cdf_quantize_ref(probs_unnorm, precision: int):
    """Unnormalized probs (B, V) -> integer CDF interior points (B, V) by
    cumulative rounding (matches core.cdf.quantize_cdf_points)."""
    V = probs_unnorm.shape[-1]
    budget = jnp.float32((1 << precision) - V)
    cum = jnp.cumsum(probs_unnorm.astype(jnp.float32), axis=-1)
    cum = cum / cum[..., -1:]
    pts = jnp.floor(cum * budget + 0.5).astype(jnp.int32)
    return pts + (1 + jnp.arange(V, dtype=jnp.int32))


def _pad_vocab(logits, block_v: int):
    """f32 logits padded with NEG_INF to whole vocab blocks — the values
    the kernels mask a partial last block's lanes to."""
    V = logits.shape[1]
    pad = -V % block_v
    return jnp.pad(logits.astype(jnp.float32), ((0, 0), (0, pad)),
                   constant_values=NEG_INF)


def cdf_quantize_blocked_ref(logits, precision: int, block_v: int):
    """Blocked-accumulation oracle for ac_cdf._cdf_kernel: same running
    (max, scaled-sum) softmax, same per-block float prefix carry, same
    exactness clamps — term for term, so the kernel must match it
    BIT-identically (flat vs blocked float cumsum differ by ulps, which
    is why cdf_quantize_ref can only be compared to +-1)."""
    B, V = logits.shape
    logits = _pad_vocab(logits, block_v)
    nv = logits.shape[1] // block_v
    budget = jnp.float32((1 << precision) - V)
    m = jnp.full((B, 1), NEG_INF, jnp.float32)
    s = jnp.zeros((B, 1), jnp.float32)
    for j in range(nv):
        x = logits[:, j * block_v:(j + 1) * block_v]
        m_new = jnp.maximum(m, jnp.max(x, axis=-1, keepdims=True))
        s = s * jnp.exp(m - m_new) + \
            jnp.sum(jnp.exp(x - m_new), axis=-1, keepdims=True)
        m = m_new
    c = jnp.zeros((B, 1), jnp.float32)
    prev = jnp.zeros((B, 1), jnp.int32)
    out = []
    for j in range(nv):
        x = logits[:, j * block_v:(j + 1) * block_v]
        cum = c + jnp.cumsum(jnp.exp(x - m) / s, axis=-1)
        c = cum[:, -1:]
        local = jnp.arange(block_v, dtype=jnp.int32)[None, :]
        idx = j * block_v + local
        pts = jnp.floor(cum * budget + 0.5).astype(jnp.int32) + idx + 1
        pts = jnp.minimum(pts, budget.astype(jnp.int32) + idx + 1)
        pts = jnp.maximum(pts, prev + 1 + local)
        pts = jnp.where(idx == V - 1,
                        budget.astype(jnp.int32) + jnp.int32(V), pts)
        prev = pts[:, -1:]
        out.append(pts)
    return jnp.concatenate(out, axis=-1)[:, :V]


def topk_cdf_ref(logits, k: int, precision: int):
    """Flat-host oracle for ac_cdf._topk_cdf_kernel (single vocab block):
    lax.top_k + full-vocab softmax + escape + cumulative-rounding CDF —
    the same arithmetic as core.cdf.topk_cdf, restated here so the
    kernel tests stay self-contained."""
    logits = logits.astype(jnp.float32)
    top_vals, ids = jax.lax.top_k(logits, k)
    m = jnp.max(logits, axis=-1, keepdims=True)
    denom = jnp.sum(jnp.exp(logits - m), axis=-1, keepdims=True)
    top_p = jnp.exp(top_vals - m) / denom
    esc = jnp.clip(1.0 - jnp.sum(top_p, axis=-1, keepdims=True), 0.0, 1.0)
    pmf = jnp.concatenate([top_p, esc], axis=-1)
    pmf = pmf / jnp.sum(pmf, axis=-1, keepdims=True)
    budget = jnp.float32((1 << precision) - (k + 1))
    cum = jnp.cumsum(pmf, axis=-1)
    cum = cum / cum[..., -1:]
    pts = jnp.floor(cum * budget + 0.5).astype(jnp.int32) \
        + (1 + jnp.arange(k + 1, dtype=jnp.int32))
    zero = jnp.zeros_like(pts[..., :1])
    return ids.astype(jnp.int32), jnp.concatenate([zero, pts], axis=-1)


def topk_cdf_blocked_ref(logits, k: int, precision: int, block_v: int):
    """Blocked oracle for ac_cdf._topk_cdf_kernel with nv > 1: replays
    the kernel's running (max, sum) accumulation and its scratch-first
    k-round extract-max top-k merge, so the multi-block kernel must
    match it bit-identically."""
    B = logits.shape[0]
    logits = _pad_vocab(logits, block_v)
    nv = logits.shape[1] // block_v
    m = jnp.full((B, 1), NEG_INF, jnp.float32)
    s = jnp.zeros((B, 1), jnp.float32)
    vals = jnp.full((B, k), NEG_INF, jnp.float32)
    tids = jnp.zeros((B, k), jnp.int32)
    for j in range(nv):
        x = logits[:, j * block_v:(j + 1) * block_v]
        m_new = jnp.maximum(m, jnp.max(x, axis=-1, keepdims=True))
        s = s * jnp.exp(m - m_new) + \
            jnp.sum(jnp.exp(x - m_new), axis=-1, keepdims=True)
        m = m_new
        work = jnp.concatenate([vals, x], axis=-1)
        gid = j * block_v + jnp.arange(block_v, dtype=jnp.int32)[None, :]
        wid = jnp.concatenate([tids, jnp.broadcast_to(gid, x.shape).astype(
            jnp.int32)], axis=-1)
        iota = jnp.broadcast_to(jnp.arange(work.shape[-1], dtype=jnp.int32),
                                work.shape)
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
        vals = jnp.concatenate(new_v, axis=-1)
        tids = jnp.concatenate(new_i, axis=-1)
    top_p = jnp.exp(vals - m) / s
    esc = jnp.clip(1.0 - jnp.sum(top_p, axis=-1, keepdims=True), 0.0, 1.0)
    pmf = jnp.concatenate([top_p, esc], axis=-1)
    pmf = pmf / jnp.sum(pmf, axis=-1, keepdims=True)
    budget = jnp.float32((1 << precision) - (k + 1))
    cum = jnp.cumsum(pmf, axis=-1)
    cum = cum / cum[:, -1:]
    pts = jnp.floor(cum * budget + 0.5).astype(jnp.int32) \
        + (1 + jnp.arange(k + 1, dtype=jnp.int32))
    zero = jnp.zeros_like(pts[:, :1])
    return tids, jnp.concatenate([zero, pts], axis=-1)
