"""Core neural layers (pure JAX, shard-friendly).

Attention comes in three interchangeable implementations:
  * ``attention_masked``       — q-chunked online-softmax over the full KV
                                 (baseline; causal mask applied, masked
                                 positions still burn FLOPs — visible in the
                                 roofline "useful FLOPs" ratio).
  * ``attention_block_causal`` — triangular (q-chunk, kv-chunk) schedule that
                                 only computes unmasked blocks (beyond-paper
                                 perf iteration; ~2x FLOP cut at long S).
  * Pallas flash kernel        — kernels/flash_attention.py (TPU target).

All math in float32 accumulators, activations in cfg.dtype.
"""
from __future__ import annotations

import contextlib
import contextvars
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

NEG_INF = -1e30

# Trace-time mesh context: jit tracing does not expose the target mesh
# (jax.sharding.get_abstract_mesh() is empty unless set_mesh is active),
# so the train step wraps its body in mesh_context(mesh) and shard()
# reads it to emit constraints with only the axes that exist.
_MESH_VAR = contextvars.ContextVar("repro_mesh", default=None)


@contextlib.contextmanager
def mesh_context(mesh):
    tok = _MESH_VAR.set(mesh)
    try:
        yield
    finally:
        _MESH_VAR.reset(tok)


def shard(x, *axes):
    """Soft sharding hint against the mesh_context mesh. Axis names not in
    the mesh are dropped (e.g. 'pod' on the single-pod mesh) — naming a
    missing axis raises inside jit and a skipped constraint measurably
    de-shards activations (batch replicated across 'data' in the backward;
    found via 16x-inflated collective bytes in the compiled train step)."""
    mesh = _MESH_VAR.get()
    if mesh is None:
        return x
    names = set(mesh.axis_names)

    def filt(a):
        if isinstance(a, (tuple, list)):
            t = tuple(x for x in a if x in names)
            return t if t else None
        return a if a in names else None

    spec = P(*(filt(a) for a in axes))
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(mesh, spec))


def scale_residual(cfg, y):
    """A residual branch's output times ``cfg.residual_multiplier`` (muP);
    at 1.0 the branch is returned as it is, so programs without the
    multiplier do not change."""
    m = cfg.residual_multiplier
    return y if m == 1.0 else y * m


def rms_norm(x, weight, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps) * weight.astype(jnp.float32)).astype(dt)


def rope(x, positions, theta: float = 1e6):
    """Rotary embedding. x (..., S, H, hd), positions (..., S)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs          # (..., S, half)
    cos = jnp.cos(ang)[..., None, :]                                 # (..., S, 1, half)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def swiglu(x, w_gate, w_up, w_down, *, tp_axis="model"):
    h = jax.nn.silu(jnp.einsum("bsd,df->bsf", x, w_gate)) * \
        jnp.einsum("bsd,df->bsf", x, w_up)
    h = shard(h, ("pod", "data"), None, tp_axis)
    return jnp.einsum("bsf,fd->bsd", h, w_down)


# --------------------------------------------------------------------- attn
def _mask_bias(q_pos, k_pos, *, causal, window):
    m = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    if causal:
        m &= k_pos[None, :] <= q_pos[:, None]
    if window:
        m &= k_pos[None, :] > q_pos[:, None] - window
    return jnp.where(m, 0.0, NEG_INF).astype(jnp.float32)


def attention_masked(q, k, v, *, causal=True, window=None,
                     q_offset=0, k_offset=0, q_chunk=512, scale=None):
    """Baseline attention: scan over q chunks, each attends the full KV with
    an additive mask; online softmax keeps memory at O(q_chunk * Sk).

    q (B,Sq,H,hd), k/v (B,Sk,K,hd), GQA via head grouping. Returns (B,Sq,H,hd).
    """
    B, Sq0, H, hd = q.shape
    _, Sk, K, _ = k.shape
    G = H // K
    scale = 1.0 / math.sqrt(hd) if scale is None else scale
    qc = min(q_chunk, Sq0)
    if Sq0 % qc:  # pad q rows; padded rows are sliced off the output
        q = jnp.pad(q, ((0, 0), (0, qc - Sq0 % qc), (0, 0), (0, 0)))
    Sq = q.shape[1]
    n_chunks = max(1, Sq // qc)
    qs = q.reshape(B, n_chunks, qc, K, G, hd)
    k_pos = k_offset + jnp.arange(Sk)

    def body(i):
        qi = qs[:, i]                                               # (B,qc,K,G,hd)
        q_pos = q_offset + i * qc + jnp.arange(qc)
        bias = _mask_bias(q_pos, k_pos, causal=causal, window=window)
        s = jnp.einsum("bqkgh,bskh->bkgqs", qi.astype(jnp.float32),
                       k.astype(jnp.float32)) * scale + bias
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        o = jnp.einsum("bkgqs,bskh->bqkgh", p, v.astype(jnp.float32))
        o = o / jnp.sum(p, axis=-1)[..., None].transpose(0, 3, 1, 2, 4)
        return o.astype(q.dtype)

    out = jax.lax.map(body, jnp.arange(n_chunks))                   # (n,B,qc,K,G,hd)
    out = jnp.moveaxis(out, 0, 1).reshape(B, Sq, H, hd)
    return out[:, :Sq0]


def attention_block_causal(q, k, v, *, causal=True, window=None,
                           q_offset=0, k_offset=0, q_chunk=512, scale=None):
    """Block-sparse causal attention: a scan over only the (qi, kj) chunk
    pairs that contain unmasked entries. Cuts the masked-dense FLOP waste
    (~2x for causal, more for SWA). Online softmax across kv blocks.
    Requires q_offset == k_offset == 0 (training/prefill use)."""
    B, Sq0, H, hd = q.shape
    _, Sk0, K, _ = k.shape
    G = H // K
    scale = 1.0 / math.sqrt(hd) if scale is None else scale
    qc = min(q_chunk, Sq0)
    if Sq0 % qc:
        q = jnp.pad(q, ((0, 0), (0, qc - Sq0 % qc), (0, 0), (0, 0)))
    if Sk0 % qc:
        k = jnp.pad(k, ((0, 0), (0, qc - Sk0 % qc), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, qc - Sk0 % qc), (0, 0), (0, 0)))
    Sq, Sk = q.shape[1], k.shape[1]
    nq, nk = Sq // qc, Sk // qc

    pairs = [(i, j) for i in range(nq) for j in range(nk)
             if (not causal or j <= i)
             and (not window or (i - j) * qc < window + qc)]
    pairs = jnp.array(pairs, dtype=jnp.int32)                       # (npair, 2)

    qs = q.reshape(B, nq, qc, K, G, hd)
    ks = k.reshape(B, nk, qc, K, hd)
    vs = v.reshape(B, nk, qc, K, hd)

    def body(carry, pair):
        m_all, l_all, acc_all = carry
        i, j = pair[0], pair[1]
        qi = jax.lax.dynamic_index_in_dim(qs, i, 1, keepdims=False)
        kj = jax.lax.dynamic_index_in_dim(ks, j, 1, keepdims=False)
        vj = jax.lax.dynamic_index_in_dim(vs, j, 1, keepdims=False)
        q_pos = i * qc + jnp.arange(qc)
        k_pos = j * qc + jnp.arange(qc)
        bias = _mask_bias(q_pos, k_pos, causal=causal, window=window)
        s = jnp.einsum("bqkgh,bskh->bkgqs", qi.astype(jnp.float32),
                       kj.astype(jnp.float32)) * scale + bias
        m_i = jax.lax.dynamic_index_in_dim(m_all, i, 1, keepdims=False)
        l_i = jax.lax.dynamic_index_in_dim(l_all, i, 1, keepdims=False)
        a_i = jax.lax.dynamic_index_in_dim(acc_all, i, 1, keepdims=False)
        m_new = jnp.maximum(m_i, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_i - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l_i * alpha + jnp.sum(p, axis=-1)
        o = jnp.einsum("bkgqs,bskh->bkgqh", p, vj.astype(jnp.float32))
        a_new = a_i * alpha[..., None] + o
        m_all = jax.lax.dynamic_update_index_in_dim(m_all, m_new, i, 1)
        l_all = jax.lax.dynamic_update_index_in_dim(l_all, l_new, i, 1)
        acc_all = jax.lax.dynamic_update_index_in_dim(acc_all, a_new, i, 1)
        return (m_all, l_all, acc_all), None

    m0 = jnp.full((B, nq, K, G, qc), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, nq, K, G, qc), jnp.float32)
    a0 = jnp.zeros((B, nq, K, G, qc, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), pairs)
    out = acc / jnp.maximum(l, 1e-30)[..., None]                    # (B,nq,K,G,qc,hd)
    out = jnp.moveaxis(out, 4, 2).reshape(B, Sq, H, hd)
    return out[:, :Sq0].astype(q.dtype)


def decode_attention(q, k_cache, v_cache, pos, *, window=None, scale=None):
    """Single-step attention over a preallocated KV cache.

    q (B,1,H,hd); caches (B,S,K,hd); pos () or (B,) int32 = index of the
    new token per lane (each lane's cache holds valid entries at
    [0..pos_b-1] plus the new one at pos_b). Per-lane positions are what
    make continuous batching possible: a refilled slot restarts at
    pos_b = 0 while its neighbours keep decoding — masked lanes
    contribute exp(NEG_INF - m) == 0.0 exactly, so each lane's output is
    bit-identical to a fresh-cache decode at the same position.
    """
    B, _, H, hd = q.shape
    _, S, K, _ = k_cache.shape
    G = H // K
    scale = 1.0 / math.sqrt(hd) if scale is None else scale
    qg = q.reshape(B, K, G, hd)
    s = jnp.einsum("bkgh,bskh->bkgs", qg.astype(jnp.float32),
                   k_cache.astype(jnp.float32)) * scale
    idx = jnp.arange(S)
    pos = jnp.asarray(pos)
    posv = pos[None] if pos.ndim == 0 else pos          # (1,) or (B,)
    valid = idx[None, :] <= posv[:, None]               # (1|B, S)
    if window:
        valid &= idx[None, :] > posv[:, None] - window
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bskh->bkgh", p, v_cache.astype(jnp.float32))
    return o.reshape(B, 1, H, hd).astype(q.dtype)


def attention_dense(q, k, v, *, causal=True, window=None,
                    q_offset=0, k_offset=0, q_chunk=None, scale=None):
    """Loop-free masked attention (single einsum chain), the oracle the
    chunked implementations are tested against. Memory-naive
    (materializes S x S scores) — never used on a real workload path."""
    B, Sq, H, hd = q.shape
    _, Sk, K, _ = k.shape
    G = H // K
    s = jnp.einsum("bqkgh,bskh->bkgqs",
                   q.reshape(B, Sq, K, G, hd).astype(jnp.float32),
                   k.astype(jnp.float32))
    s = s / math.sqrt(hd) if scale is None else s * scale
    bias = _mask_bias(q_offset + jnp.arange(Sq), k_offset + jnp.arange(Sk),
                      causal=causal, window=window)
    s = s + bias
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskh->bqkgh", p, v.astype(jnp.float32))
    return o.reshape(B, Sq, H, hd).astype(q.dtype)


ATTN_IMPLS = {
    "masked": attention_masked,
    "block_causal": attention_block_causal,
    "dense": attention_dense,
}
