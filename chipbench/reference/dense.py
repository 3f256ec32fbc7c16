"""Plain reference of the dense decoder, and the benchmark's weights.

Nothing here imports the system under test. The decoder is the
published Llama/Qwen3 block written out in ``jax.numpy``:

    h = h + Wo . attn(rope(norm_q(Wq . n1(h))), rope(norm_k(Wk . n1(h))), Wv . n1(h))
    h = h + Wdown . (silu(Wgate . n2(h)) * (Wup . n2(h)))
    logits = final_norm(h) . head          (head = embedding^T when tied)

RMSNorm is ``x * rsqrt(mean(x^2) + eps) * w``; RoPE rotates the two
halves of each head (``rotate_half``); grouped-query attention maps
query head ``h`` to key/value head ``h // (H / K)``; ``qk_norm`` (Qwen3)
applies RMSNorm per head to q and k before RoPE.

Weights are stored in bfloat16, the type they are served in, and each
layer's weights are upcast to float32 inside the layer scan, so a
float32 pass over a model larger than half the chip still fits. Under
``jax.default_matmul_precision("highest")`` every product is float32.

``make_weights`` builds the parameter tree the service is handed, in the
layout of the program's model schema (stacked layers, padded vocabulary
rows), from the seed in one jitted call on the device. The reference
regenerates the same tree itself when it runs.

This is the reference a configuration file gets when it names none
(``"reference": "dense"``). A reference module provides what the harness
and the readers take from it: ``PROGRAM_FIELDS``, ``make_weights``,
``sample_documents``, ``forward`` with its ``mm`` and ``mm_int8``, and
the work counts ``flops_per_token`` and ``decode_step_bytes``.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

NEG = -1e30
BYTES = 2           # weights and key/value cache are served in bfloat16

# program fields (``ModelConfig``) set from a configuration file's
# ``model`` keys; the file's ``program.fields`` override them
PROGRAM_FIELDS = {
    "num_hidden_layers": "n_layers", "hidden_size": "d_model",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "head_dim": "d_head", "intermediate_size": "d_ff",
    "vocab_size": "vocab_size", "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps", "tie_word_embeddings": "tie_embeddings",
    "qk_norm": "qk_norm", "torch_dtype": "dtype",
}


# ------------------------------------------------------------------ shapes
def leaf_shapes(m: dict) -> dict:
    """Parameter shapes of model ``m`` (the config file's ``model``)."""
    L, D, F = m["num_hidden_layers"], m["hidden_size"], m["intermediate_size"]
    H, K, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                m["head_dim"])
    Vp = padded_vocab(m)
    layers = {
        "wq": (L, D, H * hd), "wk": (L, D, K * hd), "wv": (L, D, K * hd),
        "wo": (L, H * hd, D), "wi_gate": (L, D, F), "wi_up": (L, D, F),
        "wo_mlp": (L, F, D), "ln1": (L, D), "ln2": (L, D),
    }
    if m["qk_norm"]:
        layers.update(q_norm=(L, hd), k_norm=(L, hd))
    tree = {"embed": (Vp, D), "final_norm": (D,), "layers": layers}
    if not m["tie_word_embeddings"]:
        tree["lm_head"] = (D, Vp)
    return tree


def padded_vocab(m: dict) -> int:
    mult = m.get("vocab_pad_multiple", 1)
    return -(-m["vocab_size"] // mult) * mult


def _leaf_std(name: str, shape, init: dict) -> float | None:
    """Standard deviation of a normal leaf; None for a norm weight."""
    if name == "embed":
        return init["embed_std"]
    if name == "lm_head":
        return init["lm_head_std"]
    if name in ("ln1", "ln2", "q_norm", "k_norm", "final_norm"):
        return None
    return 1.0 / math.sqrt(shape[-2])          # fan-in of the matrix


def make_weights(m: dict, init: dict, seed: int):
    """The parameter tree of model ``m`` from ``seed``, in bfloat16, made
    on the default device in one jitted call. Matrices are normal with
    std 1/sqrt(fan-in); the embedding and head take the stds in
    ``init`` (they set the scale of the logits); per-layer norm weights
    are 1 + ``init["norm_jitter"]`` * normal; the final norm is ones."""
    shapes = leaf_shapes(m)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    names = [p[-1].key for p, _ in flat]
    dims = [s for _, s in flat]

    @jax.jit
    def build(key):
        out = []
        for i, (name, shape) in enumerate(zip(names, dims)):
            k = jax.random.fold_in(key, i)
            std = _leaf_std(name, shape, init)
            if std is not None:
                x = std * jax.random.normal(k, shape, jnp.float32)
            elif name == "final_norm":
                x = jnp.ones(shape, jnp.float32)
            else:
                x = 1.0 + init["norm_jitter"] * jax.random.normal(
                    k, shape, jnp.float32)
                if name in ("ln1", "ln2") and init.get("outlier_channels"):
                    x = x.at[..., :init["outlier_channels"]].multiply(
                        init["outlier_scale"])
            out.append(x.astype(jnp.bfloat16))
        return out

    leaves = build(jax.random.PRNGKey(seed))
    return jax.tree_util.tree_unflatten(treedef, leaves)


# ------------------------------------------------------------------ blocks
def _norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


def _rope(x, pos, theta):
    """x (B, S, H, hd) float32, pos (B, S) int."""
    half = x.shape[-1] // 2
    inv = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32)
                  / half)
    ang = pos[..., None].astype(jnp.float32) * inv          # (B, S, half)
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def mm(x, w):
    """x . w with float32 accumulation; x is cast to w's type, so bfloat16
    weights give the served one-pass product and float32 weights (under
    "highest") the reference's."""
    return jnp.matmul(x.astype(w.dtype), w, preferred_element_type=jnp.float32)


def _quant(x, axis):
    """x rounded to int8 with one absmax scale per slice along ``axis``,
    returned dequantized (float32)."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def mm_int8(x, w):
    """x . w computed in int8: x per row, w per output column (W8A8)."""
    return mm(_quant(x.astype(jnp.float32), -1),
               _quant(w.astype(jnp.float32), -2))


def _qkv(m, lp, x, pos, mm=mm):
    B, S, _ = x.shape
    H, K, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                m["head_dim"])
    eps = m["rms_norm_eps"]
    q = mm(x, lp["wq"]).reshape(B, S, H, hd)
    k = mm(x, lp["wk"]).reshape(B, S, K, hd)
    v = mm(x, lp["wv"]).reshape(B, S, K, hd)
    if m["qk_norm"]:
        q, k = _norm(q, lp["q_norm"], eps), _norm(k, lp["k_norm"], eps)
    theta = m["rope_theta"]
    return _rope(q, pos, theta), _rope(k, pos, theta), v


def _attend(m, q, k, v, mask):
    """q (B, S, H, hd); k, v (B, T, K, hd); mask (B or 1, S, T) bool."""
    G = m["num_attention_heads"] // m["num_key_value_heads"]
    k, v = jnp.repeat(k, G, axis=2), jnp.repeat(v, G, axis=2)
    s = jnp.einsum("bshd,bthd->bhst", q, k) / math.sqrt(q.shape[-1])
    s = jnp.where(mask[:, None], s, NEG)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhst,bthd->bshd", p, v)
    return o.reshape(q.shape[0], q.shape[1], -1)


def _mlp(m, lp, h, mm=mm):
    x = _norm(h, lp["ln2"], m["rms_norm_eps"])
    return h + mm(jax.nn.silu(mm(x, lp["wi_gate"])) * mm(x, lp["wi_up"]),
                  lp["wo_mlp"])


def _f32(lp):
    return jax.tree_util.tree_map(lambda w: w.astype(jnp.float32), lp)


def _head(m, params, dtype=jnp.float32):
    """The output head (D, padded V); callers keep the first V logits."""
    if m["tie_word_embeddings"]:
        return params["embed"].astype(dtype).T
    return params["lm_head"].astype(dtype)


def _embed(m, params, tokens):
    return jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)


def forward(m: dict, params, tokens, mm=mm):
    """Teacher-forced logits (B, S, V) float32 of ``tokens`` (B, S), the
    whole sequence at once with a causal mask. ``mm`` computes every
    product with a weight (``mm_int8`` for the int8 control)."""
    B, S = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    mask = (jnp.arange(S)[None, :] <= jnp.arange(S)[:, None])[None]

    def layer(h, lp):
        lp = _f32(lp)
        q, k, v = _qkv(m, lp, _norm(h, lp["ln1"], m["rms_norm_eps"]), pos,
                       mm)
        h = h + mm(_attend(m, q, k, v, mask), lp["wo"])
        return _mlp(m, lp, h, mm), None

    h, _ = jax.lax.scan(layer, _embed(m, params, tokens), params["layers"])
    h = _norm(h, params["final_norm"], m["rms_norm_eps"])
    return mm(h, _head(m, params))[..., :m["vocab_size"]]


# ---------------------------------------------------------------- sampling
def _step(m: dict, params, cache, tok, t):
    """One decode step of all lanes at position ``t`` with the weights
    in their stored type: attention reads the (L, B, S, K, hd) bfloat16
    cache in place, one group of query heads per key/value head, with
    float32 accumulation. Returns logits (B, V) and the new cache."""
    B = tok.shape[0]
    S = cache["k"].shape[2]
    H, K = m["num_attention_heads"], m["num_key_value_heads"]
    G, hd = H // K, m["head_dim"]
    pos = jnp.full((B, 1), t)
    live = (jnp.arange(S) <= t)[None, None, None, :]

    def layer(carry, xs):
        h, kc, vc = carry
        lp, i = xs
        q, k, v = _qkv(m, lp, _norm(h, lp["ln1"], m["rms_norm_eps"]), pos)
        kc = jax.lax.dynamic_update_slice(
            kc, k.astype(kc.dtype)[None], (i, 0, t, 0, 0))
        vc = jax.lax.dynamic_update_slice(
            vc, v.astype(vc.dtype)[None], (i, 0, t, 0, 0))
        qg = q.reshape(B, K, G, hd).astype(kc.dtype)
        s = jnp.einsum("bkgd,btkd->bkgt", qg, kc[i],
                       preferred_element_type=jnp.float32) / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(live, s, NEG), axis=-1)
        o = jnp.einsum("bkgt,btkd->bkgd", p.astype(vc.dtype), vc[i],
                       preferred_element_type=jnp.float32)
        h = h + mm(o.reshape(B, 1, H * hd), lp["wo"])
        return (_mlp(m, lp, h), kc, vc), None

    L = m["num_hidden_layers"]
    (h, k, v), _ = jax.lax.scan(
        layer, (_embed(m, params, tok[:, None]), cache["k"], cache["v"]),
        (params["layers"], jnp.arange(L)))
    h = _norm(h, params["final_norm"], m["rms_norm_eps"])
    logits = mm(h, _head(m, params, jnp.bfloat16))[:, 0, :m["vocab_size"]]
    return logits, {"k": k, "v": v}


@partial(jax.jit, static_argnums=(0, 2, 3, 4, 5))
def _sample(mkey, params, batch, n_tokens, top_k, bos, key):
    m = dict(mkey)
    L, K, hd = (m["num_hidden_layers"], m["num_key_value_heads"],
                m["head_dim"])
    z = jnp.zeros((L, batch, n_tokens, K, hd), jnp.bfloat16)
    cache = {"k": z, "v": z}

    def body(carry, t):
        cache, tok = carry
        logits, cache = _step(m, params, cache, tok, t)
        vals, ids = jax.lax.top_k(logits, top_k)
        pick = jax.random.categorical(jax.random.fold_in(key, t), vals)
        nxt = jnp.take_along_axis(ids, pick[:, None], axis=1)[:, 0]
        return (cache, nxt.astype(jnp.int32)), nxt.astype(jnp.int32)

    tok0 = jnp.full((batch,), bos, jnp.int32)
    _, out = jax.lax.scan(body, (cache, tok0), jnp.arange(n_tokens))
    return out.T


def sample_documents(m: dict, params, *, n_docs: int, batch: int,
                     n_tokens: int, top_k: int, bos: int, seed: int):
    """``n_docs`` documents of ``n_tokens`` tokens, each written by the
    model from BOS alone: temperature 1 inside the model's ``top_k``,
    one scanned program per batch of documents. Matmuls run at the
    default precision (one bfloat16 pass on a TPU, the served precision).
    Returns a host int32 array (n_docs, n_tokens)."""
    import numpy as np
    mkey = tuple(sorted((k, v) for k, v in m.items()
                        if not isinstance(v, (dict, list))))
    key = jax.random.PRNGKey(seed)
    out = []
    for i in range(0, n_docs, batch):
        out.append(np.asarray(_sample(mkey, params, batch, n_tokens, top_k,
                                      bos, jax.random.fold_in(key, i))))
    return np.concatenate(out)[:n_docs]


# -------------------------------------------------------------- work counts
# Operations and bytes of the decoder's work, from its shapes, for the
# readers' roofline and utilization (``chipbench/flops.py``). Positions
# count from 0, so a token at position ``pos`` attends to ``pos + 1`` keys.
def layer_params(m: dict) -> int:
    """Matrix parameters of all layers (norm weights are negligible)."""
    D, F = m["hidden_size"], m["intermediate_size"]
    H, K, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                m["head_dim"])
    return m["num_hidden_layers"] * (D * hd * (2 * H + 2 * K) + 3 * D * F)


def head_params(m: dict) -> int:
    return m["hidden_size"] * m["vocab_size"]


def kv_bytes_per_position(m: dict) -> int:
    """Key and value bytes one lane stores per position, all layers."""
    return (2 * m["num_hidden_layers"] * m["num_key_value_heads"]
            * m["head_dim"] * BYTES)


def flops_per_token(m: dict, pos: float) -> float:
    """Model FLOPs to score one token at position ``pos``: the matrix
    products, the head, and attention's two products over pos + 1 keys."""
    attn = (4 * m["num_hidden_layers"] * m["num_attention_heads"]
            * m["head_dim"] * (pos + 1))
    return 2.0 * (layer_params(m) + head_params(m)) + attn


def decode_step_bytes(m: dict, lanes: float, pos: float) -> float:
    """Least bytes one decode step over ``lanes`` coding lanes at mean
    position ``pos`` must move: every weight once (the input embedding
    only its lanes' rows), the cache read up to each lane's position and
    written at it, and the lanes' logits."""
    D, V = m["hidden_size"], m["vocab_size"]
    weights = (layer_params(m) + head_params(m) + lanes * D) * BYTES
    kv = lanes * (pos + 2) * kv_bytes_per_position(m)
    return weights + kv + lanes * V * BYTES
