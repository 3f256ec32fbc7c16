"""Plain reference of Granite-4.0-H (``granitemoehybrid``), and its weights.

Nothing here imports the system under test; it may import ``dense``'s
parts. The layer equations are those of the published model
(hf:ibm-granite/granite-4.0-h-small; ``GraniteMoeHybridDecoderLayer``,
``GraniteMoeHybridMambaLayer``, ``GraniteMoeHybridMoE``,
``GraniteMoeHybridMLP`` of transformers' ``granitemoehybrid``):

    h_0 = 12 * embed[token]                         (embedding_multiplier)
    h   = h + 0.22 * mixer(rmsnorm(h))              (residual_multiplier)
    h   = h + 0.22 * (experts(rmsnorm(h)) + shared(rmsnorm(h)))
    logits = (rmsnorm(h) . embed^T) / 16            (logits_scaling, tied)

The mixer of layer ``i`` is the ``layer_pattern`` letter ``i`` (``M``
Mamba-2, ``A`` attention; the configuration's ``layer_types`` written as
letters, since the code-length check hands the reference only scalar
keys).

Mamba-2 (one group), per token t, written as the published recurrence
with no chunking:

    [z | x | B | C | dt] = in_proj(u_t)
    [x | B | C] = silu(conv_bias + sum_k conv_w[k] * [x|B|C]_{t-3+k})
    dt = softplus(dt + dt_bias);  A = -exp(A_log)         (per head)
    s  = s * exp(dt * A) + (dt * x) outer B                (H, P, N)
    y  = s . C + D * x
    out = out_proj(rmsnorm(y * silu(z)) * gate_norm)

Attention: grouped-query, no position embedding, softmax at
``attention_multiplier`` (1/128), causal.

Experts: the router scores all ``router_outputs`` experts, keeps the
top ``num_experts_per_tok`` and softmaxes their logits; this chip holds
the first ``num_local_experts`` of them and adds only their part,
``sum_e gate_e * W2_e(silu(W1g_e u) * W1u_e u)`` over the held experts
each token was routed to (the rest would be added on the other chips of
the expert-parallel layer). The shared expert, a SwiGLU of width
``shared_intermediate_size``, runs on every token.

Weights are bfloat16, each layer's upcast to float32 inside the scan over
its run of layers; under ``jax.default_matmul_precision("highest")``
every product is float32. ``make_weights`` builds the tree in the
program's layout (``mamba``, ``attn`` stacked by kind, one ``moe`` FFN
per layer), in one jitted call on the device.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.dense import NEG, _norm, mm, mm_int8, padded_vocab

BYTES = 2           # weights, the conv state and the key/value cache
STATE_BYTES = 4     # the SSM state is kept in float32

# program fields (``ModelConfig``) set from a configuration file's
# ``model`` keys; the file's ``program.fields`` override them. The layer
# pattern, the position embedding and the gated norm are the preset's.
PROGRAM_FIELDS = {
    "num_hidden_layers": "n_layers", "hidden_size": "d_model",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff",
    "shared_intermediate_size": "shared_d_ff", "vocab_size": "vocab_size",
    "rms_norm_eps": "norm_eps", "tie_word_embeddings": "tie_embeddings",
    "router_outputs": "n_experts", "num_experts_per_tok": "top_k",
    "num_local_experts": "experts_held", "mamba_d_state": "ssm_state",
    "mamba_d_conv": "ssm_conv", "mamba_expand": "ssm_expand",
    "mamba_d_head": "ssm_headdim", "mamba_chunk_size": "ssm_chunk",
    "mamba_conv_bias": "ssm_conv_bias",
    "attention_multiplier": "attn_scale",
    "embedding_multiplier": "embedding_multiplier",
    "residual_multiplier": "residual_multiplier",
    "logits_scaling": "logits_scaling", "rope_theta": "rope_theta",
}


# ------------------------------------------------------------------ shapes
def sizes(m: dict) -> dict:
    D = m["hidden_size"]
    di = m["mamba_expand"] * D
    N, P = m["mamba_d_state"], m["mamba_d_head"]
    H = m["num_attention_heads"]
    if m.get("mamba_n_groups", 1) != 1 or \
            m.get("mamba_n_heads", di // P) != di // P:
        raise ValueError("the reference computes one group of "
                         "mamba_expand * hidden_size / mamba_d_head heads")
    return {"D": D, "di": di, "N": N, "P": P, "Hs": di // P,
            "C": di + 2 * N, "K": m["mamba_d_conv"], "H": H,
            "Kv": m["num_key_value_heads"], "hd": D // H,
            "F": m["intermediate_size"],
            "Fs": m["shared_intermediate_size"], "E": m["router_outputs"],
            "Eh": m["num_local_experts"], "k": m["num_experts_per_tok"]}


def pattern(m: dict) -> str:
    p = m["layer_pattern"]
    if len(p) != m["num_hidden_layers"] or set(p) - {"M", "A"}:
        raise ValueError(f"layer_pattern {p!r} is not "
                         f"{m['num_hidden_layers']} letters M/A")
    return p


def runs(m: dict) -> list:
    """(kind, first layer, first index within its kind, length) of each
    run of consecutive layers of one mixer kind."""
    out, seen = [], {"M": 0, "A": 0}
    for i, kind in enumerate(pattern(m)):
        if out and out[-1][0] == kind:
            out[-1][3] += 1
        else:
            out.append([kind, i, seen[kind], 1])
        seen[kind] += 1
    return [tuple(r) for r in out]


def leaf_shapes(m: dict) -> dict:
    """Parameter shapes of model ``m`` (the config file's ``model``)."""
    z = sizes(m)
    D, L = z["D"], m["num_hidden_layers"]
    n_a = pattern(m).count("A")
    n_m = L - n_a
    return {
        "embed": (padded_vocab(m), D), "final_norm": (D,),
        "mamba": {
            "A_log": (n_m, z["Hs"]), "D_skip": (n_m, z["Hs"]),
            "conv_b": (n_m, z["C"]), "conv_w": (n_m, z["K"], z["C"]),
            "dt_bias": (n_m, z["Hs"]), "gate_norm": (n_m, z["di"]),
            "in_B": (n_m, D, z["N"]), "in_C": (n_m, D, z["N"]),
            "in_dt": (n_m, D, z["Hs"]), "in_x": (n_m, D, z["di"]),
            "in_z": (n_m, D, z["di"]), "ln": (n_m, D),
            "out_proj": (n_m, z["di"], D)},
        "attn": {
            "ln1": (n_a, D), "wq": (n_a, D, z["H"] * z["hd"]),
            "wk": (n_a, D, z["Kv"] * z["hd"]),
            "wv": (n_a, D, z["Kv"] * z["hd"]),
            "wo": (n_a, z["H"] * z["hd"], D)},
        "moe": {
            "ln2": (L, D), "router": (L, D, z["E"]),
            "we_gate": (L, z["Eh"], D, z["F"]),
            "we_up": (L, z["Eh"], D, z["F"]),
            "we_down": (L, z["Eh"], z["F"], D),
            "ws_gate": (L, D, z["Fs"]), "ws_up": (L, D, z["Fs"]),
            "ws_down": (L, z["Fs"], D)},
    }


NORMS = ("ln", "ln1", "ln2", "gate_norm")


def _leaf(name: str, shape, key, init: dict):
    """A leaf in float32: matrices normal with std 1/sqrt(fan-in) (the
    conv's fan-in is its width K); the embedding (also the head) at
    ``init["embed_std"]``; layer norm weights 1 + ``norm_jitter`` *
    normal; the final norm ``init["final_norm"]`` everywhere (with the
    embedding's std it sets the scale of the logits); the conv bias
    normal at ``init["conv_bias_std"]``; A_log = log(1..H), D = 1 and
    dt_bias = 1 as the published model initializes them."""
    if name == "embed":
        return init["embed_std"] * jax.random.normal(key, shape)
    if name == "final_norm":
        return jnp.full(shape, init["final_norm"], jnp.float32)
    if name in NORMS:
        return 1.0 + init["norm_jitter"] * jax.random.normal(key, shape)
    if name == "conv_b":
        return init["conv_bias_std"] * jax.random.normal(key, shape)
    if name == "A_log":
        return jnp.broadcast_to(
            jnp.log(jnp.arange(1, shape[-1] + 1, dtype=jnp.float32)), shape)
    if name in ("D_skip", "dt_bias"):
        return jnp.ones(shape, jnp.float32)
    return jax.random.normal(key, shape) / math.sqrt(shape[-2])


def make_weights(m: dict, init: dict, seed: int):
    """The parameter tree of model ``m`` from ``seed``, in bfloat16, made
    on the default device in one jitted call."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        leaf_shapes(m), is_leaf=lambda x: isinstance(x, tuple))
    names = [p[-1].key for p, _ in flat]
    dims = [s for _, s in flat]

    @jax.jit
    def build(key):
        return [_leaf(n, s, jax.random.fold_in(key, i), init)
                .astype(jnp.bfloat16)
                for i, (n, s) in enumerate(zip(names, dims))]

    return jax.tree_util.tree_unflatten(treedef,
                                        build(jax.random.PRNGKey(seed)))


# ------------------------------------------------------------------ blocks
def _mamba(m, lp, u, conv, ssm, mm):
    """Mamba-2 mixer over u (B, S, D), the normed input, from the conv
    window ``conv`` (B, K-1, C) and state ``ssm`` (B, H, P, N) left by the
    tokens before it, one token at a time. Returns (out, conv, ssm)."""
    z_ = sizes(m)
    B, S, _ = u.shape
    di, N, Hs, P, K = z_["di"], z_["N"], z_["Hs"], z_["P"], z_["K"]
    z = mm(u, lp["in_z"])
    xbc = jnp.concatenate([mm(u, lp["in_x"]), mm(u, lp["in_B"]),
                           mm(u, lp["in_C"])], -1)
    dt = jax.nn.softplus(mm(u, lp["in_dt"]) + lp["dt_bias"])      # (B,S,H)
    win = jnp.concatenate([conv, xbc], 1)                         # (B,K-1+S,C)
    c = lp["conv_b"] + sum(win[:, k:k + S] * lp["conv_w"][k]
                           for k in range(K))
    c = jax.nn.silu(c)
    x, Bm, Cm = c[..., :di], c[..., di:di + N], c[..., di + N:]
    x = x.reshape(B, S, Hs, P)
    A = -jnp.exp(lp["A_log"])

    def token(s, t):
        x_t, b_t, c_t, dt_t = t
        s = (s * jnp.exp(dt_t * A)[..., None, None]
             + (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None, :])
        y = jnp.einsum("bhpn,bn->bhp", s, c_t) + lp["D_skip"][:, None] * x_t
        return s, y

    ssm, y = jax.lax.scan(token, ssm, tuple(
        jnp.moveaxis(a, 1, 0) for a in (x, Bm, Cm, dt)))
    y = jnp.moveaxis(y, 0, 1).reshape(B, S, di)
    g = _norm(y * jax.nn.silu(z), lp["gate_norm"], m["rms_norm_eps"])
    return mm(g, lp["out_proj"]), win[:, -(K - 1):], ssm


def _attention(m, lp, u, kc, vc, t0, mm):
    """Causal grouped-query attention of u (B, S, D) at positions t0.. over
    the key/value cache (B, T, Kv, hd), written at those positions; no
    position embedding, softmax at ``attention_multiplier``."""
    z = sizes(m)
    B, S, _ = u.shape
    H, Kv, hd = z["H"], z["Kv"], z["hd"]
    q = mm(u, lp["wq"]).reshape(B, S, H, hd)
    k = mm(u, lp["wk"]).reshape(B, S, Kv, hd)
    v = mm(u, lp["wv"]).reshape(B, S, Kv, hd)
    kc = jax.lax.dynamic_update_slice(kc, k.astype(kc.dtype), (0, t0, 0, 0))
    vc = jax.lax.dynamic_update_slice(vc, v.astype(vc.dtype), (0, t0, 0, 0))
    G = H // Kv
    kk = jnp.repeat(kc.astype(jnp.float32), G, axis=2)
    vv = jnp.repeat(vc.astype(jnp.float32), G, axis=2)
    s = jnp.einsum("bshd,bthd->bhst", q, kk) * m["attention_multiplier"]
    live = jnp.arange(kc.shape[1])[None, :] <= (t0 + jnp.arange(S))[:, None]
    p = jax.nn.softmax(jnp.where(live[None, None], s, NEG), axis=-1)
    o = jnp.einsum("bhst,bthd->bshd", p, vv).reshape(B, S, H * hd)
    return mm(o, lp["wo"]), kc, vc


def _experts(m, lp, u, mm):
    """The held routed experts' part plus the shared expert of u (B,S,D)."""
    z = sizes(m)
    B, S, D = u.shape
    x = u.reshape(B * S, D)
    vals, ids = jax.lax.top_k(mm(x, lp["router"]), z["k"])
    gate = jnp.einsum("tk,tke->te", jax.nn.softmax(vals, -1),
                      jax.nn.one_hot(ids, z["Eh"]))         # held experts
    h = jax.nn.silu(mm(x, lp["we_gate"])) * mm(x, lp["we_up"])  # (Eh,T,F)
    routed = jnp.einsum("te,etd->td", gate, mm(h, lp["we_down"]))
    shared = mm(jax.nn.silu(mm(x, lp["ws_gate"])) * mm(x, lp["ws_up"]),
                lp["ws_down"])
    return (routed + shared).reshape(B, S, D)


def _take(tree, i, f32: bool):
    return jax.tree_util.tree_map(
        lambda a: a[i].astype(jnp.float32) if f32 else a[i], tree)


def _layers(m, params, h, cache, t0, mm, f32: bool):
    """Every layer over h (B, S, D) at positions t0.. from ``cache`` (one
    tuple of states per run: (conv, ssm) or (k, v), each (n, B, ...));
    returns h and the new cache. With ``f32`` each layer's weights are
    upcast to float32 inside the scan over its run."""
    eps, rm = m["rms_norm_eps"], m["residual_multiplier"]
    out = []
    for (kind, i0, j0, n), state in zip(runs(m), cache):
        def body(h, xs):
            i, j, a, b = xs
            if kind == "M":
                lp = _take(params["mamba"], j, f32)
                y, a, b = _mamba(m, lp, _norm(h, lp["ln"], eps), a, b, mm)
            else:
                lp = _take(params["attn"], j, f32)
                y, a, b = _attention(m, lp, _norm(h, lp["ln1"], eps), a, b,
                                     t0, mm)
            h = h + rm * y
            fp = _take(params["moe"], i, f32)
            h = h + rm * _experts(m, fp, _norm(h, fp["ln2"], eps), mm)
            return h, (a, b)

        h, state = jax.lax.scan(body, h, (jnp.arange(i0, i0 + n),
                                          jnp.arange(j0, j0 + n), *state))
        out.append(state)
    return h, out


def _fresh(m, batch: int, length: int) -> list:
    """Zero states per run, float32: the conv window and SSM state of a
    Mamba run, the key/value cache of ``length`` positions of an
    attention run."""
    z = sizes(m)
    out = []
    for kind, _, _, n in runs(m):
        if kind == "M":
            out.append((jnp.zeros((n, batch, z["K"] - 1, z["C"])),
                        jnp.zeros((n, batch, z["Hs"], z["P"], z["N"]))))
        else:
            kv = jnp.zeros((n, batch, length, z["Kv"], z["hd"]))
            out.append((kv, kv))
    return out


def _embed(m, params, tokens):
    return (jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
            * m["embedding_multiplier"])


def _logits(m, params, h, dtype=jnp.float32):
    h = _norm(h, params["final_norm"], m["rms_norm_eps"])
    head = params["embed"].astype(dtype).T
    return mm(h, head)[..., :m["vocab_size"]] / m["logits_scaling"]


def forward(m: dict, params, tokens, mm=mm):
    """Teacher-forced logits (B, S, V) float32 of ``tokens`` (B, S), every
    Mamba layer run token by token from a zero state. ``mm`` computes
    every product with a weight (``mm_int8`` for the int8 control)."""
    B, S = tokens.shape
    h, _ = _layers(m, params, _embed(m, params, tokens), _fresh(m, B, S), 0,
                   mm, True)
    return _logits(m, params, h)


# ---------------------------------------------------------------- sampling
@partial(jax.jit, static_argnums=(0, 2, 3, 4, 5))
def _sample(mkey, params, batch, n_tokens, top_k, bos, key):
    m = dict(mkey)

    def body(carry, t):
        cache, tok = carry
        h, cache = _layers(m, params, _embed(m, params, tok[:, None]), cache,
                           t, mm, False)
        logits = _logits(m, params, h, jnp.bfloat16)[:, 0]
        vals, ids = jax.lax.top_k(logits, top_k)
        pick = jax.random.categorical(jax.random.fold_in(key, t), vals)
        nxt = jnp.take_along_axis(ids, pick[:, None], axis=1)[:, 0]
        return (cache, nxt.astype(jnp.int32)), nxt.astype(jnp.int32)

    tok0 = jnp.full((batch,), bos, jnp.int32)
    _, out = jax.lax.scan(body, (_fresh(m, batch, n_tokens), tok0),
                          jnp.arange(n_tokens))
    return out.T


def sample_documents(m: dict, params, *, n_docs: int, batch: int,
                     n_tokens: int, top_k: int, bos: int, seed: int):
    """``n_docs`` documents of ``n_tokens`` tokens, each written by the
    model from BOS alone: temperature 1 inside the model's ``top_k``, one
    scanned program per batch, the weights in their stored bfloat16 at the
    default precision. Returns a host int32 array (n_docs, n_tokens)."""
    mkey = tuple(sorted((k, v) for k, v in m.items()
                        if not isinstance(v, (dict, list))))
    key = jax.random.PRNGKey(seed)
    out = [np.asarray(_sample(mkey, params, batch, n_tokens, top_k, bos,
                              jax.random.fold_in(key, i)))
           for i in range(0, n_docs, batch)]
    return np.concatenate(out)[:n_docs]


# -------------------------------------------------------------- work counts
# Operations and bytes of one decode step, from the shapes, for the
# readers' roofline and utilization (``chipbench/flops.py``).
def _mamba_matrix_params(z) -> int:
    """in_proj (z, x, B, C, dt) and out_proj of one Mamba layer."""
    D, di = z["D"], z["di"]
    return D * (2 * di + 2 * z["N"] + z["Hs"]) + di * D


def _attn_matrix_params(z) -> int:
    return z["D"] * z["hd"] * (2 * z["H"] + 2 * z["Kv"])


def _ffn_matrix_params(z, experts: float) -> float:
    """Router, ``experts`` routed SwiGLU experts and the shared one."""
    D = z["D"]
    return D * z["E"] + experts * 3 * D * z["F"] + 3 * D * z["Fs"]


def flops_per_token(m: dict, pos: float) -> float:
    """Model FLOPs to score one token at position ``pos``: every matrix
    product (the routed experts at their expected share here, top-k x
    held / router outputs experts a token), the head, the conv, the SSM
    state update s * exp(dt A) + (dt x) outer B and its read-out s . C (5
    FLOPs a state element), and attention's two products over pos + 1
    keys."""
    z = sizes(m)
    p = pattern(m)
    n_a, L = p.count("A"), len(p)
    n_m = L - n_a
    routed = z["k"] * z["Eh"] / z["E"]
    mats = (n_m * _mamba_matrix_params(z) + n_a * _attn_matrix_params(z)
            + L * _ffn_matrix_params(z, routed)
            + z["D"] * m["vocab_size"])
    ssm = n_m * (5 * z["Hs"] * z["P"] * z["N"] + 2 * z["K"] * z["C"])
    attn = 4 * n_a * z["H"] * z["hd"] * (pos + 1)
    return 2.0 * mats + ssm + attn


def decode_step_bytes(m: dict, lanes: float, pos: float) -> float:
    """Least bytes one decode step over ``lanes`` lanes at mean position
    ``pos`` must move: every weight held once (all held experts; the
    input embedding only its lanes' rows; norms, conv and SSM vectors
    included), the SSM state (float32) and the conv window read and
    written, the key/value cache read up to each lane's position and
    written at it (as ``dense`` counts it), and the lanes' logits."""
    z = sizes(m)
    p = pattern(m)
    n_a, L = p.count("A"), len(p)
    n_m = L - n_a
    D, V = z["D"], m["vocab_size"]
    mamba = (_mamba_matrix_params(z) + (z["K"] + 1) * z["C"]
             + 3 * z["Hs"] + z["di"] + D)
    weights = (n_m * mamba + n_a * (_attn_matrix_params(z) + D)
               + L * (_ffn_matrix_params(z, z["Eh"]) + D)
               + D * V + lanes * D + D) * BYTES
    state = 2 * n_m * lanes * (z["Hs"] * z["P"] * z["N"] * STATE_BYTES
                               + (z["K"] - 1) * z["C"] * BYTES)
    kv = lanes * (pos + 2) * 2 * n_a * z["Kv"] * z["hd"] * BYTES
    return weights + state + kv + lanes * V * BYTES
