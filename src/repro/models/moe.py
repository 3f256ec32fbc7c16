"""Top-k token-choice Mixture-of-Experts with expert parallelism.

Dispatch design (token-replicated EP, MaxText-flavoured):

* Tokens are sharded over the batch axes (pod, data) and *replicated* over
  the TP/EP axis ('model'), exactly like every other activation in the
  model — no extra resharding on entry.
- Experts are sharded over 'model' (E_loc = E / tp); expert weights keep the
  FSDP axis on D (all-gathered over 'data' at use, like dense FSDP).
* Each model shard routes all of its local tokens, keeps only the
  (token, slot) pairs owned by its local experts, packs them into an
  (E_loc, C, D) capacity buffer with a sort-based rank (no (T,E) one-hot
  blowup), runs the expert FFNs as one batched einsum, scatters back, and
  psums partial outputs over 'model'.
* Communication per layer = FSDP weight all-gather + one psum over
  'model' — there is **no all-to-all**; the trade is E-way routing compute
  replication (router is D*E, negligible). An a2a variant is a
  perf-iteration candidate.

The same routine with tp=1 is the single-device reference path used in
smoke tests and as the oracle for the distributed test.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from .layers import rms_norm


def _route(x_flat, router_w, top_k):
    """x (T,D) -> (weights (T,k) fp32, experts (T,k) int32). Softmax over the
    selected top-k logits (qwen3/mixtral convention)."""
    logits = jnp.einsum("td,de->te", x_flat, router_w).astype(jnp.float32)
    vals, experts = jax.lax.top_k(logits, top_k)
    weights = jax.nn.softmax(vals, axis=-1)
    return weights, experts


def _rank_within_expert(flat_experts, n_experts):
    """Position of each (token,slot) within its expert's arrival order.
    Sort-based: O(Tk log Tk) local, no (Tk, E) one-hot materialization."""
    Tk = flat_experts.shape[0]
    order = jnp.argsort(flat_experts, stable=True)
    se = jnp.sort(flat_experts)
    first = jnp.searchsorted(se, jnp.arange(n_experts))
    rank_sorted = jnp.arange(Tk) - first[se]
    return jnp.zeros((Tk,), jnp.int32).at[order].set(rank_sorted.astype(jnp.int32))


def _expert_ffn(buf, w_gate, w_up, w_down):
    """buf (E,C,D) through per-expert SwiGLU."""
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, w_gate)) * \
        jnp.einsum("ecd,edf->ecf", buf, w_up)
    return jnp.einsum("ecf,efd->ecd", h, w_down)


def moe_ffn_local(x_flat, lp, cfg: ModelConfig, *, shard_id=0, n_shards=1,
                  gathered=None, dropless=False, count=False):
    """Dispatch + expert compute for the experts owned by `shard_id`.
    Returns the *partial* output (full output iff n_shards == 1); with
    `count`, also the number of (token, slot) pairs its experts computed.

    The router always scores all cfg.n_experts; the expert weights hold
    only this shard's E / n_shards experts. On one chip with no mesh this
    is the expert layer of a chip that holds a share of the experts
    (cfg.experts_held): it computes its own experts' part for the tokens
    routed to them, and what the other shards would add is left out.

    dropless=True sets capacity C = T: since top-k experts are distinct per
    token, no expert can receive more than T tokens, so nothing is ever
    dropped. The compression/serving paths REQUIRE dropless — capacity
    drops depend on the whole dispatch group, so a capacity-dropped scoring
    pass and the decompressor's decode pass could disagree, breaking
    losslessness. Training uses the standard capacity factor."""
    T, D = x_flat.shape
    E, k = cfg.n_experts, cfg.top_k
    E_loc = E // n_shards
    C = T if dropless else max(1, int(math.ceil(T * k / E * cfg.capacity_factor)))
    w_gate, w_up, w_down = gathered if gathered is not None else (
        lp["we_gate"], lp["we_up"], lp["we_down"])

    weights, experts = _route(x_flat, lp["router"], k)      # (T,k)
    fe = experts.reshape(-1)                                # (Tk,)
    rank = _rank_within_expert(fe, E)
    local = (fe >= shard_id * E_loc) & (fe < (shard_id + 1) * E_loc)
    keep = (rank < C) & local
    le = fe - shard_id * E_loc                              # local expert id
    dest = jnp.where(keep, le * C + rank, E_loc * C)        # overflow slot
    tok_idx = jnp.repeat(jnp.arange(T), k)
    buf = jnp.zeros((E_loc * C + 1, D), x_flat.dtype)
    buf = buf.at[dest].set(x_flat[tok_idx], mode="drop",
                           unique_indices=False)
    out_buf = _expert_ffn(buf[:-1].reshape(E_loc, C, D),
                          w_gate, w_up, w_down)
    out_buf = jnp.concatenate(
        [out_buf.reshape(E_loc * C, D), jnp.zeros((1, D), out_buf.dtype)], 0)
    y_slots = out_buf[dest] * (weights.reshape(-1)[:, None] *
                               keep[:, None]).astype(out_buf.dtype)
    y = jnp.sum(y_slots.reshape(T, k, D), axis=1)
    if count:
        return y, jnp.sum(keep, dtype=jnp.int32)
    return y


def moe_block(cfg: ModelConfig, lp: dict, x, *, mesh=None, dropless=False,
              dispatch_group: int = 0):
    """Full MoE FFN sub-block (post-norm residual applied by caller).
    x (B,S,D). With a mesh, runs the EP shard_map path; otherwise the
    single-shard reference path. `dispatch_group` > 0 splits the tokens
    into groups of that size before dispatch (bounds the dropless buffer
    for long prefills; any grouping is exact when dropless)."""
    B, S, D = x.shape
    x_flat = x.reshape(B * S, D)
    if mesh is None or "model" not in mesh.axis_names or \
            mesh.shape["model"] == 1:
        if dropless and dispatch_group and x_flat.shape[0] > dispatch_group:
            G = dispatch_group
            T = x_flat.shape[0]
            assert T % G == 0, (T, G)
            y = jax.lax.map(
                lambda xg: moe_ffn_local(xg, lp, cfg, dropless=True),
                x_flat.reshape(T // G, G, D))
            return y.reshape(B, S, D)
        y = moe_ffn_local(x_flat, lp, cfg, dropless=dropless)
        return y.reshape(B, S, D)

    from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    tp = mesh.shape["model"]

    def mapped(xf, router, wg, wu, wd):
        # FSDP gather of expert weights over 'data' (D rows axis=2 of (E,D,F))
        if "data" in mesh.axis_names and mesh.shape["data"] > 1:
            wg = jax.lax.all_gather(wg, "data", axis=1, tiled=True)
            wu = jax.lax.all_gather(wu, "data", axis=1, tiled=True)
            wd = jax.lax.all_gather(wd, "data", axis=2, tiled=True)
        shard_id = jax.lax.axis_index("model")
        lp_loc = {"router": router, "we_gate": wg, "we_up": wu, "we_down": wd}

        def run(xg):
            return moe_ffn_local(xg, lp_loc, cfg, shard_id=shard_id,
                                 n_shards=tp, gathered=(wg, wu, wd),
                                 dropless=dropless)

        if dropless and dispatch_group and xf.shape[0] > dispatch_group:
            G = dispatch_group
            T = xf.shape[0]
            assert T % G == 0, (T, G)
            y = jax.lax.map(run, xf.reshape(T // G, G, xf.shape[1]))
            y = y.reshape(T, xf.shape[1])
        else:
            y = run(xf)
        return jax.lax.psum(y, "model")

    y = shard_map(
        mapped, mesh=mesh,
        in_specs=(P(batch_axes, None), P(None, None),
                  P("model", "data", None), P("model", "data", None),
                  P("model", None, "data")),
        out_specs=P(batch_axes, None),
        check_rep=False,
    )(x_flat, lp["router"], lp["we_gate"], lp["we_up"], lp["we_down"])
    return y.reshape(B, S, D)


def moe_dense_block(cfg: ModelConfig, lp: dict, x, *, positions,
                    attn_impl="masked", q_chunk=512, mesh=None,
                    dropless=False, dispatch_group=0):
    """Attention + MoE FFN transformer block."""
    from .transformer import attn_block
    a, _ = attn_block(cfg, lp, rms_norm(x, lp["ln1"], cfg.norm_eps),
                      positions=positions, attn_impl=attn_impl,
                      q_chunk=q_chunk)
    x = x + a
    x = x + moe_block(cfg, lp, rms_norm(x, lp["ln2"], cfg.norm_eps),
                      mesh=mesh, dropless=dropless,
                      dispatch_group=dispatch_group)
    return x


def forward(params, cfg: ModelConfig, batch: dict, *, attn_impl="masked",
            q_chunk=512, mesh=None, dropless=False, dispatch_group=0,
            return_hidden=False):
    from .transformer import _scan_blocks, embed_tokens, lm_logits
    tokens = batch["tokens"]
    x = embed_tokens(cfg, params, tokens)
    positions = jnp.arange(tokens.shape[1])
    x = _scan_blocks(cfg, params["layers"], x,
                     lambda h, lp: moe_dense_block(
                         cfg, lp, h, positions=positions,
                         attn_impl=attn_impl, q_chunk=q_chunk, mesh=mesh,
                         dropless=dropless, dispatch_group=dispatch_group))
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if return_hidden:
        return x
    return lm_logits(cfg, params, x)


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=None):
    from .transformer import init_cache as dense_init_cache
    return dense_init_cache(cfg, batch, max_len, dtype)


def decode_step(params, cfg: ModelConfig, cache, prev_tokens, *,
                dropless=True):
    from .transformer import (_decode_attn_one, embed_tokens, lm_logits)
    pos = cache["pos"]
    x = embed_tokens(cfg, params, prev_tokens[:, None])

    def body(carry, xs):
        h = carry
        lp, kc, vc = xs
        a, kc, vc = _decode_attn_one(cfg, lp, rms_norm(h, lp["ln1"], cfg.norm_eps),
                                     kc, vc, pos)
        h = h + a
        h = h + moe_block(cfg, lp, rms_norm(h, lp["ln2"], cfg.norm_eps),
                          dropless=dropless)
        return h, (kc, vc)

    from .transformer import scan_xs
    x, (k_new, v_new) = scan_xs(
        cfg, body, x, (params["layers"], cache["k"], cache["v"]))
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = lm_logits(cfg, params, x)[:, 0]
    return logits, {"k": k_new, "v": v_new, "pos": pos + 1}
