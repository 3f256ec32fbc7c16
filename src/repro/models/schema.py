"""Single source of truth for parameter trees.

Every model family declares its parameters as a nested dict of ``Leaf``
entries (shape, logical axes, init). From the schema we derive:
  * ``init_params``  — real arrays (smoke tests, measured benchmarks)
  * ``abstract_params`` — ShapeDtypeStructs (no allocation)
  * ``param_axes``   — logical-axis tree consumed by sharding/specs.py

Logical axis names (mapped to mesh axes in sharding/specs.py):
  embed    d_model rows (FSDP axis)
  heads    fused q-head dim (TP)         kv_heads  fused kv-head dim
  mlp      ffn hidden (TP)               vocab     vocabulary (TP)
  expert   MoE expert (EP)               ssm_inner mamba inner channels (TP)
  layers   stacked-layer axis (never sharded)
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig


@dataclass(frozen=True)
class Leaf:
    shape: tuple
    axes: tuple            # logical axis name (or None) per dim
    init: str = "normal"   # normal | zeros | ones | small_normal | a_log | conv
    scale: float = 1.0


def _attn_leaves(cfg: ModelConfig, L: Optional[int], cross: bool = False) -> dict:
    """Attention block leaves; L=None means unstacked (shared block)."""
    D, hd = cfg.d_model, cfg.head_dim
    Hp, Kp = cfg.padded_heads, cfg.padded_kv_heads
    pre = (L,) if L else ()
    lax = ("layers",) if L else ()
    s_in = 1.0 / np.sqrt(D)
    s_out = 1.0 / np.sqrt(Hp * hd)
    p = "x" if cross else ""
    leaves = {
        f"w{p}q": Leaf(pre + (D, Hp * hd), lax + ("embed", "heads"), "normal", s_in),
        f"w{p}k": Leaf(pre + (D, Kp * hd), lax + ("embed", "kv_heads"), "normal", s_in),
        f"w{p}v": Leaf(pre + (D, Kp * hd), lax + ("embed", "kv_heads"), "normal", s_in),
        f"w{p}o": Leaf(pre + (Hp * hd, D), lax + ("heads", "embed"), "normal", s_out),
    }
    if cfg.qk_norm and not cross:
        leaves["q_norm"] = Leaf(pre + (hd,), lax + (None,), "ones")
        leaves["k_norm"] = Leaf(pre + (hd,), lax + (None,), "ones")
    return leaves


def _mlp_leaves(cfg: ModelConfig, L: Optional[int]) -> dict:
    D, F = cfg.d_model, cfg.d_ff
    pre = (L,) if L else ()
    lax = ("layers",) if L else ()
    s_in, s_out = 1.0 / np.sqrt(D), 1.0 / np.sqrt(F)
    return {
        "wi_gate": Leaf(pre + (D, F), lax + ("embed", "mlp"), "normal", s_in),
        "wi_up": Leaf(pre + (D, F), lax + ("embed", "mlp"), "normal", s_in),
        "wo_mlp": Leaf(pre + (F, D), lax + ("mlp", "embed"), "normal", s_out),
    }


def _moe_leaves(cfg: ModelConfig, L: int) -> dict:
    """Router over all n_experts; expert weights for the experts held
    here (cfg.experts_held, 0 = all)."""
    D, F, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    Eh = cfg.experts_held or E
    s_in, s_out = 1.0 / np.sqrt(D), 1.0 / np.sqrt(F)
    # Experts take the TP ('model') axis => per-expert F stays unsharded;
    # D rows keep the FSDP ('embed' -> data) axis.
    return {
        "router": Leaf((L, D, E), ("layers", "embed", None), "normal", s_in),
        "we_gate": Leaf((L, Eh, D, F), ("layers", "expert", "expert_embed", None), "normal", s_in),
        "we_up": Leaf((L, Eh, D, F), ("layers", "expert", "expert_embed", None), "normal", s_in),
        "we_down": Leaf((L, Eh, F, D), ("layers", "expert", None, "expert_embed"), "normal", s_out),
    }


def _ssm_leaves(cfg: ModelConfig, L: int) -> dict:
    D = cfg.d_model
    di, N, Hs, KC = cfg.ssm_d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_conv
    s = 1.0 / np.sqrt(D)
    leaves = {
        "ln": Leaf((L, D), ("layers", None), "ones"),
        "in_z": Leaf((L, D, di), ("layers", "embed", "ssm_inner"), "normal", s),
        "in_x": Leaf((L, D, di), ("layers", "embed", "ssm_inner"), "normal", s),
        "in_B": Leaf((L, D, N), ("layers", "embed", None), "normal", s),
        "in_C": Leaf((L, D, N), ("layers", "embed", None), "normal", s),
        "in_dt": Leaf((L, D, Hs), ("layers", "embed", "ssm_inner"), "normal", s),
        "conv_w": Leaf((L, KC, di + 2 * N), ("layers", None, "ssm_inner"), "conv"),
        "A_log": Leaf((L, Hs), ("layers", "ssm_inner"), "a_log"),
        "D_skip": Leaf((L, Hs), ("layers", "ssm_inner"), "ones"),
        "dt_bias": Leaf((L, Hs), ("layers", "ssm_inner"), "zeros"),
        "out_proj": Leaf((L, di, D), ("layers", "ssm_inner", "embed"),
                         "normal", 1.0 / np.sqrt(di)),
    }
    if cfg.ssm_conv_bias:
        leaves["conv_b"] = Leaf((L, di + 2 * N), ("layers", "ssm_inner"),
                                "zeros")
    if cfg.ssm_gated_norm:
        leaves["gate_norm"] = Leaf((L, di), ("layers", "ssm_inner"), "ones")
    return leaves


def _norm(L: Optional[int], name: str, D: int) -> dict:
    if L:
        return {name: Leaf((L, D), ("layers", None), "ones")}
    return {name: Leaf((D,), (None,), "ones")}


def schema(cfg: ModelConfig) -> dict:
    """Nested dict of Leaf for the given config."""
    D, L, Vp = cfg.d_model, cfg.n_layers, cfg.padded_vocab
    # embed table: rows replicated, D takes the FSDP axis — a vocab-sharded
    # table turns every lookup into an all-gather + full remat (measured:
    # XLA "involuntary full rematerialization"); the LM head keeps vocab->TP.
    tree: dict = {"embed": Leaf((Vp, D), ("vocab_rows", "embed"), "normal", 1.0)}
    if not cfg.tie_embeddings:
        # lm_head D replicated: FSDP-sharding D makes XLA produce the logits
        # as data-partial products + a (B,S,V_loc) fp32 all-reduce (measured
        # 3 x 2.4 GiB per microbatch on qwen3-1.7b); a replicated D costs
        # only D*V_loc bytes per chip.
        tree["lm_head"] = Leaf((D, Vp), ("embed_head", "vocab"), "normal", 1.0 / np.sqrt(D))
    tree.update(_norm(None, "final_norm", D))

    if cfg.family in ("dense", "vlm"):
        layers = {**_attn_leaves(cfg, L), **_mlp_leaves(cfg, L),
                  **_norm(L, "ln1", D), **_norm(L, "ln2", D)}
        tree["layers"] = layers
    elif cfg.family == "moe":
        layers = {**_attn_leaves(cfg, L), **_moe_leaves(cfg, L),
                  **_norm(L, "ln1", D), **_norm(L, "ln2", D)}
        tree["layers"] = layers
    elif cfg.family == "ssm":
        tree["layers"] = _ssm_leaves(cfg, L)
    elif cfg.family == "hybrid":
        tree["layers"] = _ssm_leaves(cfg, L)
        tree["shared_attn"] = {**_attn_leaves(cfg, None), **_mlp_leaves(cfg, None),
                               **_norm(None, "ln1", D), **_norm(None, "ln2", D)}
    elif cfg.family == "hybrid_moe":
        # stacked by kind: mixers in layer order within their kind, and one
        # expert FFN (routed + shared) per layer
        kinds = cfg.layer_types[:L]
        n_attn = kinds.count("attention")
        tree["mamba"] = _ssm_leaves(cfg, L - n_attn)
        tree["attn"] = {**_attn_leaves(cfg, n_attn), **_norm(n_attn, "ln1", D)}
        Fs = cfg.shared_d_ff
        tree["moe"] = {
            **_moe_leaves(cfg, L), **_norm(L, "ln2", D),
            "ws_gate": Leaf((L, D, Fs), ("layers", "embed", "mlp"), "normal", 1.0 / np.sqrt(D)),
            "ws_up": Leaf((L, D, Fs), ("layers", "embed", "mlp"), "normal", 1.0 / np.sqrt(D)),
            "ws_down": Leaf((L, Fs, D), ("layers", "mlp", "embed"), "normal", 1.0 / np.sqrt(Fs)),
        }
    elif cfg.family == "encdec":
        Le = cfg.n_enc_layers
        tree["enc_layers"] = {**_attn_leaves(cfg, Le), **_mlp_leaves(cfg, Le),
                              **_norm(Le, "ln1", D), **_norm(Le, "ln2", D)}
        tree["enc_final_norm"] = Leaf((D,), (None,), "ones")
        tree["dec_layers"] = {**_attn_leaves(cfg, L), **_attn_leaves(cfg, L, cross=True),
                              **_mlp_leaves(cfg, L),
                              **_norm(L, "ln1", D), **_norm(L, "ln_x", D),
                              **_norm(L, "ln2", D)}
    else:
        raise ValueError(cfg.family)
    return tree


def _init_leaf(leaf: Leaf, key, dtype) -> jnp.ndarray:
    if leaf.init == "zeros":
        return jnp.zeros(leaf.shape, dtype)
    if leaf.init == "ones":
        return jnp.ones(leaf.shape, dtype)
    if leaf.init == "a_log":  # mamba2: A in [1, 16) -> log
        u = jax.random.uniform(key, leaf.shape, jnp.float32, 1.0, 16.0)
        return jnp.log(u).astype(dtype)
    if leaf.init == "conv":
        fan = leaf.shape[-2] if len(leaf.shape) > 1 else 4
        return (jax.random.normal(key, leaf.shape, jnp.float32) / np.sqrt(fan)).astype(dtype)
    return (leaf.scale * jax.random.normal(key, leaf.shape, jnp.float32)).astype(dtype)


def init_params(cfg: ModelConfig, key) -> dict:
    sch = schema(cfg)
    flat, treedef = jax.tree_util.tree_flatten(
        sch, is_leaf=lambda x: isinstance(x, Leaf))
    keys = jax.random.split(key, len(flat))
    dtype = jnp.dtype(cfg.dtype)
    arrs = [_init_leaf(leaf, k, dtype) for leaf, k in zip(flat, keys)]
    return jax.tree_util.tree_unflatten(treedef, arrs)


def abstract_params(cfg: ModelConfig) -> dict:
    sch = schema(cfg)
    dtype = jnp.dtype(cfg.dtype)
    return jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(l.shape, dtype),
        sch, is_leaf=lambda x: isinstance(x, Leaf))


def param_axes(cfg: ModelConfig) -> dict:
    sch = schema(cfg)
    return jax.tree_util.tree_map(
        lambda l: l.axes, sch, is_leaf=lambda x: isinstance(x, Leaf))


def count_params(cfg: ModelConfig) -> int:
    sch = schema(cfg)
    flat, _ = jax.tree_util.tree_flatten(sch, is_leaf=lambda x: isinstance(x, Leaf))
    return int(sum(np.prod(l.shape) for l in flat))
