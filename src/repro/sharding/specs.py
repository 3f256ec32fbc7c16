"""Logical axis -> mesh axis mapping (the sharding policy).

One place decides how every parameter and input batch of the train step
is laid out on the (pod, data, model) mesh; see DESIGN.md §4 for the table
and the divisibility fallbacks (an axis that does not divide its mesh
degree is replicated).
"""
from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.launch.mesh import batch_axes, mesh_degree
from repro.models.schema import param_axes


def _rules(cfg: ModelConfig, mesh) -> dict:
    """FSDP (D rows over 'data') + TP — optimizer states shard with the
    params."""
    tp = mesh_degree(mesh, "model")
    dp = mesh_degree(mesh, "data")
    ssm_ok = cfg.family in ("ssm", "hybrid") and \
        cfg.ssm_heads % tp == 0 and cfg.ssm_d_inner % tp == 0
    return {
        "embed": "data" if cfg.d_model % dp == 0 and dp > 1 else None,
        "expert_embed": "data" if cfg.d_model % dp == 0 and dp > 1 else None,
        "vocab_rows": None,
        "embed_head": None,
        "heads": "model" if (cfg.padded_heads * cfg.head_dim) % tp == 0 else None,
        "kv_heads": "model" if cfg.padded_kv_heads % tp == 0 else None,
        "mlp": "model" if cfg.d_ff % tp == 0 and cfg.d_ff else None,
        "vocab": "model" if cfg.padded_vocab % tp == 0 else None,
        "expert": "model" if cfg.n_experts % tp == 0 and cfg.n_experts else None,
        "ssm_inner": "model" if ssm_ok else None,
        "layers": None,
        None: None,
    }


def param_pspecs(cfg: ModelConfig, mesh):
    """PartitionSpec tree matching init_params/abstract_params structure."""
    rules = _rules(cfg, mesh)
    axes_tree = param_axes(cfg)
    return jax.tree_util.tree_map(
        lambda axes: P(*(rules[a] for a in axes)),
        axes_tree, is_leaf=lambda x: isinstance(x, tuple))


def batch_pspecs(cfg: ModelConfig, mesh, *, global_batch: int):
    """Input batch specs. Batch dim shards over (pod, data) when divisible."""
    ba = batch_axes(mesh)
    nb = 1
    for a in ba:
        nb *= mesh.shape[a]
    bspec = ba if ba and global_batch % nb == 0 else None
    specs = {"tokens": P(bspec, None)}
    if cfg.family == "vlm":
        specs["img_embeds"] = P(bspec, None, None)
    if cfg.family == "encdec":
        specs["frames"] = P(bspec, None, None)
    return specs
