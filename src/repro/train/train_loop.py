"""Distributed training step builder.

make_train_step(cfg, mesh, ...) returns a jitted (params, opt_state, batch)
-> (params, opt_state, metrics) function with:
  * gradient accumulation over microbatches (lax.scan) — activation memory
    O(microbatch), FSDP all-gathers of layer i+1 overlap layer i's compute
    inside the layer scan (XLA latency-hiding on TPU);
  * per-layer remat (jax.checkpoint around the scanned block);
  * AdamW with sharded (ZeRO) states, global-norm clip, lr schedule;
  * optional int8 gradient compression with error feedback (DCN reduce).

The same builder is used by the smoke tests (1-device mesh), the measured
CPU runs and the multi-device tests.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import api as model_api
from repro.sharding.specs import batch_pspecs, param_pspecs
from .optimizer import AdamWConfig, adamw_update, init_opt_state


def make_train_step(cfg: ModelConfig, mesh, *,
                    opt: Optional[AdamWConfig] = None,
                    num_microbatches: int = 1,
                    attn_impl: str = "masked",
                    global_batch: Optional[int] = None,
                    donate: bool = True,
                    loss_block: int = 0):
    opt = opt or AdamWConfig()
    pspecs = param_pspecs(cfg, mesh)
    fam_kw = {}
    if cfg.family == "moe" and mesh is not None and \
            "model" in mesh.axis_names and mesh.shape["model"] > 1:
        fam_kw["mesh"] = mesh

    def loss_on(params, mb):
        return model_api.loss_fn(params, cfg, mb, attn_impl=attn_impl,
                                 loss_block=loss_block, **fam_kw)

    def _train_step_body(params, opt_state, batch):
        if num_microbatches == 1:
            loss, grads = jax.value_and_grad(loss_on)(params, batch)
        else:
            def mb_slice(i):
                return jax.tree_util.tree_map(
                    lambda x: x.reshape((num_microbatches,
                                         x.shape[0] // num_microbatches)
                                        + x.shape[1:])[i], batch)

            def accum(carry, i):
                g_acc, l_acc = carry
                loss, g = jax.value_and_grad(loss_on)(params, mb_slice(i))
                g_acc = jax.tree_util.tree_map(
                    lambda a, b: a + b.astype(jnp.float32), g_acc, g)
                return (g_acc, l_acc + loss), None

            g0 = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (grads, loss_sum), _ = jax.lax.scan(
                accum, (g0, jnp.zeros((), jnp.float32)),
                jnp.arange(num_microbatches))
            grads = jax.tree_util.tree_map(
                lambda g: g / num_microbatches, grads)
            loss = loss_sum / num_microbatches
        new_params, new_opt, gnorm = adamw_update(params, grads, opt_state, opt)
        metrics = {"loss": loss, "grad_norm": gnorm,
                   "step": new_opt["step"]}
        return new_params, new_opt, metrics

    def train_step(params, opt_state, batch):
        from repro.models.layers import mesh_context
        with mesh_context(mesh):
            return _train_step_body(params, opt_state, batch)

    if mesh is None:
        return jax.jit(train_step, donate_argnums=(0, 1) if donate else ())

    gb = global_batch or 1
    bspecs = batch_pspecs(cfg, mesh, global_batch=gb)
    opt_specs = {"m": pspecs, "v": pspecs, "step": P()}
    if opt.grad_compress:
        opt_specs["err"] = pspecs
    sh = lambda spec: NamedSharding(mesh, spec)
    in_sh = (jax.tree_util.tree_map(sh, pspecs),
             jax.tree_util.tree_map(sh, opt_specs),
             {k: sh(v) for k, v in bspecs.items()})
    out_sh = (jax.tree_util.tree_map(sh, pspecs),
              jax.tree_util.tree_map(sh, opt_specs),
              {"loss": sh(P()), "grad_norm": sh(P()), "step": sh(P())})
    return jax.jit(train_step, in_shardings=in_sh, out_shardings=out_sh,
                   donate_argnums=(0, 1) if donate else ())


def init_train_state(cfg: ModelConfig, key, opt: Optional[AdamWConfig] = None):
    """Single-host init (smoke tests / measured runs)."""
    params = model_api.init_params(cfg, key)
    opt_state = init_opt_state(params, opt or AdamWConfig())
    return params, opt_state
