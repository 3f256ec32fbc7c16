"""Mamba2 — SSD (state-space duality, arXiv:2405.21060) blocks.

Chunked SSD forward for training/prefill (the quadratic intra-chunk part is
also implemented as a Pallas kernel, kernels/ssd_scan.py), and the O(1)
recurrent decode step.

Per layer:  x -> [z | xc | B | C | dt] projections; causal conv1d over
(xc,B,C) (plus a bias with ``cfg.ssm_conv_bias``); SSD recurrence with
per-head scalar decay A; gated output y * silu(z) (with
``cfg.ssm_gated_norm``, RMSNorm of that product times its own weight, as
Granite-4.0-H gates it). State per head: (P, N) with P=headdim,
N=ssm_state.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from .layers import rms_norm, scale_residual, shard


def _conv1d_causal(x, w, state=None, bias=None):
    """Causal depthwise conv. x (B,S,C), w (K,C), bias (C,) or None. If
    `state` (B,K-1,C) is given, it prefixes x (for decode); returns
    (y, new_state)."""
    K = w.shape[0]
    if state is None:
        pad = jnp.zeros((x.shape[0], K - 1, x.shape[2]), x.dtype)
    else:
        pad = state
    xp = jnp.concatenate([pad, x], axis=1)
    y = sum(xp[:, i:i + x.shape[1]] * w[i] for i in range(K))
    if bias is not None:
        y = y + bias
    new_state = xp[:, -(K - 1):]
    return jax.nn.silu(y), new_state


def ssd_chunked(x, dt, A, Bm, Cm, *, chunk: int, initial_state=None):
    """Chunked SSD scan.

    x  (B,S,H,P)   inputs per head
    dt (B,S,H)     positive step sizes
    A  (H,)        negative per-head decay rates
    Bm (B,S,N), Cm (B,S,N)  input/output projections (single group)
    Returns y (B,S,H,P), final_state (B,H,P,N).
    """
    Bsz, S0, H, Pd = x.shape
    N = Bm.shape[-1]
    Q = min(chunk, S0)
    if S0 % Q:  # pad sequence to a chunk multiple (dt=0 => identity steps)
        pad = Q - S0 % Q
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
    S = x.shape[1]
    nc = S // Q
    xc = x.reshape(Bsz, nc, Q, H, Pd)
    dtc = dt.reshape(Bsz, nc, Q, H)
    Bc = Bm.reshape(Bsz, nc, Q, N)
    Cc = Cm.reshape(Bsz, nc, Q, N)

    a = dtc * A  # (B,nc,Q,H) log-decay per step (negative)
    cum = jnp.cumsum(a, axis=2)                     # inclusive cumsum within chunk
    seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]   # (B,nc,Q_i,Q_j,H)
    ii, jj = jnp.meshgrid(jnp.arange(Q), jnp.arange(Q), indexing="ij")
    causal = (ii >= jj)[None, None, :, :, None]
    # mask BEFORE exp: exp of large positive (acausal) entries would give
    # inf * 0 = NaN in the backward pass
    L = jnp.exp(jnp.where(causal, seg, -jnp.inf))   # decay from j to i

    # intra-chunk: y_intra[i] = sum_j L[i,j] (C_i . B_j) dt_j x_j
    G = jnp.einsum("bcin,bcjn->bcij", Cc, Bc)       # (B,nc,Q,Q)
    W = G[..., None] * L                            # (B,nc,Q,Q,H)
    y_intra = jnp.einsum("bcijh,bcjh,bcjhp->bcihp", W, dtc, xc)

    # chunk-boundary states: S_c = decay(chunk) S_{c-1} + sum_j decay(end-j) dt_j x_j B_j
    chunk_decay = jnp.exp(cum[:, :, -1])            # (B,nc,H)
    end_decay = jnp.exp(cum[:, :, -1:, :] - cum)    # (B,nc,Q,H) decay j -> chunk end
    S_in = jnp.einsum("bcjh,bcjh,bcjhp,bcjn->bchpn", end_decay, dtc, xc, Bc)

    def scan_body(s_prev, inp):
        dec, s_in = inp                             # (B,H), (B,H,P,N)
        s_new = s_prev * dec[:, :, None, None] + s_in
        return s_new, s_prev                        # emit state ENTERING the chunk

    s0 = initial_state if initial_state is not None else \
        jnp.zeros((Bsz, H, Pd, N), x.dtype)
    s0 = s0.astype(jnp.float32)
    final, s_enter = jax.lax.scan(
        scan_body,
        s0,
        (jnp.moveaxis(chunk_decay, 1, 0).astype(jnp.float32),
         jnp.moveaxis(S_in, 1, 0).astype(jnp.float32)))
    s_enter = jnp.moveaxis(s_enter, 0, 1)           # (B,nc,H,P,N)

    # inter-chunk: y_inter[i] = exp(cum_i) * C_i . S_enter
    y_inter = jnp.einsum("bcih,bcin,bchpn->bcihp",
                         jnp.exp(cum), Cc, s_enter.astype(x.dtype))
    y = (y_intra + y_inter).reshape(Bsz, S, H, Pd)[:, :S0]
    return y.astype(x.dtype), final.astype(x.dtype)


def _gate(cfg: ModelConfig, lp: dict, y, z, dtype):
    """The SSM output gated by z: y * silu(z), or with ``ssm_gated_norm``
    RMSNorm(y * silu(z)) * lp["gate_norm"] computed in float32."""
    if cfg.ssm_gated_norm:
        g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
        return rms_norm(g, lp["gate_norm"], cfg.norm_eps).astype(dtype)
    return (y * jax.nn.silu(z)).astype(dtype)


def ssm_block(cfg: ModelConfig, lp: dict, x):
    """Full mamba2 layer (training/prefill). x (B,S,D) -> (B,S,D)."""
    B, S, D = x.shape
    di, N, H, Pd = cfg.ssm_d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_headdim
    h = rms_norm(x, lp["ln"], cfg.norm_eps)
    z = jnp.einsum("bsd,de->bse", h, lp["in_z"])
    xc = jnp.einsum("bsd,de->bse", h, lp["in_x"])
    Bm = jnp.einsum("bsd,dn->bsn", h, lp["in_B"])
    Cm = jnp.einsum("bsd,dn->bsn", h, lp["in_C"])
    dt_raw = jnp.einsum("bsd,dh->bsh", h, lp["in_dt"])
    conv_in = jnp.concatenate([xc, Bm, Cm], axis=-1)
    conv_out, _ = _conv1d_causal(conv_in, lp["conv_w"], bias=lp.get("conv_b"))
    xc, Bm, Cm = jnp.split(conv_out, [di, di + N], axis=-1)
    xc = shard(xc, ("pod", "data"), None, None)
    dt = jax.nn.softplus(dt_raw + lp["dt_bias"])
    A = -jnp.exp(lp["A_log"].astype(jnp.float32))
    y, _ = ssd_chunked(xc.reshape(B, S, H, Pd), dt, A, Bm, Cm,
                       chunk=cfg.ssm_chunk)
    y = y + lp["D_skip"][None, None, :, None] * xc.reshape(B, S, H, Pd)
    y = _gate(cfg, lp, y.reshape(B, S, di), z, x.dtype)
    return x + scale_residual(cfg, jnp.einsum("bse,ed->bsd", y, lp["out_proj"]))


# ------------------------------------------------------------------- decode
def init_ssm_cache(cfg: ModelConfig, batch: int, n_layers: int, dtype=None):
    dtype = dtype or jnp.dtype(cfg.dtype)
    di, N, H, Pd = cfg.ssm_d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_headdim
    return {
        "conv": jnp.zeros((n_layers, batch, cfg.ssm_conv - 1, di + 2 * N), dtype),
        "state": jnp.zeros((n_layers, batch, H, Pd, N), jnp.float32),
    }


def ssm_decode_step(cfg: ModelConfig, lp: dict, x, conv_state, ssm_state):
    """One-token mamba2 step. x (B,1,D) -> (y (B,1,D), conv_state, ssm_state)."""
    B = x.shape[0]
    di, N, H, Pd = cfg.ssm_d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_headdim
    h = rms_norm(x, lp["ln"], cfg.norm_eps)
    z = jnp.einsum("bsd,de->bse", h, lp["in_z"])
    xc = jnp.einsum("bsd,de->bse", h, lp["in_x"])
    Bm = jnp.einsum("bsd,dn->bsn", h, lp["in_B"])
    Cm = jnp.einsum("bsd,dn->bsn", h, lp["in_C"])
    dt_raw = jnp.einsum("bsd,dh->bsh", h, lp["in_dt"])
    conv_in = jnp.concatenate([xc, Bm, Cm], axis=-1)
    conv_out, conv_state = _conv1d_causal(conv_in, lp["conv_w"], conv_state,
                                          lp.get("conv_b"))
    xc, Bm, Cm = jnp.split(conv_out[:, 0], [di, di + N], axis=-1)
    dt = jax.nn.softplus(dt_raw[:, 0] + lp["dt_bias"])            # (B,H)
    A = -jnp.exp(lp["A_log"].astype(jnp.float32))
    xh = xc.reshape(B, H, Pd)
    dA = jnp.exp(dt * A)                                           # (B,H)
    upd = (dt[..., None, None] * xh[..., None] *
           Bm[:, None, None, :])                                   # (B,H,P,N)
    ssm_state = ssm_state * dA[..., None, None] + upd.astype(jnp.float32)
    y = jnp.einsum("bhpn,bn->bhp", ssm_state.astype(x.dtype), Cm)
    y = y + lp["D_skip"][None, :, None] * xh
    y = _gate(cfg, lp, y.reshape(B, 1, di), z, x.dtype)
    out = scale_residual(cfg, jnp.einsum("bse,ed->bsd", y, lp["out_proj"]))
    return x + out, conv_state, ssm_state


def forward(params, cfg: ModelConfig, batch: dict, *, return_hidden=False, **_):
    """Teacher-forced scoring for the pure-SSM family."""
    from .transformer import _scan_blocks, embed_tokens, lm_logits
    x = embed_tokens(cfg, params, batch["tokens"])
    x = _scan_blocks(cfg, params["layers"], x,
                     lambda h, lp: ssm_block(cfg, lp, h))
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if return_hidden:
        return x
    return lm_logits(cfg, params, x)


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=None):
    c = init_ssm_cache(cfg, batch, cfg.n_layers, dtype)
    c["pos"] = jnp.zeros((batch,), jnp.int32)   # per-lane (slot-resettable)
    return c


def decode_step(params, cfg: ModelConfig, cache, prev_tokens):
    from .transformer import embed_tokens, lm_logits
    x = embed_tokens(cfg, params, prev_tokens[:, None])

    def body(carry, xs):
        h = carry
        lp, cs, ss = xs
        h, cs, ss = ssm_decode_step(cfg, lp, h, cs, ss)
        return h, (cs, ss)

    from .transformer import scan_xs
    x, (conv_new, state_new) = scan_xs(
        cfg, body, x, (params["layers"], cache["conv"], cache["state"]))
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = lm_logits(cfg, params, x)[:, 0]
    return logits, {"conv": conv_new, "state": state_new,
                    "pos": cache["pos"] + 1}
