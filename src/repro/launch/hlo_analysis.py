"""Collective-traffic + roofline-term extraction from compiled HLO.

collective_bytes is not in cost_analysis(): we parse the optimized HLO
text and sum the OUTPUT shape bytes of every all-gather / all-reduce /
reduce-scatter / all-to-all / collective-permute op (per-participant
bytes, the quantity the ICI/DCN link actually carries).
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_OP_RE = re.compile(
    r"=\s*((?:\([^)]*\)|\S+))\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_stats(hlo_text: str, *, wire_correction: bool = False) -> dict:
    """Per-collective-kind output bytes (per participant) + op counts.
    `-start` ops are counted once (`-done` carries no shape of its own
    in the tuple form, so only count starts and plain ops).

    wire_correction: the CPU backend PROMOTES bf16 all-reduces to
    f32 (bf16 reductions unsupported on host) — 2x the bytes a TPU
    lowering moves. Our explicit shard_map psums keep their jax op name
    ('%psum*'); with correction on, f32 all-reduces named psum are counted
    at half (their true bf16 payload). Recorded per cell as
    'wire_corrected_bytes'."""
    by_kind: dict = {k: {"bytes": 0, "count": 0} for k in _COLLECTIVES}
    promoted = 0
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        shape_str, kind = m.group(1), m.group(2)
        if f"{kind}-done(" in line:
            continue
        b = _shape_bytes(shape_str)
        if wire_correction and kind == "all-reduce" and "f32[" in shape_str \
                and re.search(r"%psum(\.\d+)?\s*=", line):
            promoted += b // 2
            b -= b // 2
        by_kind[kind]["bytes"] += b
        by_kind[kind]["count"] += 1
    total = sum(v["bytes"] for v in by_kind.values())
    return {"total_bytes": total, "by_kind": by_kind,
            "bf16_promotion_correction_bytes": promoted}


# ------------------------------------------------------------ roofline terms
@dataclass(frozen=True)
class ChipPeaks:
    flops: float       # bf16 FLOP/s per chip
    hbm_bw: float      # HBM bytes/s per chip
    ici_bw: float      # ICI bytes/s per link


#: Published per-chip peaks, keyed by ``jax.Device.device_kind``.
#: "TPU v5 lite" is TPU v5e — Google Cloud documentation, "TPU v5e":
#: 197 TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s ICI per chip over four
#: links (50 GB/s each).
PEAKS = {"TPU v5 lite": ChipPeaks(flops=197e12, hbm_bw=819e9, ici_bw=50e9)}


def chip_peaks(device_kind: str) -> ChipPeaks:
    """Peaks of one chip of ``device_kind``; a kind with no published
    entry is an error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r} (known: {sorted(PEAKS)})") from None


def analytic_memory_bytes(cfg, shape, *, n_chips: int, tp: int,
                          num_microbatches: int = 1) -> float:
    """Per-device HBM traffic model assuming flash-style attention (scores
    stay in VMEM) and fused elementwise chains. Used for the roofline
    memory term because the loop-free probes materialize S^2 scores (an
    upper bound).

    Components (bytes, per device, per step):
      weights     — per-chip weight slice read once per pass
                    (fwd / bwd-dgrad / bwd-wgrad => 3x for train, 1x serve)
      optimizer   — adam m/v/p read+write (train only)
      grad accum  — fp32 buffer r/w per microbatch (train only)
      activations — residual-stream traffic: C_ACT touches of (tok x D)
      logits      — vocab-sharded logits chain, C_LOGIT touches
      kv cache    — decode: read full cache slice; train/prefill: write once
    """
    import numpy as np
    dtype_b = 2 if cfg.dtype == "bfloat16" else 4
    P = 0
    from repro.models.schema import count_params
    P = count_params(cfg)
    dp = max(1, n_chips // tp)
    B, S = shape.global_batch, shape.seq_len
    kind = shape.kind
    D = cfg.d_model
    L = cfg.n_layers + (cfg.n_enc_layers if cfg.family == "encdec" else 0)
    tok_loc = B * (S if kind != "decode" else 1) / dp

    C_ACT_F, C_ACT_B = 12, 30      # touches per token per layer (fwd / bwd+remat)
    C_LOGIT_F, C_LOGIT_B = 6, 10   # fp32 logits chain touches

    w_slice = P * dtype_b / tp     # per-chip weight bytes touched per pass
    Vp_loc = cfg.padded_vocab / tp
    logit_loc = tok_loc * Vp_loc * 4

    if kind == "train":
        weights = 3 * w_slice * num_microbatches
        optim = (P / n_chips) * (dtype_b * 2 + 16 + 4)   # p rw + m,v rw(fp32)
        gacc = 2 * (P / n_chips) * 4 * num_microbatches
        acts = tok_loc * D * dtype_b * L * (C_ACT_F + C_ACT_B)
        logits = logit_loc * (C_LOGIT_F + C_LOGIT_B)
        return weights + optim + gacc + acts + logits
    if kind == "prefill":
        weights = w_slice
        acts = tok_loc * D * dtype_b * L * C_ACT_F
        logits = logit_loc * C_LOGIT_F
        return weights + acts + logits
    # decode: weight slice + full KV-cache slice read + tiny activations
    weights = w_slice
    kv_heads = getattr(cfg, "padded_kv_heads", 0)
    if cfg.family in ("ssm", "hybrid"):
        di, N = cfg.ssm_d_inner, cfg.ssm_state
        state = cfg.n_layers * (B / dp) * cfg.ssm_heads * cfg.ssm_headdim * N * 4
        cache = 2 * state  # read + write
        if cfg.family == "hybrid":
            n_app = cfg.n_layers // cfg.hybrid_ssm_per_block
            eff_S = min(S, cfg.sliding_window or S)
            cache += n_app * B * eff_S * kv_heads * cfg.head_dim * 2 * \
                dtype_b / n_chips
    else:
        eff_S = min(S, cfg.sliding_window or S)
        # the cache is taken as sharded over BOTH axes: batch (or seq) ->
        # data, kv-heads (or seq) -> model  =>  divisor = n_chips
        kv_b = 1 if getattr(cfg, "kv_cache_dtype", None) == "int8" else dtype_b
        cache = L * B * eff_S * kv_heads * cfg.head_dim * 2 * kv_b / n_chips
        if kv_b == 1:  # int8 scales (fp16 per position/head)
            cache += L * B * eff_S * kv_heads * 2 * 2 * 2 / n_chips
    acts = tok_loc * D * dtype_b * L * C_ACT_F
    logits = logit_loc * C_LOGIT_F
    return weights + cache + acts + logits


@dataclass
class Roofline:
    hlo_flops: float
    hlo_bytes: float
    collective_bytes: float   # per participant (already per-chip)
    n_chips: int
    device_kind: str          # key into PEAKS
    model_flops: float = 0.0  # 6·N·D analytic
    memory_bytes_analytic: float = 0.0  # per device, flash-corrected model

    def __post_init__(self):
        self.peaks = chip_peaks(self.device_kind)

    @property
    def t_compute(self) -> float:
        return self.hlo_flops / (self.n_chips * self.peaks.flops)

    @property
    def t_memory(self) -> float:
        """Memory term. Uses the flash-corrected analytic traffic model when
        available (the probe's HLO bytes materialize S^2 attention scores —
        an upper bound reported separately as t_memory_probe)."""
        if self.memory_bytes_analytic:
            return self.memory_bytes_analytic / self.peaks.hbm_bw
        return self.hlo_bytes / (self.n_chips * self.peaks.hbm_bw)

    @property
    def t_memory_probe(self) -> float:
        return self.hlo_bytes / (self.n_chips * self.peaks.hbm_bw)

    @property
    def t_collective(self) -> float:
        return self.collective_bytes / self.peaks.ici_bw

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def useful_flops_ratio(self) -> float:
        return self.model_flops / self.hlo_flops if self.hlo_flops else 0.0

    @property
    def t_star(self) -> float:
        """The binding roofline bound (max of the three terms): the
        fastest a step with this op mix can possibly run."""
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def roofline_fraction(self) -> float:
        """Fraction of the binding roofline that USEFUL work represents:
        (model_flops / peak) / max(all three terms)."""
        t_star = self.t_star
        if t_star == 0:
            return 0.0
        t_ideal = self.model_flops / (self.n_chips * self.peaks.flops)
        return t_ideal / t_star

    def attainment(self, measured_s: float) -> float:
        """Measured-vs-roofline: fraction of the hardware bound a
        *measured* step time achieves (``t_star / measured``, in (0, 1]
        for an honest measurement; >1 means the model or the measurement
        is wrong — surface it, don't clamp). 0.0 when either side is
        missing. This is the quantitative "as fast as the hardware
        allows" signal (ROADMAP): 1.0 = step time equals the binding
        compute/memory/collective bound."""
        if measured_s is None or measured_s <= 0 or self.t_star <= 0:
            return 0.0
        return self.t_star / float(measured_s)

    def to_dict(self) -> dict:
        return {
            "hlo_flops": self.hlo_flops, "hlo_bytes": self.hlo_bytes,
            "collective_bytes": self.collective_bytes,
            "n_chips": self.n_chips, "device_kind": self.device_kind,
            "model_flops": self.model_flops,
            "memory_bytes_analytic": self.memory_bytes_analytic,
            "t_compute_s": self.t_compute, "t_memory_s": self.t_memory,
            "t_memory_probe_s": self.t_memory_probe,
            "t_collective_s": self.t_collective,
            "t_star_s": self.t_star,
            "bottleneck": self.bottleneck,
            "useful_flops_ratio": self.useful_flops_ratio,
            "roofline_fraction": self.roofline_fraction,
        }


def roofline_from_compiled(compiled, hlo_text: str, n_chips: int,
                           model_flops: float, device_kind: str) -> Roofline:
    ca = compiled.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    flops = float(ca.get("flops", 0.0))
    byts = float(ca.get("bytes accessed", 0.0))
    coll = collective_stats(hlo_text)["total_bytes"]
    return Roofline(hlo_flops=flops, hlo_bytes=byts,
                    collective_bytes=float(coll), n_chips=n_chips,
                    device_kind=device_kind, model_flops=model_flops)
