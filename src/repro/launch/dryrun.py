import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede any jax import: jax locks the device count on first init.
"""Multi-pod dry-run: lower + compile every (architecture × input shape)
cell on the production meshes and record memory / cost / collective
analysis (results/dryrun/) for the roofline.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3_14b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod-only-train4k]
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh single   # 16x16
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh multi    # 2x16x16

Each cell writes an entry into results/dryrun/<arch>__<shape>__<mesh>.json
(incremental — safe to re-run; existing entries are skipped unless --force).
"""
import argparse
import json
import pathlib
import time

import jax

from repro import obs

WIRE_CORRECTION = os.environ.get("REPRO_EXPLICIT_TP", "0") == "1"

from repro.configs.base import SHAPES, ModelConfig, ShapeConfig
from repro.configs.registry import ARCH_IDS, get_config
from repro.launch import hlo_analysis
from repro.launch.input_specs import (abstract_opt_state, decode_input_specs,
                                      train_input_specs)
from repro.launch.mesh import make_production_mesh
from repro.models.schema import abstract_params

RESULTS = pathlib.Path(__file__).resolve().parents[3] / "results" / "dryrun"

# the chip the production meshes are made of (TPU v5e); it keys the
# roofline's peak rates
TARGET_DEVICE_KIND = "TPU v5 lite"

# long_500k runs only for sub-quadratic archs (SSM / hybrid / SWA);
# see DESIGN.md §5.
SUBQUADRATIC = {"mamba2_130m", "zamba2_7b", "h2o_danube_3_4b"}

# grad-accum microbatch count for train_4k, per arch (memory-driven)
MICROBATCHES = {
    "qwen3_moe_235b_a22b": 16, "llava_next_34b": 16, "qwen3_14b": 16,
    "deepseek_7b": 16, "zamba2_7b": 8, "h2o_danube_3_4b": 8,
    "qwen3_1_7b": 16, "granite_moe_1b_a400m": 8, "whisper_large_v3": 8,
    "mamba2_130m": 4,
}


def cell_applicable(arch_id: str, shape_name: str) -> tuple[bool, str]:
    if shape_name == "long_500k" and arch_id not in SUBQUADRATIC:
        return False, "long_500k needs sub-quadratic attention (DESIGN.md §5)"
    return True, ""


def lower_cell(arch_id: str, shape_name: str, mesh, *, attn_impl="masked",
               sharded_topk=True, loss_block=0, extra: dict | None = None):
    """Lower + compile one cell; returns result dict."""
    cfg = get_config(arch_id)
    if extra:
        cfg = cfg.with_(**{k: v for k, v in extra.items()
                           if hasattr(cfg, k)})
    shape = SHAPES[shape_name]
    params_abs = abstract_params(cfg)
    n_chips = 1
    for s in mesh.devices.shape:
        n_chips *= s
    t0 = time.time()

    if shape.kind == "train":
        from repro.train.train_loop import make_train_step
        from repro.train.optimizer import AdamWConfig
        nmb = MICROBATCHES.get(arch_id, 4)
        step = make_train_step(cfg, mesh, opt=AdamWConfig(),
                               num_microbatches=nmb, attn_impl=attn_impl,
                               global_batch=shape.global_batch, donate=True,
                               loss_block=loss_block)
        batch = train_input_specs(cfg, shape)
        opt_abs = abstract_opt_state(params_abs)
        lowered = step.lower(params_abs, opt_abs, batch)
        # tokens processed per step (model flops basis)
        n_tokens = shape.global_batch * shape.seq_len
        flops_per_token = 6 * cfg.n_active_params()
    elif shape.kind == "prefill":
        from repro.serve.steps import make_score_step
        step = make_score_step(cfg, mesh, topk=64, attn_impl=attn_impl,
                               global_batch=shape.global_batch,
                               sharded_topk=sharded_topk)
        batch = train_input_specs(cfg, shape)
        lowered = step.lower(params_abs, batch)
        n_tokens = shape.global_batch * shape.seq_len
        flops_per_token = 2 * cfg.n_active_params()
    else:  # decode
        from repro.serve.steps import make_serve_step
        step = make_serve_step(cfg, mesh, batch=shape.global_batch, topk=64,
                               donate=True, sharded_topk=sharded_topk)
        cache_abs, prev = decode_input_specs(cfg, shape)
        lowered = step.lower(params_abs, cache_abs, prev)
        n_tokens = shape.global_batch  # one token per stream
        flops_per_token = 2 * cfg.n_active_params()

    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    model_flops = float(flops_per_token) * n_tokens
    roof = hlo_analysis.roofline_from_compiled(
        compiled, hlo, n_chips, model_flops, TARGET_DEVICE_KIND)
    coll = hlo_analysis.collective_stats(hlo,
                                         wire_correction=WIRE_CORRECTION)

    result = {
        "arch": arch_id, "shape": shape_name,
        "mesh": "x".join(str(s) for s in mesh.devices.shape),
        "mesh_axes": list(mesh.axis_names),
        "kind": shape.kind, "n_chips": n_chips,
        "attn_impl": attn_impl, "sharded_topk": sharded_topk,
        "loss_block": loss_block,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": {
            "bytes_per_device": getattr(mem, "temp_size_in_bytes", 0) +
                                getattr(mem, "argument_size_in_bytes", 0) +
                                getattr(mem, "output_size_in_bytes", 0),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "generated_code_bytes":
                getattr(mem, "generated_code_size_in_bytes", None),
        },
        "roofline": roof.to_dict(),
        "collectives": coll,
    }
    if extra:
        result["extra"] = extra
    return result


# --------------------------------------------------------------- cost probes
# XLA's HloCostAnalysis counts while-loop bodies ONCE (scan-over-layers,
# microbatch scan, chunked attention all hide their trip counts), so the
# scanned production program under-reports FLOPs/bytes/collective-bytes.
# The probes lower LOOP-FREE programs (scan_layers=False, dense attention,
# one microbatch, single logits block) at 1-2 layers and reduced batch and
# extrapolate linearly — every hidden quantity is linear in (layers,
# microbatches). Caveat: the probes' dense
# attention materializes S^2 scores, so the *memory* term is an upper bound
# for flash-style attention; an analytic score-bytes correction is included.


def _probe_cfg(cfg: ModelConfig, n_layers: int) -> ModelConfig:
    kw = dict(n_layers=n_layers, scan_layers=False)
    if cfg.family == "encdec":
        kw["n_enc_layers"] = n_layers
    return cfg.with_(**kw)


def _probe_metrics(arch_id, cfg, shape, mesh, *, n_layers, global_batch,
                   attn_impl="dense", sharded_topk=False, loss_block=0):
    """Compile one loop-free probe; return metric dict."""
    pc = _probe_cfg(cfg, n_layers)
    pshape = ShapeConfig(shape.name, shape.seq_len, global_batch, shape.kind)
    params_abs = abstract_params(pc)
    if shape.kind == "train":
        from repro.train.train_loop import make_train_step
        from repro.train.optimizer import AdamWConfig
        step = make_train_step(pc, mesh, opt=AdamWConfig(),
                               num_microbatches=1, attn_impl=attn_impl,
                               global_batch=global_batch, donate=False,
                               loss_block=0)
        lowered = step.lower(params_abs,
                             abstract_opt_state(params_abs),
                             train_input_specs(pc, pshape))
    elif shape.kind == "prefill":
        from repro.serve.steps import make_score_step
        step = make_score_step(pc, mesh, topk=64, attn_impl=attn_impl,
                               s_block=shape.seq_len,
                               global_batch=global_batch,
                               sharded_topk=sharded_topk)
        lowered = step.lower(params_abs, train_input_specs(pc, pshape))
    else:
        from repro.serve.steps import make_serve_step
        step = make_serve_step(pc, mesh, batch=global_batch, topk=64,
                               donate=False, sharded_topk=sharded_topk)
        cache_abs, prev = decode_input_specs(pc, pshape)
        lowered = step.lower(params_abs, cache_abs, prev)
    compiled = lowered.compile()
    ca = compiled.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    coll = hlo_analysis.collective_stats(compiled.as_text(),
                                         wire_correction=WIRE_CORRECTION)
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
            "coll": float(coll["total_bytes"])}


def _probe_layer_counts(cfg: ModelConfig):
    return 1, 2, cfg.n_layers


def _hybrid_probe_cfgs(cfg):
    """(n_layers, per_block) probe pairs separating SSM-layer and shared-
    attn slopes: slope(2,2)=2s+a+..., slope(8,4)=4s+a."""
    return [(2, 2), (4, 2), (8, 4)]


def _attn_flops_dense(cfg: ModelConfig, shape) -> tuple:
    """Analytic dense-attention FLOPs over all passes, and the block-causal
    compute fraction ((nq+1)/(2 nq) of dense). Used to correct probe FLOPs
    when attn_impl='block_causal' (the triangular scan cannot be probed
    loop-free)."""
    if cfg.family == "ssm" or not cfg.padded_heads or shape.kind == "decode":
        return 0.0, 1.0
    S = shape.seq_len
    tokens = shape.global_batch * S
    n_attn = cfg.n_layers
    if cfg.family == "hybrid":
        n_attn = cfg.n_layers // cfg.hybrid_ssm_per_block
    per_tok = 4.0 * S * cfg.padded_heads * cfg.head_dim
    passes = 4.0 if shape.kind == "train" else 1.0  # fwd + bwd(2x) + remat
    nq = max(1, S // 512)
    frac = (nq + 1) / (2.0 * nq)
    return per_tok * tokens * n_attn * passes, frac


def probe_roofline(arch_id: str, shape_name: str, mesh,
                   sharded_topk=True, attn_impl="masked",
                   cfg_extra=None) -> dict:
    """Loop-corrected cost metrics for one cell (single-pod mesh).

    Simplified extrapolation: 2 probes in layer count at one microbatch
    size; the whole program scales x num_microbatches. The optimizer
    update is wrongly scaled by that (it runs once per step), a <=2%
    FLOP error on these models.
    """
    cfg = get_config(arch_id)
    if cfg_extra:
        cfg = cfg.with_(**{k: v for k, v in cfg_extra.items()
                           if hasattr(cfg, k)})
    shape = SHAPES[shape_name]
    L1, L2, L_eff = _probe_layer_counts(cfg)
    metrics = {}
    if shape.kind == "train":
        nmb = MICROBATCHES.get(arch_id, 4)
        b = max(shape.global_batch // nmb, 16)
        nmb_eff = shape.global_batch / b
        if cfg.family == "hybrid":
            per = cfg.hybrid_ssm_per_block
            probes = []
            for (L, pb) in _hybrid_probe_cfgs(cfg):
                pc = cfg.with_(hybrid_ssm_per_block=pb)
                probes.append(_probe_metrics(
                    arch_id, pc, shape, mesh, n_layers=L, global_batch=b))
            A, B, C = probes   # groups: 1x(2s+a), 2x(2s+a), 2x(4s+a)
            n_groups = cfg.n_layers // per
            n_rest = cfg.n_layers - n_groups * per
            for k in ("flops", "bytes", "coll"):
                g2 = B[k] - A[k]            # 2s + a
                g4 = (C[k] - (A[k] - g2))    # 2*(4s+a) => per-group:
                g4 = (C[k] - (A[k] - g2)) / 2.0
                s_lay = (g4 - g2) / 2.0
                a_att = g2 - 2 * s_lay
                fix = A[k] - g2
                total_1mb = fix + cfg.n_layers * s_lay + n_groups * a_att
                metrics[k] = max(0.0, total_1mb * nmb_eff)
            return metrics
        C1 = _probe_metrics(arch_id, cfg, shape, mesh, n_layers=L1,
                            global_batch=b)
        C2 = _probe_metrics(arch_id, cfg, shape, mesh, n_layers=L2,
                            global_batch=b)
        for k in ("flops", "bytes", "coll"):
            slope = (C2[k] - C1[k]) / (L2 - L1)
            metrics[k] = max(0.0, (C1[k] + slope * (L_eff - L1)) * nmb_eff)
    else:
        if cfg.family == "hybrid":
            per = cfg.hybrid_ssm_per_block
            probes = []
            for (L, pb) in _hybrid_probe_cfgs(cfg):
                pc = cfg.with_(hybrid_ssm_per_block=pb)
                probes.append(_probe_metrics(
                    arch_id, pc, shape, mesh, n_layers=L,
                    global_batch=shape.global_batch))
            A, B, C = probes
            n_groups = cfg.n_layers // per
            for k in ("flops", "bytes", "coll"):
                g2 = B[k] - A[k]
                g4 = (C[k] - (A[k] - g2)) / 2.0
                s_lay = (g4 - g2) / 2.0
                a_att = g2 - 2 * s_lay
                fix = A[k] - g2
                metrics[k] = max(0.0, fix + cfg.n_layers * s_lay +
                                 n_groups * a_att)
            return metrics
        C1 = _probe_metrics(arch_id, cfg, shape, mesh, n_layers=L1,
                            global_batch=shape.global_batch,
                            sharded_topk=sharded_topk)
        C2 = _probe_metrics(arch_id, cfg, shape, mesh, n_layers=L2,
                            global_batch=shape.global_batch,
                            sharded_topk=sharded_topk)
        for k in ("flops", "bytes", "coll"):
            slope = (C2[k] - C1[k]) / (L2 - L1)
            metrics[k] = max(0.0, C1[k] + slope * (L_eff - L1))
    # block-causal: probes ran dense attention; subtract the analytic
    # triangular saving from the extrapolated FLOPs (exact block count)
    if attn_impl == "block_causal" and "flops" in metrics:
        dense_flops, frac = _attn_flops_dense(cfg, shape)
        n_chips = mesh.devices.size
        metrics["flops"] = max(
            0.0, metrics["flops"] - dense_flops * (1 - frac) / n_chips)
        metrics["block_causal_correction"] = dense_flops * (1 - frac)
    # analytic dense-attention score-bytes (memory-term upper-bound caveat)
    if cfg.family not in ("ssm",) and cfg.padded_heads:
        S = shape.seq_len if shape.kind != "decode" else 1
        Sk = shape.seq_len
        per_dev_tokens = shape.global_batch * S / max(1, mesh.devices.size //
                                                      mesh.shape["model"])
        scores = per_dev_tokens * cfg.padded_heads * Sk * 4 * 3
        n_attn = cfg.n_layers if cfg.family != "hybrid" else \
            cfg.n_layers // cfg.hybrid_ssm_per_block
        metrics["attn_scores_bytes_analytic"] = scores * n_attn * \
            (3 if shape.kind == "train" else 1)
    return metrics


def run_cells(cells, mesh_kind: str, *, force=False, attn_impl="masked",
              tag="", probe=None, sharded_topk=True, loss_block=0,
              kv_int8=False):
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    if probe is None:
        probe = mesh_kind == "single"  # roofline table is single-pod only
    RESULTS.mkdir(parents=True, exist_ok=True)
    ok = fail = skip = 0
    for arch_id, shape_name in cells:
        name = f"{arch_id}__{shape_name}__{mesh_kind}" + \
            (f"__{tag}" if tag else "")
        out = RESULTS / f"{name}.json"
        applicable, why = cell_applicable(arch_id, shape_name)
        if not applicable:
            out.write_text(json.dumps(
                {"arch": arch_id, "shape": shape_name, "mesh": mesh_kind,
                 "skipped": why}, indent=1))
            obs.log("dryrun.skip", cell=name, why=why)
            skip += 1
            continue
        if out.exists() and not force:
            obs.log("dryrun.cached", cell=name)
            ok += 1
            continue
        obs.log("dryrun.lower", cell=name)
        try:
            extra = {"kv_cache_dtype": "int8"} if kv_int8 else None
            res = lower_cell(arch_id, shape_name, mesh, attn_impl=attn_impl,
                             sharded_topk=sharded_topk, loss_block=loss_block,
                             extra=extra)
            if probe:
                pm = probe_roofline(arch_id, shape_name, mesh,
                                    sharded_topk=sharded_topk,
                                    attn_impl=attn_impl,
                                    cfg_extra=extra)
                n_chips = res["n_chips"]
                cfg_r = get_config(arch_id)
                if extra:
                    cfg_r = cfg_r.with_(**{k: v for k, v in extra.items()
                                           if hasattr(cfg_r, k)})
                mem_analytic = hlo_analysis.analytic_memory_bytes(
                    cfg_r, SHAPES[shape_name], n_chips=n_chips,
                    tp=mesh.shape["model"],
                    num_microbatches=MICROBATCHES.get(arch_id, 4))
                roof = hlo_analysis.Roofline(
                    hlo_flops=pm["flops"] * n_chips,
                    hlo_bytes=pm["bytes"] * n_chips,
                    collective_bytes=pm["coll"],
                    n_chips=n_chips, device_kind=TARGET_DEVICE_KIND,
                    model_flops=res["roofline"]["model_flops"],
                    memory_bytes_analytic=mem_analytic)
                res["roofline_raw_scanned"] = res["roofline"]
                rd = roof.to_dict()
                rd["note"] = ("loop-corrected via unrolled probes; "
                              "memory term is a dense-attn upper bound")
                if "attn_scores_bytes_analytic" in pm:
                    rd["attn_scores_bytes_analytic"] = \
                        pm["attn_scores_bytes_analytic"]
                res["roofline"] = rd
            out.write_text(json.dumps(res, indent=1))
            r = res["roofline"]
            obs.log("dryrun.ok", cell=name, compile_s=res["compile_s"],
                    mem_gib=round(
                        res["memory"]["bytes_per_device"] / 2**30, 2),
                    bottleneck=r["bottleneck"],
                    roofline_frac=round(r["roofline_fraction"], 3))
            ok += 1
        except Exception as e:  # noqa: BLE001 — record, continue
            # structured error sidecar + counted failure (obs.log_exception
            # increments errors.total / errors.dryrun.cell_failed, so a
            # sweep's failures are countable in the registry snapshot, not
            # only greppable from .err files)
            out.with_suffix(".err").write_text(json.dumps(
                {"cell": name, "error": obs.exception_record(e)}, indent=1))
            obs.log_exception("dryrun.cell_failed", e, cell=name)
            obs.registry().counter(
                "dryrun.cell_failures", "dry-run cells that failed to "
                "lower/compile").inc()
            fail += 1
    obs.log("dryrun.done", ok=ok, fail=fail, skip=skip)
    return fail


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--attn-impl", default="masked")
    ap.add_argument("--sharded-topk", action="store_true", default=True)
    ap.add_argument("--no-sharded-topk", dest="sharded_topk",
                    action="store_false")
    ap.add_argument("--kv-int8", action="store_true")
    ap.add_argument("--loss-block", type=int, default=0)
    ap.add_argument("--tag", default="")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else ARCH_IDS
    shapes = [args.shape] if args.shape else list(SHAPES)
    cells = [(a, s) for a in archs for s in shapes]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    rc = 0
    for mk in meshes:
        rc += run_cells(cells, mk, force=args.force,
                        attn_impl=args.attn_impl, tag=args.tag,
                        sharded_topk=args.sharded_topk,
                        loss_block=args.loss_block,
                        kv_int8=args.kv_int8)
    raise SystemExit(1 if rc else 0)


if __name__ == "__main__":
    main()
