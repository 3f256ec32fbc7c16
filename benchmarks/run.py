"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` rows plus the full result tables, and
appends one schema-versioned record per bench to ``results/history.jsonl``
(the bench trajectory ``tools/bench_regress.py`` gates on — DESIGN.md §13).
Runs the small byte-level predictors (the paper's 1B-14B models scaled
down; trends are the claims under test) on whatever backend JAX finds.
Rows name no device, so a time from them is not a chip measurement.

  PYTHONPATH=src python -m benchmarks.run [--quick] [--only name]
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import numpy as np

sys.path[:0] = ["src", "."]

from repro.obs import console  # noqa: E402

RESULTS = pathlib.Path(__file__).resolve().parents[1] / "results"
#: (name, us_per_call, derived) staged by _csv; main() drains the stage
#: into the history store after each bench (with that bench's registry).
ROWS: list[tuple[str, float, str]] = []


def _csv(name: str, us: float, derived: str):
    ROWS.append((name, us, derived))
    console(f"{name},{us:.1f},{derived}")


def _compressor(pred, chunk=64, topk=32, batch=32):
    from repro.core import LLMCompressor
    return LLMCompressor(pred, chunk_size=chunk, topk=topk,
                         decode_batch=batch)


def _ratio(pred, data: bytes, chunk=64, topk=32, verify=False):
    from repro.data.tokenizer import encode
    comp = _compressor(pred, chunk=chunk, topk=topk)
    toks = encode(data)
    t0 = time.time()
    blob, stats = comp.compress(toks)
    dt = time.time() - t0
    if verify:
        out = comp.decompress(blob)
        assert np.array_equal(out, toks), "LOSSLESS VIOLATION"
    return len(data) / len(blob), dt, stats


# ------------------------------------------------------- paper table analogs
def table2_information(quick=False):
    """Paper Table 2 + Fig 2: entropy / MI / n-gram redundancy of
    machine-gen vs human vs LLM-gen text."""
    from benchmarks.prep import human_dataset, llm_dataset
    from repro.core.entropy import analyze
    n = 4096 if quick else 12288
    structured = (b"ORDER|4231|PENDING|2024-01-01|ACME|1200.00|EA|\n" * 400)[:n]
    rows = {}
    t0 = time.time()
    rows["llm_generated"] = analyze(llm_dataset("wiki", n).decode("latin1"))
    rows["human_generated"] = analyze(human_dataset("wiki", n).decode("latin1"))
    rows["machine_structured"] = analyze(structured.decode("latin1"))
    console("\n== table2_information (entropy/byte, MI, top-10 n-gram coverage) ==")
    keys = list(next(iter(rows.values())))
    console(f"{'dataset':22s} " + " ".join(f"{k[:12]:>12s}" for k in keys))
    for name, r in rows.items():
        console(f"{name:22s} " + " ".join(f"{r[k]:12.3f}" for k in keys))
    _csv("table2_information", (time.time() - t0) * 1e6 / 3,
         f"llm_MI={rows['llm_generated']['mutual_info_bits']}")
    (RESULTS / "table2_information.json").write_text(json.dumps(rows, indent=1))
    return rows


def table3_traditional(quick=False):
    """Paper Table 3: traditional compressors on LLM-generated text."""
    from benchmarks.prep import llm_dataset
    from repro.core.baselines import run_baselines
    n = 4096 if quick else 8192
    doms = ("wiki", "code", "math")
    console("\n== table3_traditional (compression ratios) ==")
    out = {}
    t0 = time.time()
    for d in doms:
        out[d] = run_baselines(llm_dataset(d, n))
        console(f"{d:10s} " + " ".join(f"{k}={v:5.2f}" for k, v in out[d].items()))
    _csv("table3_traditional", (time.time() - t0) * 1e6 / len(doms),
         f"wiki_lzma={out['wiki']['lzma']}")
    (RESULTS / "table3_traditional.json").write_text(json.dumps(out, indent=1))
    return out


def table5_main(quick=False):
    """Paper Table 5: every method x every dataset category, including the
    LLM compressor ('ours'). Round-trip verified on one dataset."""
    from benchmarks.prep import DOMAINS, llm_dataset, predictor
    from repro.core.baselines import run_baselines
    n = 3072 if quick else 6144
    doms = DOMAINS[:4] if quick else DOMAINS
    pred = predictor("pred-base")
    console("\n== table5_main (ratios; ours = pred-base LLM compressor) ==")
    table = {}
    t0 = time.time()
    for i, d in enumerate(doms):
        data = llm_dataset(d, n)
        row = run_baselines(data)
        r, dt, stats = _ratio(pred, data, verify=(i == 0))
        row["ours_llm"] = round(r, 3)
        row["ours_bits_per_byte"] = round(8.0 / r, 3)
        table[d] = row
        console(f"{d:10s} " + " ".join(f"{k}={v:6.2f}" for k, v in row.items()))
    avg_ours = np.mean([r["ours_llm"] for r in table.values()])
    avg_gzip = np.mean([r["gzip"] for r in table.values()])
    _csv("table5_main", (time.time() - t0) * 1e6 / len(doms),
         f"ours_avg={avg_ours:.2f};gzip_avg={avg_gzip:.2f};"
         f"ours_over_gzip={avg_ours/avg_gzip:.2f}")
    (RESULTS / "table5_main.json").write_text(json.dumps(table, indent=1))
    return table


def fig_chunk_size(quick=False):
    """Paper §5.4: ratio vs chunk size (16..256), diminishing returns."""
    from benchmarks.prep import llm_dataset, predictor
    pred = predictor("pred-base")
    data = llm_dataset("wiki", 3072 if quick else 6144)
    chunks = (16, 32, 64) if quick else (16, 32, 64, 128, 256)
    console("\n== fig_chunk_size (ratio vs chunk) ==")
    t0 = time.time()
    out = {}
    for c in chunks:
        r, dt, _ = _ratio(pred, data, chunk=c)
        out[c] = round(r, 3)
        console(f"chunk={c:4d} ratio={r:.3f}")
    _csv("fig_chunk_size", (time.time() - t0) * 1e6 / len(chunks),
         ";".join(f"c{c}={v}" for c, v in out.items()))
    (RESULTS / "fig_chunk_size.json").write_text(
        json.dumps({str(k): v for k, v in out.items()}))
    return out


def fig_model_size(quick=False):
    """Paper §5.5 / Fig 6: ratio vs predictor size."""
    from benchmarks.prep import llm_dataset, predictor
    from repro.models.schema import count_params
    data = llm_dataset("wiki", 3072 if quick else 6144)
    names = ("pred-tiny", "pred-small") if quick else \
        ("pred-tiny", "pred-small", "pred-base")
    console("\n== fig_model_size (ratio vs params) ==")
    t0 = time.time()
    out = {}
    for n in names:
        pred = predictor(n)
        r, _, _ = _ratio(pred, data)
        out[n] = {"params": count_params(pred.cfg), "ratio": round(r, 3)}
        console(f"{n:12s} params={out[n]['params']:>10,d} ratio={r:.3f}")
    _csv("fig_model_size", (time.time() - t0) * 1e6 / len(names),
         ";".join(f"{k}={v['ratio']}" for k, v in out.items()))
    (RESULTS / "fig_model_size.json").write_text(json.dumps(out))
    return out


def fig_data_scale(quick=False):
    """Paper §5.6 / Fig 7: ratio vs dataset size (LLM ratio stays flat,
    dictionary methods drift slowly)."""
    from benchmarks.prep import llm_dataset, predictor
    from repro.core.baselines import gzip_ratio, lzma_ratio
    pred = predictor("pred-base")
    sizes = (2048, 4096) if quick else (2048, 4096, 8192, 16384)
    console("\n== fig_data_scale ==")
    t0 = time.time()
    out = {}
    for n in sizes:
        data = llm_dataset("wiki", n)
        r, _, _ = _ratio(pred, data)
        out[n] = {"ours": round(r, 3), "gzip": round(gzip_ratio(data), 3),
                  "lzma": round(lzma_ratio(data), 3)}
        console(f"n={n:6d} ours={out[n]['ours']:.3f} gzip={out[n]['gzip']:.3f} "
              f"lzma={out[n]['lzma']:.3f}")
    spread = max(v['ours'] for v in out.values()) - \
        min(v['ours'] for v in out.values())
    _csv("fig_data_scale", (time.time() - t0) * 1e6 / len(sizes),
         f"ours_spread={spread:.3f}")
    (RESULTS / "fig_data_scale.json").write_text(
        json.dumps({str(k): v for k, v in out.items()}))
    return out


def fig9_human_vs_llm(quick=False):
    """Paper Fig 9: the SAME model compresses LLM-generated text far better
    than human text, and the gap grows with chunk size."""
    from benchmarks.prep import human_dataset, llm_dataset, predictor
    from repro.data.synthetic import human_like_ood
    pred = predictor("pred-base")
    n = 3072 if quick else 6144
    gen = llm_dataset("web", n)
    hum = human_dataset("web", n, seed=5)          # in-training-distribution
    hum_ood = human_like_ood("web", n, seed=5)     # realistic (OOV mass)
    chunks = (16, 64) if quick else (16, 32, 64, 128)
    console("\n== fig9_human_vs_llm ==")
    t0 = time.time()
    out = {}
    for c in chunks:
        rg, _, _ = _ratio(pred, gen, chunk=c)
        rh, _, _ = _ratio(pred, hum, chunk=c)
        ro, _, _ = _ratio(pred, hum_ood, chunk=c)
        out[c] = {"llm_gen": round(rg, 3), "human_indist": round(rh, 3),
                  "human_ood": round(ro, 3),
                  "gap_indist": round(rg / rh, 3),
                  "gap_ood": round(rg / ro, 3)}
        console(f"chunk={c:4d} llm_gen={rg:.3f} human_indist={rh:.3f} "
              f"human_ood={ro:.3f} gap={rg/rh:.2f}/{rg/ro:.2f}x")
    _csv("fig9_human_vs_llm", (time.time() - t0) * 1e6 / len(chunks),
         ";".join(f"c{c}_gap={v['gap_indist']}/{v['gap_ood']}"
                  for c, v in out.items()))
    (RESULTS / "fig9_human_vs_llm.json").write_text(
        json.dumps({str(k): v for k, v in out.items()}))
    return out


def fig8_domain_models(quick=False):
    """Paper §5.7.2 / Fig 8: a domain-specialized predictor beats a similar-
    size general predictor on its own domain. The test corpus is NEUTRAL
    domain text (not generated by either competitor — the paper's datasets
    come from external GPT models)."""
    from benchmarks.prep import human_dataset, train_predictor
    from repro.serve.engine import ModelPredictor
    from repro.data.tokenizer import BOS_ID
    data = human_dataset("math", 3072 if quick else 6144, seed=41)
    console("\n== fig8_domain_models (math domain) ==")
    t0 = time.time()
    out = {}
    p_gen, cfg = train_predictor("pred-small")
    p_dom, cfg_d = train_predictor("pred-small", seed=3, domain_mix=("math",))
    for name, params, c in (("general-small", p_gen, cfg),
                            ("math-small", p_dom, cfg_d)):
        pred = ModelPredictor(params, c, bos_id=BOS_ID)
        r, _, _ = _ratio(pred, data)
        out[name] = round(r, 3)
        console(f"{name:14s} ratio={r:.3f}")
    _csv("fig8_domain_models", (time.time() - t0) * 1e6 / 2,
         f"general={out['general-small']};domain={out['math-small']}")
    (RESULTS / "fig8_domain_models.json").write_text(json.dumps(out))
    return out


def coder_throughput(quick=False):
    """Host entropy-coder + CDF-pipeline throughput (the system's
    TPU/host interface cost): reference AC vs. batched interleaved rANS
    at the production decode-batch size (see benchmarks/coder_bench.py
    for the full B-sweep)."""
    from repro.core import ac, rans
    from repro.core.cdf import pmf_to_cdf, quantize_pmf, topk_quantized_jit
    import jax.numpy as jnp
    rng = np.random.default_rng(0)
    n = 20_000 if quick else 60_000
    pmf = rng.dirichlet(np.ones(256) * 0.3)
    cdf = pmf_to_cdf(np.asarray(quantize_pmf(jnp.asarray(pmf), 16)))
    syms = rng.choice(256, n, p=pmf)
    t0 = time.time()
    enc = ac.ArithmeticEncoder()
    for s in syms:
        enc.encode(int(s), cdf)
    blob = enc.finish()
    t_enc = time.time() - t0
    t0 = time.time()
    dec = ac.ArithmeticDecoder(blob)
    out = [dec.decode(cdf) for _ in range(n)]
    t_dec = time.time() - t0
    assert out == list(syms)
    # batched rANS: same total token count spread over B=64 streams
    B = 64
    bsyms = syms[:n - n % B].reshape(B, -1)
    bcdf = np.broadcast_to(cdf, (B,) + cdf.shape)
    t0 = time.time()
    renc = rans.BatchedRansEncoder(B)
    for t in range(bsyms.shape[1]):
        renc.put_symbols(bsyms[:, t], bcdf, 16)
    rblobs = renc.finish()
    r_enc = time.time() - t0
    t0 = time.time()
    rdec = rans.BatchedRansDecoder(rblobs)
    rout = np.empty_like(bsyms)
    for t in range(bsyms.shape[1]):
        rout[:, t] = rdec.get(bcdf, 16)
    r_dec = time.time() - t0
    assert np.array_equal(rout, bsyms)
    rn = bsyms.size
    speedup = (rn / (r_enc + r_dec)) / (n / (t_enc + t_dec))
    lg = jnp.asarray(rng.normal(size=(64, 4096)).astype(np.float32))
    topk_quantized_jit(lg, 64, 16)  # warm
    t0 = time.time()
    for _ in range(20):
        topk_quantized_jit(lg, 64, 16)[0].block_until_ready()
    t_cdf = (time.time() - t0) / 20
    console("\n== coder_throughput ==")
    console(f"AC encode {n/t_enc/1e3:.0f} ksym/s | decode {n/t_dec/1e3:.0f} "
          f"ksym/s | rANS(B=64) encode {rn/r_enc/1e3:.0f} ksym/s | decode "
          f"{rn/r_dec/1e3:.0f} ksym/s ({speedup:.1f}x) | "
          f"topk-CDF (64x4096) {t_cdf*1e3:.2f} ms/call")
    _csv("coder_throughput", t_enc / n * 1e6,
         f"enc_ksym_s={n/t_enc/1e3:.0f};dec_ksym_s={n/t_dec/1e3:.0f};"
         f"rans_enc_ksym_s={rn/r_enc/1e3:.0f};"
         f"rans_dec_ksym_s={rn/r_dec/1e3:.0f};rans_speedup={speedup:.1f}")
    return {"enc_sym_s": n / t_enc, "dec_sym_s": n / t_dec,
            "rans_enc_sym_s": rn / r_enc, "rans_dec_sym_s": rn / r_dec}


def service_throughput(quick=False):
    """Continuous-batching service vs naive grouped decode on ragged jobs
    (chunk counts 1..2B) — the ROADMAP's many-concurrent-users shape.
    Full sweep + the >= 1.5x CI gate live in benchmarks/service_bench.py."""
    from benchmarks.service_bench import run_bench, run_mixed
    t0 = time.time()
    if quick:
        res = run_bench(n_jobs=12, slots=4, chunk=16)
        mixed = run_mixed(slots=4, chunk=16)
    else:
        res = run_bench()
        mixed = run_mixed()
    res.update(mixed)
    _csv("service_throughput", (time.time() - t0) * 1e6 / res["n_jobs"],
         f"jobs_per_s={res['service_jobs_per_s']:.2f};"
         f"wall_speedup={res['wall_speedup']:.2f};"
         f"step_speedup={res['step_speedup']:.2f};"
         f"occupancy={res['occupancy']:.2f}")
    (RESULTS / "service_throughput.json").write_text(json.dumps(res, indent=1))
    return res


def decompress_throughput(quick=False):
    """Speculative (draft/verify/accept) vs lock-step batched decode on
    argmax-following text — DESIGN.md §9's tentpole. The >= 2x wall and
    dispatch-ratio CI gates live in benchmarks/decompress_bench.py."""
    from benchmarks.decompress_bench import run_bench
    if quick:
        res = run_bench(n_jobs=2, tokens=1024, slots=4, dispatch_ms=0.5)
    else:
        res = run_bench()
    _csv("decompress_throughput",
         1e6 / max(1e-9, res["spec_tok_per_s"]),
         f"wall_speedup={res['wall_speedup']:.2f};"
         f"dispatch_ratio={res['dispatch_ratio']:.2f};"
         f"tok_per_s={res['spec_tok_per_s']:.0f}")
    (RESULTS / "decompress_throughput.json").write_text(
        json.dumps(res, indent=1))
    return res


def telemetry_overhead(quick=False):
    """DESIGN.md §10 + §13 gates: running the service decode bench with
    the metrics registry enabled must cost < 2% wall time over disabled,
    and with a timeline recorder installed <= 10% (telemetry is always
    byte-inert; this bounds its *time* cost too). benchmarks/run.py
    exits non-zero when either gate fails."""
    from benchmarks.service_bench import run_overhead
    t0 = time.time()
    if quick:
        res = run_overhead(n_jobs=12, slots=4, chunk=16, repeats=3)
    else:
        res = run_overhead()
    _csv("telemetry_overhead", (time.time() - t0) * 1e6,
         f"overhead_pct={res['overhead'] * 100:.2f};"
         f"timeline_pct={res['timeline_overhead'] * 100:.2f};"
         f"pass={res['gate_pass']}")
    (RESULTS / "telemetry_overhead.json").write_text(
        json.dumps(res, indent=1))
    return res


def router_routing(quick=False):
    """DESIGN.md §11 gate: adaptive per-chunk codec routing loses at
    most 2% to the better of pure-LLM / fallback-only on EVERY traffic
    segment, and beats both on mixed traffic (where neither strategy
    wins every chunk). All strategies measured as v5 containers, so
    index overhead cancels. Full table + CLI gate live in
    benchmarks/router_bench.py."""
    from benchmarks.router_bench import run_bench
    t0 = time.time()
    res = run_bench(seg_bytes=1024 if quick else 8192)
    console("\n== router_routing (v5 ratios per traffic segment) ==")
    for name, s in res["segments"].items():
        console(f"{name:16s} llm={s['llm']:.3f} fb={s['fallback']:.3f} "
              f"routed={s['routed']:.3f} "
              f"{'ok' if s['pass'] else 'FAIL'}")
    mixed = res["segments"]["mixed_traffic"]
    _csv("router_routing", (time.time() - t0) * 1e6 / len(res["segments"]),
         f"mixed_routed={mixed['routed']};mixed_llm={mixed['llm']};"
         f"mixed_fb={mixed['fallback']};pass={res['gate_pass']}")
    (RESULTS / "router_routing.json").write_text(json.dumps(res, indent=1))
    return res


def context_ratio(quick=False):
    """DESIGN.md §12 gates: carried-context v6 archives must beat
    context-free chunking by >= 1.10x on the order-K corpus, and the
    radix prefix cache must cut shared-prefix prefill lane-steps by
    >= 1.3x with byte-identical output. Full sweep + CLI gate live in
    benchmarks/context_bench.py."""
    from benchmarks.context_bench import run_prefill_bench, run_ratio_bench
    t0 = time.time()
    if quick:
        ratio = run_ratio_bench(n_tokens=512)
        prefill = run_prefill_bench(n_jobs=6, prefix_len=48)
    else:
        ratio = run_ratio_bench()
        prefill = run_prefill_bench()
    res = {"ratio": ratio, "prefill": prefill,
           "gate_pass": ratio["gate_pass"] and prefill["gate_pass"]}
    console("\n== context_ratio (carried v6 vs context-free; prefix cache) ==")
    console(f"carried gain {ratio['ratio_gain']:.3f}x "
          f"(floor {ratio['ratio_floor']}x) | prefill savings "
          f"{prefill['prefill_savings']:.2f}x "
          f"(floor {prefill['prefill_floor']}x, "
          f"{prefill['cache_hits']} hits)")
    _csv("context_ratio", (time.time() - t0) * 1e6,
         f"gain={ratio['ratio_gain']:.3f};"
         f"prefill_savings={prefill['prefill_savings']:.2f};"
         f"cache_hits={prefill['cache_hits']};pass={res['gate_pass']}")
    (RESULTS / "context_ratio.json").write_text(json.dumps(res, indent=1))
    return res


ALL = [table2_information, table3_traditional, table5_main, fig_chunk_size,
       fig_model_size, fig_data_scale, fig9_human_vs_llm, fig8_domain_models,
       coder_throughput, service_throughput, decompress_throughput,
       telemetry_overhead, router_routing, context_ratio]


def main() -> None:
    from repro import obs
    from repro.compile_cache import configure_compile_cache
    from repro.obs.bench_history import BenchHistory, BenchRecord
    configure_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None)
    ap.add_argument("--history", default=str(RESULTS / "history.jsonl"),
                    help="bench-trajectory JSONL this run appends to")
    args = ap.parse_args()
    RESULTS.mkdir(parents=True, exist_ok=True)
    hist = BenchHistory(args.history)
    t0 = time.time()
    gate_failures = []
    for fn in ALL:
        if args.only and args.only not in fn.__name__:
            continue
        # each bench runs against a fresh process-global registry; its
        # compact snapshot (compressor/rans/draft counters, span-derived
        # phase breakdown) rides the bench's history record
        reg = obs.MetricsRegistry(name=fn.__name__)
        prev = obs.set_registry(reg)
        n_before = len(ROWS)
        try:
            out = fn(quick=args.quick)
        finally:
            obs.set_registry(prev)
        for name, us, derived in ROWS[n_before:]:
            hist.append(BenchRecord.build(name, us, derived, registry=reg,
                                          quick=args.quick))
        if isinstance(out, dict) and out.get("gate_pass") is False:
            gate_failures.append(fn.__name__)
    console(f"\n# total {time.time()-t0:.0f}s")
    console("\n# rows appended to " + str(hist.path))
    for name, us, derived in ROWS:
        console(f"{name},{us:.1f},{derived}")
    if gate_failures:
        console(f"FAIL: benchmark gate(s): {', '.join(gate_failures)}",
                err=True)
        sys.exit(1)


if __name__ == "__main__":
    main()
