"""Continuous-batching service benchmark: jobs/sec on ragged traffic.

Compares the slot scheduler (repro.service) against the naive grouped
decode path (LLMCompressor.decompress per job) on a RAGGED workload —
jobs whose chunk counts span 1..2B, with partial final chunks. The
grouped path runs every group to its longest member and leaves lanes
empty in each job's final group; the scheduler refills finished slots
from the queue on the next step, so its model-step count approaches
total_tokens / B.

Asserted metric: **jobs/sec** (the ISSUE's throughput criterion) —
measured margin is ~5-10x, far above the 1.5x floor, so CI timing noise
cannot flip it. The deterministic model-step speedup is reported
alongside; on a uniform 1..2B-chunk workload its structural ceiling is
E[ceil(k/B)]*B/E[k] ~= 1.4x (occupancy 0.99 vs ~0.70), and it
*understates* the service's edge: the grouped path additionally pays a
jit recompile per distinct group shape with a real model, which the
model-free table predictor here does not charge it for. Exits non-zero
below the floor, so CI regresses loudly (same convention as
coder_bench.py).

  PYTHONPATH=src python benchmarks/service_bench.py [--smoke]
"""
from __future__ import annotations

import argparse
import sys
import time

import numpy as np

sys.path[:0] = ["src", "."]

from repro.obs import console  # noqa: E402

SPEEDUP_FLOOR = 1.5
OVERHEAD_LIMIT = 0.02          # telemetry-enabled slowdown budget (§10)
TIMELINE_LIMIT = 0.10          # timeline-recording slowdown budget (§13)
OVERHEAD_ABS_SLACK_S = 0.010   # absolute per-leg jitter allowance


class TablePredictor:
    """Deterministic model-free predictor (next-token logits from a fixed
    (V, V) table) with a decode_step counter — isolates scheduling from
    model cost, and the step counter is the dispatch count a real
    accelerator would pay."""

    def __init__(self, vocab_size=64, seed=0):
        self.vocab_size = int(vocab_size)
        self.bos_id = self.vocab_size - 1
        rng = np.random.default_rng(seed)
        self._table = (rng.standard_normal(
            (self.vocab_size, self.vocab_size)) * 2.0).astype(np.float32)
        self.n_steps = 0

    def score_chunks(self, tokens):
        tokens = np.asarray(tokens, np.int32)
        prev = np.concatenate(
            [np.full((tokens.shape[0], 1), self.bos_id, np.int32),
             tokens[:, :-1]], axis=1)
        return self._table[prev]

    def begin_decode(self, batch):
        return None

    def decode_step(self, state, prev_tokens):
        self.n_steps += 1
        return self._table[np.asarray(prev_tokens, np.int32)], state

    # speculative decode hooks (decompress_bench.py): one verify forward
    # scores all K+1 positions; counts as ONE model dispatch, which is
    # exactly the economy speculation buys on a real accelerator
    def verify_steps(self, state, seq):
        self.n_steps += 1
        return self._table[np.asarray(seq, np.int32)], state

    def rollback(self, snapshots, accepted):
        return snapshots


def ragged_workload(rng, n_jobs: int, slots: int, chunk: int):
    """Job sizes spanning 1 token .. 2B chunks (the ISSUE's acceptance
    workload): every job ends in a partial chunk with high probability."""
    sizes = [1 + int(rng.integers(0, 2 * slots * chunk))
             for _ in range(n_jobs)]
    return [rng.integers(0, 60, n).astype(np.int32) for n in sizes]


def run_bench(n_jobs=24, slots=8, chunk=32, topk=8, seed=0, log=console):
    from repro.core import LLMCompressor
    from repro.service import CompressionService

    rng = np.random.default_rng(seed)
    datas = ragged_workload(rng, n_jobs, slots, chunk)
    total_tokens = sum(d.size for d in datas)
    total_chunks = sum(max(1, -(-d.size // chunk)) for d in datas)

    pred = TablePredictor()
    comp = LLMCompressor(pred, chunk_size=chunk, topk=topk,
                         decode_batch=slots, container_version=4)
    blobs = [comp.compress(d)[0] for d in datas]

    # ---- naive: one grouped decompress per job, sequentially
    pred.n_steps = 0
    t0 = time.time()
    for b, d in zip(blobs, datas):
        out = comp.decompress(b)
        assert np.array_equal(out, d), "LOSSLESS VIOLATION (grouped)"
    naive_dt = time.time() - t0
    naive_steps = pred.n_steps

    # ---- service: all jobs share one slot machine
    svc = CompressionService(pred, slots=slots, chunk_size=chunk, topk=topk)
    pred.n_steps = 0
    t0 = time.time()
    handles = [svc.submit_decompress(b) for b in blobs]
    for h, d in zip(handles, datas):
        assert np.array_equal(h.result(), d), "LOSSLESS VIOLATION (service)"
    svc_dt = time.time() - t0
    svc_steps = pred.n_steps
    assert svc_steps == svc.stats.model_steps

    step_speedup = naive_steps / max(1, svc_steps)
    wall_speedup = naive_dt / max(1e-9, svc_dt)
    log(f"workload: {n_jobs} jobs, {total_chunks} chunks, "
        f"{total_tokens} tokens, B={slots}, C={chunk}")
    log(f"naive grouped : {naive_steps:6d} model steps  "
        f"{n_jobs / naive_dt:7.2f} jobs/s  ({naive_dt:.2f}s)")
    log(f"slot scheduler: {svc_steps:6d} model steps  "
        f"{n_jobs / svc_dt:7.2f} jobs/s  ({svc_dt:.2f}s)  "
        f"occupancy {svc.stats.occupancy:.2f}")
    log(f"step speedup {step_speedup:.2f}x | wall speedup {wall_speedup:.2f}x")
    return {
        "n_jobs": n_jobs, "slots": slots, "chunk": chunk,
        "naive_steps": naive_steps, "service_steps": svc_steps,
        "naive_jobs_per_s": n_jobs / naive_dt,
        "service_jobs_per_s": n_jobs / svc_dt,
        "step_speedup": step_speedup, "wall_speedup": wall_speedup,
        "occupancy": svc.stats.occupancy,
    }


def run_mixed(slots=8, chunk=32, topk=8, seed=1, log=console):
    """Mixed-direction traffic demo: compress and decompress jobs share
    the same batch; verified lossless. Reported, not asserted — the
    speedup claim is the decode comparison above."""
    from repro.core import LLMCompressor
    from repro.service import CompressionService

    rng = np.random.default_rng(seed)
    datas = ragged_workload(rng, 10, slots, chunk)
    pred = TablePredictor()
    comp = LLMCompressor(pred, chunk_size=chunk, topk=topk,
                         decode_batch=slots, container_version=4)
    blobs = [comp.compress(d)[0] for d in datas[:5]]
    svc = CompressionService(pred, slots=slots, chunk_size=chunk, topk=topk)
    t0 = time.time()
    hc = [svc.submit_compress(d) for d in datas[5:]]
    hd = [svc.submit_decompress(b) for b in blobs]
    for h, d in zip(hd, datas[:5]):
        assert np.array_equal(h.result(), d)
    for h, d in zip(hc, datas[5:]):
        blob, _ = h.result()
        assert np.array_equal(comp.decompress(blob), d)
    dt = time.time() - t0
    log(f"mixed traffic : 5 compress + 5 decompress jobs in {dt:.2f}s, "
        f"{svc.stats.model_steps} steps, occupancy "
        f"{svc.stats.occupancy:.2f}")
    return {"mixed_steps": svc.stats.model_steps,
            "mixed_occupancy": svc.stats.occupancy}


def run_overhead(n_jobs=24, slots=8, chunk=32, topk=8, repeats=5, seed=0,
                 log=console):
    """Telemetry-overhead gate (DESIGN.md §10): the same ragged decode
    workload through two services — registry enabled vs disabled —
    interleaved, min-of-repeats (min is the noise-robust estimator for a
    deterministic workload) — plus a third leg with a timeline recorder
    installed (DESIGN.md §13: one event-ring write per span; the per-step
    spans themselves run in the enabled leg too). Decoded tokens are
    compared against the originals every repeat on all legs: telemetry
    must never change output bytes.
    Budgets: enabled <= disabled * (1 + 2%) + 10ms absolute slack;
    recording <= *enabled* * (1 + 10%) + the same slack — the recorder
    requires the registry, so its budget bounds the marginal cost of
    the timeline on top of telemetry (the budgets compose: disabled ->
    recording is bounded by both chained together). The timeline leg is
    judged on the MEDIAN of per-round recording/enabled ratios: adjacent
    legs share one drift regime, so the ratio cancels the low-frequency
    CPU noise that min-of-repeats cannot (each min may come from a
    different regime). Override with $REPRO_TELEMETRY_OVERHEAD_MAX /
    $REPRO_TIMELINE_OVERHEAD_MAX."""
    import os
    import statistics

    from repro import obs
    from repro.core import LLMCompressor
    from repro.service import CompressionService

    rng = np.random.default_rng(seed)
    datas = ragged_workload(rng, n_jobs, slots, chunk)
    pred = TablePredictor()
    comp = LLMCompressor(pred, chunk_size=chunk, topk=topk,
                         decode_batch=slots, container_version=4)
    blobs = [comp.compress(d)[0] for d in datas]

    def leg(enabled, record=False):
        svc = CompressionService(
            pred, slots=slots, chunk_size=chunk, topk=topk,
            trace=obs.TimelineRecorder() if record else None)
        svc.registry.enabled = enabled
        t0 = time.perf_counter()
        handles = [svc.submit_decompress(b) for b in blobs]
        outs = [h.result() for h in handles]
        dt = time.perf_counter() - t0
        if record:
            svc.close()             # uninstall the recorder before the
        for o, d in zip(outs, datas):    # next (untraced) leg runs
            assert np.array_equal(o, d), \
                f"LOSSLESS VIOLATION (telemetry enabled={enabled})"
        return dt

    inf = float("inf")
    best = {"disabled": inf, "enabled": inf, "recording": inf}
    ratios = []
    leg(True)                       # warm all paths outside the clocks
    leg(False)
    leg(True, record=True)
    for _ in range(repeats):        # interleaved: drift-fair
        best["disabled"] = min(best["disabled"], leg(False))
        t_ena = leg(True)
        t_rec = leg(True, record=True)
        best["enabled"] = min(best["enabled"], t_ena)
        best["recording"] = min(best["recording"], t_rec)
        ratios.append(t_rec / max(1e-9, t_ena))
    limit = float(os.environ.get("REPRO_TELEMETRY_OVERHEAD_MAX",
                                 OVERHEAD_LIMIT))
    tl_limit = float(os.environ.get("REPRO_TIMELINE_OVERHEAD_MAX",
                                    TIMELINE_LIMIT))
    overhead = best["enabled"] / max(1e-9, best["disabled"]) - 1.0
    tl_overhead = statistics.median(ratios) - 1.0
    ok = best["enabled"] <= best["disabled"] * (1.0 + limit) \
        + OVERHEAD_ABS_SLACK_S
    tl_ok = tl_overhead <= tl_limit \
        + OVERHEAD_ABS_SLACK_S / max(1e-9, best["enabled"])
    log(f"telemetry overhead: enabled {best['enabled'] * 1e3:.1f}ms vs "
        f"disabled {best['disabled'] * 1e3:.1f}ms -> {overhead * 100:+.2f}% "
        f"(budget {limit * 100:.0f}%) {'PASS' if ok else 'FAIL'}")
    log(f"timeline overhead: recording {best['recording'] * 1e3:.1f}ms vs "
        f"enabled {best['enabled'] * 1e3:.1f}ms, median round ratio "
        f"{tl_overhead * 100:+.2f}% (budget {tl_limit * 100:.0f}%) "
        f"{'PASS' if tl_ok else 'FAIL'}")
    return {"enabled_s": best["enabled"], "disabled_s": best["disabled"],
            "recording_s": best["recording"],
            "overhead": overhead, "limit": limit,
            "timeline_overhead": tl_overhead, "timeline_limit": tl_limit,
            "repeats": repeats,
            "n_jobs": n_jobs, "slots": slots, "chunk": chunk,
            "gate_pass": ok and tl_ok}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small workload for the CI fast job")
    ap.add_argument("--overhead", action="store_true",
                    help="also run the telemetry-overhead gate")
    args = ap.parse_args()
    if args.smoke:
        res = run_bench(n_jobs=16, slots=4, chunk=16)
    else:
        res = run_bench()
    run_mixed(slots=4 if args.smoke else 8,
              chunk=16 if args.smoke else 32)
    console(f"service_throughput,"
            f"{1e6 / max(1e-9, res['service_jobs_per_s']):.1f},"
            f"step_speedup={res['step_speedup']:.2f};"
            f"occupancy={res['occupancy']:.2f};"
            f"jobs_per_s={res['service_jobs_per_s']:.2f}")
    if res["wall_speedup"] < SPEEDUP_FLOOR:
        console(f"FAIL: jobs/sec speedup {res['wall_speedup']:.2f}x < "
                f"{SPEEDUP_FLOOR}x on ragged workload", err=True)
        return 1
    console(f"PASS: jobs/sec speedup {res['wall_speedup']:.2f}x >= "
            f"{SPEEDUP_FLOOR}x (model steps: {res['step_speedup']:.2f}x, "
            f"occupancy {res['occupancy']:.2f})")
    if args.overhead:
        if args.smoke:
            ores = run_overhead(n_jobs=12, slots=4, chunk=16, repeats=3)
        else:
            ores = run_overhead()
        if not ores["gate_pass"]:
            console(f"FAIL: telemetry overhead {ores['overhead'] * 100:.2f}%"
                    f" (budget {ores['limit'] * 100:.0f}%) / timeline "
                    f"{ores['timeline_overhead'] * 100:.2f}% (budget "
                    f"{ores['timeline_limit'] * 100:.0f}%)", err=True)
            return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
