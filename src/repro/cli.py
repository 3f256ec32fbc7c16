"""``llmc`` — command-line front end for the LLM compression system.

    llmc compress   IN OUT [--codec rans|ac] [--chunk N] [--topk K]
                           [--slots B] [--predictor NAME] [--v3]
                           [--route auto|llm|zstd|lzma|raw] [--sidecar]
                           [--context-window W] [--shared-prefix FILE]
                           [--trace OUT.json]
    llmc decompress IN OUT [--predictor NAME] [--sidecar]
    llmc range      IN OUT --chunks LO:HI [--predictor NAME]
    llmc info       IN
    llmc stats      [--tokens N] [--format json|prom|text]
                    [--predictor NAME]

``compress``/``decompress`` route through the continuous-batching
service (repro.service) and write/read v4 seekable containers by
default; ``--route auto`` turns on adaptive per-chunk codec routing
(DESIGN.md §11) and writes a v5 mixed-codec container whose index
records each chunk's codec tag — decode follows the recorded tags, it
never guesses. ``--context-window``/``--shared-prefix`` write a v6
container whose chunks are coded under declared context recipes
(DESIGN.md §12) — the ratio lever of the paper's long-context regime.
``range`` random-access-decodes a chunk interval from a v4+ archive
(mixed-codec v5 and carried-context v6 included); ``info`` prints
header + index (for v5 the per-chunk codec tags, for v6 also the
context recipes and shared-prefix dictionary) without loading any
model. All-fallback archives decompress and range-decode model-free:
no predictor is ever constructed.

``stats`` (DESIGN.md §10) runs a small round-trip workload through a
``CompressionService`` and prints its telemetry snapshot — occupancy,
bits/token histogram, escape counts, job counters — as JSON (default),
Prometheus text exposition (``--format prom``), or a human summary
(``--format text``). ``--sidecar`` on compress/decompress writes the
job's per-chunk diagnostics next to the container as
``<container>.diag.json``.

Predictors come from the benchmark prep cache (trained byte-level LMs,
benchmarks/prep.py), so the model-dependent commands must run from a
repo checkout; ``info`` works anywhere. Registered as a console script
in pyproject.toml (``pip install -e . && llmc info archive.llmc``).
"""
from __future__ import annotations

import argparse
import sys
import time


def _predictor(name: str):
    sys.path[:0] = ["src", "."]
    try:
        from benchmarks.prep import predictor
    except ImportError as e:
        raise SystemExit(
            f"llmc: cannot load predictor {name!r} ({e}); the model-"
            f"dependent commands need a repo checkout with benchmarks/"
        )
    return predictor(name)


def _cmd_info(args) -> int:
    from repro.core import read_header, read_index
    from repro.core.compressor import VERSION_V4, VERSION_V5, VERSION_V6
    blob = open(args.input, "rb").read()
    info = read_header(blob)
    print(f"{args.input}: LLMC v{info.version} codec={info.codec_name} "
          f"chunk_size={info.chunk_size} n_tokens={info.n_tokens} "
          f"n_chunks={info.n_chunks} vocab={info.vocab} topk={info.topk} "
          f"precision={info.precision} ({len(blob)} bytes)")
    if info.version >= VERSION_V4:
        info = read_index(blob, info)
        tagged = info.version >= VERSION_V5
        ctxed = info.version >= VERSION_V6
        cols = "offset, bytes, tokens, xxh64" + (", codec" if tagged else "") \
            + (", context" if ctxed else "")
        budget = f" ctx_budget={info.ctx_budget};" if ctxed else ""
        print(f"index: footer verified; encode_batch={info.encode_batch};"
              f"{budget} per-chunk ({cols}):")
        for i, e in enumerate(info.entries):
            tag = f"  {e.codec_name}" if tagged else ""
            rec = f"  {e.recipe_name}" if ctxed else ""
            print(f"  chunk {i:4d}: {e.offset:8d} {e.length:6d} "
                  f"{e.n_tokens:5d} {e.checksum:016x}{tag}{rec}")
        if tagged:
            counts = {}
            for e in info.entries:
                counts[e.codec_name] = counts.get(e.codec_name, 0) + 1
            mix = "  ".join(f"{n}×{c}" for c, n in sorted(counts.items()))
            print(f"codecs: {mix}" if mix else "codecs: (empty)")
        if ctxed:
            rcounts = {}
            for e in info.entries:
                name = e.recipe_name.split("(")[0].split("[")[0]
                rcounts[name] = rcounts.get(name, 0) + 1
            mix = "  ".join(f"{n}×{r}" for r, n in sorted(rcounts.items()))
            print(f"contexts: {mix}" if mix else "contexts: (empty)")
            if info.shared_prefixes:
                for j, (name, toks) in enumerate(info.shared_prefixes):
                    print(f"shared prefix [{j}]: {name!r} "
                          f"({len(toks)} tokens)")
            else:
                print("shared prefixes: none")
    else:
        print("index: none (v2/v3 container — no random access)")
    return 0


def _service(args, pred):
    from repro.core.cdf import DEFAULT_PRECISION
    from repro.service import CompressionService
    return CompressionService(pred, slots=args.slots, chunk_size=args.chunk,
                              topk=args.topk,
                              precision=getattr(args, "precision",
                                                DEFAULT_PRECISION),
                              route=getattr(args, "route", "llm"),
                              trace=getattr(args, "trace", None) or None)


def _print_phases(rep) -> None:
    """One-line per-job phase breakdown (DESIGN.md §13)."""
    if rep is None:
        return
    parts = "  ".join(f"{k}={v * 1e3:.1f}ms"
                      for k, v in sorted(rep.phases.items()) if v > 0)
    print(f"phases ({rep.total_s * 1e3:.0f}ms wall, coverage "
          f"{rep.coverage:.0%}): {parts}")


def _cmd_compress(args) -> int:
    from repro.core import LLMCompressor
    from repro.data.tokenizer import encode
    args.slots = args.slots or 16
    pred = _predictor(args.predictor)
    data = open(args.input, "rb").read()
    toks = encode(data)
    sp = None
    if args.shared_prefix:
        sp = encode(open(args.shared_prefix, "rb").read())
    t0 = time.time()
    handle = None
    svc = None
    rec = None
    if args.codec == "ac" or args.v3:
        if args.route != "llm":
            # routing needs v5 codec tags; v3 can't carry them and the
            # ac estimator path never routes — fail with a clear message
            raise SystemExit("llmc: --route requires the default service "
                             "path (rans codec, no --v3)")
        if args.context_window or sp is not None:
            raise SystemExit("llmc: context options need the default "
                             "service path (rans codec, no --v3) — they "
                             "write a v6 container")
        # legacy codec / wire-minimal container: grouped path
        from repro import obs
        if args.trace:
            rec = obs.TimelineRecorder()
            obs.timeline.install(rec)
        comp = LLMCompressor(pred, chunk_size=args.chunk, topk=args.topk,
                             decode_batch=args.slots, codec=args.codec,
                             container_version=3 if args.v3 else 4)
        try:
            blob, stats = comp.compress(toks)
        finally:
            if rec is not None and obs.timeline.active() is rec:
                obs.timeline.uninstall()
    else:
        svc = _service(args, pred)
        handle = svc.submit_compress(
            toks, shared_prefix=sp, context_window=args.context_window)
        blob, stats = handle.result()
    open(args.output, "wb").write(blob)
    if args.trace:
        from repro import obs
        if svc is not None:
            rep = handle.phase_report()
            path = svc.write_timeline()
            svc.close()
        else:
            rec.save(args.trace)
            path = args.trace
            rep = obs.PhaseReport.from_recorder(rec)
        print(f"timeline -> {path} (Chrome-trace JSON; load in "
              f"chrome://tracing or ui.perfetto.dev)")
        _print_phases(rep)
    if args.sidecar:
        from repro import obs
        if handle is not None:
            path = handle.write_sidecar(args.output)
        else:   # grouped path: per-chunk diagnostics ride on stats.chunks
            path = obs.write_sidecar(args.output, obs.JobDiagnostics(
                kind="compress", codec=args.codec, n_tokens=stats.n_tokens,
                container_bytes=len(blob), chunks=stats.chunks))
        print(f"diagnostics -> {path}")
    print(f"{len(data)}B -> {len(blob)}B "
          f"({len(data) / max(1, len(blob)):.2f}x, "
          f"{stats.n_tokens} tokens, {time.time() - t0:.1f}s)")
    return 0


def _cmd_decompress(args) -> int:
    from repro.core import (LLMCompressor, container_is_model_free,
                            decompress_model_free, read_header)
    from repro.data.tokenizer import decode
    blob = open(args.input, "rb").read()
    info = read_header(blob)        # fail fast + learn the geometry
    if info.version >= 4:
        from repro.core import read_index
        info = read_index(blob, info)
        if container_is_model_free(info):
            # every chunk is fallback-coded: decode without constructing
            # a predictor (no model load, no prefix cache, no service)
            t0 = time.time()
            toks = decompress_model_free(blob)
            open(args.output, "wb").write(decode(toks))
            print(f"{len(blob)}B -> decoded {toks.size} tokens "
                  f"(model-free, {time.time() - t0:.1f}s)")
            return 0
    pred = _predictor(args.predictor)
    args.chunk, args.topk = info.chunk_size, info.topk
    args.precision = info.precision
    args.slots = args.slots or info.encode_batch or 16
    t0 = time.time()
    handle = None
    if info.codec_name == "ac":
        # legacy codec: the service is rANS-only (and its rANS precision
        # cap would reject legal high-precision AC archives) — grouped
        # decode directly, same result
        comp = LLMCompressor(pred, chunk_size=args.chunk, topk=args.topk,
                             precision=args.precision, codec="ac",
                             decode_batch=args.slots)
        toks = comp.decompress(blob)
    elif args.draft:
        # speculative grouped decode: draft/verify/accept (DESIGN.md §9),
        # identical tokens, fewer model dispatches on predictable text
        comp = LLMCompressor(pred, chunk_size=args.chunk, topk=args.topk,
                             precision=args.precision,
                             decode_batch=args.slots, draft_k=args.draft)
        toks = comp.decompress(blob)
    else:
        handle = _service(args, pred).submit_decompress(blob)
        toks = handle.result()
    if args.sidecar:
        if handle is not None:
            print(f"diagnostics -> {handle.write_sidecar(args.input)}")
        else:
            print("llmc: note: --sidecar needs the service decode path "
                  "(rans codec, no --draft); skipped", file=sys.stderr)
    open(args.output, "wb").write(decode(toks))
    print(f"{len(blob)}B -> decoded {toks.size} tokens "
          f"({time.time() - t0:.1f}s)")
    return 0


def _cmd_range(args) -> int:
    from repro.core import (ContainerError, LLMCompressor,
                            decompress_range_model_free, read_index)
    from repro.data.tokenizer import decode
    blob = open(args.input, "rb").read()
    info = read_index(blob)
    try:
        lo, hi = (int(x) for x in args.chunks.split(":"))
    except ValueError:
        raise SystemExit(f"llmc: --chunks expects LO:HI integers, "
                         f"got {args.chunks!r}")
    if 0 <= lo < hi <= len(info.entries) \
            and all(not e.is_llm for e in info.entries[lo:hi]):
        # every requested chunk is fallback-coded (recipes are none by
        # format law), so the range decodes without a model
        t0 = time.time()
        try:
            toks = decompress_range_model_free(blob, lo, hi)
        except ContainerError as e:
            raise SystemExit(f"llmc: {e}")
        open(args.output, "wb").write(decode(toks))
        print(f"chunks [{lo}, {hi}) -> {toks.size} tokens "
              f"(model-free, {time.time() - t0:.1f}s)")
        return 0
    if args.slots and info.encode_batch and args.slots != info.encode_batch:
        print(f"llmc: note: range decode runs at the container's recorded "
              f"encode batch ({info.encode_batch}); --slots {args.slots} "
              f"ignored", file=sys.stderr)
    pred = _predictor(args.predictor)
    comp = LLMCompressor(pred, chunk_size=info.chunk_size, topk=info.topk,
                         precision=info.precision,
                         decode_batch=args.slots or info.encode_batch or 16)
    t0 = time.time()
    try:
        toks = comp.decompress_range(blob, lo, hi)
    except ContainerError as e:
        # empty/reversed/out-of-bounds ranges and corrupt containers all
        # arrive here with a precise message — never a bare IndexError
        raise SystemExit(f"llmc: {e}")
    open(args.output, "wb").write(decode(toks))
    print(f"chunks [{lo}, {hi}) -> {toks.size} tokens "
          f"({time.time() - t0:.1f}s)")
    return 0


def _cmd_stats(args) -> int:
    """Exercise a CompressionService on a small round-trip workload and
    print its telemetry snapshot (DESIGN.md §10)."""
    import numpy as np
    pred = _predictor(args.predictor)
    args.chunk = args.chunk or 64
    args.topk = args.topk or 0
    args.slots = args.slots or 8
    svc = _service(args, pred)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, max(2, pred.vocab_size - 1), args.tokens,
                        dtype=np.int32)
    blob, _ = svc.submit_compress(toks).result()
    rt = svc.submit_decompress(blob).result()
    if not np.array_equal(rt, toks):
        raise SystemExit("llmc: stats round-trip mismatch (BUG)")
    snap = svc.snapshot()
    if args.format == "prom":
        sys.stdout.write(svc.registry.to_prometheus())
    elif args.format == "text":
        sched = snap["scheduler"]
        bpt = snap["chunk_bits_per_token"] or {}
        print(f"workload: {args.tokens} tokens round-tripped "
              f"({len(blob)} container bytes)")
        print(f"occupancy {snap['occupancy']:.3f}  model_steps "
              f"{sched['model_steps']}  chunks {sched['chunks_completed']}"
              f"  refills {sched['refills']}  failures "
              f"{sched['chunk_failures']}")
        if bpt:
            print(f"bits/token: mean {bpt['mean']:.2f}  p50 {bpt['p50']:g}"
                  f"  p95 {bpt['p95']:g}  p99 {bpt['p99']:g}  "
                  f"({bpt['count']} chunks)")
        acc = snap["draft_acceptance"]
        print(f"draft acceptance: "
              f"{'n/a (no speculative decode)' if acc is None else acc}")
        print(f"jobs: {snap['jobs']}")
        phases = {k: v for k, v in (snap.get("phases") or {}).items()
                  if v > 0}
        if phases:
            print("phase seconds: " + "  ".join(
                f"{k}={v:.3f}" for k, v in sorted(phases.items())))
    else:
        import json
        print(json.dumps(snap, indent=1, default=str))
    return 0


def main(argv=None) -> int:
    from repro.compile_cache import configure_compile_cache
    configure_compile_cache()
    ap = argparse.ArgumentParser(
        prog="llmc", description="LLM next-token-prediction compressor")
    sub = ap.add_subparsers(dest="cmd", required=True)

    def common(p, model=True):
        p.add_argument("input")
        if p.prog.split()[-1] != "info":
            p.add_argument("output")
        if model:
            p.add_argument("--predictor", default="pred-base")
            # default: 16 for compress; for decompress/range, the v4
            # container's recorded encode batch (bit-exactness needs the
            # decoder to run the model program at the encoder's batch)
            p.add_argument("--slots", type=int, default=None)

    p = sub.add_parser("compress", help="file -> .llmc container")
    common(p)
    p.add_argument("--codec", choices=("rans", "ac"), default="rans")
    p.add_argument("--chunk", type=int, default=128)
    p.add_argument("--topk", type=int, default=48)
    p.add_argument("--v3", action="store_true",
                   help="write the wire-minimal v3 container "
                        "(no index/checksums)")
    p.add_argument("--route", choices=("llm", "auto", "zstd", "lzma", "raw"),
                   default="llm",
                   help="per-chunk codec routing (DESIGN.md §11): 'auto' "
                        "probes model fit per chunk and writes a v5 "
                        "mixed-codec container; a codec name forces that "
                        "fallback for every chunk; 'llm' (default) keeps "
                        "the pure entropy-coded v4 path")
    p.add_argument("--sidecar", action="store_true",
                   help="write per-chunk diagnostics (bits/token, "
                        "escapes) to OUT.diag.json")
    p.add_argument("--context-window", type=int, default=0, metavar="W",
                   help="carry each chunk's W-token tail into the next "
                        "chunk of its stripe (writes a v6 container with "
                        "per-chunk context recipes, DESIGN.md §12)")
    p.add_argument("--shared-prefix", default="", metavar="FILE",
                   help="condition stripe-head chunks on FILE's tokens "
                        "as a named shared prefix (v6; jobs sharing the "
                        "prefix reuse one prefilled KV state)")
    p.add_argument("--trace", default="", metavar="OUT.json",
                   help="record a span timeline of the run and export it "
                        "as Chrome-trace JSON (chrome://tracing / "
                        "ui.perfetto.dev), plus a per-job phase cost "
                        "breakdown (DESIGN.md §13)")
    p.set_defaults(fn=_cmd_compress)

    p = sub.add_parser("decompress", help=".llmc container -> file")
    common(p)
    p.add_argument("--draft", type=int, default=0, metavar="K",
                   help="speculative decode: self-draft K tokens per "
                        "verify forward (0 = lock-step)")
    p.add_argument("--sidecar", action="store_true",
                   help="write per-chunk diagnostics to IN.diag.json")
    p.set_defaults(fn=_cmd_decompress)

    p = sub.add_parser("range", help="random-access decode (v4+ seekable "
                                     "containers, mixed-codec v5 included)")
    common(p)
    p.add_argument("--chunks", required=True, metavar="LO:HI")
    p.set_defaults(fn=_cmd_range)

    p = sub.add_parser("info", help="print header + index (no model)")
    common(p, model=False)
    p.set_defaults(fn=_cmd_info)

    p = sub.add_parser(
        "stats", help="run a sample workload, print service telemetry")
    p.add_argument("--predictor", default="pred-base")
    p.add_argument("--tokens", type=int, default=2048,
                   help="workload size in tokens (default 2048)")
    p.add_argument("--chunk", type=int, default=64)
    p.add_argument("--topk", type=int, default=0)
    p.add_argument("--slots", type=int, default=8)
    p.add_argument("--format", choices=("json", "prom", "text"),
                   default="json",
                   help="snapshot format: structured JSON (default), "
                        "Prometheus text exposition, or human summary")
    p.set_defaults(fn=_cmd_stats)

    args = ap.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
