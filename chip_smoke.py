"""Smoke run of the compression system on one TPU chip.

    python chip_smoke.py

Drives the main path once through the entry points a user calls, and
fails unless every phase round-trips byte for byte:

* Phase A — ``CompressionService`` at the full published width of
  Qwen3-1.7B (``configs/qwen3_1_7b.CONFIG``) with seeded random weights:
  the model generates a few hundred tokens per lane (the paper's setting,
  a model coding its own text), 16 slots compress them as one job per lane
  with top-48 CDFs, and the same service decompresses them.
* Phase B — the ``llmc`` CLI (``repro.cli.main``) compresses and
  decompresses a seeded synthetic text. Its predictor, pred-base, is
  trained in this run into an emptied directory.

The numbers it prints describe this smoke run; they are not benchmark
metrics. Artifacts go to ``results/chip_smoke/``. Off a TPU it exits
non-zero before running anything; on success the last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import json
import pathlib
import shutil
import sys
import time

REPO = pathlib.Path(__file__).resolve().parent
OUT = REPO / "results" / "chip_smoke"

SEED = 0
LANES = 16           # generated streams, one compress job each
GEN_TOKENS = 256     # tokens per lane
SAMPLE_TOP_K = 40    # sample inside the coder's top-48 slots
SLOTS, CHUNK, TOPK = 16, 128, 48
CLI_BYTES = 4096


def device_check():
    """Print the toolchain and devices; exit non-zero unless JAX's first
    device is a TPU."""
    from importlib import metadata

    import jax
    import jaxlib
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    print(f"jax {jax.__version__}  jaxlib {jaxlib.__version__}  "
          f"libtpu {libtpu}")
    devices = jax.devices()
    dev = devices[0]
    print(f"devices: {devices}")
    print(f"platform={dev.platform} device_kind={dev.device_kind} "
          f"count={len(devices)}")
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found {dev.platform!r}, not a "
                         f"TPU; refusing to run on it")
    return dev


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (or fetching
    from the persistent cache) while the context is open."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __enter__(self):
        import jax
        self.seconds = 0.0
        self.cache_hits = 0

        def on_duration(event, duration, **_):
            if event in self.EVENTS:
                self.seconds += duration

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        self._listeners = (on_duration, on_event)
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)
        return self

    def __exit__(self, *exc):
        import jax
        on_duration, on_event = self._listeners
        jax.monitoring.unregister_event_duration_listener(on_duration)
        jax.monitoring.unregister_event_listener(on_event)
        return False


def _on_device(tree, device) -> int:
    """Leaf count of ``tree``; raises unless every leaf lives on
    ``device`` alone."""
    import jax
    leaves = jax.tree_util.tree_leaves(tree)
    off = [x for x in leaves if x.devices() != {device}]
    if off:
        raise RuntimeError(f"{len(off)} of {len(leaves)} leaves are not on "
                           f"{device} (first on {off[0].devices()})")
    return len(leaves)


def phase_service(cfg, device, out_dir: pathlib.Path, *, seed=SEED,
                  lanes=LANES, gen_tokens=GEN_TOKENS, slots=SLOTS,
                  chunk_size=CHUNK, topk=TOPK) -> dict:
    """Phase A: seeded weights placed on ``device``, generated text,
    ``CompressionService`` compress -> decompress; raises unless every
    lane round-trips exactly."""
    import jax
    import numpy as np

    from repro.models.schema import init_params
    from repro.serve.engine import ModelPredictor
    from repro.service import CompressionService

    t0 = time.perf_counter()
    with CompileClock() as clock:
        params = jax.device_put(init_params(cfg, jax.random.PRNGKey(seed)),
                                device)
        n_leaves = _on_device(params, device)
        n_params = sum(int(x.size) for x in jax.tree_util.tree_leaves(params))
        pred = ModelPredictor(params, cfg)

        t = time.perf_counter()
        toks = pred.generate(gen_tokens, batch=lanes, top_k=SAMPLE_TOP_K,
                             seed=seed)
        gen_s = time.perf_counter() - t

        svc = CompressionService(pred, slots=slots, chunk_size=chunk_size,
                                 topk=topk)
        t = time.perf_counter()
        handles = [svc.submit_compress(row) for row in toks]
        blobs = [h.result()[0] for h in handles]
        compress_s = time.perf_counter() - t
        escapes_compress = svc.stats.escapes

        t = time.perf_counter()
        handles = [svc.submit_decompress(b) for b in blobs]
        decoded = [h.result() for h in handles]
        decompress_s = time.perf_counter() - t
        bad = [i for i, (a, b) in enumerate(zip(decoded, toks))
               if not np.array_equal(a, b)]
        if bad:
            raise RuntimeError(f"phase A: lanes {bad} did not round-trip")

        # one more step through the public decode API at the service's
        # geometry: the cache it returns must sit on the device, and the
        # logits must be finite
        logits, cache = pred.decode_step(
            pred.begin_decode(slots), np.zeros(slots, np.int32))
        n_cache = _on_device(cache, device)
        if logits.shape != (slots, cfg.vocab_size) \
                or not np.isfinite(logits).all():
            raise RuntimeError(f"phase A: decode logits {logits.shape} "
                               f"not finite at the expected shape")

    out_dir.mkdir(parents=True, exist_ok=True)
    for i, b in enumerate(blobs):
        (out_dir / f"service_lane{i:02d}.llmc").write_bytes(b)
    n_tok = int(toks.size)
    n_bytes = sum(len(b) for b in blobs)
    return {
        "config": cfg.name, "params": n_params, "param_leaves": n_leaves,
        "cache_leaves": n_cache, "device": str(device),
        "lanes": lanes, "tokens": n_tok, "container_bytes": n_bytes,
        "bits_per_token": 8 * n_bytes / n_tok,
        "escapes_compress": int(escapes_compress),
        "escapes_decompress": int(svc.stats.escapes - escapes_compress),
        "model_steps": int(svc.stats.model_steps),
        "round_trip": "byte-identical",
        "generate_s": gen_s, "compress_s": compress_s,
        "decompress_s": decompress_s,
        "wall_s": time.perf_counter() - t0, "compile_s": clock.seconds,
        "compile_cache_hits": clock.cache_hits,
    }


def phase_cli(out_dir: pathlib.Path, *, seed=SEED,
              n_bytes=CLI_BYTES) -> dict:
    """Phase B: ``llmc compress`` then ``llmc decompress`` of a seeded
    synthetic text; raises unless the bytes come back unchanged."""
    import benchmarks.prep as prep
    from repro.cli import main as llmc
    from repro.data.synthetic import human_like

    # train pred-base afresh: never pick up a checkpoint left on disk
    prep.CACHE = out_dir / "bench_cache"
    src, arc, back = (out_dir / n for n in ("cli_input.txt",
                                           "cli_input.llmc",
                                           "cli_roundtrip.txt"))
    out_dir.mkdir(parents=True, exist_ok=True)
    data = human_like("wiki", n_bytes, seed=seed)
    src.write_bytes(data)

    t0 = time.perf_counter()
    with CompileClock() as clock:
        t = time.perf_counter()
        if llmc(["compress", str(src), str(arc)]) != 0:
            raise RuntimeError("phase B: llmc compress failed")
        compress_s = time.perf_counter() - t
        t = time.perf_counter()
        if llmc(["decompress", str(arc), str(back)]) != 0:
            raise RuntimeError("phase B: llmc decompress failed")
        decompress_s = time.perf_counter() - t
    if back.read_bytes() != data:
        raise RuntimeError("phase B: llmc round trip changed the bytes")
    size = arc.stat().st_size
    return {
        "input_bytes": len(data), "container_bytes": size,
        "bits_per_byte": 8 * size / len(data),
        "round_trip": "byte-identical",
        "compress_s_incl_training": compress_s,
        "decompress_s": decompress_s,
        "wall_s": time.perf_counter() - t0, "compile_s": clock.seconds,
        "compile_cache_hits": clock.cache_hits,
    }


def _report(name: str, res: dict) -> None:
    print(f"[smoke run, not a benchmark] {name}: " + "  ".join(
        f"{k}={v:.6g}" if isinstance(v, float) else f"{k}={v}"
        for k, v in res.items()))


def main() -> int:
    dev = device_check()
    sys.path[:0] = [str(REPO / "src"), str(REPO)]
    import jax

    from repro.compile_cache import configure_compile_cache
    from repro.configs.qwen3_1_7b import CONFIG

    print(f"compile cache: {configure_compile_cache()}")
    shutil.rmtree(OUT, ignore_errors=True)
    OUT.mkdir(parents=True)

    summary = {"device_kind": dev.device_kind,
               "service": phase_service(CONFIG, dev, OUT)}
    _report("phase A (qwen3-1.7b, CompressionService)", summary["service"])
    summary["cli"] = phase_cli(OUT)
    _report("phase B (llmc compress/decompress, pred-base)", summary["cli"])
    (OUT / "summary.json").write_text(json.dumps(summary, indent=1))

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
