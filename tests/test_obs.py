"""Telemetry layer (DESIGN.md §10): registry, spans, logs, diagnostics,
and the load-bearing guarantee — telemetry NEVER changes output bytes.
"""
import importlib.util
import json
import pathlib

import numpy as np
import pytest

from _hypo import given, settings, st
from helpers import GoldenPredictor
from repro import obs
from repro.core import LLMCompressor
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry)
from repro.service import CompressionService
from repro.service.scheduler import SchedulerStats

REPO = pathlib.Path(__file__).resolve().parents[1]


# ------------------------------------------------------------- registry
def test_counter_gauge_roundtrip():
    reg = MetricsRegistry()
    c = reg.counter("x.count", "help text")
    c.inc()
    c.inc(4)
    assert c.value == 5 and reg.value("x.count") == 5
    g = reg.gauge("x.level")
    g.set(2.5)
    assert reg.get("x.level").value == 2.5
    assert reg.value("missing", default=-1) == -1
    # same name + same type -> same instrument; wrong type -> TypeError
    assert reg.counter("x.count") is c
    with pytest.raises(TypeError):
        reg.gauge("x.count")


def test_histogram_buckets_quantiles():
    h = Histogram("h")
    for v in (0.0, 0.75, 1.5, 3.0, 3.9, 100.0):
        h.observe(v)
    assert h.count == 6
    assert h.sum == pytest.approx(109.15)
    # v in (2**(e-1), 2**e]: 0.75 -> le 1, 1.5 -> le 2, 3.0/3.9 -> le 4
    assert h.nonzero_buckets() == {0.0: 1, 1.0: 1, 2.0: 1, 4.0: 2, 128.0: 1}
    assert h.quantile(0.5) == 2.0
    assert h.quantile(1.0) == 128.0
    assert h.mean == pytest.approx(109.15 / 6)


def test_snapshot_and_prometheus():
    reg = MetricsRegistry(name="t")
    reg.counter("a.total", "things").inc(3)
    reg.histogram("b.seconds").observe(0.5)
    snap = reg.snapshot()
    assert snap["a.total"] == {"type": "counter", "value": 3}
    assert snap["b.seconds"]["count"] == 1
    json.loads(reg.to_json())            # JSON-serializable end to end
    prom = reg.to_prometheus()
    assert "# TYPE repro_a_total counter" in prom
    assert "repro_a_total 3" in prom
    assert 'repro_b_seconds_bucket{le="+Inf"} 1' in prom
    assert "repro_b_seconds_count 1" in prom


# ---------------------------------------------------------------- spans
def test_span_nesting_records_path_histogram():
    reg = MetricsRegistry()
    with obs.span("outer", reg):
        assert obs.trace.current() == "outer"
        with obs.span("inner", reg):
            assert obs.trace.current() == "outer/inner"
    assert obs.trace.current() == ""
    assert reg.get("span.outer.seconds").count == 1
    assert reg.get("span.outer/inner.seconds").count == 1


def test_span_disabled_is_noop():
    reg = MetricsRegistry(enabled=False)
    sp = obs.span("quiet", reg)
    assert sp is obs.trace.NULL
    with sp:
        pass
    assert reg.get("span.quiet.seconds") is None


# ----------------------------------------------------------------- logs
def test_log_error_increments_counters(capsys):
    prev = obs.set_registry(MetricsRegistry())
    try:
        obs.log_error("unit.test_event", detail="x y")
        reg = obs.registry()
        assert reg.value("errors.total") == 1
        assert reg.value("errors.unit.test_event") == 1
    finally:
        obs.set_registry(prev)
    assert obs.format_event("e", {"a": 1, "b": "x y"}) == "e a=1 b='x y'"


def test_exception_record_structure():
    try:
        raise ValueError("boom")
    except ValueError as e:
        rec = obs.exception_record(e)
    assert rec["type"] == "ValueError" and rec["message"] == "boom"
    assert rec["traceback"][-1]["func"] == "test_exception_record_structure"
    json.dumps(rec)


# -------------------------------------------------- SchedulerStats view
def test_scheduler_stats_attribute_compat():
    s = SchedulerStats()
    assert s.occupancy == 0.0            # no steps -> no division
    s.model_steps += 3
    s.lane_steps += 12
    s.token_steps += 9
    assert (s.model_steps, s.steps) == (3, 3)
    assert s.occupancy == pytest.approx(0.75)
    # the attributes ARE registry counters
    assert s.registry.value("scheduler.model_steps") == 3
    assert s.snapshot()["occupancy"] == pytest.approx(0.75)
    # standalone instances are isolated
    assert SchedulerStats().model_steps == 0


# --------------------------------------------------- service stats surface
def _roundtrip_service(toks, enabled=True, topk=8, slots=4, chunk=16):
    pred = GoldenPredictor()
    svc = CompressionService(pred, slots=slots, chunk_size=chunk, topk=topk)
    svc.registry.enabled = enabled
    ch = svc.submit_compress(toks)
    blob, _ = ch.result()
    dh = svc.submit_decompress(blob)
    out = dh.result()
    assert np.array_equal(out, toks)
    return svc, ch, dh, blob


def test_service_stats_dual_api():
    toks = np.random.default_rng(7).integers(0, 63, 150).astype(np.int32)
    svc, *_ = _roundtrip_service(toks)
    # attribute view (pre-PR-7 API)
    assert svc.stats.model_steps > 0
    assert 0.0 < svc.stats.occupancy <= 1.0
    # callable view: structured snapshot
    snap = svc.stats()
    assert snap == svc.snapshot()
    assert snap["jobs"] == {"submitted": 2, "failed": 0,
                            "compress": 1, "decompress": 1}
    assert snap["occupancy"] == pytest.approx(svc.stats.occupancy)
    assert snap["chunk_bits_per_token"]["count"] == 2 * 10  # 150/16 chunks
    assert snap["draft_acceptance"] is None   # no speculative decode ran
    assert snap["metrics"]["scheduler.model_steps"]["value"] \
        == svc.stats.model_steps
    json.dumps(snap, default=str)


def test_service_stats_prometheus_exposition():
    toks = np.random.default_rng(8).integers(0, 63, 40).astype(np.int32)
    svc, *_ = _roundtrip_service(toks)
    prom = svc.registry.to_prometheus()
    assert "repro_scheduler_model_steps" in prom
    assert "repro_chunk_bits_per_token_count" in prom


# -------------------------------------------------------- job diagnostics
def test_job_diagnostics_and_sidecar(tmp_path):
    n, chunk = 150, 16
    toks = np.random.default_rng(9).integers(0, 63, n).astype(np.int32)
    svc, ch, dh, blob = _roundtrip_service(toks, chunk=chunk)
    for h, kind in ((ch, "compress"), (dh, "decompress")):
        diag = h.diagnostics
        assert diag.kind == kind and diag.codec == "rans"
        assert diag.n_tokens == n
        assert len(diag.chunks) == -(-n // chunk)
        assert [c.chunk_index for c in diag.chunks] == list(range(10))
        assert sum(c.n_tokens for c in diag.chunks) == n
        assert all(c.bits_per_token > 0 for c in diag.chunks)
        # coded_bits is the quantized information content; the realized
        # stream adds only the coder state flush + byte rounding
        for c in diag.chunks:
            assert 0 < c.coded_bits <= 8 * c.stream_bytes
        assert diag.draft_acceptance is None
    assert ch.diagnostics.container_bytes == len(blob)
    # compress-side and decode-side accruals price the SAME code
    for cc, dc in zip(ch.diagnostics.chunks, dh.diagnostics.chunks):
        assert cc.coded_bits == pytest.approx(dc.coded_bits, rel=1e-9)
        assert cc.n_escapes == dc.n_escapes
    # sidecar: JSON next to the container, never inside it
    target = tmp_path / "a.llmc"
    target.write_bytes(blob)
    path = dh.write_sidecar(target)
    assert path == tmp_path / "a.llmc.diag.json"
    rec = obs.read_sidecar(target)
    assert rec["kind"] == "decompress" and rec["n_tokens"] == n
    assert len(rec["chunks"]) == 10


def test_diagnostics_empty_when_disabled():
    toks = np.random.default_rng(10).integers(0, 63, 50).astype(np.int32)
    svc, ch, dh, _ = _roundtrip_service(toks, enabled=False)
    assert ch.diagnostics.chunks == []
    assert dh.diagnostics.chunks == []
    # load-bearing counters still ran (disabled gates only extras)
    assert svc.stats.model_steps > 0
    assert svc.snapshot()["chunk_bits_per_token"] is None


def test_job_failure_counted_once():
    """A mid-flight chunk failure increments chunk_failures AND the job
    failure counter exactly once (v3: no checksums, so the corruption
    reaches the scheduler's exhaustion check instead of failing at
    submit)."""
    from repro.core import ContainerError
    pred = GoldenPredictor()
    comp = LLMCompressor(pred, chunk_size=16, topk=8, decode_batch=4,
                         container_version=3)
    toks = np.random.default_rng(11).integers(0, 63, 64).astype(np.int32)
    blob, _ = comp.compress(toks)
    bad = bytearray(blob)
    bad[len(bad) // 2] ^= 0x10              # flip inside a coded stream
    svc = CompressionService(pred, slots=4, chunk_size=16, topk=8)
    with pytest.raises(ContainerError):
        svc.submit_decompress(bytes(bad)).result()
    assert svc.stats.chunk_failures >= 1
    assert svc.registry.value("service.jobs_failed") == 1
    assert svc.snapshot()["jobs"]["failed"] == 1
    # errors are also countable in the process-global registry
    assert obs.registry().value("errors.scheduler.chunk_failed") >= 1


# --------------------------------------- byte-identity: the hard invariant
def _compress_blob(pred, toks, enabled, *, topk, codec, draft_k=0):
    reg = MetricsRegistry(enabled=enabled)
    comp = LLMCompressor(pred, chunk_size=16, topk=topk, decode_batch=4,
                         codec=codec, draft_k=draft_k, registry=reg)
    blob, _ = comp.compress(toks)
    out = comp.decompress(blob)
    assert np.array_equal(out, toks), "LOSSLESS VIOLATION"
    return blob


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(1, 120))
def test_byte_identity_enabled_vs_disabled(seed, n):
    """Property: the container bytes are identical with telemetry on and
    off, across codecs, top-k modes, and the speculative decode path."""
    pred = GoldenPredictor()
    toks = np.random.default_rng(seed).integers(0, 63, n).astype(np.int32)
    for codec, topk, draft_k in (("rans", 0, 0), ("rans", 8, 0),
                                 ("rans", 8, 4), ("ac", 8, 0)):
        on = _compress_blob(pred, toks, True, topk=topk, codec=codec,
                            draft_k=draft_k)
        off = _compress_blob(pred, toks, False, topk=topk, codec=codec,
                             draft_k=draft_k)
        assert on == off, f"telemetry changed bytes ({codec}, k={topk})"


def test_byte_identity_service_paths():
    toks = np.random.default_rng(13).integers(0, 63, 300).astype(np.int32)
    _, _, _, blob_on = _roundtrip_service(toks, enabled=True)
    _, _, _, blob_off = _roundtrip_service(toks, enabled=False)
    assert blob_on == blob_off
    # and the service container matches the grouped compressor's
    ref = LLMCompressor(GoldenPredictor(), chunk_size=16, topk=8,
                        decode_batch=4, container_version=4)
    assert blob_on == ref.compress(toks)[0]


def test_speculative_diagnostics_counters():
    """Speculative decode records rounds / acceptance / rollbacks."""
    pred = GoldenPredictor()
    # argmax-following stream: the suffix draft gets real acceptance
    argmax = pred._table.argmax(axis=-1)
    toks = np.zeros(256, np.int32)
    prev = pred.bos_id
    for i in range(256):
        prev = toks[i] = argmax[prev]
    reg = MetricsRegistry()
    comp = LLMCompressor(pred, chunk_size=32, topk=8, decode_batch=4,
                         draft_k=4, registry=reg)
    blob, _ = comp.compress(toks)
    out = comp.decompress(blob)
    assert np.array_equal(out, toks)
    assert reg.value("spec.rounds") > 0
    assert reg.value("spec.drafted_tokens") > 0
    assert 0 <= reg.value("spec.drafted_accepted") \
        <= reg.value("spec.drafted_tokens")
    h = reg.get("spec.accept_depth")
    assert h is not None and h.count > 0


# ------------------------------------------------------------- repo lint
def _load_lint():
    spec = importlib.util.spec_from_file_location(
        "lint_no_print", REPO / "tools" / "lint_no_print.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_lint_tool_flags_calls_not_strings(tmp_path):
    lint = _load_lint()
    (tmp_path / "bad.py").write_text(
        's = "print(this is a string literal)"\n'
        "obj.print()\n"                      # method, not the builtin
        "print('flagged')\n")
    (tmp_path / "cli.py").write_text("print('allowed')\n")
    problems = lint.lint(tmp_path)
    assert len(problems) == 1 and "bad.py:3" in problems[0]


def test_repo_tree_passes_lint():
    lint = _load_lint()
    assert lint.lint(REPO / "src" / "repro") == []


# ------------------------------------------- per-step spans on the served path
STEP_SPANS = ("service.step", "service.step/model.decode_step",
              "service.step/cdf.build",
              "service.step/cdf.build/transfer.cdf_to_host",
              "service.step/coder.step")


def _tiny_predictor(vocab=258, cls=None):
    import jax

    from helpers import tiny
    from repro.models import init_params
    from repro.serve.engine import ModelPredictor
    cfg = tiny("dense", vocab_size=vocab)
    params = init_params(cfg, jax.random.PRNGKey(0))
    return (cls or ModelPredictor)(params, cfg, bos_id=257)


def _tiny_model_service(topk=8, slots=4, chunk=16, vocab=258, cls=None):
    return CompressionService(_tiny_predictor(vocab, cls), slots=slots,
                              chunk_size=chunk, topk=topk)


def _span_counts(reg):
    return {k[len("span."):-len(".seconds")]: v["count"]
            for k, v in reg.snapshot().items() if k.startswith("span.")}


def _model_roundtrip(svc, seed=31, n=100):
    toks = np.random.default_rng(seed).integers(0, 256, n).astype(np.int32)
    blob, _ = svc.submit_compress(toks).result()
    assert np.array_equal(svc.submit_decompress(blob).result(), toks)
    return blob


def test_service_step_spans_once_per_model_step():
    """Every model step opens each per-step span exactly once, all in
    the service's registry (the engine's and the coder's spans inherit
    it), and model.decode_step never nests in itself."""
    glob0 = set(_span_counts(obs.registry()))
    svc = _tiny_model_service()
    _model_roundtrip(svc)
    spans = _span_counts(svc.registry)
    steps = svc.stats.model_steps
    assert steps > 0
    for path in STEP_SPANS:
        assert spans[path] == steps, path
    assert spans["service.step/service.finish_slot"] \
        == svc.stats.chunks_completed
    # one flush per compressed chunk: 100 tokens in chunks of 16
    assert spans["service.step/service.finish_slot/rans.flush_slot"] \
        == -(-100 // 16)
    assert spans["service.step/service.refill"] >= 1
    assert spans["service.step/service.refill/model.reset_slots"] >= 1
    for path in spans:
        assert path.count("model.decode_step") <= 1, path
        assert path.startswith("service.step"), path
    assert set(_span_counts(obs.registry())) == glob0
    assert svc.snapshot()["phases"]["model"] > 0


@pytest.mark.parametrize("topk", [8, 0])
def test_transfer_counters_match_shapes(topk):
    """transfer.d2h_bytes / h2d_bytes equal the bytes of the arrays each
    step moves, computed from their shapes and dtypes: ids and CDFs
    down, previous tokens and refill masks up. The logits stay on the
    device and count in neither."""
    from repro.core.cdf import full_cdf_jit, topk_cdf_jit
    svc = _tiny_model_service(topk=topk)
    _model_roundtrip(svc)
    B, pred = svc.slots, svc.predictor
    pred.set_decode_len(svc.chunk_size)
    logits, _ = pred.decode_step(pred.begin_decode(B),
                                 np.zeros(B, np.int32))
    outs = topk_cdf_jit(logits, topk, svc.precision) if topk \
        else (full_cdf_jit(logits, svc.precision),)
    fetched = sum(np.asarray(o).nbytes for o in outs)
    steps = svc.stats.model_steps
    resets = _span_counts(svc.registry)[
        "service.step/service.refill/model.reset_slots"]
    reg = svc.registry
    assert reg.value("transfer.d2h_bytes") == steps * fetched
    assert reg.value("transfer.h2d_bytes") == steps * B * 4 + resets * B


def test_host_logits_count_as_uploaded():
    """An adapter that returns host logits (here a numpy table) has them
    uploaded into the CDF program each step: h2d counts their bytes."""
    svc = CompressionService(GoldenPredictor(), slots=4, chunk_size=16,
                             topk=8)
    toks = np.random.default_rng(5).integers(0, 64, 60).astype(np.int32)
    svc.submit_compress(toks).result()
    B, pred = svc.slots, svc.predictor
    logits, _ = pred.decode_step(pred.begin_decode(B), np.zeros(B, np.int32))
    assert isinstance(logits, np.ndarray)
    assert svc.registry.value("transfer.h2d_bytes") == \
        svc.stats.model_steps * logits.nbytes
    assert svc.registry.value("transfer.d2h_bytes") == \
        svc.stats.model_steps * B * (8 + 8 + 2) * 4


@pytest.mark.parametrize("topk", [8, 0])
def test_device_logits_containers_byte_identical(topk):
    """The service over device logits writes the same bytes as over an
    adapter that copies them to the host first (the CDF program then
    uploads them again): the CDF program reads the same bfloat16 bits
    either way. Each container decodes back to its tokens."""
    import jax

    from repro.serve.engine import ModelPredictor

    class HostLogits(ModelPredictor):
        def decode_step(self, state, prev_tokens):
            logits, state = super().decode_step(state, prev_tokens)
            return np.asarray(logits), state

    dev = _tiny_model_service(topk=topk)
    host = _tiny_model_service(topk=topk, cls=HostLogits)
    pred = dev.predictor
    pred.set_decode_len(dev.chunk_size)
    logits, _ = pred.decode_step(pred.begin_decode(dev.slots),
                                 np.zeros(dev.slots, np.int32))
    assert isinstance(logits, jax.Array)
    for seed, n in ((41, 100), (42, 37)):
        assert _model_roundtrip(dev, seed, n) == \
            _model_roundtrip(host, seed, n)


@pytest.mark.parametrize("vocab", [258, 1030])
def test_logits_never_reach_the_host(vocab):
    """At top-K the host fetches (B, K) ids and (B, K+2) CDFs a step,
    int32, whatever the vocabulary: the (B, V) logits are never copied."""
    K = 8
    svc = _tiny_model_service(topk=K, vocab=vocab)
    _model_roundtrip(svc)
    assert svc.registry.value("transfer.d2h_bytes") == \
        svc.stats.model_steps * svc.slots * (K + K + 2) * 4


def test_span_registry_inheritance_survives_exceptions():
    """A span with no registry records into the innermost open span's
    registry, else the global one — also after an exception unwinds
    spans, and for timeline-only spans under a disabled registry."""
    own, glob = MetricsRegistry(), MetricsRegistry()
    prev = obs.set_registry(glob)
    try:
        assert obs.trace.current_registry() is glob
        with obs.span("outer", own):
            assert obs.trace.current_registry() is own
            with pytest.raises(RuntimeError):
                with obs.span("inner"):
                    assert obs.trace.current_registry() is own
                    raise RuntimeError("boom")
            assert obs.trace.current_registry() is own
            with obs.span("after"):
                pass
        with pytest.raises(RuntimeError):
            with obs.span("raising", own):
                raise RuntimeError("boom")
        assert obs.trace.current_registry() is glob
        with obs.span("top"):
            pass
        off = MetricsRegistry(enabled=False)
        with obs.TimelineRecorder() as rec:
            with obs.span("quiet", off):
                with obs.span("child"):
                    assert obs.trace.current_registry() is off
    finally:
        obs.set_registry(prev)
    assert set(_span_counts(own)) == {"outer", "outer/inner",
                                      "outer/after", "raising"}
    assert set(_span_counts(glob)) == {"top"}
    assert _span_counts(off) == {}
    assert [e.path for e in rec.events()] == ["quiet", "quiet/child"]


def test_disabled_registry_records_no_spans_and_keeps_bytes():
    on = _tiny_model_service()
    off = _tiny_model_service()
    off.registry.enabled = False
    assert _model_roundtrip(on) == _model_roundtrip(off)
    assert _span_counts(off.registry) == {}
    for name in ("transfer.d2h_bytes", "transfer.h2d_bytes",
                 "scheduler.step_compiles"):
        assert off.registry.value(name) == 0
    assert off.stats.model_steps == on.stats.model_steps


def test_step_compiles_zero_when_warm_and_counts_rebuild():
    """scheduler.step_compiles reads 0 over a warmed run and counts the
    compiles a forced geometry rebuild (a shared prefix sets a context
    budget, so a new cache length) brings into a step."""
    svc = _tiny_model_service()
    _model_roundtrip(svc)                    # compiles every program
    c = svc.registry.counter("scheduler.step_compiles")
    warm = c.value
    _model_roundtrip(svc, seed=32)
    assert c.value == warm
    toks = np.random.default_rng(33).integers(0, 256, 40).astype(np.int32)
    svc.submit_compress(toks, shared_prefix=np.arange(5)).result()
    assert svc.scheduler._ctx_budget == 5
    assert c.value > warm
