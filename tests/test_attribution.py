"""Performance attribution layer (DESIGN.md §13): timeline recorder,
Chrome-trace export, per-job phase reports, bench history + regression
gate, and the roofline attainment math.
"""
import importlib.util
import json
import pathlib
import threading

import numpy as np
import pytest

from helpers import GoldenPredictor
from repro import obs
from repro.obs.bench_history import (BenchHistory, BenchRecord,
                                     parse_derived, validate_record)
from repro.obs.metrics import MetricsRegistry
from repro.obs.timeline import (PhaseReport, SpanEvent, TimelineRecorder,
                                phase_of, phases_from_registry)
from repro.service import CompressionService

REPO = pathlib.Path(__file__).resolve().parents[1]


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, REPO / "tools" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------------ timeline recorder
def test_ring_buffer_bounds_and_drop_counter():
    rec = TimelineRecorder(capacity=8)
    for i in range(20):
        rec.record(f"s{i}", f"s{i}", t0=float(i), dur=0.5)
    assert len(rec) == 8
    assert rec.dropped == 12
    evs = rec.events()
    assert len(evs) == 8
    # the ring keeps the NEWEST events, oldest-first
    assert [e.name for e in evs] == [f"s{i}" for i in range(12, 20)]
    with pytest.raises(ValueError):
        TimelineRecorder(capacity=0)


def test_spans_feed_installed_recorder():
    reg = MetricsRegistry()
    with TimelineRecorder() as rec:
        with obs.span("outer", reg, tags={"job": 1}):
            with obs.span("model.step", reg):
                pass
    assert obs.timeline.active() is None        # context exit uninstalls
    evs = rec.events()
    assert [e.name for e in evs] == ["outer", "model.step"]
    assert evs[1].path == "outer/model.step"
    assert evs[0].tags == {"job": 1}
    # nesting invariant the phase sweep relies on: child inside parent
    assert evs[0].t0 <= evs[1].t0 and evs[1].t1 <= evs[0].t1 + 1e-9
    # uninstalled -> no further events
    with obs.span("after", reg):
        pass
    assert len(rec.events()) == 2


def test_timeline_only_span_overrides_registry_gate():
    """With a recorder installed, spans against a DISABLED registry still
    land on the timeline (the process-wide recorder must see coder/model
    spans recording against the global registry) — but never observe into
    the disabled registry."""
    reg = MetricsRegistry(enabled=False)
    assert obs.span("quiet", reg) is obs.trace.NULL     # no recorder
    with TimelineRecorder() as rec:
        sp = obs.span("quiet", reg)
        assert sp is not obs.trace.NULL
        with sp:
            pass
    assert [e.name for e in rec.events()] == ["quiet"]
    assert reg.get("span.quiet.seconds") is None


def test_chrome_trace_structure(tmp_path):
    reg = MetricsRegistry()
    with TimelineRecorder() as rec:
        with obs.span("service.step", reg):
            with obs.span("model.decode_step", reg):
                pass
    path = tmp_path / "trace.json"
    rec.save(path)
    doc = json.loads(path.read_text())
    assert doc["displayTimeUnit"] == "ms"
    assert doc["otherData"]["dropped_events"] == 0
    evs = doc["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    assert meta and meta[0]["name"] == "process_name"
    xs = [e for e in evs if e["ph"] == "X"]
    assert {e["name"] for e in xs} == {"service.step", "model.decode_step"}
    for e in xs:
        # complete events: µs ts/dur, pid/tid, category = phase bucket
        assert e["ts"] >= 0 and e["dur"] >= 0
        assert isinstance(e["tid"], int) and e["pid"] == 1
        assert e["cat"] == phase_of(e["name"])
        assert "path" in e["args"]


# --------------------------------------------------- span failure safety
def test_span_exception_restores_nesting_path():
    reg = MetricsRegistry()
    with pytest.raises(RuntimeError):
        with obs.span("outer", reg):
            with obs.span("inner", reg):
                raise RuntimeError("boom")
    assert obs.trace.current() == ""
    # both spans still closed into their histograms
    assert reg.get("span.outer.seconds").count == 1
    assert reg.get("span.outer/inner.seconds").count == 1


def test_span_stack_is_per_thread():
    reg = MetricsRegistry()
    paths = {}

    def worker(tag):
        with obs.span(tag, reg):
            paths[tag] = obs.trace.current()

    with obs.span("main_outer", reg):
        ts = [threading.Thread(target=worker, args=(f"t{i}",))
              for i in range(4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert obs.trace.current() == "main_outer"
    # worker threads never saw the main thread's open span
    assert paths == {f"t{i}": f"t{i}" for i in range(4)}


def test_recorder_safe_from_many_threads():
    rec = TimelineRecorder(capacity=64)
    barrier = threading.Barrier(8)

    def pound():
        barrier.wait()
        for i in range(100):
            rec.record("x", "x", t0=float(i), dur=0.1)

    ts = [threading.Thread(target=pound) for _ in range(8)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert len(rec) == 64                       # never exceeds capacity
    assert rec.dropped == 8 * 100 - 64
    assert len(rec.events()) == 64


# -------------------------------------------------------- phase rollup
def test_phase_report_exclusive_attribution():
    """Synthetic nest: 10s window, scheduler step [1,9] containing model
    [2,5] and coder [6,8] -> exclusive scheduler 3s, model 3s, coder 2s,
    unattributed 2s ([0,1] + [9,10])."""
    evs = [
        SpanEvent("service.step", "service.step", 1.0, 8.0, tid=1),
        SpanEvent("model.decode_step", "service.step/model.decode_step",
                  2.0, 3.0, tid=1),
        SpanEvent("rans.flush_slot", "service.step/rans.flush_slot",
                  6.0, 2.0, tid=1),
    ]
    rep = PhaseReport.from_events(evs, t0=0.0, t1=10.0)
    assert rep.total_s == 10.0
    assert rep.phases["scheduler"] == pytest.approx(3.0)
    assert rep.phases["model"] == pytest.approx(3.0)
    assert rep.phases["coder"] == pytest.approx(2.0)
    assert rep.phases["unattributed"] == pytest.approx(2.0)
    assert sum(rep.phases.values()) == pytest.approx(rep.total_s)
    assert rep.coverage == pytest.approx(0.8)
    # window clipping: an event straddling t0 contributes only its
    # in-window part
    clipped = PhaseReport.from_events(evs, t0=3.0, t1=10.0)
    assert clipped.phases["model"] == pytest.approx(2.0)   # [3,5] of [2,5]
    d = rep.to_dict()
    assert d["coverage"] == pytest.approx(0.8)
    json.dumps(d)


def test_phase_report_empty_window():
    rep = PhaseReport.from_events([], t0=0.0, t1=0.0)
    assert rep.total_s == 0.0 and rep.coverage == 0.0
    assert sum(rep.phases.values()) == 0.0


def test_phases_from_registry_direct_child_subtraction():
    reg = MetricsRegistry()
    reg.histogram("span.service.step.seconds").observe(10.0)
    reg.histogram("span.service.step/model.decode_step.seconds").observe(6.0)
    reg.histogram(
        "span.service.step/model.decode_step/host.pack.seconds").observe(1.0)
    ph = phases_from_registry(reg)
    assert ph["scheduler"] == pytest.approx(4.0)    # 10 - direct child 6
    assert ph["model"] == pytest.approx(5.0)        # 6 - direct child 1
    assert ph["host"] == pytest.approx(1.0)


# ------------------------------------- traced service run (end to end)
def _traced_roundtrip(tmp_path, n=300, chunk=16):
    toks = np.random.default_rng(21).integers(0, 63, n).astype(np.int32)
    out_path = tmp_path / "svc.trace.json"
    svc = CompressionService(GoldenPredictor(), slots=4, chunk_size=chunk,
                             topk=8, trace=str(out_path))
    try:
        ch = svc.submit_compress(toks)
        blob, _ = ch.result()
        dh = svc.submit_decompress(blob)
        assert np.array_equal(dh.result(), toks)
        # reports and diagnostics must be taken while the recorder is
        # attached — close() detaches it (the CLI does the same dance)
        reports = [h.phase_report() for h in (ch, dh)]
        diags = [h.diagnostics for h in (ch, dh)]
    finally:
        svc.close()
    return blob, out_path, reports, diags


def test_service_trace_export_and_phase_report(tmp_path):
    blob, out_path, reports, diags = _traced_roundtrip(tmp_path)
    # close() wrote the Chrome-trace file to the trace= path
    doc = json.loads(out_path.read_text())
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert len(xs) > 10
    cats = {e["cat"] for e in xs}
    assert {"scheduler", "model", "coder"} <= cats
    # per-job attribution: phases sum to job wall within 5%, and spans
    # cover >=90% of the wall (the ISSUE acceptance bar)
    for rep, diag in zip(reports, diags):
        assert rep.total_s > 0
        assert sum(rep.phases.values()) == pytest.approx(
            rep.total_s, rel=0.05)
        assert rep.coverage >= 0.90, \
            f"coverage {rep.coverage:.3f} < 0.90 ({rep.phases})"
        assert rep.phases.get("model", 0.0) > 0
        # diagnostics sidecar carries the same breakdown
        assert diag.phases is not None
        assert diag.wall_s > 0
    # recorder uninstalled by close(): later spans don't leak in
    assert obs.timeline.active() is None


def test_trace_keeps_bytes_identical(tmp_path):
    """Recording a timeline must never change container bytes."""
    toks = np.random.default_rng(21).integers(0, 63, 200).astype(np.int32)
    svc = CompressionService(GoldenPredictor(), slots=4, chunk_size=16,
                             topk=8)
    plain, _ = svc.submit_compress(toks).result()
    traced, *_ = _traced_roundtrip(tmp_path, n=200)
    assert traced == plain


def test_snapshot_quantiles_and_phases():
    toks = np.random.default_rng(23).integers(0, 63, 150).astype(np.int32)
    svc = CompressionService(GoldenPredictor(), slots=4, chunk_size=16,
                             topk=8)
    blob, _ = svc.submit_compress(toks).result()
    assert np.array_equal(svc.submit_decompress(blob).result(), toks)
    snap = svc.snapshot()
    bpt = snap["chunk_bits_per_token"]
    for k in ("p50", "p95", "p99"):
        assert k in bpt and bpt[k] >= 0
    assert bpt["p50"] <= bpt["p95"] <= bpt["p99"]
    # span-derived phase breakdown rides the snapshot (cheap signal)
    assert "phases" in snap
    assert all(v >= 0 for v in snap["phases"].values())
    json.dumps(snap, default=str)


# ------------------------------------ Prometheus exposition conformance
def _parse_prometheus(text):
    """Minimal exposition-format parser: {metric: {labels_str: value}},
    plus declared TYPEs. Raises on lines that don't parse."""
    samples, types = {}, {}
    for line in text.splitlines():
        if not line.strip():
            continue
        if line.startswith("# TYPE "):
            _, _, name, typ = line.split()
            types[name] = typ
            continue
        if line.startswith("#"):
            continue
        name_part, _, val = line.rpartition(" ")
        assert name_part, f"unparseable sample line: {line!r}"
        if "{" in name_part:
            name, _, rest = name_part.partition("{")
            labels = rest.rstrip("}")
        else:
            name, labels = name_part, ""
        float(val)      # every sample value must be a number
        samples.setdefault(name, {})[labels] = float(val)
    return samples, types


def test_prometheus_exposition_conformance():
    reg = MetricsRegistry(name="t")
    reg.counter("jobs.total", "jobs").inc(3)
    reg.gauge("queue.depth").set(2)
    h = reg.histogram("step.seconds", "step wall")
    for v in (0.001, 0.002, 0.004, 0.1, 1.5, 30.0):
        h.observe(v)
    samples, types = _parse_prometheus(reg.to_prometheus())
    assert types["repro_jobs_total"] == "counter"
    assert types["repro_queue_depth"] == "gauge"
    assert types["repro_step_seconds"] == "histogram"
    # histogram series: buckets cumulative + monotone, +Inf == _count,
    # _sum present and consistent
    buckets = samples["repro_step_seconds_bucket"]
    assert '+Inf' in str(buckets)
    pairs = []
    for labels, v in buckets.items():
        le = labels.split('le="')[1].rstrip('"')
        pairs.append((float("inf") if le == "+Inf" else float(le), v))
    pairs.sort()
    counts = [v for _, v in pairs]
    assert counts == sorted(counts), "buckets must be cumulative"
    assert pairs[-1][0] == float("inf")
    assert pairs[-1][1] == samples["repro_step_seconds_count"][""] == 6
    assert samples["repro_step_seconds_sum"][""] == pytest.approx(31.607)
    # quantile companion gauges for scrapers without histogram_quantile()
    for q in ("p50", "p95", "p99"):
        assert samples[f"repro_step_seconds_{q}"][""] >= 0
    assert samples["repro_step_seconds_p50"][""] \
        <= samples["repro_step_seconds_p99"][""]


# -------------------------------------------------------- bench history
def _record(bench, us, derived="", quick=True, **kw):
    return BenchRecord.build(bench, us, derived, quick=quick, commit="test",
                             ts="2026-08-08T00:00:00+00:00", **kw)


def test_bench_history_append_and_validate(tmp_path):
    hist = BenchHistory(tmp_path / "history.jsonl")
    reg = MetricsRegistry()
    reg.counter("n.total").inc(7)
    reg.histogram("span.service.step.seconds").observe(0.5)
    hist.append(_record("svc", 100.0, "jobs_s=81.0;speedup=5.02x",
                        registry=reg))
    hist.append(_record("svc", 105.0, "jobs_s=80.0"))
    # two appends -> two schema-valid rows (the acceptance criterion)
    rows = [json.loads(ln) for ln in
            hist.path.read_text().splitlines()]
    assert len(rows) == 2
    assert all(validate_record(r) == [] for r in rows)
    assert rows[0]["values"] == {"jobs_s": 81.0, "speedup": 5.02}
    assert rows[0]["metrics"]["n.total"] == 7
    assert "bucket" not in json.dumps(rows[0]["metrics"])  # compacted
    assert rows[0]["phases"]["scheduler"] == pytest.approx(0.5)
    assert hist.latest("svc")["us_per_call"] == 105.0
    assert [r["us_per_call"] for r in hist.trailing("svc")] == [100.0]


def test_bench_history_skips_corrupt_lines(tmp_path):
    hist = BenchHistory(tmp_path / "history.jsonl")
    hist.append(_record("b", 10.0))
    with open(hist.path, "a") as f:
        f.write("{truncated mid-wr\n")
        f.write('{"schema": 1, "bench": "b"}\n')      # missing fields
    hist.append(_record("b", 11.0))
    assert [r["us_per_call"] for r in hist.load("b")] == [10.0, 11.0]
    assert hist.benches() == ["b"]


def test_parse_derived_forms():
    assert parse_derived("a=1;b=2.5x; c = 3 ;skip;d=oops") == \
        {"a": 1.0, "b": 2.5, "c": 3.0}
    assert parse_derived("") == {}


def test_validate_record_rejects_malformed():
    good = _record("b", 1.0).to_dict()
    assert validate_record(good) == []
    assert validate_record("nope") != []
    bad = dict(good)
    del bad["us_per_call"]
    assert any("us_per_call" in p for p in validate_record(bad))
    bad = dict(good, values={"r": "high"})
    assert any("not numeric" in p for p in validate_record(bad))
    bad = dict(good, schema=99)
    assert any("newer" in p for p in validate_record(bad))


# ------------------------------------------------- regression gate (CI)
def test_bench_regress_fails_on_wall_regression(tmp_path):
    regress = _load_tool("bench_regress")
    hist = BenchHistory(tmp_path / "history.jsonl")
    for _ in range(5):
        hist.append(_record("svc", 100.0, "ratio=4.0"))
    hist.append(_record("svc", 120.0, "ratio=4.0"))   # +20% wall
    problems = regress.run_gate(hist.path, log=lambda *a, **k: None)
    assert len(problems) == 1 and "wall" in problems[0]
    # the CLI entrypoint exits nonzero on it
    assert regress.main(["--history", str(hist.path)]) == 1


def test_bench_regress_fails_on_ratio_regression(tmp_path):
    regress = _load_tool("bench_regress")
    hist = BenchHistory(tmp_path / "history.jsonl")
    for _ in range(3):
        hist.append(_record("router", 50.0, "bpt_improvement=0.30"))
    hist.append(_record("router", 50.0, "bpt_improvement=0.20"))
    problems = regress.run_gate(hist.path, log=lambda *a, **k: None)
    assert len(problems) == 1 and "bpt_improvement" in problems[0]
    # speedups are wall-derived noise: they ride the 15% wall rule,
    # not the 1% ratio rule
    assert not regress.is_ratio_key("speedup")
    assert regress.is_ratio_key("compression_ratio")


def test_bench_regress_passes_within_budget_and_vacuously(tmp_path):
    regress = _load_tool("bench_regress")
    # missing file: empty trajectory passes
    assert regress.run_gate(tmp_path / "none.jsonl",
                            log=lambda *a, **k: None) == []
    hist = BenchHistory(tmp_path / "history.jsonl")
    hist.append(_record("b", 100.0))            # single record: vacuous
    assert regress.run_gate(hist.path, log=lambda *a, **k: None) == []
    hist.append(_record("b", 110.0))            # +10% < 15% budget
    assert regress.run_gate(hist.path, log=lambda *a, **k: None) == []
    assert regress.main(["--history", str(hist.path)]) == 0


def test_bench_regress_separates_quick_and_full(tmp_path):
    """Quick and full runs are different workloads — a full run 10x the
    quick wall must not read as a regression."""
    regress = _load_tool("bench_regress")
    hist = BenchHistory(tmp_path / "history.jsonl")
    for _ in range(3):
        hist.append(_record("b", 100.0, quick=True))
    hist.append(_record("b", 1000.0, quick=False))
    assert regress.run_gate(hist.path, log=lambda *a, **k: None) == []


# ----------------------------------------------------- roofline attainment
def test_roofline_t_star_and_attainment():
    from repro.launch.hlo_analysis import Roofline
    r = Roofline(hlo_flops=1e12, hlo_bytes=1e9, collective_bytes=0.0,
                 n_chips=1, device_kind="TPU v5 lite")
    assert r.t_star == pytest.approx(
        max(r.t_compute, r.t_memory, r.t_collective))
    assert r.attainment(r.t_star * 2) == pytest.approx(0.5)
    # missing/invalid measurements read as 0.0 ("no attainment shown"),
    # never a crash
    assert r.attainment(None) == 0.0
    assert r.attainment(0.0) == 0.0
    assert r.to_dict()["t_star_s"] == pytest.approx(r.t_star)


def test_roofline_peaks_keyed_by_device_kind():
    from repro.launch.hlo_analysis import Roofline, chip_peaks
    assert chip_peaks("TPU v5 lite").flops == 197e12
    assert chip_peaks("TPU v5 lite").hbm_bw == 819e9
    # an unknown chip is an error, never v5e's numbers
    with pytest.raises(ValueError, match="no published peaks"):
        chip_peaks("cpu")
    with pytest.raises(ValueError, match="no published peaks"):
        Roofline(hlo_flops=1e12, hlo_bytes=1e9, collective_bytes=0.0,
                 n_chips=1, device_kind="TPU v4")


def test_attainment_rows_from_stored_cells():
    spec = importlib.util.spec_from_file_location(
        "roofline_bench", REPO / "benchmarks" / "roofline.py")
    roofline = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(roofline)
    arch, shape = roofline.ARCH_ORDER[0], roofline.SHAPE_ORDER[0]
    # pre-§13 cell: no t_star_s recorded -> derived from the three terms
    cells = {(arch, shape): {"roofline": {
        "t_compute_s": 0.004, "t_memory_s": 0.002, "t_collective_s": 0.001,
        "bottleneck": "compute"}}}
    rows = roofline.attainment_rows(cells, {f"{arch}/{shape}": 0.008})
    assert len(rows) == 1
    a, s, t_star, measured, att, bn = rows[0]
    assert (a, s, bn) == (arch, shape, "compute")
    assert t_star == pytest.approx(0.004)
    assert att == pytest.approx(0.5)
    # cells without a measurement are skipped, not zero-attainment
    assert roofline.attainment_rows(cells, {}) == []
    table = roofline.attainment_table(cells, {f"{arch}/{shape}": 0.008})
    assert "attainment" in table and "0.500" in table
