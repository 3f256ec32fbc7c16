"""CPU tests of the program-span reduction (``chipbench/spans.py``), the
readers of the host-layer metrics, and ``chipbench/layers.py`` on the
fixtures' tiny cell (run with ``python -m pytest chipbench/tests``)."""
from __future__ import annotations

import json
import pathlib
import sys
import time

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
FIX = pathlib.Path(__file__).resolve().parent / "fixtures"
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import harness, layers, spans  # noqa: E402


class _Ev:
    def __init__(self, name, start_ns, duration_ns):
        self.name, self.start_ns, self.duration_ns = name, start_ns, \
            duration_ns


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class _Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


def _planes(host_events, ops):
    host = _Plane("/host:CPU", [_Line("main", [
        _Ev(n, s, d) for n, s, d in host_events])])
    dev = _Plane("/device:TPU:0", [_Line("XLA Ops", [
        _Ev(f"fusion.{i}", s, d) for i, (s, d) in enumerate(ops)])])
    return [host, dev]


def test_idle_split_by_hand():
    """Window [0, 100). Busy [10, 30) and [60, 70). Step one [5, 50)
    holds model [5, 20), then cdf [20, 35) > transfer [25, 30), coder
    [35, 45); step two [55, 90) holds model [55, 70) and coder [80, 88).
    Idle: [0, 10) outside (5) and model (5); [30, 60) cdf (5), coder
    (10), step (5), outside (5), model (5); [70, 100) step (10), coder
    (8), step (2), outside (10). The gap [30, 60) straddles both steps
    and the stretch between them, and idle falls outside every
    service.step."""
    host = [("bench.traced", 0, 100), ("bench.poll", 5, 45),
            ("service.step", 5, 45), ("model.decode_step", 5, 15),
            ("cdf.build", 20, 15), ("transfer.cdf_to_host", 25, 5),
            ("np.asarray", 25, 5), ("coder.step", 35, 10),
            ("service.step", 55, 35), ("model.decode_step", 55, 15),
            ("coder.step", 80, 8)]
    red = spans.reduce_planes(_planes(host, [(10, 20), (60, 10)]))
    ns = 1e-9
    assert red["window_s"] == pytest.approx(100 * ns)
    assert red["idle_s"] == pytest.approx(70 * ns)
    assert red["steps"] == red["decode_steps"] == 2
    want = {"none": 20, "model.decode_step": 10, "cdf.build": 5,
            "coder.step": 18, "service.step": 17}
    assert red["idle_by_span"].keys() == want.keys()
    for k, v in want.items():
        assert red["idle_by_span"][k] == pytest.approx(v * ns), k
    assert red["idle"] == {"transfer": pytest.approx(5 * ns),
                           "coder": pytest.approx(18 * ns),
                           "scheduler": pytest.approx(47 * ns)}
    assert sum(red["idle"].values()) == pytest.approx(red["idle_s"])


def test_innermost_nesting_and_clipping():
    """Spans that start before the window are clipped to it; a span
    nested in another wins over it, and the parent resumes after it."""
    segs = spans.innermost([(-5, 50, "service.step"),
                            (10, 20, "coder.step"),
                            (20, 30, "rans.flush_slot"),
                            (40, 200, "model.decode_step")], 0, 100)
    assert segs == [(0, 10, "service.step"), (10, 20, "coder.step"),
                    (20, 30, "rans.flush_slot"), (30, 40, "service.step"),
                    (40, 50, "model.decode_step"), (50, 100, "none")]
    # a partition of the window, in order
    assert all(a[1] == b[0] for a, b in zip(segs, segs[1:]))


def test_program_without_spans_reads_nothing():
    """An older program mirrors no program spans: every idle nanosecond
    is ``none`` and no service.step is counted, so the idle readers
    return None rather than a number."""
    red = spans.reduce_planes(_planes(
        [("bench.traced", 0, 100), ("bench.poll", 0, 90)], [(10, 40)]))
    assert red["steps"] == 0
    assert red["idle_by_span"] == {"none": pytest.approx(60e-9)}
    rec = {"spans": red, "counters": {"model_steps": 9}}
    for name in ("idle_transfer_ms", "idle_coder_ms", "idle_scheduler_ms"):
        assert harness.metric_reader(name)(rec) is None


def test_sampled_step_spans_read_nothing():
    """A program that opens service.step on 1 model step in 16 (as the
    scheduler once did) gives no per-step idle reading."""
    host = [("bench.traced", 0, 3200)]
    for i in range(32):
        if i % 16 == 0:
            host.append(("service.step", 100 * i, 90))
        host.append(("model.decode_step", 100 * i + 10, 50))
    red = spans.reduce_planes(_planes(host, [(0, 10)]))
    assert (red["steps"], red["decode_steps"]) == (2, 32)
    assert harness.metric_reader("idle_coder_ms")({"spans": red}) is None


def _record():
    return {
        "counters": {"model_steps": 100},
        "registry": {
            "counters": {"transfer.d2h_bytes": 3_000_000_000,
                         "transfer.h2d_bytes": 1_000_000_000,
                         "scheduler.step_compiles": 1},
            "spans": {
                "service.step": {"seconds": 3.0, "count": 100},
                "service.step/model.decode_step": {"seconds": 1.2,
                                                   "count": 100},
                "service.step/cdf.build/transfer.cdf_to_host":
                    {"seconds": 0.5, "count": 100},
                "service.step/cdf.build": {"seconds": 0.6, "count": 100},
                "service.step/coder.step": {"seconds": 0.3, "count": 100},
                "service.step/service.finish_slot": {"seconds": 0.4,
                                                     "count": 20},
                "service.step/service.finish_slot/rans.flush_slot":
                    {"seconds": 0.2, "count": 10}}},
        "spans": {"steps": 50, "decode_steps": 51, "idle_s": 0.35,
                  "idle": {"transfer": 0.25, "coder": 0.05,
                           "scheduler": 0.05}},
    }


@pytest.mark.parametrize("name,want", [
    ("transfer_mb_per_step", 40.0),          # 4e9 bytes / 100 / 1e6
    ("coder_host_ms", 5.0),                  # (0.3 + 0.2) s / 100
    ("scheduler_host_ms", 5.0),              # (3 - 1.2 - .6 - .3 - .4) s
    ("idle_transfer_ms", 5.0),               # 0.25 s / 50 steps
    ("idle_coder_ms", 1.0),
    ("idle_scheduler_ms", 1.0),
    ("step_compiles", 1.0),
])
def test_host_layer_readers(name, want):
    rec = _record()
    assert harness.metric_reader(name)(rec) == pytest.approx(want)
    # a record without the program's registry or spans reads nothing
    bare = {"counters": rec["counters"], "trace": None}
    assert harness.metric_reader(name)(bare) is None


def test_scheduler_host_ms_needs_every_step():
    """A sampled service.step (fewer spans than model steps) is not a
    per-step time: the reader returns None."""
    rec = _record()
    rec["registry"]["spans"]["service.step"]["count"] = 7
    assert harness.metric_reader("scheduler_host_ms")(rec) is None


def test_layers_run_on_tiny_cell():
    """``layers.run`` drives the fixtures' tiny cell on the CPU: every
    model step of the window opened each per-step span once, the
    transfer counters moved, nothing compiled, and the idle split of the
    (device-less) trace is a partition of its window."""
    cell = harness.load_cell("tiny.ingest", bench_file=FIX / "BENCHMARK.json",
                             limits_dir=FIX / "limits")
    out = layers.run("tiny.ingest", 20240611, 2.0, time.perf_counter(),
                     require_tpu=False, cell=cell)
    json.dumps(out)
    steps = out["counters"]["model_steps"]
    assert steps > 0
    sp = out["registry"]["spans"]
    for path in ("service.step", "service.step/model.decode_step",
                 "service.step/cdf.build",
                 "service.step/cdf.build/transfer.cdf_to_host",
                 "service.step/coder.step"):
        assert sp[path]["count"] == steps, path
    red = out["spans"]
    assert red["idle_s"] == pytest.approx(red["window_s"])   # no device
    assert sum(red["idle"].values()) == pytest.approx(red["idle_s"])
    assert red["steps"] > 0
    m = out["metrics"]
    assert m["transfer_mb_per_step"] > 0
    assert m["step_compiles"] == 0
    for name in ("coder_host_ms", "scheduler_host_ms", "idle_transfer_ms",
                 "idle_coder_ms", "idle_scheduler_ms"):
        assert m[name] >= 0, name
