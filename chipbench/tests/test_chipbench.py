"""CPU tests of the chip benchmark's harness (run with
``python -m pytest chipbench/tests``).

The cells here are the fixtures' tiny Qwen3-shaped model; the harness
skips its look for a TPU and drives the rest of a run as on the chip.
"""
from __future__ import annotations

import json
import math
import os
import pathlib
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
FIX = pathlib.Path(__file__).resolve().parent / "fixtures"
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import flops, harness, trace  # noqa: E402
from chipbench.reference import dense  # noqa: E402

CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
HOST_LAYERS = ("transfer_mb_per_step", "coder_host_ms", "scheduler_host_ms",
               "step_compiles", "idle_transfer_ms", "idle_coder_ms",
               "idle_scheduler_ms")


def tiny_cell(name: str, **limits) -> dict:
    cell = harness.load_cell(name, bench_file=FIX / "BENCHMARK.json",
                             limits_dir=FIX / "limits")
    cell["limits"] = dict(cell["limits"], **limits)
    return cell


def tiny_run(name: str, seed: int = 20240611, seconds: float = 2.0,
             **limits) -> dict:
    return harness.run(name, seed, seconds, False,
                       t_start=time.perf_counter(), require_tpu=False,
                       cell=tiny_cell(name, **limits))


# ------------------------------------------------------------------ files
# Reference modules a new configuration names, written as new files: the
# dense decoder with its work counted three times, and one whose head is
# scaled by 2 (a reference the program does not compute).
REF_TIMES_3 = '''"""The dense decoder; its work counted three times."""
from chipbench.reference import dense
from chipbench.reference.dense import (PROGRAM_FIELDS, forward, make_weights,
                                       mm, mm_int8, sample_documents)


def flops_per_token(m, pos):
    return 3 * dense.flops_per_token(m, pos)


def decode_step_bytes(m, lanes, pos):
    return 3 * dense.decode_step_bytes(m, lanes, pos)
'''
REF_HEAD_X2 = '''"""The dense decoder with its head scaled by 2."""
from chipbench.reference import dense
from chipbench.reference.dense import (PROGRAM_FIELDS, decode_step_bytes,
                                       flops_per_token, make_weights, mm,
                                       mm_int8, sample_documents)


def forward(m, params, tokens, mm=mm):
    return 2.0 * dense.forward(m, params, tokens, mm)
'''


def _add_tiny_cell(root: pathlib.Path, reference: str, source: str) -> str:
    """Copy the benchmark under ``root`` and add, as new files and entries
    only, a tiny configuration naming the reference module ``reference``
    (written from ``source``), a deeper-queue mix, its cell's limits and a
    per-layer metric; returns the new cell's name."""
    shutil.copytree(ROOT / "chipbench", root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    conf = json.loads((FIX / "tiny.json").read_text())
    (root / f"chipbench/reference/{reference}.py").write_text(source)
    (root / "chipbench/configs/tiny-new.json").write_text(
        json.dumps(dict(conf, name="tiny-new", reference=reference)))
    mix = json.loads((ROOT / "chipbench/traffic/ingest.json").read_text())
    mix["outstanding_chunks_per_slot"] = 5
    (root / "chipbench/traffic/deep-queue.json").write_text(json.dumps(mix))
    (root / "chipbench/limits/tiny-new.deep-queue.json").write_text(
        (FIX / "limits/tiny.ingest.json").read_text())
    (root / "chipbench/metrics/refills_per_step.py").write_text(
        "def read(rec):\n    return 7.0\n")
    bench["configs"].append({"name": "tiny-new", "source": "test",
                             "file": "chipbench/configs/tiny-new.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny-new.deep-queue",
                               "config": "tiny-new", "traffic": "deep-queue",
                               "chips": 1, "why": "test"})
    bench["per_layer"].append({
        "name": "refills_per_step", "unit": "ratio", "better": "higher",
        "source": "program_counter", "layer": "scheduler",
        "moves": "tokens_per_s", "workloads": ["tiny-new.deep-queue"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return "tiny-new.deep-queue"


def _run_keeping_record(name, cell, monkeypatch):
    """``harness.run`` of ``cell`` on the CPU, and the record it drove."""
    recs = []
    drive = harness.drive

    def keep(*a, **kw):
        recs.append(drive(*a, **kw))
        return recs[-1]
    monkeypatch.setattr(harness, "drive", keep)
    out = harness.run(name, 20240611, 2.0, False, t_start=time.perf_counter(),
                      require_tpu=False, cell=cell)
    return out, recs[0]


def test_loader_finds_new_config_mix_and_metric(tmp_path, monkeypatch):
    """A configuration, its plain reference, a traffic mix and a per-layer
    metric are added by adding files and BENCHMARK.json entries; no
    existing file changes. The cell runs end to end, checked against the
    reference its configuration names, and the roofline and utilization
    readers take that reference's work counts."""
    before = {p: p.read_bytes()
              for p in (ROOT / "chipbench").rglob("*")
              if p.is_file() and "__pycache__" not in p.parts}
    name = _add_tiny_cell(tmp_path, "tiny_times3", REF_TIMES_3)
    for p, data in before.items():
        copy = tmp_path / p.relative_to(ROOT)
        assert copy.read_bytes() == data, f"{copy} differs"

    cell = harness.load_cell(name, root=tmp_path)
    assert cell["config"]["name"] == "tiny-new"
    assert cell["traffic"]["outstanding_chunks_per_slot"] == 5
    assert [m["name"] for m in cell["per_layer"]] == ["refills_per_step"]
    assert {"tokens_per_s", "bits_per_token", "setup_s"} <= \
        {m["name"] for m in cell["end_to_end"]}
    assert harness.metric_reader("refills_per_step",
                                 cell["bench_dir"])({}) == 7.0

    out, rec = _run_keeping_record(name, cell, monkeypatch)
    assert out["correct"], out["compared"]
    assert rec["reference"] == "tiny_times3"
    assert out["metrics"]["bits_per_token"]["value"] > 0
    # the record's readers count the named module's work: three times
    # the dense decoder's, for the same window
    rec = dict(rec, peaks=harness.peaks_for("TPU v5 lite"),
               trace={"programs": {"_decode": {"seconds": 0.9,
                                               "count": 100}}})
    for metric in ("mfu", "decode_roofline"):
        read = harness.metric_reader(metric, cell["bench_dir"])
        assert read(rec) == pytest.approx(
            3 * read(dict(rec, reference="dense")), rel=1e-12), metric
    for p, data in before.items():
        copy = tmp_path / p.relative_to(ROOT)
        assert copy.read_bytes() == data, f"{copy} changed"


def test_named_reference_decides_correct(tmp_path, monkeypatch):
    """The check computes the reference the configuration names, not the
    dense default: a reference with its head scaled by 2 gives code
    lengths the program's do not match, and the run is not correct."""
    name = _add_tiny_cell(tmp_path, "tiny_head_x2", REF_HEAD_X2)
    cell = harness.load_cell(name, root=tmp_path)
    out, rec = _run_keeping_record(name, cell, monkeypatch)
    assert rec["reference"] == "tiny_head_x2"
    assert out["correct"] is False
    assert out["compared"]["failed_jobs"]["value"] == 0
    assert out["compared"]["roundtrip_mismatched_tokens"]["value"] == 0
    assert out["compared"]["abs_gap_bits_per_token"]["value"] > \
        out["compared"]["abs_gap_bits_per_token"]["limit"]


# The dense default as it computed before a configuration could name its
# reference: work counts at mean position 127.5 over the cell's slots,
# the program fields the files set, and on the fixtures' tiny model a
# digest of the weight leaves and the code lengths of a fixed chunk set.
DENSE_PINS = {
    "qwen3-1.7b": (3470376960.0, 4411146240.0, {
        "n_layers": 28, "d_model": 2048, "n_heads": 16, "n_kv_heads": 8,
        "d_head": 128, "d_ff": 6144, "vocab_size": 151936,
        "rope_theta": 1000000, "norm_eps": 1e-06, "tie_embeddings": True,
        "qk_norm": True, "dtype": "bfloat16"}),
    "deepseek-7b": (6941696000.0, 7935361024.0, {
        "n_layers": 15, "d_model": 4096, "n_heads": 32, "n_kv_heads": 32,
        "d_head": 128, "d_ff": 11008, "vocab_size": 102400,
        "rope_theta": 10000, "norm_eps": 1e-06, "tie_embeddings": False,
        "qk_norm": False, "dtype": "bfloat16"}),
}
TINY_WEIGHTS_SHA256 = \
    "b8487c34e6451198817cb41104c19e1d5520a7a7bf3ee1e486dff2f93d7f07d7"
TINY_CHUNK_BITS = [474.46408462524414, 261.0270414352417, 13.017898559570312,
                   503.7782220840454, 76.62140083312988, 484.79073214530945,
                   295.912145614624, 134.0672082901001]


@pytest.mark.parametrize("name", sorted(DENSE_PINS))
def test_dense_default_counts_and_fields_pinned(name):
    import importlib
    conf = json.loads((ROOT / f"chipbench/configs/{name}.json").read_text())
    assert "reference" not in conf
    assert harness.reference_module(conf) is dense
    fl, by, fields = DENSE_PINS[name]
    m, lanes = conf["model"], conf["service"]["slots"]
    assert dense.flops_per_token(m, 127.5) == fl
    assert dense.decode_step_bytes(m, lanes, 127.5) == by
    peaks = harness.peaks_for("TPU v5 lite")
    rec = {"reference": "dense", "model": m, "peaks": peaks,
           "mean_pos": 127.5}
    assert flops.decode_step_seconds(rec, lanes) == \
        max(lanes * fl / 197e12, by / 819e9)
    cfg = harness.program_config(conf)
    base = importlib.import_module(conf["program"]["module"]).CONFIG
    assert cfg == base.with_(**fields)


def test_dense_default_weights_and_bits_pinned():
    import hashlib
    import jax
    conf = json.loads((FIX / "tiny.json").read_text())
    ref = harness.reference_module(conf)
    m = harness.model_spec(conf, harness.program_config(conf))
    params = ref.make_weights(m, conf["init"], conf["init"]["seed"])
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.shape).encode())
        h.update(a.view(np.uint16).tobytes())
    assert h.hexdigest() == TINY_WEIGHTS_SHA256
    chunks = np.random.default_rng(0).integers(
        0, m["vocab_size"], (8, 32)).astype(np.int32)
    valid = np.array([32, 17, 1, 32, 5, 32, 20, 9])
    bits = harness.reference_bits(ref, conf, m, chunks, valid, 4)
    np.testing.assert_allclose(bits, TINY_CHUNK_BITS, rtol=1e-6)


def test_suffixed_metric_copies_share_a_reader():
    read = harness.metric_reader("step_ms.read")
    assert read({"counters": {"model_steps": 4}, "poll_s": 0.1}) == \
        pytest.approx(25.0)


def test_benchmark_cells_resolve():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell["end_to_end"] and cell["per_layer"]
        assert "setup_s" in {m["name"] for m in cell["end_to_end"]}
        for m in cell["end_to_end"] + cell["per_layer"]:
            harness.metric_reader(m["name"])
        cfg = harness.program_config(cell["config"])
        assert cfg.n_layers == cell["config"]["model"]["num_hidden_layers"]


def test_peaks_table():
    v5e = harness.peaks_for("TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud documentation, TPU v5e" in v5e["source"]
    with pytest.raises(SystemExit):
        harness.peaks_for("TPU v99")


# ---------------------------------------------------------------- flops
def _model(name):
    return json.loads((ROOT / f"chipbench/configs/{name}.json").read_text()
                      )["model"]


def test_qwen3_flops_and_bytes_by_hand():
    m = _model("qwen3-1.7b")
    # per layer: q 2048x2048, k and v 2048x1024, o 2048x2048, MLP 3 x
    # 2048x6144 = 4,194,304 x 2 + 2,097,152 x 2 + 37,748,736
    per_layer = 2 * 2048 * 2048 + 2 * 2048 * 1024 + 3 * 2048 * 6144
    assert per_layer == 50_331_648
    assert dense.layer_params(m) == 28 * per_layer == 1_409_286_144
    assert dense.head_params(m) == 2048 * 151936 == 311_164_928
    assert dense.kv_bytes_per_position(m) == 2 * 28 * 8 * 128 * 2 == 114_688
    # a token at position 99: 2 x (1,409,286,144 + 311,164,928) matrix
    # FLOPs plus 4 x 28 layers x 16 heads x 128 x 100 keys for attention
    assert dense.flops_per_token(m, 99) == \
        2 * 1_720_451_072 + 4 * 28 * 16 * 128 * 100
    # 64 lanes at mean position 127.5: weights + 64 embedding rows, the
    # cache read to each position and written at it, and the logits
    want = ((1_720_451_072 + 64 * 2048) * 2 + 64 * 129.5 * 114_688
            + 64 * 151936 * 2)
    assert dense.decode_step_bytes(m, 64, 127.5) == want
    peaks = harness.peaks_for("TPU v5 lite")
    rec = {"reference": "dense", "model": m, "peaks": peaks,
           "mean_pos": 127.5}
    assert flops.decode_step_seconds(rec, 64) == pytest.approx(want / 819e9)


def test_deepseek_flops_and_bytes_by_hand():
    m = _model("deepseek-7b")
    per_layer = 4 * 4096 * 4096 + 3 * 4096 * 11008
    assert per_layer == 202_375_168
    assert dense.layer_params(m) == 15 * per_layer == 3_035_627_520
    assert dense.head_params(m) == 4096 * 102400 == 419_430_400
    assert dense.kv_bytes_per_position(m) == 2 * 15 * 32 * 128 * 2 \
        == 245_760
    assert dense.flops_per_token(m, 0) == \
        2 * (3_035_627_520 + 419_430_400) + 4 * 15 * 32 * 128
    # the untied input embedding is read only at the lanes' rows
    want = ((3_455_057_920 + 32 * 4096) * 2 + 32 * 2 * 245_760
            + 32 * 102400 * 2)
    assert dense.decode_step_bytes(m, 32, 0) == want


def test_mfu_and_roofline_readers():
    m = _model("qwen3-1.7b")
    peaks = harness.peaks_for("TPU v5 lite")
    rec = {"model": m, "peaks": peaks, "mean_pos": 127.5, "poll_s": 2.0,
           "reference": "dense",
           "counters": {"token_steps": 6400, "model_steps": 100,
                        "lane_steps": 6400},
           "trace": {"programs": {"_decode": {"seconds": 0.9,
                                              "count": 100}}}}
    mfu = harness.metric_reader("mfu")(rec)
    assert mfu == pytest.approx(
        100 * 6400 * dense.flops_per_token(m, 127.5) / (2.0 * 197e12))
    roof = harness.metric_reader("decode_roofline")(rec)
    assert roof == pytest.approx(
        100 * flops.decode_step_seconds(rec, 64) / 0.009)
    rec["trace"] = None
    assert harness.metric_reader("decode_roofline")(rec) is None


# ---------------------------------------------------------------- trace
def test_trace_reduction_on_chip_fixture():
    """Busy, idle and per-program times of a cut of a chip trace, against
    the numbers its events give by hand (``fixtures/trace_expected.json``)."""
    red = trace.reduce_file(FIX / "trace_cut.xplane.pb")
    want = json.loads((FIX / "trace_expected.json").read_text())
    assert red["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert red["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert red["devices"] == 1
    for name, p in want["programs"].items():
        assert red["programs"][name]["count"] == p["count"]
        assert red["programs"][name]["seconds"] == \
            pytest.approx(p["seconds"], rel=1e-9)
    assert sum(g for _, g in red["idle_gaps"]) <= \
        red["window_s"] - red["busy_s"] + 1e-9
    for name, _ in red["idle_gaps"]:
        assert set(name.split(">")) <= set(want["host_spans"])
    # the longest gap of the cut is the wait for the logits to reach
    # the host, inside the benchmark's poll()
    assert red["idle_gaps"][0][0] == "bench.poll>np.asarray(jax.Array)"
    bd = trace.breakdown(red)
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


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


def test_trace_reduction_by_hand():
    host = _Plane("/host:CPU", [_Line("main", [
        _Ev("bench.traced", 1000, 10000), _Ev("bench.poll", 1000, 6000),
        _Ev("bench.submit", 7000, 4000)])])
    dev = _Plane("/device:TPU:0", [
        _Line("XLA Modules", [_Ev("jit__decode(3)", 500, 3500),
                              _Ev("jit_topk_cdf(9)", 5000, 1000)]),
        _Line("XLA Ops", [_Ev("fusion.1", 500, 2000),
                          _Ev("fusion.2", 2000, 2000),
                          _Ev("sort", 5000, 1000)])])
    red = trace.reduce_planes([host, dev])
    assert red["window_s"] == pytest.approx(10e-6)
    # ops clipped to [1000, 11000): 1000-4000 and 5000-6000 => 4 us busy
    assert red["busy_s"] == pytest.approx(4e-6)
    assert red["programs"]["_decode"] == {"seconds": pytest.approx(3e-6),
                                          "count": 1}
    assert red["programs"]["topk_cdf"]["count"] == 1
    assert red["idle_gaps"][0] == ["bench.submit", pytest.approx(5e-6)]
    assert red["idle_gaps"][1] == ["bench.poll", pytest.approx(1e-6)]


# ------------------------------------------------------------ reference
@pytest.mark.parametrize("qk_norm", [True, False])
@pytest.mark.parametrize("tied", [True, False])
def test_reference_matches_program_forward(qk_norm, tied):
    import jax
    import jax.numpy as jnp
    from repro.configs.qwen3_1_7b import CONFIG
    from repro.models import api as model_api

    m = {"hidden_size": 64, "intermediate_size": 96, "num_hidden_layers": 2,
         "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
         "vocab_size": 200, "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
         "tie_word_embeddings": tied, "qk_norm": qk_norm,
         "vocab_pad_multiple": 1}
    cfg = CONFIG.with_(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                       d_head=16, d_ff=96, vocab_size=200, rope_theta=1e4,
                       qk_norm=qk_norm, tie_embeddings=tied, dtype="float32",
                       head_pad_multiple=1, vocab_pad_multiple=1)
    init = {"embed_std": 0.5, "lm_head_std": 0.5, "norm_jitter": 0.1}
    params = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32),
                                    dense.make_weights(m, init, 7))
    harness.check_layout(params, cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0, 200)
    with jax.default_matmul_precision("highest"):
        want = model_api.forward(params, cfg, {"tokens": tokens})
        got = dense.forward(m, params, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_greedy_pool_follows_the_reference():
    """The pool sampler's cached decode step computes the reference's
    model: greedy documents (top-1) are the reference forward's argmax
    at nearly every position (bfloat16 against float32 ties aside)."""
    import jax.numpy as jnp
    conf = json.loads((FIX / "tiny.json").read_text())
    m = dict(conf["model"], vocab_pad_multiple=1)
    params = dense.make_weights(m, conf["init"], 5)
    bos = m["vocab_size"] - 1
    docs = dense.sample_documents(m, params, n_docs=8, batch=8,
                                  n_tokens=32, top_k=1, bos=bos, seed=3)
    inp = np.concatenate([np.full((8, 1), bos), docs[:, :-1]], 1)
    logits = dense.forward(m, params, jnp.asarray(inp, jnp.int32))
    agree = float((np.asarray(logits).argmax(-1) == docs).mean())
    assert agree > 0.95, agree


def test_reference_code_length_matches_coder_formula():
    """A chunk's reference bits equal precision - log2(freq) summed by
    hand over the quantized top-K CDF."""
    import jax.numpy as jnp
    from chipbench.reference import codelen
    logits = jnp.asarray([[[3.0, 1.0, 0.5, -2.0, -9.0]]])
    k, prec = 2, 8
    bits = float(codelen.token_bits(logits, jnp.asarray([[1]]), k, prec)[0,
                                                                          0])
    p = np.exp([3.0, 1.0, 0.5, -2.0, -9.0])
    p /= p.sum()
    pmf = np.asarray([p[0], p[1], 1 - p[0] - p[1]])
    cum = np.cumsum(pmf)
    pts = np.floor(cum * (2 ** prec - 3) + 0.5) + np.arange(1, 4)
    freq = np.diff(pts, prepend=0)
    assert bits == pytest.approx(prec - math.log2(freq[1]), abs=1e-4)
    esc = float(codelen.token_bits(logits, jnp.asarray([[4]]), k, prec)[0,
                                                                         0])
    assert esc == pytest.approx(prec - math.log2(freq[2]) + 3, abs=1e-4)


# --------------------------------------------------------------- a run
def test_command_refuses_off_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "qwen3-1.7b.ingest", "--seed", "3000000000", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "not a TPU" in p.stderr


def test_command_refuses_without_the_program(tmp_path):
    """A checkout that holds only BENCHMARK.json and the benchmark's
    directory has no system to measure."""
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "qwen3-1.7b.ingest", "--seed", "1", "--seconds", "1", "--trace",
         "0"], cwd=tmp_path, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


@pytest.mark.parametrize("name", ["tiny.ingest", "tiny.readback"])
def test_result_line_keys_and_correct(name):
    out = tiny_run(name)
    assert set(out) - {"compared"} == CONTRACT_KEYS
    assert list(out)[-1] == "compared"
    assert out["correct"], out["compared"]
    assert out["failed"] == 0 and out["attempted"] > 0
    want = {m["name"] for m in tiny_cell(name)["end_to_end"]}
    assert set(out["metrics"]) == want
    for v in out["metrics"].values():
        assert set(v) == {"value", "unit"} and v["value"] > 0
    assert set(out["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    json.dumps(out)


def test_traced_run_reads_host_layers():
    """A traced run's record carries the service registry's window deltas
    and the program-span split of its trace: each host-layer reader reads
    a value, and the window compiled nothing."""
    out = harness.run("tiny.ingest", 20240611, 2.0, True,
                      t_start=time.perf_counter(), require_tpu=False,
                      cell=tiny_cell("tiny.ingest"))
    assert out["correct"], out["compared"]
    for name in HOST_LAYERS:
        assert out["metrics"][name]["value"] >= 0, name
    assert out["metrics"]["step_compiles"]["value"] == 0
    assert out["metrics"]["transfer_mb_per_step"]["value"] > 0


def test_every_seed_gets_the_same_work():
    """Seeds (large ones too) change the order of the job sizes, not
    their amount: each deck deals the same job sizes, and an open loop
    offers the same number of jobs, the last one due at the window's
    close."""
    import itertools
    from chipbench import traffic
    a = harness.seeds(2 ** 33 + 12345)
    assert a == harness.seeds(2 ** 33 + 12345)
    assert all(0 <= v < 2 ** 31 for v in a.values())
    assert len(set(a.values())) == 3
    assert a != harness.seeds(7)
    for name in ("qwen3-1.7b", "deepseek-7b"):     # one model a config
        init = json.loads((ROOT / f"chipbench/configs/{name}.json"
                           ).read_text())["init"]
        assert isinstance(init["seed"], int)
    for mix in ("ingest", "readback"):
        m = json.loads((ROOT / f"chipbench/traffic/{mix}.json").read_text())
        deck = sorted(traffic.deck_sizes(m["lengths"]))
        for seed in (1, 2 ** 33 + 7):
            rng = np.random.default_rng(seed)
            got = itertools.islice(traffic.size_stream(m["lengths"], rng),
                                   len(deck))
            assert sorted(got) == deck
    m = json.loads((ROOT / "chipbench/traffic/readback.json").read_text())
    d1 = traffic.arrival_times(m, 40.0, np.random.default_rng(1))
    d2 = traffic.arrival_times(m, 40.0, np.random.default_rng(2))
    assert len(d1) == len(d2) == round(m["rate_jobs_per_s"] * 40.0)
    assert d1[-1] == pytest.approx(40.0) and d2[-1] == pytest.approx(40.0)
    assert np.all(np.diff(d1) >= 0)


# --------------------------------------------- faults and the control
def _fault_state_unchanged(monkeypatch):
    from repro.serve.engine import ModelPredictor
    orig = ModelPredictor.decode_step

    def step(self, state, prev):
        logits, _ = orig(self, state, prev)
        return logits, state
    monkeypatch.setattr(ModelPredictor, "decode_step", step)


def _fault_half_batch(monkeypatch):
    from repro.serve.engine import ModelPredictor
    orig = ModelPredictor.decode_step

    def step(self, state, prev):
        logits, state = orig(self, state, prev)
        logits = np.array(logits)
        logits[logits.shape[0] // 2:] = 0.0
        return logits, state
    monkeypatch.setattr(ModelPredictor, "decode_step", step)


def _fault_container_altered(monkeypatch):
    import repro.service.api as api
    orig = api.write_container

    def write(streams, **kw):
        streams = list(streams)
        if streams and streams[0]:
            s = bytearray(streams[0])
            s[0] ^= 0x10
            streams[0] = bytes(s)
        return orig(streams, **kw)
    monkeypatch.setattr(api, "write_container", write)


def _fault_token_altered(monkeypatch):
    from repro.core import rans
    orig = rans.BatchedRansDecoder.get
    calls = {"n": 0}

    def get(self, cdfs, precision, mask):
        slots = orig(self, cdfs, precision, mask)
        calls["n"] += 1
        if calls["n"] % 50 == 0:
            slots = np.where(mask, np.maximum(slots - 1, 0) + (slots == 0),
                             slots)
        return slots
    monkeypatch.setattr(rans.BatchedRansDecoder, "get", get)


@pytest.mark.parametrize("name,fault", [
    ("tiny.ingest", _fault_state_unchanged),
    ("tiny.ingest", _fault_half_batch),
    ("tiny.ingest", _fault_container_altered),
    ("tiny.readback", _fault_state_unchanged),
    ("tiny.readback", _fault_token_altered),
])
def test_planted_fault_is_not_correct(name, fault, monkeypatch):
    fault(monkeypatch)
    out = tiny_run(name, seed=77)
    assert out["correct"] is False, out["compared"]


@pytest.mark.parametrize("name", ["tiny.ingest", "tiny.readback"])
def test_control_fails_the_limits(name):
    """The reference in int8, put in the program's place and judged by
    the harness's own limits and rule, is not correct on any seed, while
    the program on the same chunks is."""
    lim = tiny_cell(name)["limits"]
    for seed in (101, 102, 103):
        out = harness.run(name, seed, 2.0, False,
                          t_start=time.perf_counter(), require_tpu=False,
                          cell=tiny_cell(name), control=True)
        assert out["correct"], out["compared"]
        assert out["control_correct"] is False, out["control"]
        assert set(out["control"]) == set(lim["compare"])
        assert any(c["value"] is None or c["value"] > c["limit"]
                   for c in out["control"].values()), out["control"]


@pytest.mark.parametrize("readings,ok", [
    ({"a": 0.5, "b": 0}, True),
    ({"a": 0.5, "b": 1}, False),
    ({"a": math.nan, "b": 0}, False),
    ({"a": None, "b": 0}, False),
])
def test_judge_rule(readings, ok):
    """One rule for the program and the control: every reading at or
    under its limit; a missing or non-finite reading fails."""
    compared, correct = harness.judge(readings, {"a": 0.5, "b": 0})
    assert correct is ok
    assert list(compared) == list(readings)
