"""CPU tests of the Granite-4.0-H-Small configuration and its plain
reference (``chipbench/reference/granite_hybrid.py``): a tiny Granite-
shaped cell runs ``correct`` through ``harness.run`` with its int8
control not ``correct``, the work counts match a count by hand, the
configuration file keeps the published numbers, and the expert-layer
readers read a made-up record."""
from __future__ import annotations

import json
import pathlib
import sys
import time

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
FIX = pathlib.Path(__file__).resolve().parent / "fixtures"
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import harness  # noqa: E402
from chipbench.reference import granite_hybrid as ref  # noqa: E402

CONF = json.loads((ROOT / "chipbench/configs/granite-4.0-h-small.json")
                  .read_text())
TINY = "tiny-granite.ingest"


def test_tiny_granite_cell_is_correct_and_its_control_is_not(monkeypatch):
    cell = harness.load_cell(TINY, bench_file=FIX / "granite/BENCHMARK.json",
                             limits_dir=FIX / "limits")
    recs = []
    drive = harness.drive
    monkeypatch.setattr(harness, "drive",
                        lambda *a, **kw: recs.append(drive(*a, **kw))
                        or recs[-1])
    out = harness.run(TINY, 2000000001, 2.0, False,
                      t_start=time.perf_counter(), require_tpu=False,
                      cell=cell, control=True)
    assert out["correct"], out["compared"]
    assert out["control_correct"] is False
    rec = recs[0]
    assert rec["reference"] == "granite_hybrid"
    assert out["metrics"]["bits_per_token"]["value"] > 0
    c = rec["registry"]["counters"]
    m = cell["config"]["model"]
    slots = cell["config"]["service"]["slots"]
    steps = rec["counters"]["model_steps"]
    assert c["moe.expert_rows"] == (steps * m["num_local_experts"] * slots
                                    * m["num_hidden_layers"])
    share = harness.metric_reader("moe_local_share")(rec)
    assert 0.03 < share < 0.3       # 2 of 16 held: 0.125 when uniform
    assert harness.metric_reader("moe_rows_per_routed")(rec) == \
        pytest.approx(c["moe.expert_rows"] / c["moe.routed_local"])


def test_granite_counts_by_hand():
    """One decode step of the cell (64 lanes, mean position 127.5) counted
    by hand from the published widths: 9 Mamba layers, 1 attention layer,
    10 expert layers with 9 of 72 experts held, top-10."""
    m = CONF["model"]
    D, di, N, P, H = 4096, 8192, 128, 64, 128
    mamba = D * (2 * di + 2 * N + H) + di * D              # in/out proj
    attn = D * 128 * (32 + 8 + 8) + 32 * 128 * D
    ffn_routed = D * 72 + 10 * 9 / 72 * 3 * D * 768 + 3 * D * 1536
    head = D * 100352
    ssm = 5 * H * P * N + 2 * 4 * (di + 2 * N)             # update, conv
    flops = (2 * (9 * mamba + attn + 10 * ffn_routed + head) + 9 * ssm
             + 4 * 32 * 128 * 128.5)
    assert ref.flops_per_token(m, 127.5) == pytest.approx(flops, rel=1e-12)
    assert flops == pytest.approx(3_415_435_264)

    mamba_all = mamba + 5 * (di + 2 * N) + 3 * H + di + D  # conv, vectors
    ffn_held = D * 72 + 9 * 3 * D * 768 + 3 * D * 1536 + D
    weights = 2 * (9 * mamba_all + attn + D + 10 * ffn_held + head
                   + 64 * D + D)
    state = 2 * 9 * 64 * (H * P * N * 4 + 3 * (di + 2 * N) * 2)
    kv = 64 * 129.5 * 2 * 8 * 128 * 2
    logits = 64 * 100352 * 2
    assert ref.decode_step_bytes(m, 64, 127.5) == pytest.approx(
        weights + state + kv + logits, rel=1e-12)
    assert weights + state + kv + logits == pytest.approx(9_766_933_760)


def test_config_keeps_the_published_numbers():
    """The file holds the catalog's config.json numbers at its top level
    as run, and the same in ``model``; only the depth and the experts
    held are cut, both named in ``reduced`` beside their published
    values; the program runs the file's numbers and the published
    layer pattern."""
    m = CONF["model"]
    assert CONF["reduced"] == ["num_hidden_layers", "num_local_experts"]
    assert CONF["published"] == {"num_hidden_layers": 40,
                                 "num_local_experts": 72}
    for k, v in m.items():
        if k in CONF:
            assert CONF[k] == v, k
    widths = {"hidden_size": 4096, "intermediate_size": 768,
              "shared_intermediate_size": 1536, "num_attention_heads": 32,
              "num_key_value_heads": 8, "mamba_d_state": 128,
              "mamba_d_head": 64, "mamba_n_heads": 128, "mamba_expand": 2,
              "mamba_d_conv": 4, "num_experts_per_tok": 10,
              "router_outputs": 72, "vocab_size": 100352}
    assert {k: m[k] for k in widths} == widths
    assert len(m["layer_types"]) == 40
    assert m["layer_pattern"] == "".join(
        "A" if t == "attention" else "M"
        for t in m["layer_types"][:m["num_hidden_layers"]])
    cfg = harness.program_config(CONF)
    assert list(cfg.layer_types[:cfg.n_layers]) == \
        m["layer_types"][:m["num_hidden_layers"]]
    assert (cfg.n_layers, cfg.n_experts, cfg.experts_held, cfg.top_k,
            cfg.head_dim, cfg.position_embedding, cfg.attn_scale) == \
        (10, 72, 9, 10, 128, "nope", 0.0078125)
    assert cfg.ssm_heads == m["mamba_n_heads"]
    assert (cfg.ssm_conv_bias, cfg.ssm_gated_norm) == (True, True)
    import jax
    from repro.models.schema import abstract_params
    want = jax.tree_util.tree_map(lambda a: a.shape, abstract_params(cfg))
    assert ref.leaf_shapes(harness.model_spec(CONF, cfg)) == want


def test_expert_layer_readers_on_a_made_up_record():
    rec = {"counters": {"lane_steps": 6400, "model_steps": 100},
           "model": {"num_experts_per_tok": 10, "num_hidden_layers": 10},
           "registry": {"counters": {"moe.routed_local": 80000,
                                     "moe.expert_rows": 576000}}}
    assert harness.metric_reader("moe_local_share")(rec) == \
        pytest.approx(0.125)
    assert harness.metric_reader("moe_rows_per_routed")(rec) == \
        pytest.approx(7.2)
    dense = {"counters": {"lane_steps": 6400, "model_steps": 100},
             "registry": {"counters": {}}}
    for name in ("moe_local_share", "moe_rows_per_routed"):
        assert harness.metric_reader(name)(dense) is None
        assert harness.metric_reader(name)(dict(dense, registry=None)) \
            is None
