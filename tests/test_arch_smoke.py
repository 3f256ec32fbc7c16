"""Per-assigned-architecture smoke tests: REDUCED same-family config, one
forward + one train step on CPU; output shapes + no NaNs (deliverable f)."""
import jax
import numpy as np
import pytest

from repro.configs.base import SHAPES
from repro.configs.registry import ARCH_IDS, get_config, get_smoke_config
from repro.launch.mesh import local_mesh
from repro.models import forward, init_params
from repro.train.train_loop import make_train_step


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_forward_and_train_step(arch):
    cfg = get_smoke_config(arch)
    assert cfg.family == get_config(arch).family
    params = init_params(cfg, jax.random.PRNGKey(0))
    B, S = 2, 24
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                          cfg.vocab_size)}
    if cfg.family == "vlm":
        batch["img_embeds"] = jax.random.normal(
            jax.random.PRNGKey(2), (B, cfg.n_img_tokens, cfg.d_model))
    if cfg.family == "encdec":
        batch["frames"] = jax.random.normal(
            jax.random.PRNGKey(2), (B, 8, cfg.d_model))
    logits = forward(params, cfg, batch)
    exp_S = S if cfg.family != "vlm" else S
    assert logits.shape == (B, exp_S, cfg.padded_vocab)
    assert np.isfinite(np.asarray(logits, np.float32)).all(), "NaN in logits"

    from repro.train.optimizer import AdamWConfig, init_opt_state
    step = make_train_step(cfg, local_mesh(), opt=AdamWConfig(),
                           global_batch=B)
    opt_state = init_opt_state(params, AdamWConfig())
    params2, opt_state, metrics = step(params, opt_state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["grad_norm"]))


def test_full_configs_match_assignment():
    """Exact assigned hyperparameters (spot checks)."""
    c = get_config("qwen3_moe_235b_a22b")
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads) == (94, 4096, 64, 4)
    assert (c.n_experts, c.top_k, c.d_ff, c.vocab_size) == (128, 8, 1536, 151936)
    c = get_config("llava_next_34b")
    assert (c.n_layers, c.d_model, c.n_heads, c.d_ff) == (60, 7168, 56, 20480)
    c = get_config("zamba2_7b")
    assert (c.n_layers, c.d_model, c.ssm_state) == (81, 3584, 64)
    c = get_config("h2o_danube_3_4b")
    assert c.sliding_window == 4096 and c.d_model == 3840
    c = get_config("whisper_large_v3")
    assert c.n_enc_layers == 32 and c.vocab_size == 51866
    c = get_config("mamba2_130m")
    assert c.ssm_state == 128 and c.n_heads == 0
    assert all(SHAPES)  # 4 shapes defined


def test_param_counts_sane():
    from repro.models.schema import count_params
    expected = {"qwen3_moe_235b_a22b": 235e9, "qwen3_14b": 15e9,
                "llava_next_34b": 35e9, "deepseek_7b": 6.9e9,
                "mamba2_130m": 0.13e9, "qwen3_1_7b": 1.7e9,
                "zamba2_7b": 6.8e9, "h2o_danube_3_4b": 4.0e9}
    for arch, want in expected.items():
        got = count_params(get_config(arch))
        assert abs(got - want) / want < 0.15, (arch, got, want)
