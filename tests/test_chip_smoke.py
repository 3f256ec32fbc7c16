"""chip_smoke.py's phases at a tiny width on the CPU, its refusal to run
off a TPU, the compile-cache placement rule, and kernels that never
interpret in place of the chip."""
import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]


def _chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_phase_service_round_trips_at_smoke_width(tmp_path):
    from repro.configs.qwen3_1_7b import SMOKE_CONFIG
    smoke = _chip_smoke()
    dev = jax.devices()[0]
    res = smoke.phase_service(SMOKE_CONFIG, dev, tmp_path, lanes=4,
                              gen_tokens=64, slots=4, chunk_size=32)
    assert res["round_trip"] == "byte-identical"
    assert res["tokens"] == 4 * 64
    assert res["param_leaves"] > 0 and res["cache_leaves"] > 0
    assert res["container_bytes"] == sum(
        p.stat().st_size for p in tmp_path.glob("service_lane*.llmc"))
    assert res["bits_per_token"] > 0


def test_main_refuses_cpu(capsys):
    smoke = _chip_smoke()
    assert jax.devices()[0].platform != "tpu"
    with pytest.raises(SystemExit) as e:
        smoke.main()
    assert e.value.code not in (0, None)
    out = capsys.readouterr().out
    assert '"ok": true' not in out
    assert "platform=cpu" in out


@pytest.fixture
def cache_dir_config():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_leaves_env_dir_alone(monkeypatch, cache_dir_config,
                                            tmp_path):
    from repro.compile_cache import configure_compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert configure_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_repo_dir(monkeypatch, cache_dir_config):
    from repro.compile_cache import configure_compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = str(REPO / ".jax_cache")
    assert configure_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want


@pytest.mark.parametrize("kernel", ["flash_attention", "decode_attention",
                                    "ssd_intra", "cdf_points", "topk_cdf"])
def test_pallas_impl_raises_off_tpu(kernel):
    from repro.kernels import ops
    assert jax.default_backend() != "tpu"
    x = jnp.ones((1, 2, 8, 4))
    dt = jnp.ones((1, 8, 2))
    lg = jnp.asarray(np.random.default_rng(0).normal(size=(2, 256)),
                     jnp.float32)
    call = {
        "flash_attention": lambda: ops.flash_attention(x, x, x,
                                                       impl="pallas"),
        "decode_attention": lambda: ops.decode_attention(
            x[:, :, 0], x, x, jnp.ones((1,), jnp.int32), impl="pallas"),
        "ssd_intra": lambda: ops.ssd_intra(
            jnp.ones((1, 8, 2, 4)), dt, jnp.ones((2,)), jnp.ones((1, 8, 4)),
            jnp.ones((1, 8, 4)), impl="pallas"),
        "cdf_points": lambda: ops.cdf_points(lg, 16, impl="pallas"),
        "topk_cdf": lambda: ops.topk_cdf(lg, 8, 16, impl="pallas"),
    }[kernel]
    with pytest.raises(RuntimeError, match="needs a TPU backend"):
        call()
