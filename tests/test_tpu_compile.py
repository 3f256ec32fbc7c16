"""Compile-only checks for one TPU v5e chip, described and not attached.

The TPU compiler refuses what the interpreter and the CPU backend accept:
block shapes off the (8, 128) tile, primitives Mosaic cannot lower, and
programs larger than the chip's memory. These tests compile the served
decode step, the XLA top-k CDF and every Pallas kernel at real widths for
a v5e chip. The topology is described inside a fixture (never at import),
so every test worker collects the same tests and only the worker that runs
this file loads the TPU compiler.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

V = 151936                      # Qwen3 vocabulary
HBM_BYTES = 16 * 2 ** 30        # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # no compiler logs on disk
        try:
            yield topologies.get_topology_desc(platform="tpu",
                                               topology_name="v5e:2x2")
        except Exception as e:   # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo, no_persistent_cache):
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def test_decode_step_fits_one_chip(one_chip):
    from repro.configs.qwen3_1_7b import CONFIG as cfg
    from repro.models import api as model_api
    from repro.models.schema import abstract_params
    from repro.serve.engine import ModelPredictor

    B, max_len = 16, 1024
    params = _on(one_chip, abstract_params(cfg))
    cache = _on(one_chip, jax.eval_shape(
        lambda: model_api.init_cache(cfg, B, max_len)))
    prev = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip)
    pred = ModelPredictor(params, cfg)
    compiled = pred._decode.lower(params, cache, prev, {}).compile()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.output_size_in_bytes
    assert used < HBM_BYTES, used


def test_granite_decode_step_fits_one_chip(one_chip):
    """Granite-4.0-H-Small as the benchmark cuts it (10 of 40 layers, 9 of
    72 experts held) at its 64 slots of chunk 256: weights, the mixed
    cache (float32 SSM state) and the new cache fit with room."""
    from repro.configs.granite_4_0_h_small import CONFIG
    from repro.models import api as model_api
    from repro.models.schema import abstract_params
    from repro.serve.engine import ModelPredictor

    cfg = CONFIG.with_(n_layers=10, experts_held=9)
    B, max_len = 64, 256
    params = _on(one_chip, abstract_params(cfg))
    cache = _on(one_chip, jax.eval_shape(
        lambda: model_api.init_cache(cfg, B, max_len)))
    prev = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip)
    pred = ModelPredictor(params, cfg)
    compiled = pred._decode.lower(params, cache, prev, {}).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < 0.75 * HBM_BYTES, used


@pytest.mark.parametrize("cell", ["qwen3-1.7b", "deepseek-7b",
                                  "granite-4.0-h-small"])
def test_decode_step_updates_cache_in_place(one_chip, cell):
    """At the benchmark cells' shapes the dense decode program writes its
    new K/V rows into the donated cache: the output aliases every cache
    byte and no cache-sized temporary is made. Granite's program is not
    donated (its scans pass the cache through their inputs and outputs)."""
    from repro.models import api as model_api
    from repro.models.schema import abstract_params
    from repro.serve.engine import ModelPredictor

    if cell == "qwen3-1.7b":
        from repro.configs.qwen3_1_7b import CONFIG as cfg
        B = 64
    elif cell == "deepseek-7b":
        from repro.configs.deepseek_7b import CONFIG
        cfg, B = CONFIG.with_(n_layers=15), 32
    else:
        from repro.configs.granite_4_0_h_small import CONFIG
        cfg, B = CONFIG.with_(n_layers=10, experts_held=9), 64
    params = _on(one_chip, abstract_params(cfg))
    cache = _on(one_chip, jax.eval_shape(
        lambda: model_api.init_cache(cfg, B, 256)))
    prev = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip)
    pred = ModelPredictor(params, cfg)
    mem = pred._decode.lower(params, cache, prev, {}).compile() \
        .memory_analysis()
    if cfg.family not in model_api.IN_PLACE_DECODE:
        assert mem.alias_size_in_bytes == 0
        return
    kv = sum(cache[n].size * cache[n].dtype.itemsize for n in ("k", "v"))
    assert mem.alias_size_in_bytes >= kv, (mem.alias_size_in_bytes, kv)
    assert mem.temp_size_in_bytes < 0.01 * kv, mem.temp_size_in_bytes


def _selection_operand_dims(hlo: str) -> list:
    """Dims of every operand of a ``sort`` or ``TopK`` custom call in a
    compiled HLO module's text."""
    dims, out = {}, []
    for line in hlo.splitlines():
        head = re.match(r"\s*(?:ROOT )?%(\S+) = (.*)", line)
        if not head:
            continue
        call = re.search(r"\s([\w-]+)\(([^)]*)\)", head[2])
        if not call:
            continue
        dims[head[1]] = [int(d) for shp in re.findall(
            r"\w\[([\d,]+)\]", head[2][:call.start()])
            for d in shp.split(",")]
        if call[1] == "sort" or 'custom_call_target="TopK"' in line:
            out.extend(dims.get(op.strip().lstrip("%"), [])
                       for op in call[2].split(",") if "%" in op)
    return out


def test_xla_topk_cdf_compiles(one_chip):
    """The top-48 CDF program compiles, and at the Qwen3 cell's 64 lanes
    of bfloat16 logits no sort or TopK reads an operand as wide as the
    vocabulary: the selection runs over block maxima and 48 blocks."""
    from repro.core.cdf import topk_cdf_jit
    logits = jax.ShapeDtypeStruct((16, V), jnp.float32, sharding=one_chip)
    topk_cdf_jit.lower(logits, 48, 16).compile()
    logits = jax.ShapeDtypeStruct((64, V), jnp.bfloat16, sharding=one_chip)
    operands = _selection_operand_dims(
        topk_cdf_jit.lower(logits, 48, 16).compile().as_text())
    assert operands and all(operands), operands
    assert not any(V in d for d in operands), operands


def _kernel_case(name, s):
    """(function, argument shapes) of one Pallas kernel at real widths."""
    from repro.kernels.ac_cdf import cdf_points, topk_cdf_points
    from repro.kernels.decode_attention import decode_attention
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.ssd_scan import ssd_intra
    bf, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    if name == "decode_attention":        # qwen3-1.7b heads, 16 lanes
        return decode_attention, [s((16, 16, 128), bf),
                                  s((16, 8, 2048, 128), bf),
                                  s((16, 8, 2048, 128), bf), s((16,), i32)]
    if name == "flash_attention":         # qwen3-1.7b heads, 4k prefill
        return flash_attention, [s((1, 16, 4096, 128), bf),
                                 s((1, 8, 4096, 128), bf),
                                 s((1, 8, 4096, 128), bf)]
    if name == "topk_cdf_points":         # the service's top-48 CDF
        return (lambda x: topk_cdf_points(x, 48, 16)), [s((16, V), f32)]
    if name == "cdf_points":              # full-vocabulary CDF
        return (lambda x: cdf_points(x, 20)), [s((16, V), f32)]
    # ssd_intra at mamba2-130m widths: 24 heads of 64, state 128, Q 256
    return ssd_intra, [s((2, 256, 24, 64), f32), s((2, 256, 24), f32),
                       s((24,), f32), s((2, 256, 128), f32),
                       s((2, 256, 128), f32)]


@pytest.mark.parametrize("name", ["decode_attention", "flash_attention",
                                  "topk_cdf_points", "cdf_points",
                                  "ssd_intra"])
def test_pallas_kernel_compiles(one_chip, name):
    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    fn, args = _kernel_case(name, shape)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
