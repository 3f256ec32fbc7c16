"""Multi-device correctness (8 host devices in a subprocess — the parent
test process must keep seeing 1 device)."""
import os
import pathlib
import subprocess
import sys
import textwrap

import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]


def _run(code: str, timeout=900):
    # the 8 devices are host devices: the child stays on the CPU backend
    # and never starts the TPU runtime
    env = {"PYTHONPATH": f"{REPO}/src:{REPO}",
           "HOME": os.environ.get("HOME", str(REPO)),
           "PATH": "/usr/bin:/bin",
           "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS", "cpu"),
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8"}
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=timeout)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    return out.stdout


@pytest.mark.slow
def test_moe_ep_matches_single_device_oracle():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.base import ModelConfig
        from repro.models import init_params, forward
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2,2,2), ("pod","data","model"))
        cfg = ModelConfig(name="m", family="moe", n_layers=2, d_model=64,
                          n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=256,
                          n_experts=8, top_k=2, head_pad_multiple=2,
                          vocab_pad_multiple=8, dtype="float32", remat=False)
        p = init_params(cfg, jax.random.PRNGKey(0))
        t = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 256)
        ref = forward(p, cfg, {"tokens": t}, dropless=True)
        out = jax.jit(lambda p, t: forward(p, cfg, {"tokens": t},
                      dropless=True, mesh=mesh))(p, t)
        err = float(jnp.abs(ref - out).max())
        assert err < 2e-4, err
        print("EP_OK", err)
    """)
    assert "EP_OK" in out


@pytest.mark.slow
def test_train_on_multipod_mesh():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.base import ModelConfig
        from repro.launch.mesh import make_mesh
        from repro.train.train_loop import make_train_step, init_train_state
        mesh = make_mesh((2,2,2), ("pod","data","model"))
        base = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                    d_ff=128, vocab_size=256, head_pad_multiple=2,
                    vocab_pad_multiple=8, dtype="float32", remat=True)
        losses = {}
        for fam, kw in [("dense", {}), ("moe", dict(n_experts=8, top_k=2))]:
            cfg = ModelConfig(name=fam, family=fam, **{**base, **kw})
            params, opt_state = init_train_state(cfg, jax.random.PRNGKey(0))
            step = make_train_step(cfg, mesh, num_microbatches=2,
                                   global_batch=8)
            batch = {"tokens": jax.random.randint(jax.random.PRNGKey(2),
                                                  (8, 17), 0, 256)}
            l0 = None
            for i in range(6):
                params, opt_state, m = step(params, opt_state, batch)
                l0 = l0 or float(m["loss"])
            assert float(m["loss"]) < l0
            losses[fam] = float(m["loss"])
        print("MESH_OK", losses)
    """)
    assert "MESH_OK" in out


@pytest.mark.slow
def test_elastic_checkpoint_reshard():
    """Save on a (4,2) mesh, restore onto (2,4) and (8,1): identical values."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np, tempfile
        from jax.sharding import NamedSharding
        from repro.configs.base import ModelConfig
        from repro.models import init_params
        from repro.launch.mesh import make_mesh
        from repro.sharding.specs import param_pspecs
        from repro.train.checkpoint import save_checkpoint, restore_latest
        cfg = ModelConfig(name="d", family="dense", n_layers=2, d_model=64,
                          n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=256,
                          head_pad_multiple=2, vocab_pad_multiple=8,
                          dtype="float32", remat=False)
        mesh_a = make_mesh((4, 2), ("data", "model"))
        params = init_params(cfg, jax.random.PRNGKey(0))
        sh_a = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh_a, s), param_pspecs(cfg, mesh_a))
        params_a = jax.tree_util.tree_map(jax.device_put, params, sh_a)
        d = tempfile.mkdtemp()
        save_checkpoint(d, 1, {"params": params_a})
        for shape in ((2, 4), (8, 1)):
            mesh_b = make_mesh(shape, ("data", "model"))
            sh_b = jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh_b, s), param_pspecs(cfg, mesh_b))
            restored, _ = restore_latest(d, {"params": params},
                                         shardings={"params": sh_b})
            for a, b in zip(jax.tree_util.tree_leaves(params),
                            jax.tree_util.tree_leaves(restored["params"])):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        print("ELASTIC_OK")
    """)
    assert "ELASTIC_OK" in out
