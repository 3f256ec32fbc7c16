"""Sharding policy unit tests (no multi-device needed)."""
import jax
from jax.sharding import PartitionSpec as P

from repro.configs.registry import ARCH_IDS, get_config
from repro.launch.mesh import make_mesh
from repro.models.schema import abstract_params, schema, Leaf
from repro.sharding.specs import batch_pspecs, param_pspecs


def _fake_mesh():
    # single real device, but the POLICY is computed from names/shape only
    return make_mesh((1, 1), ("data", "model"))


def test_param_specs_match_tree_structure():
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        pa = abstract_params(cfg)
        ps = param_pspecs(cfg, _fake_mesh())
        assert jax.tree_util.tree_structure(pa) == \
            jax.tree_util.tree_structure(ps)


def test_specs_rank_matches_shapes():
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        pa = jax.tree_util.tree_leaves(abstract_params(cfg))
        ps = jax.tree_util.tree_leaves(
            param_pspecs(cfg, _fake_mesh()),
            is_leaf=lambda x: isinstance(x, P))
        for a, s in zip(pa, ps):
            assert len(s) <= len(a.shape), (a.shape, s)


def test_divisibility_policy():
    """Every sharded dim must divide by the production TP/DP degrees."""
    import numpy as np

    class FakeMesh:
        shape = {"data": 16, "model": 16}
        axis_names = ("data", "model")

    mesh = FakeMesh()
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        sch = schema(cfg)
        specs = param_pspecs(cfg, mesh)
        flat_s, _ = jax.tree_util.tree_flatten(
            sch, is_leaf=lambda x: isinstance(x, Leaf))
        flat_p = jax.tree_util.tree_leaves(
            specs, is_leaf=lambda x: isinstance(x, P))
        for leaf, spec in zip(flat_s, flat_p):
            for dim, ax in zip(leaf.shape, tuple(spec)):
                if ax is None:
                    continue
                deg = mesh.shape[ax] if isinstance(ax, str) else \
                    int(np.prod([mesh.shape[a] for a in ax]))
                assert dim % deg == 0, (arch, leaf.shape, spec)


def test_batch_unshardable_falls_back_to_replicated():
    class FakeMesh:
        shape = {"data": 16, "model": 16}
        axis_names = ("data", "model")

    cfg = get_config("qwen3_14b")
    specs = batch_pspecs(cfg, FakeMesh(), global_batch=1)
    assert specs["tokens"][0] is None
