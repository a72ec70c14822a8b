"""Sharding-rule unit tests on an AbstractMesh (no placeholder devices)."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import ASSIGNED_ARCHS, INPUT_SHAPES, get_config, \
    supports_shape
from repro.distributed import sharding as sh
from repro.launch import specs as specs_mod
from repro.models import transformer

MESH_SP = AbstractMesh((16, 16), ("data", "model"))
MESH_MP = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def test_shard_axes_divisibility_fallback():
    assert sh.shard_axes(256, ("data",), MESH_SP) == "data"
    assert sh.shard_axes(7, ("data",), MESH_SP) is None          # replicate
    assert sh.shard_axes(32, ("pod", "data"), MESH_MP) == ("pod", "data")
    # 16 doesn't divide 32 -> falls back to the 16-wide suffix
    assert sh.shard_axes(16, ("pod", "data"), MESH_MP) == "data"
    assert sh.shard_axes(2, ("pod", "data"), MESH_MP) == "pod"
    assert sh.shard_axes(1, ("pod", "data"), MESH_MP) is None


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
@pytest.mark.parametrize("mesh", [MESH_SP, MESH_MP],
                         ids=["16x16", "2x16x16"])
def test_param_specs_cover_all_leaves(arch, mesh):
    cfg = get_config(arch)
    shapes = jax.eval_shape(
        lambda: transformer.init_params(cfg, jax.random.PRNGKey(0)))
    specs = sh.param_specs(cfg, shapes, mesh)
    n_leaves = len(jax.tree.leaves(shapes))
    spec_leaves = jax.tree.leaves(specs,
                                  is_leaf=lambda x: isinstance(x, P))
    assert len(spec_leaves) == n_leaves
    # every spec's sharded dims divide the mesh axes (fallback worked)
    for leaf, spec in zip(jax.tree.leaves(shapes), spec_leaves):
        for dim, entry in zip(leaf.shape, tuple(spec)):
            if entry is None:
                continue
            axes = (entry,) if isinstance(entry, str) else entry
            total = 1
            for a in axes:
                total *= mesh.shape[a]
            assert dim % total == 0, (arch, spec, leaf.shape)


@pytest.mark.parametrize("arch", ["llama3-405b", "mixtral-8x7b",
                                  "falcon-mamba-7b", "hymba-1.5b"])
def test_weights_sharded_enough_to_fit(arch):
    """2-D sharded params must fit v5e HBM (16 GiB) with Adam moments."""
    cfg = get_config(arch)
    shapes = jax.eval_shape(
        lambda: transformer.init_params(cfg, jax.random.PRNGKey(0)))
    specs = sh.param_specs(cfg, shapes, MESH_SP)
    per_device = 0
    for leaf, spec in zip(
            jax.tree.leaves(shapes),
            jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))):
        n = 1
        for entry in tuple(spec):
            if entry is None:
                continue
            axes = (entry,) if isinstance(entry, str) else entry
            for a in axes:
                n *= MESH_SP.shape[a]
        per_device += leaf.size * 2 // n          # bf16
    assert per_device * 3 < 16 * 2 ** 30, (      # params + 2 Adam moments
        f"{arch}: {per_device * 3 / 2**30:.1f} GiB/device")


@pytest.mark.parametrize("shape_name", list(INPUT_SHAPES))
def test_input_specs_exist_for_all_archs(shape_name):
    shape = INPUT_SHAPES[shape_name]
    for arch in ASSIGNED_ARCHS:
        cfg = get_config(arch)
        ok, why = supports_shape(cfg, shape)
        if not ok:
            assert shape_name == "long_500k" and why
            continue
        spec = specs_mod.input_specs(cfg, shape, MESH_SP)
        assert spec["kind"] in ("train", "prefill", "decode")
        for leaf in jax.tree.leaves(spec["args"]):
            assert isinstance(leaf, jax.ShapeDtypeStruct)


def test_decode_state_specs_flash_decoding_layout():
    cfg = get_config("llama3-405b")
    shape = INPUT_SHAPES["decode_32k"]
    state = specs_mod.decode_state_shapes(cfg, shape)
    specs = sh.decode_state_specs(cfg, state, MESH_SP)
    assert tuple(specs["k"]) == (None, "data", "model", None, None)
    assert tuple(specs["ssm"]) if "ssm" in specs else True
