"""Compile the kernels ``auto`` selects on TPU for a described v5e chip.

No chip is attached: the TPU compiler, which is installed with JAX,
compiles for a ``v5e:2x2`` topology that is only described. A kernel it
refuses (unaligned slices, too much VMEM, scalars stored to vector
memory) fails here at no chip time. Sizes are the ones ``chip_smoke.py``
runs: GAE over (T, B) = (16, 4096), env steps at B = 4096 and a ragged
513, replay at the prioritized buffer's capacity (100_000 rounded up to
a power of two) with a 256-row minibatch. Kernels the compiler refuses
at those sizes are pinned to the reference through
``kernels.select.TPU_REFUSED``.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every test worker
imports this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import registry
from repro.kernels import select
from repro.kernels.env_step import env_step_pallas
from repro.kernels.env_step import ref as env_ref
from repro.kernels.gae.gae_pallas import gae_pallas
from repro.kernels.replay_ring.replay_ring_pallas import ring_gather_pallas
from repro.kernels.sum_tree.sum_tree_pallas import sumtree_find_pallas

CAPACITY = 1 << 17          # PrioritizedBuffer(capacity=100_000)
MINIBATCH = 256
OBS_DIM = 14                # cheetah


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_text(fn, *args) -> str:
    """Compile ``fn`` for the described chip; its HLO text."""
    return jax.jit(fn).lower(*args).compile().as_text()


def test_gae_compiles_for_tpu(one_chip):
    T, B = 16, 4096
    tb = _spec(one_chip, (T, B))
    text = _compile_text(
        lambda r, v, nt, lv: gae_pallas(r, v, nt, lv, gamma=0.99, lam=0.95,
                                        interpret=False),
        tb, tb, tb, _spec(one_chip, (B,)))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("batch", [4096, 513])
@pytest.mark.parametrize("name", ["cheetah", "pendulum"])
def test_env_step_compiles_for_tpu(one_chip, name, batch):
    env = registry.make("env", name)
    keys = jax.random.split(jax.random.PRNGKey(0), batch)
    state, obs = jax.eval_shape(jax.vmap(env.reset), keys)

    def placed(tree):
        return jax.tree.map(lambda s: _spec(one_chip, s.shape, s.dtype), tree)

    step = env_step_pallas.STEP_BATCH_PALLAS[name]
    params = {"max_episode_steps": env.max_episode_steps,
              "reward_scale": 1.0}
    params.update(
        {"cheetah": {"ctrl_cost": 0.1},
         "pendulum": {"max_torque": env_ref.PENDULUM_MAX_TORQUE}}[name])
    text = _compile_text(
        lambda s, a, rs, ro: step(s, a, rs, ro, interpret=False, **params),
        placed(state), _spec(one_chip, (batch, env.act_dim)),
        placed(state), placed(obs))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("feat", [OBS_DIM, 1])
def test_ring_gather_compiles_for_tpu(one_chip, feat):
    text = _compile_text(
        lambda s, i: ring_gather_pallas(s, i, interpret=False),
        _spec(one_chip, (CAPACITY, feat)),
        _spec(one_chip, (MINIBATCH,), jnp.int32))
    assert "tpu_custom_call" in text


def test_sumtree_find_compiles_for_tpu(one_chip):
    text = _compile_text(
        lambda f, m: sumtree_find_pallas(f, m, capacity=CAPACITY,
                                         interpret=False),
        _spec(one_chip, (2 * CAPACITY - 1,)), _spec(one_chip, (MINIBATCH,)))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("kernel", sorted(select.TPU_REFUSED))
def test_tpu_refused_kernels_resolve_to_ref(monkeypatch, kernel):
    """``auto`` keeps a kernel the TPU compiler refuses on the reference;
    ``pallas`` names the reason instead of failing inside Mosaic."""
    monkeypatch.setattr(select.jax, "default_backend", lambda: "tpu")
    assert select.resolve("auto", kernel) == ("ref", False)
    with pytest.raises(ValueError, match="no Pallas form that compiles"):
        select.resolve("pallas", kernel)
    # every other kernel resolves to the compiled Pallas path on TPU
    assert select.resolve("auto", "sum_tree.find") == ("pallas", False)
    assert select.resolve("pallas", "replay_ring.gather") == ("pallas",
                                                              False)
