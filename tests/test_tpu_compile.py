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
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import registry
from repro.kernels import select
from repro.kernels.env_step import env_step_pallas
from repro.kernels.env_step import ops as env_step_ops
from repro.kernels.env_step import ref as env_ref
from repro.kernels.gae import ops as gae_ops
from repro.kernels.gae.gae_pallas import gae_pallas
from repro.kernels.replay_ring import ops as ring_ops
from repro.kernels.replay_ring.replay_ring_pallas import ring_gather_pallas
from repro.kernels.sum_tree import ops as tree_ops
from repro.kernels.sum_tree.ref import SumTree
from repro.kernels.sum_tree.sum_tree_pallas import level_sizes
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


# ------------------------------------------- the dispatchers' op scopes
# The benchmark cells' widths: GAE over the fused cell's (16, 4096) and
# the sync cell's (1000, 20); the cheetah step at the fused cell's 4096
# and the SAC cell's 64 envs; the SAC cell's 2^20-row prioritized replay
# (the ring's fields as ``data/replay.py`` stores them) and 256-row draw.
SAC_CAPACITY = 1 << 20
SAC_RING = {"obs": (OBS_DIM,), "actions": (6,), "rewards": (),
            "next_obs": (OBS_DIM,), "discounts": ()}
OP_NAME = re.compile(r'op_name="([^"]+)"')


@pytest.fixture
def pallas(monkeypatch):
    """Every dispatcher takes the compiled Pallas path, as on the chip."""
    monkeypatch.setattr(select, "resolve",
                        lambda impl=None, kernel=None: ("pallas", False))


def _kernel_scopes(text: str) -> list:
    """The ``op_name`` path of each Pallas custom call in ``text``."""
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert calls, "no Pallas kernel in the program"
    return [OP_NAME.search(line).group(1).split("/") for line in calls]


@pytest.mark.parametrize("shape", [(16, 4096), (1000, 20)])
def test_gae_dispatcher_names_its_kernel(one_chip, pallas, shape):
    tb = _spec(one_chip, shape)
    text = _compile_text(lambda r, v, d, lv: gae_ops.gae(r, v, d, lv),
                         tb, tb, tb, _spec(one_chip, shape[1:]))
    assert all("learner.gae" in path for path in _kernel_scopes(text))


@pytest.mark.parametrize("batch", [4096, 64])
def test_env_step_dispatcher_names_its_kernel(one_chip, pallas, batch):
    env = registry.make("env", "cheetah")
    keys = jax.random.split(jax.random.PRNGKey(0), batch)
    state, obs = jax.eval_shape(jax.vmap(env.reset), keys)

    def placed(tree):
        return jax.tree.map(lambda s: _spec(one_chip, s.shape, s.dtype), tree)

    text = _compile_text(
        lambda s, a, rs, ro: env_step_ops.env_step(
            "cheetah", s, a, rs, ro, max_episode_steps=env.max_episode_steps,
            reward_scale=1.0, ctrl_cost=0.1),
        placed(state), _spec(one_chip, (batch, env.act_dim)),
        placed(state), placed(obs))
    assert all("envs.step" in path for path in _kernel_scopes(text))


def test_sumtree_find_dispatcher_names_its_kernel(one_chip, pallas):
    tree = SumTree(tuple(_spec(one_chip, (n,))
                         for n in level_sizes(SAC_CAPACITY)))
    text = _compile_text(tree_ops.sumtree_find_batch, tree,
                         _spec(one_chip, (MINIBATCH,)))
    assert all("replay.find" in path for path in _kernel_scopes(text))


def test_ring_gather_dispatcher_names_its_kernels(one_chip, pallas):
    storage = {k: _spec(one_chip, (SAC_CAPACITY,) + dims)
               for k, dims in SAC_RING.items()}
    text = _compile_text(ring_ops.ring_gather, storage,
                         _spec(one_chip, (MINIBATCH,), jnp.int32))
    scopes = _kernel_scopes(text)
    # one launch per (capacity, width) field; the one-wide fields go to
    # XLA's gather in place, under the same scope
    assert len(scopes) == sum(len(dims) == 1 for dims in SAC_RING.values())
    assert all("replay.gather" in path for path in scopes)
    gathers = [line for line in text.splitlines()
               if re.search(r"= f32\[256\]\S* gather\(", line)]
    assert len(gathers) == sum(not dims for dims in SAC_RING.values())
    assert all("replay.gather" in OP_NAME.search(line).group(1)
               for line in gathers)


def _while_bodies(text: str) -> str:
    """The HLO text of every ``while`` loop body in ``text``."""
    names = set(re.findall(r"\bwhile\(.*\bbody=(%[\w.-]+)", text))
    assert names, "no while loop in the program"
    blocks = text.split("\n\n")
    return "\n".join(b for b in blocks
                     if any(b.lstrip().startswith(n + " ") for n in names))


def test_ring_gather_in_a_loop_never_relays_the_ring(one_chip, pallas):
    """Draws inside a scan (the SAC update loop) read the ring where it
    lies: no leaf is reshaped into a padded (capacity, 1) layout, and the
    loop body copies no ring-sized buffer."""
    storage = {k: _spec(one_chip, (SAC_CAPACITY,) + dims)
               for k, dims in SAC_RING.items()}

    def draws(storage, idx):
        return jax.lax.scan(
            lambda s, i: (s, ring_ops.ring_gather(s, i)), storage, idx)

    text = _compile_text(draws, storage,
                         _spec(one_chip, (2, MINIBATCH), jnp.int32))
    assert not re.search(rf"\bf32\[{SAC_CAPACITY},1\]", text)
    body_copies = [
        line for line in _while_bodies(text).splitlines()
        if re.search(rf"= \w+\[{SAC_CAPACITY}[,\]]\S* copy\(", line)]
    assert not body_copies, body_copies
