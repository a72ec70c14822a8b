"""Env-step kernel family: Pallas (interpret on CPU) vs ref parity for
all three physics envs, the batched auto-reset fast-path regression
against single-env semantics, and the full kernel-selection table
(mode × platform, GPU included).

Parity contract (see also ``env_step_pallas``'s module docstring):

* int/bool leaves (step counters, ``done``) — EXACT, all envs.
* the auto-reset select — EXACT (reset candidates pass through the
  ``where`` untouched; pinned by the all-done terminal test).
* pendulum and cheetah f32 leaves — EXACT at every tested B.
* cartpole f32 arithmetic leaves — within 4 ulps (measured worst: 3).
  The kernel bodies evaluate the *verbatim* ref expressions, but XLA
  CPU applies FMA contraction per fusion context, so two
  differently-shaped compilations of the same ops (the ``(B,)`` ref vs
  the ``(1, b)``-tiled interpreted kernel) are not bitwise-stable
  against each other: cartpole's ``xdot``/``thdot`` chains hit one
  contraction difference (strict-rounding recomputation sides with the
  kernel) which propagates through the few remaining ops of the step.
  The bound is asserted in ulps, not an allclose hand-wave.

Comparisons run under ``jax.jit`` on both sides — that is how the
kernels are always reached in practice (rollouts trace them inside a
scan), and eager op-by-op execution is itself a third fusion context.

The guarantee training correctness rests on — ``auto_reset_batch`` (the
VectorEnv step, ref batch fast-path) bitwise-identical to
``vmap(auto_reset(env))`` — is EXACT and tested below; those two
compile through the same-shaped graphs.
"""
import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import envs
from repro.envs.base import auto_reset, auto_reset_batch
from repro.kernels import select
from repro.kernels.env_step import ops as env_ops
from repro.kernels.env_step import ref as env_ref

KEY = jax.random.PRNGKey(23)

ENV_PARAMS = {
    "pendulum": dict(max_torque=2.0),
    "cartpole": dict(force_max=10.0),
    "cheetah": dict(ctrl_cost=0.1),
}


@pytest.fixture(autouse=True)
def _restore_kernel_mode():
    prev = select.kernel_mode()
    yield
    select.set_kernel_mode(prev)


def assert_trees_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for xa, xb in zip(la, lb):
        assert jnp.asarray(xa).dtype == jnp.asarray(xb).dtype
        np.testing.assert_array_equal(np.asarray(xa), np.asarray(xb))


def _ulp_distance(a, b):
    """Lexicographic-bit distance between f32 arrays (0 == bitwise equal,
    1 == adjacent representable floats)."""
    ia = a.view(np.int32).astype(np.int64)
    ib = b.view(np.int32).astype(np.int64)
    ia = np.where(ia < 0, np.int64(-(2 ** 31)) - ia, ia)
    ib = np.where(ib < 0, np.int64(-(2 ** 31)) - ib, ib)
    return np.abs(ia - ib)


def assert_trees_equal_ulp(a, b, max_ulps):
    """Exact on int/bool leaves; f32 leaves within ``max_ulps`` (the XLA
    CPU FMA-contraction bound — see the module docstring)."""
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for xa, xb in zip(la, lb):
        xa, xb = np.asarray(xa), np.asarray(xb)
        assert xa.dtype == xb.dtype and xa.shape == xb.shape
        if xa.dtype.kind in "iub" or max_ulps == 0:
            np.testing.assert_array_equal(xa, xb)
        else:
            dist = _ulp_distance(xa, xb)
            assert dist.max(initial=0) <= max_ulps, (
                f"float leaves differ by {dist.max()} ulps "
                f"({(dist > max_ulps).sum()} elements past {max_ulps})")


# pendulum/cheetah parity is bitwise; cartpole admits the contraction
# bound (measured worst across B in {1..4096}: 3 ulps)
PARITY_ULPS = {"pendulum": 0, "cartpole": 4, "cheetah": 0}


def _batch_inputs(name, B, *, max_episode_steps=3, key=KEY):
    """(state, actions, reset_state, reset_obs, params) for one batched
    step; ``max_episode_steps=3`` keeps terminal auto-resets in play."""
    env = envs.make(name, max_episode_steps=max_episode_steps)
    ks = jax.random.split(jax.random.fold_in(key, B), 3)
    states, _ = jax.vmap(env.reset)(jax.random.split(ks[0], B))
    actions = jax.random.uniform(ks[1], (B, env.act_dim),
                                 minval=-1.0, maxval=1.0)
    reset_state, reset_obs = jax.vmap(env.reset)(jax.random.split(ks[2], B))
    params = dict(max_episode_steps=max_episode_steps, reward_scale=1.0,
                  **ENV_PARAMS[name])
    return env, states, actions, reset_state, reset_obs, params


# B sweep crosses the default b_block=512: 513/700 exercise grid padding
# (nb=2 with a ragged final tile); 1 is the degenerate single instance.
@pytest.mark.parametrize("name", sorted(env_ref.STEP_BATCH_REF))
@pytest.mark.parametrize("B", [1, 7, 37, 512, 513, 700])
def test_env_step_pallas_matches_ref(name, B):
    env, states, actions, rs, ro, params = _batch_inputs(name, B)

    @partial(jax.jit, static_argnums=0)
    def run(impl, s, a, rs, ro):
        return env_ops.env_step(name, s, a, rs, ro, impl=impl, **params)

    out_ref = run("ref", states, actions, rs, ro)
    out_pl = run("pallas", states, actions, rs, ro)
    assert_trees_equal_ulp(out_ref, out_pl, PARITY_ULPS[name])
    # shapes/dtypes of the bundle: state pytree, obs (B, obs_dim),
    # rewards (B,) float, dones (B,) bool
    _, obs, rew, done = out_pl
    assert obs.shape == (B, env.obs_dim)
    assert rew.shape == (B,) and rew.dtype == jnp.float32
    assert done.shape == (B,) and done.dtype == jnp.bool_


@pytest.mark.parametrize("name", sorted(env_ref.STEP_BATCH_REF))
def test_env_step_terminal_auto_reset_parity(name):
    """Drive past the horizon so every instance hits done: the fused
    select must hand back the reset candidates exactly, with the reward
    staying the terminal transition's (the auto_reset contract). The
    reset re-synchronizes both impls to the identical candidates, so
    any in-flight ulp drift dies at each episode boundary."""
    B = 33
    env, states, actions, rs, ro, params = _batch_inputs(
        name, B, max_episode_steps=2)

    @partial(jax.jit, static_argnums=0)
    def run(impl, s):
        outs = []
        for _ in range(3):  # step 3x a horizon of 2 -> all instances reset
            s, obs, rew, done = env_ops.env_step(name, s, actions, rs, ro,
                                                 impl=impl, **params)
            outs.append((obs, rew, done))
        return s, outs

    out_ref = run("ref", states)
    out_pl = run("pallas", states)
    assert_trees_equal_ulp(out_ref, out_pl, PARITY_ULPS[name])
    # the reset step itself (step 2 of 3) handed back the candidates
    # through the select verbatim on both sides
    _, ref_steps = out_ref
    _, pl_steps = out_pl
    assert bool(np.all(np.asarray(ref_steps[1][2])))  # all done
    assert_trees_equal(ref_steps[1][0], pl_steps[1][0])  # reset obs exact


@pytest.mark.parametrize("name", sorted(env_ref.STEP_BATCH_REF))
def test_batched_fast_path_matches_vmap_exactly(name):
    """``auto_reset_batch`` (both with the env's fused ``batch_step`` and
    with the plain vmap+single-where fallback) is bitwise
    ``vmap(auto_reset(env))`` across steps that include terminal resets —
    the regression pin that single-env auto-reset semantics are
    unchanged by the batch fast-path."""
    B = 17
    env = envs.make(name, max_episode_steps=3)
    plain = dataclasses.replace(env, batch_step=None)
    states, obs = jax.vmap(env.reset)(
        jax.random.split(jax.random.fold_in(KEY, 1), B))
    keys = jax.random.split(jax.random.fold_in(KEY, 2), B)
    actions = jax.random.uniform(jax.random.fold_in(KEY, 3),
                                 (B, env.act_dim), minval=-1.0, maxval=1.0)

    def sweep(step):
        @jax.jit
        def run(s, k):
            outs = []
            for _ in range(5):
                s, obs, rew, done = step(s, actions, k)
                outs.append((obs, rew, done))
            return s, outs
        return run(states, keys)

    vm = jax.vmap(auto_reset(env))
    ref_out = sweep(lambda s, a, k: vm(s, a, k))
    fused_out = sweep(auto_reset_batch(env))
    fallback_out = sweep(auto_reset_batch(plain))
    assert_trees_equal(ref_out, fused_out)
    assert_trees_equal(ref_out, fallback_out)


def test_env_step_unknown_env_rejected():
    with pytest.raises(KeyError, match="pendulum"):
        env_ops.env_step("walker", None, None, None, None)


def test_env_step_non_f32_falls_back_to_ref():
    """The kernels are f32-only; other dtypes must dispatch the ref path
    (same values as an explicit ref call), not fail to lower."""
    name = "pendulum"
    env = envs.make(name, max_episode_steps=3, dtype=jnp.float16)
    B = 9
    states, _ = jax.vmap(env.reset)(
        jax.random.split(jax.random.fold_in(KEY, 4), B))
    actions = jnp.zeros((B, 1))
    rs, ro = jax.vmap(env.reset)(
        jax.random.split(jax.random.fold_in(KEY, 5), B))
    params = dict(max_episode_steps=3, reward_scale=1.0, max_torque=2.0,
                  dtype=jnp.float16)
    out_pl = env_ops.env_step(name, states, actions, rs, ro,
                              impl="pallas", **params)
    out_ref = env_ops.env_step(name, states, actions, rs, ro,
                               impl="ref", **params)
    assert_trees_equal(out_ref, out_pl)
    assert out_pl[1].dtype == jnp.float16


# ========================================================= selection table
# mode × platform -> (implementation, interpret): auto compiles Pallas on
# TPU (Mosaic) only — the kernels use TPU memory spaces — and every other
# platform interprets them.
@pytest.mark.parametrize("platform,mode,expect", [
    ("cpu", "ref", ("ref", False)),
    ("cpu", "pallas", ("pallas", True)),
    ("cpu", "auto", ("ref", False)),
    ("tpu", "ref", ("ref", False)),
    ("tpu", "pallas", ("pallas", False)),
    ("tpu", "auto", ("pallas", False)),
    ("gpu", "ref", ("ref", False)),
    ("gpu", "pallas", ("pallas", True)),
    ("gpu", "auto", ("ref", False)),
    ("cuda", "auto", ("ref", False)),
    ("rocm", "auto", ("ref", False)),
])
def test_selection_table(monkeypatch, platform, mode, expect):
    monkeypatch.setattr(select.jax, "default_backend", lambda: platform)
    assert select.resolve(mode) == expect
    # the global mode resolves through the same table
    select.set_kernel_mode(mode)
    assert select.resolve() == expect
