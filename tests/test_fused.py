"""Fused-engine and backend-layer tests.

* The fused collect->GAE->PPO scan must reproduce a stepped SyncRunner run
  bitwise (same seed, same params out) — fusing is a scheduling change,
  not a numerical one.
* Inline/Threaded/Sharded backends are different schedules of the same
  sampler work and must produce identically-shaped (and, from identical
  carries, identical-valued) merged trajectories.
"""
import jax
import numpy as np
import pytest

from repro import envs
from repro.algos.ppo import PPOConfig, make_mlp_learner
from repro.core import (
    FusedRunner,
    InlineBackend,
    SyncRunner,
    ThreadedBackend,
    make_backend,
)
from repro.core import sampler as sampler_mod
from repro.core.fused import TrainState, make_fused_train_loop
from repro.data import trajectory
from repro.optim import adam

HORIZON = 16
BATCH = 8


def _pieces(seed=0, hidden=32):
    env = envs.make("pendulum")
    from repro.models import mlp_policy
    params = mlp_policy.init_policy(jax.random.PRNGKey(seed), env.obs_dim,
                                    env.act_dim, hidden)
    opt = adam(1e-3)
    learn = make_mlp_learner(opt, PPOConfig(epochs=2, minibatches=2))
    return env, params, opt, learn


def _carry(env, seed=1, batch=BATCH):
    return sampler_mod.init_env_carry(env, jax.random.PRNGKey(seed), batch)


def _assert_trees_equal(a, b):
    for xa, xb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(xa), np.asarray(xb))


# ============================================================ fused parity
def test_fused_matches_stepped_bitwise():
    """3 iterations on pendulum: fused scan == stepped SyncRunner, exact."""
    env, params, opt, learn = _pieces()
    stepped = SyncRunner(sampler_mod.make_env_rollout(env, HORIZON), learn,
                         params, opt.init(params), [_carry(env)], 1)
    stepped.run(3)

    fused = FusedRunner(env, learn, params, opt.init(params), _carry(env),
                        horizon=HORIZON)
    fused.run(3)

    _assert_trees_equal(stepped.params, fused.params)
    _assert_trees_equal(stepped.opt_state, fused.opt_state)


def test_fused_chunking_invariant():
    """Running 4 iterations as 1 chunk or 2+2 gives identical params."""
    env, params, opt, learn = _pieces()
    one = FusedRunner(env, learn, params, opt.init(params), _carry(env),
                      horizon=HORIZON, chunk=4)
    one.run(4)
    two = FusedRunner(env, learn, params, opt.init(params), _carry(env),
                      horizon=HORIZON, chunk=2)
    two.run(4)
    _assert_trees_equal(one.params, two.params)
    assert len(one.logs) == len(two.logs) == 4


def test_fused_loop_metrics_stacked():
    env, params, opt, learn = _pieces()
    loop = make_fused_train_loop(env, learn, HORIZON, chunk=3)
    # the loop donates its input; copy so ``params`` survives for comparison
    state = jax.tree.map(jax.numpy.copy,
                         TrainState(params, opt.init(params), _carry(env)))
    state2, metrics = loop(state)
    assert metrics["loss"].shape == (3,)
    assert metrics["mean_return"].shape == (3,)
    assert np.all(np.isfinite(np.asarray(metrics["loss"])))
    # params actually changed
    moved = any(
        not np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(params),
                        jax.tree.leaves(state2.params)))
    assert moved


def test_fused_runner_logs():
    env, params, opt, learn = _pieces()
    runner = FusedRunner(env, learn, params, opt.init(params), _carry(env),
                         horizon=HORIZON)
    logs = runner.run(3)
    assert [l.iteration for l in logs] == [0, 1, 2]
    for log in logs:
        assert log.samples == BATCH * HORIZON
        assert log.learn_time > 0
        assert log.collect_time == 0.0      # no host-visible split, by design


# ========================================================== backend parity
def _backend_pair(kind):
    env, params, opt, learn = _pieces()
    rollout = sampler_mod.make_env_rollout(env, HORIZON)
    carries = lambda: [_carry(env, seed=1 + i, batch=4) for i in range(2)]
    ref = InlineBackend(rollout, carries())
    other = make_backend(kind, rollout, carries(), env=env, horizon=HORIZON)
    return params, ref, other


@pytest.mark.parametrize("kind", ["threaded", "sharded"])
def test_backend_parity_with_inline(kind):
    params, ref, other = _backend_pair(kind)
    merged_ref, stats_ref = ref.collect(params)
    merged, stats = other.collect(params)
    assert set(merged) == set(merged_ref)
    for k in merged_ref:
        assert merged[k].shape == merged_ref[k].shape, k
        np.testing.assert_array_equal(np.asarray(merged[k]),
                                      np.asarray(merged_ref[k]))
    assert stats.samples == stats_ref.samples
    assert stats.critical_path > 0
    assert stats.serial_equivalent >= stats.critical_path - 1e-9


def test_threaded_backend_advances_carries():
    env, params, opt, learn = _pieces()
    rollout = sampler_mod.make_env_rollout(env, HORIZON)
    bk = ThreadedBackend(rollout, [_carry(env, seed=i) for i in range(3)])
    m1, _ = bk.collect(params)
    m2, _ = bk.collect(params)
    assert not np.array_equal(np.asarray(m1["obs"]), np.asarray(m2["obs"]))
    bk.close()


def test_sync_runner_over_threaded_backend():
    env, params, opt, learn = _pieces()
    rollout = sampler_mod.make_env_rollout(env, HORIZON)
    bk = ThreadedBackend(rollout, [_carry(env, seed=i) for i in range(2)])
    runner = SyncRunner(None, learn, params, opt.init(params), backend=bk)
    logs = runner.run(2)
    assert len(logs) == 2
    assert logs[0].samples == 2 * BATCH * HORIZON
    # the samplers' spans reach the iteration's record from pool threads
    for log in logs:
        assert log.spans["samplers.rollout"] == log.collect_time_serial > 0
    bk.close()
