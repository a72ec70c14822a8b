"""The tracer (``core.timing``): spans, their record and counts, and the
runners' phases as spans on the profiler's host plane."""
import pathlib
import sys

import jax
import jax.numpy as jnp
import pytest

from repro import envs
from repro.algos.ppo import PPOConfig, make_mlp_learner
from repro.core import AsyncOrchestrator, SyncRunner, timing
from repro.core import sampler as sampler_mod
from repro.experiment import ExperimentSpec, Schedule, build
from repro.models import mlp_policy
from repro.optim import adam

ROOT = pathlib.Path(__file__).resolve().parents[1]
HORIZON = 8
BATCH = 4
SAMPLERS = 2


class FakeClock:
    """Each reading advances by the next of ``steps`` seconds."""

    def __init__(self, *steps):
        self.now, self.steps = 0.0, list(steps)

    def __call__(self):
        self.now += self.steps.pop(0)
        return self.now


def test_spans_nest_with_parents_and_self_time(monkeypatch):
    # readings: it 1 | a 1 ... a +2 | b 1 | b2 1 ... b2 +1 ... b +1 | it +1
    monkeypatch.setattr(timing, "clock",
                        FakeClock(1, 1, 2, 1, 1, 1, 1, 1))
    with timing.iteration("it", 7) as rec:
        root = timing.current()
        with timing.span("a") as a:
            assert a.parent is root and a.step_num == 7
        with timing.span("b") as b:
            with timing.span("a", sampler=1) as a2:
                assert a2.parent is b
    assert timing.current() is None
    assert (a.seconds, a2.seconds, b.seconds) == (2.0, 1.0, 3.0)
    assert b.self_seconds == 2.0
    assert root.name == "it" and root.parent is None
    assert root.seconds == 8.0 and root.self_seconds == 3.0
    assert rec.spans == {"a": 3.0, "b": 3.0, "it": 8.0}
    assert rec.counts == {}


def test_compiles_count_at_the_innermost_span():
    x = jax.block_until_ready(jnp.arange(6.0))
    head = jax.block_until_ready(x[:3])
    rec = timing.Record()
    with timing.recording(rec):
        jax.jit(lambda v: v * 3 + 1)(x)                     # no span open
        with timing.span("outer"), timing.span("inner"):
            jax.jit(lambda v: v - 7)(x)                     # a fresh jit
        with timing.span("outer"):
            host = timing.pull(head)
    assert rec.counts["compiles@outside"] == 1
    assert rec.counts["compiles@inner"] == 1
    assert "compiles@outer" not in rec.counts
    assert rec.counts["host_pulls@outer"] == 1
    assert list(host) == [0.0, 1.0, 2.0]
    # with no record open a compile goes nowhere
    jax.jit(lambda x: x / 9)(jnp.arange(4.0))
    assert timing.current() is None


def _sync_runner():
    env = envs.make("pendulum")
    params = mlp_policy.init_policy(jax.random.PRNGKey(0), env.obs_dim,
                                    env.act_dim, 16)
    opt = adam(1e-3)
    learn = make_mlp_learner(opt, PPOConfig(epochs=1, minibatches=2))
    carries = [sampler_mod.init_env_carry(env, jax.random.PRNGKey(1 + i),
                                          BATCH) for i in range(SAMPLERS)]
    return SyncRunner(sampler_mod.make_env_rollout(env, HORIZON), learn,
                      params, opt.init(params), carries, SAMPLERS)


def _fused_runner():
    spec = ExperimentSpec(env="pendulum", algo="ppo", runtime="fused",
                          model={"hidden": 16},
                          schedule=Schedule(env_batch=BATCH,
                                            horizon=HORIZON, chunk=2))
    return build(spec)


STEPPED = {"runner.iteration", "samplers.collect", "samplers.rollout",
           "samplers.merge", "learner.step", "runner.log"}
CHUNK = {"runner.chunk", "runner.dispatch", "runner.wait", "runner.pull",
         "runner.log"}


def test_sync_runner_logs_its_spans_and_counts():
    logs = _sync_runner().run(2)
    for log in logs:
        assert set(log.spans) == STEPPED
        assert log.collect_time_serial == log.spans["samplers.rollout"]
        assert log.learn_time == log.spans["learner.step"]
        assert log.counts["host_pulls@runner.log"] == 1
        assert log.spans["runner.iteration"] >= (
            log.spans["samplers.collect"] + log.spans["learner.step"]
            + log.spans["runner.log"])
    # the first iteration compiles its rollout inside the rollout span
    assert logs[0].counts["compiles@samplers.rollout"] >= 1
    assert all(k.split("@")[0] in ("compiles", "host_pulls")
               for log in logs for k in log.counts)


def test_fused_runner_puts_call_level_spans_on_the_chunk_head():
    runner = _fused_runner()
    logs = runner.run(4)
    heads, rest = logs[0::2], logs[1::2]
    for head in heads:
        assert set(head.spans) == CHUNK
        assert head.counts["host_pulls@runner.pull"] == 1
        assert head.learn_time == pytest.approx(
            (head.spans["runner.dispatch"] + head.spans["runner.wait"]) / 2)
    assert heads[0].counts["compiles@runner.dispatch"] >= 1
    assert all(log.spans == {} and log.counts == {} for log in rest)
    assert all(log.learn_time == head.learn_time
               for head, log in zip(heads, rest))


def test_async_learner_phases():
    env = envs.make("pendulum")
    params = mlp_policy.init_policy(jax.random.PRNGKey(0), env.obs_dim,
                                    env.act_dim, 16)
    opt = adam(1e-3)
    learn = make_mlp_learner(opt, PPOConfig(epochs=1, minibatches=2))
    carries = [sampler_mod.init_env_carry(env, jax.random.PRNGKey(1 + i),
                                          BATCH) for i in range(SAMPLERS)]
    orch = AsyncOrchestrator(sampler_mod.make_env_rollout(env, HORIZON),
                             learn, params, opt.init(params), carries,
                             SAMPLERS)
    try:
        logs = orch.run(2, timeout=120)
    finally:
        orch.close()
    assert len(logs) == 2
    for log in logs:
        assert {"runner.iteration", "learner.wait_experience",
                "learner.step", "learner.publish",
                "runner.log"} <= set(log.spans)
        assert log.learn_time == log.spans["learner.step"]


def _profiled(run, tmp_path):
    sys.path.insert(0, str(ROOT))
    try:
        from bench import tracing
    finally:
        sys.path.remove(str(ROOT))
    with tracing.profile(str(tmp_path)):
        logs = run()
    names = [name for name, _, _ in tracing.load(str(tmp_path), 0).host]
    return logs, names


def test_profiled_sync_run_holds_the_phases_on_the_host_plane(tmp_path):
    runner = _sync_runner()
    runner.run(1)                           # compile outside the profile
    logs, names = _profiled(lambda: runner.run(2), tmp_path)
    assert len(logs) == 3
    assert names.count("runner.iteration") == 2
    assert names.count("samplers.rollout") == 2 * SAMPLERS
    assert names.count("learner.step") == 2
    assert names.count("runner.log") == 2


def test_profiled_fused_run_holds_the_chunk_phases(tmp_path):
    runner = _fused_runner()
    runner.run(2)
    _, names = _profiled(lambda: runner.run(4), tmp_path)
    for name in CHUNK:
        assert names.count(name) == 2, name
