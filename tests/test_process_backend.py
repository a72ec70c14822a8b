"""The actor plane: WorkerSpec serialization, the shared-memory transport
primitives, ``process == inline`` determinism, worker-crash surfacing and
lifecycle (close/reap) semantics."""
import json
import os

import jax
import numpy as np
import pytest

from repro import experiment
from repro.core import sampler as sampler_mod
from repro.core.ipc import ParamsChannel, ShmRing, WorkerCrashed
from repro.experiment import ExperimentSpec, Schedule

TINY = dict(num_samplers=4, global_batch=8, horizon=8, iterations=2, seed=0)


def _spec(backend, algo="ppo", runtime="sync", buffer=None,
          buffer_kwargs=None, **sched):
    return ExperimentSpec(env="pendulum", algo=algo, backend=backend,
                          runtime=runtime, model={"hidden": 16},
                          buffer=buffer, buffer_kwargs=buffer_kwargs or {},
                          schedule=Schedule(**{**TINY, **sched}))


def _assert_trees_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for xa, xb in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(xa), np.asarray(xb))


# ============================================================== WorkerSpec
def test_worker_spec_roundtrips_through_json():
    spec = sampler_mod.WorkerSpec(
        env="pendulum", algo="ppo", horizon=8, batch=2, seed=7,
        kernels="ref", env_kwargs={"reward_scale": 0.5},
        algo_kwargs={"hidden": 16, "lr": 1e-3})
    restored = sampler_mod.WorkerSpec.from_dict(
        json.loads(json.dumps(spec.to_dict())))
    assert restored == spec


def test_worker_spec_build_is_registry_only():
    """A spec rebuilds rollout/carry/params without any parent state."""
    spec = sampler_mod.WorkerSpec(env="pendulum", algo="ppo", horizon=4,
                                  batch=3, seed=5,
                                  algo_kwargs={"hidden": 16})
    rollout, carry, params = spec.build()
    assert carry[1].shape == (3, 3)           # (batch, obs_dim)
    _, traj = jax.jit(rollout)(params, carry)
    assert traj["obs"].shape == (4, 3, 3)
    # the carry is the one the inline path builds for the same seed
    import repro.envs as envs
    env = envs.make("pendulum")
    expected = sampler_mod.init_env_carry(env, jax.random.PRNGKey(5), 3)
    _assert_trees_equal(carry, expected)


# ============================================================= split_batch
def test_split_batch_raises_naming_both_values():
    with pytest.raises(ValueError, match=r"global_batch=10.*num_samplers=4"):
        sampler_mod.split_batch(10, 4)
    with pytest.raises(ValueError, match="num_samplers=0"):
        sampler_mod.split_batch(8, 0)
    assert sampler_mod.split_batch(8, 4) == 2


def test_split_batch_error_reaches_experiment_build():
    with pytest.raises(ValueError, match="not divisible"):
        experiment.build(_spec("inline", global_batch=10))


# ==================================================== transport primitives
def test_shm_ring_write_read_ack(tmp_path):
    example = {"obs": np.zeros((4, 3), np.float32),
               "dones": np.zeros((4,), bool)}
    ring = ShmRing.create(example, slots=2, prefix=f"t-{id(tmp_path)}")
    try:
        traj = {"obs": np.arange(12, dtype=np.float32).reshape(4, 3),
                "dones": np.array([0, 1, 0, 1], bool)}
        assert ring.is_free(1)
        ring.write(1, traj, worker_id=3, policy_version=9,
                   collect_seconds=0.5, loop_seconds=1.0)
        assert not ring.is_free(1)
        out, meta = ring.read(1)
        np.testing.assert_array_equal(out["obs"], traj["obs"])
        np.testing.assert_array_equal(out["dones"], traj["dones"])
        assert (meta["worker_id"], meta["policy_version"]) == (3, 9)
        assert meta["collect_seconds"] == 0.5
        ring.ack(1)
        assert ring.is_free(1)
        # slot 0 untouched
        assert ring.is_free(0)
    finally:
        ring.close(unlink=True)


def test_params_channel_versioning(tmp_path):
    leaves = [np.zeros((2, 2), np.float32), np.zeros((3,), np.float32)]
    chan = ParamsChannel.create(leaves, prefix=f"c-{id(tmp_path)}")
    try:
        assert chan.version == 0
        v1 = chan.publish([np.ones((2, 2), np.float32),
                           np.full((3,), 2.0, np.float32)])
        assert v1 == 1 and chan.version == 1
        out, v = chan.read(min_version=1)
        assert v == 1
        np.testing.assert_array_equal(out[0], np.ones((2, 2)))
        # unchanged version -> no copy
        none, v = chan.read(last_version=1)
        assert none is None and v == 1
        with pytest.raises(ValueError, match="leaves"):
            chan.publish([np.ones((2, 2), np.float32)])
    finally:
        chan.close(unlink=True)


# =========================================== determinism: process == inline
def test_process_collect_exactly_matches_inline_n4():
    """The acceptance criterion: N=4 worker processes produce trajectories
    exactly equal to the inline backend's for matched per-worker seeds —
    including across iterations (carry state persists inside workers)."""
    ri = experiment.build(_spec("inline"))
    rp = experiment.build(_spec("process"))
    try:
        assert rp.backend.num_samplers == 4
        for _ in range(2):                       # carries persist exactly
            ti, si = ri.backend.collect(ri.params)
            tp, sp = rp.backend.collect(rp.params)
            assert sorted(ti) == sorted(tp)
            _assert_trees_equal(ti, tp)
            assert si.samples == sp.samples
            assert len(sp.per_sampler_seconds) == 4
    finally:
        ri.close()
        rp.close()


def test_num_workers_overrides_num_samplers():
    res = experiment.run(_spec("process", num_samplers=4, num_workers=2))
    assert res.runner.backend.num_samplers == 2
    assert res.logs[-1].samples == TINY["global_batch"] * TINY["horizon"]


# ====================================================== crash + lifecycle
def test_worker_crash_surfaces_with_worker_id():
    """With supervision disabled (max_respawns=0), worker death surfaces
    as WorkerCrashed from collect — the pre-supervisor contract."""
    runner = experiment.build(_spec("process", num_samplers=2,
                                    max_respawns=0))
    try:
        assert runner.backend.supervisor is None
        runner.backend.collect(runner.params)        # healthy first sweep
        runner.backend.pool._procs[0].terminate()
        runner.backend.pool._procs[0].join(timeout=10)
        with pytest.raises(WorkerCrashed, match="died"):
            runner.backend.collect(runner.params)
    finally:
        runner.close()


def test_run_reaps_workers_and_close_is_idempotent():
    spec = _spec("process", num_samplers=2)
    res = experiment.run(spec)                       # run() closes in finally
    procs = res.runner.backend.pool._procs
    assert procs and all(not p.is_alive() for p in procs)
    res.runner.close()                               # double-close is safe
    assert all(log.samples == TINY["global_batch"] * TINY["horizon"]
               for log in res.logs)
    assert np.isfinite(res.logs[-1].mean_return)


def test_worker_env_pins_children_to_cpu(monkeypatch):
    """A worker spawned while the learner's environment names an
    accelerator still gets the CPU client (the learner holds the chip);
    the parent's environment is restored after the spawn."""
    from repro.core import ipc
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    with ipc._worker_env():
        assert os.environ["JAX_PLATFORMS"] == "cpu"
    assert os.environ["JAX_PLATFORMS"] == "tpu"
