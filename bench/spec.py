"""The general generator: a configuration file and a traffic file -> the
``ExperimentSpec`` the system is built from, plus the data a mix asks
set-up to make (a replay filled to capacity).

Every key of a traffic file is plain data; nothing here names a cell.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from bench.envs import cheetah

# traffic keys that go straight into the system's ``Schedule``
SCHEDULE_KEYS = ("num_samplers", "global_batch", "horizon", "chunk",
                 "env_batch", "learner_devices")


def iterations_per_call(traffic: Dict[str, Any]) -> int:
    """Iterations one call of the measured loop runs: a whole fused chunk
    (one dispatch), or one collect -> learn iteration of a stepped
    runner."""
    if traffic["runtime"] == "fused":
        return int(traffic.get("chunk") or 1)
    return 1


def env_steps_per_iteration(traffic: Dict[str, Any]) -> int:
    batch = traffic.get("env_batch") or traffic["global_batch"]
    return int(batch) * int(traffic["horizon"])


def experiment_spec(cfg: Dict[str, Any], traffic: Dict[str, Any],
                    seed: int):
    """The system's spec for one run of a cell."""
    from repro.experiment import ExperimentSpec, Schedule
    sched = {k: traffic[k] for k in SCHEDULE_KEYS if k in traffic}
    algo_kwargs = dict(cfg["algo_kwargs"])
    if "updates_per_collect" in traffic:
        algo_kwargs["updates_per_collect"] = int(
            traffic["updates_per_collect"])
    return ExperimentSpec(
        env=cfg["env"], algo=cfg["algo"], backend=traffic["backend"],
        runtime=traffic["runtime"], buffer=cfg["buffer"],
        model=dict(cfg["model"]), env_kwargs=dict(cfg["env_kwargs"]),
        algo_kwargs=algo_kwargs, buffer_kwargs=dict(cfg["buffer_kwargs"]),
        schedule=Schedule(seed=int(seed), **sched))


# fold_in tag of the replay fill's key, apart from every key the system
# derives from the seed
FILL_TAG = 0xF111


def replay_fill(fill: Dict[str, int], seed: int, dtype=jnp.float32):
    """A time-major trajectory of ``steps`` x ``envs`` cheetah transitions
    under uniform random actions in [-1, 1], from fresh starts (episodes
    end and restart inside it once ``steps`` passes the episode length).
    One jitted call on the device; the same seed gives the same rows."""
    envs, steps = int(fill["envs"]), int(fill["steps"])

    @jax.jit
    def make(key):
        k_carry, k_act = jax.random.split(key)
        carry = cheetah.init_carry(k_carry, envs, dtype)

        def body(carry, k):
            state, obs, keys = carry
            split = jax.vmap(jax.random.split)(keys)
            action = jax.random.uniform(k, (envs, cheetah.ACT_DIM),
                                        minval=-1.0, maxval=1.0
                                        ).astype(dtype)
            state, obs2, reward, done = cheetah.step_auto_reset(
                state, action, split[:, 1], dtype)
            out = {"obs": obs, "actions": action, "rewards": reward,
                   "dones": done, "next_obs": obs2}
            return (state, obs2, split[:, 0]), out

        _, traj = jax.lax.scan(body, carry, jax.random.split(k_act, steps))
        return traj

    return make(jax.random.fold_in(jax.random.PRNGKey(seed), FILL_TAG))
