"""Plain planar-cheetah physics: the benchmark's own copy.

The same equations as the system's cheetah environment (a 6-joint planar
chain of damped, neighbour-coupled, torque-driven joints; reward forward
velocity minus 0.1 |a|^2; 1000-step episodes), written once over a batch
of ``(B,)``-leading state leaves. The references and the replay fill use
it; it imports nothing of the system under test.

State: ``(th (B, 6), om (B, 6), vx (B,), pitch (B,), t (B,) int32)``.
Observation: ``[th, om, vx, pitch]`` (14 values).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

N_JOINTS = 6
OBS_DIM = 2 * N_JOINTS + 2
ACT_DIM = N_JOINTS
DT = 0.05
DAMPING = 1.5
STIFFNESS = 4.0
GEAR = 6.0
COUPLING = 0.8
MAX_EPISODE_STEPS = 1000
CTRL_COST = 0.1


def reset_one(key, dtype=jnp.float32):
    """One instance's start state and observation."""
    k1, k2 = jax.random.split(key)
    th = jax.random.uniform(k1, (N_JOINTS,), minval=-0.1, maxval=0.1)
    om = jax.random.uniform(k2, (N_JOINTS,), minval=-0.1, maxval=0.1)
    state = (th.astype(dtype), om.astype(dtype), jnp.zeros((), dtype),
             jnp.zeros((), dtype), jnp.zeros((), jnp.int32))
    return state, obs_one(state)


def obs_one(state):
    th, om, vx, pitch, _ = state
    return jnp.concatenate([th, om, jnp.stack([vx, pitch])])


def reset(keys, dtype=jnp.float32):
    """Batched reset: one start state per key."""
    return jax.vmap(lambda k: reset_one(k, dtype))(keys)


def step(state, actions):
    """Batched physics step without reset: ``(state', obs, reward, done)``."""
    th, om, vx, pitch, t = state
    a = jnp.clip(actions, -1.0, 1.0)
    neighbour = COUPLING * (jnp.roll(th, 1, axis=-1) - th)
    om = om + DT * (GEAR * a - DAMPING * om - STIFFNESS * th + neighbour)
    th = th + DT * om
    thrust = jnp.mean(jnp.sin(th[:, :-1] - th[:, 1:])
                      * (om[:, :-1] - om[:, 1:]), axis=-1)
    vx = 0.9 * vx + DT * (8.0 * thrust)
    pitch = 0.95 * pitch + 0.05 * jnp.mean(th, axis=-1)
    t = t + 1
    reward = vx - CTRL_COST * jnp.sum(a ** 2, axis=-1)
    done = t >= MAX_EPISODE_STEPS
    obs = jnp.concatenate([th, om, jnp.stack([vx, pitch], axis=-1)], axis=-1)
    return (th, om, vx, pitch, t), obs, reward, done


def step_auto_reset(state, actions, keys, dtype=jnp.float32):
    """Step, then replace finished instances by a fresh start drawn from
    the second half of each instance's key (the first half is the
    physics step's, which draws nothing). The reward and done flag stay
    the finishing step's."""
    split = jax.vmap(jax.random.split)(keys)
    reset_state, reset_obs = reset(split[:, 1], dtype)
    state, obs, reward, done = step(state, actions)

    def pick(r, n):
        mask = done.reshape(done.shape + (1,) * (n.ndim - 1))
        return jnp.where(mask, r, n)

    state = jax.tree.map(pick, reset_state, state)
    return state, pick(reset_obs, obs), reward, done


def init_carry(key, batch: int, dtype=jnp.float32):
    """A sampler's start: ``(states, obs, per-instance keys)``."""
    k_reset, k_keys = jax.random.split(key)
    states, obs = reset(jax.random.split(k_reset, batch), dtype)
    return states, obs, jax.random.split(k_keys, batch)
