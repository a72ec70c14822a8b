"""Plain reference for walle-mlp-ppo: PPO with a Gaussian-MLP policy and
value net on the planar cheetah, from the seed up.

What it computes, in straightforward ``jax.numpy`` (no kernels, no
runner, no buffers):

* weights from ``PRNGKey(seed)``: policy ``[obs, h, h, act]`` and value
  ``[obs, h, h, 1]`` nets from the two halves of the key, log-std -0.5;
* each sampler's start from its own key (``seed`` for one batch of
  ``env_batch`` instances, ``seed + i`` for sampler ``i`` of
  ``num_samplers``), then ``horizon`` steps of: split each instance's key
  in three (next key, action noise, env), Gaussian action, the plain
  cheetah step and, where an episode ends, a fresh start;
* GAE (gamma, lambda) with the bootstrap value of the last observation,
  advantages normalized over the whole batch, the batch flattened
  time-major, ``epochs`` passes of ``minibatches`` contiguous slices,
  each one clipped-surrogate + value - entropy loss, gradient clipped to
  a global norm, one Adam step.

These are the system's documented semantics (``experiment.build``: params
from ``PRNGKey(seed)``, sampler ``i`` from ``PRNGKey(seed + i)``), so the
random streams are the system's and the two runs can be compared step by
step.

It follows the program (``FOLLOWS_PROGRAM``; the rule is in
``bench/harness.py``). In a stepped (``sync``) cell the harness hands
``run`` the check calls' merged trajectories as ``program=[traj, ...]``.
The reference then draws no action of its own: it takes from each step
the program's action and the observation the program fed, steps its own
cheetah from the seed with that action and the step's env key, computes
the behaviour log-prob and value at the fed observation, and runs its own
GAE and learner on that data. Besides losses, gradient and change it
returns ``numbers``:

* ``env_gap``: the worst gap of its own observation (per component) and
  reward against the program's over every step of every check call, each
  against the larger of the reference's magnitude and its median
  magnitude (``reflib.scaled_gap``: angles and velocities pass through
  zero); a done flag that disagrees reads 1;
* ``logp_gap``: the worst absolute gap, in nats, of the program's stored
  behaviour log-prob against the reference's for the same action at the
  same observation (the PPO ratio is the exponential of a log-prob
  difference, so nats are its own scale);
* ``value_gap``: the worst gap of the stored values and bootstrap values,
  against the larger of the reference value and the median ``|value|``.

A fused cell is run as before, without ``program``: its trajectory never
leaves the chunk. There, and wherever ``run`` stands in the program's
place (the control), a stepped run returns its own trajectories under
``trajectories`` in the program's layout, so that the reference can
follow it as it follows the program.
"""
from __future__ import annotations

import math
import pathlib
import sys

import jax
import jax.numpy as jnp

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from bench import reflib  # noqa: E402
from bench.envs import cheetah  # noqa: E402

LOG_STD_INIT = -0.5
FOLLOWS_PROGRAM = True


def init_params(seed: int, hidden: int, dtype):
    kp, kv = jax.random.split(jax.random.PRNGKey(seed))
    sizes = [cheetah.OBS_DIM, hidden, hidden]
    params = {"pi": reflib.mlp_init(kp, sizes + [cheetah.ACT_DIM]),
              "log_std": jnp.full((cheetah.ACT_DIM,), LOG_STD_INIT,
                                  jnp.float32),
              "vf": reflib.mlp_init(kv, sizes + [1])}
    return jax.tree.map(lambda x: x.astype(dtype), params)


def samplers(traffic: dict, seed: int):
    """``(first seed, count, instances each)`` of the run's samplers."""
    if traffic.get("env_batch"):
        return seed, 1, int(traffic["env_batch"])
    n = int(traffic["num_samplers"])
    return seed, n, int(traffic["global_batch"]) // n


def make_learner(cfg: dict, *, precision, fault=None):
    """The policy, the value net and one collect's GAE -> learn:
    ``learn(params, opt, traj) -> (params, opt, mean loss)``."""
    hp = cfg["algo_kwargs"]
    gamma, lam = hp["gamma"], hp["lam"]

    def value(params, obs):
        return reflib.mlp_apply(params["vf"], obs, precision)[..., 0]

    def policy(params, obs):
        mean = reflib.mlp_apply(params["pi"], obs, precision)
        return mean, jnp.broadcast_to(jnp.exp(params["log_std"]), mean.shape)

    def gae(rewards, values, dones, last_value):
        nonterm = 1.0 - dones.astype(rewards.dtype)

        def back(carry, xs):
            adv_next, v_next = carry
            r, v, nt = xs
            delta = r + gamma * v_next * nt - v
            adv = delta + gamma * lam * nt * adv_next
            return (adv, v), adv

        _, adv = jax.lax.scan(back, (jnp.zeros_like(last_value), last_value),
                              (rewards, values, nonterm), reverse=True)
        return adv, adv + values

    def loss_fn(params, b):
        mean, std = policy(params, b["obs"])
        logp = reflib.gaussian_logp(mean, std, b["actions"])
        ratio = jnp.exp(logp - b["behavior_logp"])
        adv = b["advantages"]
        surrogate = -jnp.minimum(
            ratio * adv,
            jnp.clip(ratio, 1 - hp["clip_eps"], 1 + hp["clip_eps"]) * adv)
        v_loss = 0.5 * jnp.mean((value(params, b["obs"]) - b["returns"]) ** 2)
        entropy = jnp.sum(params["log_std"]
                          + 0.5 * math.log(2 * math.pi * math.e))
        return (jnp.mean(surrogate) + hp["value_coef"] * v_loss
                - hp["entropy_coef"] * entropy)

    def learn(params, opt, traj):
        adv, ret = gae(traj["rewards"], traj["values"], traj["dones"],
                       traj["last_value"])
        adv = (adv - jnp.mean(adv)) / (jnp.std(adv) + 1e-8)
        batch = {"obs": traj["obs"], "actions": traj["actions"],
                 "behavior_logp": traj["logp"], "advantages": adv,
                 "returns": ret}
        flat = jax.tree.map(lambda x: x.reshape((-1,) + x.shape[2:]), batch)
        n = flat["obs"].shape[0]
        mb = n // hp["minibatches"]
        rows = mb // 2 if fault == "half_batch" else mb
        losses = []
        for _ in range(hp["epochs"]):
            for k in range(hp["minibatches"]):
                b = jax.tree.map(lambda x: x[k * mb:k * mb + rows], flat)
                loss, grads = jax.value_and_grad(loss_fn)(params, b)
                grads = reflib.clip_by_global_norm(grads, hp["max_grad_norm"])
                params, opt = reflib.adam_step(grads, opt, params, hp["lr"])
                losses.append(loss)
        return params, opt, jnp.mean(jnp.stack(losses))

    return policy, value, learn


def merge_samplers(trajs: dict, horizon: int) -> dict:
    """``(samplers, T, b, ...) -> (T, samplers * b, ...)``, sampler-major,
    as the program merges its samplers' trajectories."""
    return {k: (jnp.moveaxis(v, 0, 1).reshape((horizon, -1) + v.shape[3:])
                if k != "last_value" else v.reshape(-1))
            for k, v in trajs.items()}


def make_iteration(cfg: dict, traffic: dict, *, dtype, precision,
                   fault=None):
    """One collect -> GAE -> learn iteration over stacked sampler carries:
    ``(params, opt, carries) -> (params, opt, carries, loss, traj)``,
    ``traj`` the merged trajectory in a stepped cell and None in a fused
    one."""
    horizon = int(traffic["horizon"])
    keep = traffic["runtime"] != "fused"
    policy, value, learn = make_learner(cfg, precision=precision,
                                        fault=fault)

    def rollout(params, carry):
        def body(carry, _):
            state, obs, keys = carry
            split = jax.vmap(lambda k: jax.random.split(k, 3))(keys)
            mean, std = policy(params, obs)
            noise = jax.vmap(lambda k: jax.random.normal(
                k, (cheetah.ACT_DIM,)))(split[:, 1]).astype(dtype)
            action = mean + std * noise
            logp = reflib.gaussian_logp(mean, std, action)
            v = value(params, obs)
            state, obs2, reward, done = cheetah.step_auto_reset(
                state, action, split[:, 2], dtype)
            out = {"obs": obs, "actions": action, "rewards": reward,
                   "dones": done, "logp": logp, "values": v}
            return (state, obs2, split[:, 0]), out

        carry, traj = jax.lax.scan(body, carry, None, length=horizon)
        traj["last_value"] = value(params, carry[1])
        return carry, traj

    def iteration(params, opt, carries):
        carries, trajs = jax.vmap(rollout, in_axes=(None, 0))(params, carries)
        traj = merge_samplers(trajs, horizon)
        params, opt, loss = learn(params, opt, traj)
        return params, opt, carries, loss, (traj if keep else None)

    return jax.jit(iteration)


def make_following_iteration(cfg: dict, traffic: dict, *, dtype, precision,
                             fault=None):
    """One iteration that follows the program's call: ``(params, opt,
    carries, fed_obs, actions) -> (params, opt, carries, loss, own)``.
    ``fed_obs`` and ``actions`` are the program's, merged ``(T, B, ...)``;
    ``own`` is what the reference computed at each step (its own
    observation, reward, done, behaviour log-prob and value, and the
    bootstrap value), merged the same way."""
    horizon = int(traffic["horizon"])
    policy, value, learn = make_learner(cfg, precision=precision,
                                        fault=fault)

    def rollout(params, carry, fed_obs, actions):
        def body(carry, xs):
            state, obs, keys = carry
            fed, action = xs
            split = jax.vmap(lambda k: jax.random.split(k, 3))(keys)
            mean, std = policy(params, fed)
            logp = reflib.gaussian_logp(mean, std, action)
            v = value(params, fed)
            state, obs2, reward, done = cheetah.step_auto_reset(
                state, action, split[:, 2], dtype)
            out = {"obs": obs, "rewards": reward, "dones": done,
                   "logp": logp, "values": v}
            return (state, obs2, split[:, 0]), out

        carry, traj = jax.lax.scan(body, carry, (fed_obs, actions))
        traj["last_value"] = value(params, carry[1])
        return carry, traj

    def per_sampler(x, count):
        """``(T, count * b, ...) -> (count, T, b, ...)``."""
        return jnp.moveaxis(x.reshape((horizon, count, -1) + x.shape[2:]),
                            1, 0)

    def iteration(params, opt, carries, fed_obs, actions):
        count = carries[1].shape[0]
        fed_obs = fed_obs.astype(dtype)
        actions = actions.astype(dtype)
        carries, own = jax.vmap(rollout, in_axes=(None, 0, 0, 0))(
            params, carries, per_sampler(fed_obs, count),
            per_sampler(actions, count))
        own = merge_samplers(own, horizon)
        traj = {"obs": fed_obs, "actions": actions,
                **{k: own[k] for k in ("rewards", "dones", "logp", "values",
                                       "last_value")}}
        params, opt, loss = learn(params, opt, traj)
        return params, opt, carries, loss, own

    return jax.jit(iteration)


def step_numbers(program: list, own: list) -> dict:
    """``env_gap``, ``logp_gap`` and ``value_gap`` over every step of
    every followed call (see the module docstring)."""
    import numpy as np

    def cat(trajs, key):
        return np.concatenate([np.asarray(t[key], np.float64)
                               for t in trajs])

    p_obs, r_obs = cat(program, "obs"), cat(own, "obs")
    env = [reflib.scaled_gap(p_obs[..., c], r_obs[..., c])
           for c in range(p_obs.shape[-1])]
    env.append(reflib.scaled_gap(cat(program, "rewards"),
                                 cat(own, "rewards")))
    if not np.array_equal(cat(program, "dones"), cat(own, "dones")):
        env.append(1.0)
    values = [np.concatenate([cat(t, "values").reshape(-1),
                              cat(t, "last_value").reshape(-1)])
              for t in (program, own)]
    return {"env_gap": float(max(env)),
            "logp_gap": float(np.max(np.abs(cat(program, "logp")
                                            - cat(own, "logp")))),
            "value_gap": reflib.scaled_gap(*values)}


def run(cfg: dict, traffic: dict, seed: int, steps: int, *,
        dtype=jnp.float32, precision="highest", fault=None,
        program=None) -> dict:
    """Follow the system's first ``steps`` calls from the seed; return the
    observables ``reflib.compare`` reads. With ``program`` (the check
    calls' trajectories) the reference takes the program's draws and fed
    observations and adds ``numbers``; without it, a stepped run returns
    its own ``trajectories``."""
    precision = None if precision == "default" else precision
    iters = int(traffic.get("chunk") or 1) \
        if traffic["runtime"] == "fused" else 1
    first, count, each = samplers(traffic, seed)
    keys = jnp.stack([jax.random.PRNGKey(first + i) for i in range(count)])
    carries = jax.vmap(lambda k: cheetah.init_carry(k, each, dtype))(keys)
    params = init_params(seed, int(cfg["model"]["hidden"]), dtype)
    opt = reflib.adam_init(params)
    make = make_iteration if program is None else make_following_iteration
    iteration = make(cfg, traffic, dtype=dtype, precision=precision,
                     fault=fault)
    theta0 = reflib.leaves(params)
    losses, grad, trajs = [], None, []
    for step in range(steps):
        step_losses = []
        for _ in range(iters):
            if program is None:
                params, opt, carries, loss, traj = iteration(
                    params, opt, carries)
            else:
                params, opt, carries, loss, traj = iteration(
                    params, opt, carries, program[step]["obs"],
                    program[step]["actions"])
            step_losses.append(loss)
        if traj is not None:
            trajs.append(jax.device_get(traj))
        losses.append(float(jnp.mean(jnp.stack(step_losses))))
        if step == 0:
            grad = reflib.rms_grad_norms({"": opt})
    out = {"losses": losses, "grad": grad,
           "change": reflib.change_norms(theta0, reflib.leaves(params))}
    if program is not None:
        out["numbers"] = step_numbers(program[:steps], trajs)
    elif trajs:
        out["trajectories"] = trajs
    return out


def program_observables(params, opt_state) -> tuple:
    """The system's state read the same way: ``(params leaves, gradient
    norms from Adam's state)``."""
    return reflib.leaves(params), reflib.rms_grad_norms({"": opt_state})
