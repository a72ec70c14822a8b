"""Plain reference for sac256-per: SAC (twin critics, tanh-squashed
Gaussian actor, learned temperature) on a proportional prioritized
replay, on the planar cheetah, from the seed up.

What it computes, in straightforward ``jax.numpy`` (no kernels, no
runner, no buffer objects):

* weights from ``PRNGKey(seed)`` split in three (actor, critic 1,
  critic 2), target critics a copy, log-temperature log(init_alpha);
* the replay: a ring of ``capacity`` rows per field and a sum-tree kept
  as its full list of levels, rebuilt from the leaves by pairwise sums
  after every write; it starts from the cell's fill, every row at
  priority 1;
* per iteration: ``env_batch`` instances step ``horizon`` times with
  squashed actions (each instance's key split in three: next key,
  action noise, env); the transitions enter the ring at its head at the
  running max priority ** alpha; then ``updates_per_collect`` times:
  ``batch`` stratified masses over the tree's total, a descent to each
  leaf, importance weights ``(N P(i))^-beta`` over their max, a twin
  critic step against the entropy-regularized target, an actor step
  against the fresh critic, a temperature step, a polyak step of the
  targets, and ``(|TD| + eps) ** alpha`` written back at the drawn rows.

These follow the system's documented seeds (params from
``PRNGKey(seed)``, the env batch from ``PRNGKey(seed)``, the replay's
sampling key ``fold_in(PRNGKey(seed), 0xB0FF)``), so the two runs draw
the same noise and the same first minibatch.
"""
from __future__ import annotations

import math
import pathlib
import sys

import jax
import jax.numpy as jnp

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from bench import reflib, spec  # noqa: E402
from bench.envs import cheetah  # noqa: E402

PLANE_KEY_TAG = 0xB0FF
LOG_STD_MIN, LOG_STD_MAX = -5.0, 2.0
FIELDS = ("obs", "actions", "rewards", "next_obs", "discounts")


def init_params(seed: int, hidden: int, init_alpha: float, dtype):
    ka, k1, k2 = jax.random.split(jax.random.PRNGKey(seed), 3)
    q_sizes = [cheetah.OBS_DIM + cheetah.ACT_DIM, hidden, hidden, 1]
    critic = {"q1": reflib.mlp_init(k1, q_sizes),
              "q2": reflib.mlp_init(k2, q_sizes)}
    params = {"actor": reflib.mlp_init(
                  ka, [cheetah.OBS_DIM, hidden, hidden, 2 * cheetah.ACT_DIM]),
              "critic": critic,
              "target_critic": jax.tree.map(jnp.copy, critic),
              "log_alpha": jnp.asarray(math.log(init_alpha), jnp.float32)}
    return jax.tree.map(lambda x: x.astype(dtype), params)


def levels_of(leaves):
    levels = [leaves]
    while levels[-1].shape[0] > 1:
        levels.append(levels[-1].reshape(-1, 2).sum(axis=-1))
    return levels


def descend(levels, masses):
    idx = jnp.zeros(masses.shape, jnp.int32)
    for level in levels[-2::-1]:
        idx = idx * 2
        left = level[idx]
        right = masses >= left
        masses = jnp.where(right, masses - left, masses)
        idx = jnp.where(right, idx + 1, idx)
    return idx


def make_iteration(cfg: dict, traffic: dict, *, dtype, precision,
                   fault=None):
    """``(params, opts, carry, replay, key) -> (..., critic loss)`` for
    one collect -> insert -> updates iteration."""
    hp = cfg["algo_kwargs"]
    bk = cfg["buffer_kwargs"]
    horizon = int(traffic["horizon"])
    updates = int(traffic["updates_per_collect"])
    batch = int(bk["batch_size"])
    rows = batch // 2 if fault == "half_batch" else batch
    gamma, tau = hp["gamma"], hp["tau"]
    p_alpha, beta, eps = bk["alpha"], bk["beta"], bk["eps"]
    lr = hp["lr"]

    def mlp(net, x):
        return reflib.mlp_apply(net, x, precision)

    def sample_action(net, obs, noise):
        mean, log_std = jnp.split(mlp(net, obs), 2, axis=-1)
        std = jnp.exp(jnp.clip(log_std, LOG_STD_MIN, LOG_STD_MAX))
        u = mean + std * noise
        squash = 2.0 * (math.log(2.0) - u - jax.nn.softplus(-2.0 * u))
        return jnp.tanh(u), (reflib.gaussian_logp(mean, std, u)
                             - jnp.sum(squash, axis=-1))

    def q(net, obs, act):
        return mlp(net, jnp.concatenate([obs, act], axis=-1))[..., 0]

    def normal(key, shape):
        return jax.random.normal(key, shape).astype(dtype)

    def collect(params, carry):
        def body(carry, _):
            state, obs, keys = carry
            split = jax.vmap(lambda k: jax.random.split(k, 3))(keys)
            noise = jax.vmap(lambda k: normal(k, (cheetah.ACT_DIM,)))(
                split[:, 1])
            action, _ = sample_action(params["actor"], obs, noise)
            state, obs2, reward, done = cheetah.step_auto_reset(
                state, action, split[:, 2], dtype)
            out = {"obs": obs, "actions": action, "rewards": reward,
                   "next_obs": obs2,
                   "discounts": gamma * (1.0 - done.astype(reward.dtype))}
            return (state, obs2, split[:, 0]), out

        carry, traj = jax.lax.scan(body, carry, None, length=horizon)
        return carry, {k: v.reshape((-1,) + v.shape[2:])
                       for k, v in traj.items()}

    def update(params, opts, bt, key):
        a_opt, c_opt, al_opt = opts
        k_next, k_new = jax.random.split(key)
        alpha = jnp.exp(params["log_alpha"])
        noise_next = normal(k_next, (rows, cheetah.ACT_DIM))
        noise_new = normal(k_new, (rows, cheetah.ACT_DIM))

        a_next, logp_next = sample_action(params["actor"], bt["next_obs"],
                                          noise_next)
        q_next = jnp.minimum(
            q(params["target_critic"]["q1"], bt["next_obs"], a_next),
            q(params["target_critic"]["q2"], bt["next_obs"], a_next))
        target = bt["rewards"] + bt["discounts"] * (q_next
                                                    - alpha * logp_next)

        def critic_loss(c):
            q1 = q(c["q1"], bt["obs"], bt["actions"])
            q2 = q(c["q2"], bt["obs"], bt["actions"])
            return 0.5 * jnp.mean(bt["weights"] * ((q1 - target) ** 2
                                                   + (q2 - target) ** 2)), \
                (q1, q2)

        (c_loss, (q1, q2)), c_grads = jax.value_and_grad(
            critic_loss, has_aux=True)(params["critic"])
        critic, c_opt = reflib.adam_step(c_grads, c_opt, params["critic"], lr)

        def actor_loss(a):
            act, logp = sample_action(a, bt["obs"], noise_new)
            q_min = jnp.minimum(q(critic["q1"], bt["obs"], act),
                                q(critic["q2"], bt["obs"], act))
            return jnp.mean(alpha * logp - q_min), logp

        (_, logp_new), a_grads = jax.value_and_grad(
            actor_loss, has_aux=True)(params["actor"])
        actor, a_opt = reflib.adam_step(a_grads, a_opt, params["actor"], lr)

        al_grad = -jnp.mean(logp_new - float(cheetah.ACT_DIM))
        log_alpha, al_opt = reflib.adam_step(
            al_grad, al_opt, params["log_alpha"], hp["alpha_lr"])
        params = {"actor": actor, "critic": critic,
                  "target_critic": jax.tree.map(
                      lambda t, s: (1 - tau) * t + tau * s,
                      params["target_critic"], critic),
                  "log_alpha": log_alpha}
        td = 0.5 * (jnp.abs(q1 - target) + jnp.abs(q2 - target))
        return params, (a_opt, c_opt, al_opt), td, c_loss

    def iteration(params, opts, carry, replay, key):
        storage, head, size, leaves, max_p = replay
        carry, new = collect(params, carry)
        n = new["rewards"].shape[0]
        cap = leaves.shape[0]
        rows_at = (head + jnp.arange(n)) % cap
        storage = {k: storage[k].at[rows_at].set(new[k]) for k in FIELDS}
        leaves = leaves.at[rows_at].set(
            jnp.full((n,), max_p ** p_alpha, leaves.dtype))
        head, size = (head + n) % cap, jnp.minimum(size + n, cap)
        keys = jax.random.split(key, updates + 1)

        def one(state, k):
            params, opts, leaves, max_p = state
            k_buf, k_learn = jax.random.split(k)
            levels = levels_of(leaves)
            total = levels[-1][0]
            u = (jnp.arange(batch, dtype=jnp.float32)
                 + jax.random.uniform(k_buf, (batch,))) / batch
            idx = jnp.minimum(descend(levels, u * total),
                              jnp.maximum(size, 1) - 1)
            probs = leaves[idx] / jnp.maximum(total, eps)
            w = (jnp.maximum(size, 1).astype(jnp.float32)
                 * jnp.maximum(probs, eps)) ** (-beta)
            bt = {k: storage[k][idx][:rows] for k in FIELDS}
            bt["weights"] = (w / jnp.max(w))[:rows].astype(dtype)
            params, opts, td, loss = update(params, opts, bt, k_learn)
            p = jnp.abs(td) + eps
            leaves = leaves.at[idx[:rows]].set((p ** p_alpha
                                                ).astype(leaves.dtype))
            return (params, opts, leaves, jnp.maximum(max_p, jnp.max(p))), \
                loss

        (params, opts, leaves, max_p), losses = jax.lax.scan(
            one, (params, opts, leaves, max_p), keys[1:])
        return (params, opts, carry, (storage, head, size, leaves, max_p),
                keys[0], jnp.mean(losses))

    return jax.jit(iteration, donate_argnums=(3,))


def initial_replay(cfg: dict, traffic: dict, seed: int, dtype):
    """The ring and leaves after set-up's fill: the fill's transitions,
    1-step, time-major, from row 0, each at priority 1."""
    bk = cfg["buffer_kwargs"]
    cap = 1 << (int(bk["capacity"]) - 1).bit_length()
    traj = spec.replay_fill(traffic["replay_fill"], seed, dtype)
    flat = {"obs": traj["obs"], "actions": traj["actions"],
            "rewards": traj["rewards"], "next_obs": traj["next_obs"],
            "discounts": cfg["algo_kwargs"]["gamma"]
            * (1.0 - traj["dones"].astype(traj["rewards"].dtype))}
    flat = {k: v.reshape((-1,) + v.shape[2:]) for k, v in flat.items()}
    n = flat["rewards"].shape[0]
    storage = {k: jnp.zeros((cap,) + v.shape[1:], dtype).at[
        jnp.arange(n) % cap].set(v) for k, v in flat.items()}
    leaves = jnp.zeros((cap,), jnp.float32).at[jnp.arange(n) % cap].set(1.0)
    return (storage, jnp.asarray(n % cap, jnp.int32),
            jnp.asarray(min(n, cap), jnp.int32), leaves,
            jnp.ones((), jnp.float32))


def run(cfg: dict, traffic: dict, seed: int, steps: int, *,
        dtype=jnp.float32, precision="highest", fault=None) -> dict:
    """Follow the system's first ``steps`` calls from the seed; return the
    observables ``reflib.compare`` reads."""
    precision = None if precision == "default" else precision
    hp = cfg["algo_kwargs"]
    params = init_params(seed, int(cfg["model"]["hidden"]),
                         hp["init_alpha"], dtype)
    opts = (reflib.adam_init(params["actor"]),
            reflib.adam_init(params["critic"]),
            reflib.adam_init(params["log_alpha"]))
    carry = cheetah.init_carry(jax.random.PRNGKey(seed),
                               int(traffic["env_batch"]), dtype)
    replay = initial_replay(cfg, traffic, seed, dtype)
    key = jax.random.fold_in(jax.random.PRNGKey(seed), PLANE_KEY_TAG)
    iteration = make_iteration(cfg, traffic, dtype=dtype,
                               precision=precision, fault=fault)
    iters = spec.iterations_per_call(traffic)
    theta0 = reflib.leaves(params)
    losses, grad = [], None
    for step in range(steps):
        step_losses = []
        for _ in range(iters):
            params, opts, carry, replay, key, loss = iteration(
                params, opts, carry, replay, key)
            step_losses.append(loss)
        losses.append(float(jnp.mean(jnp.stack(step_losses))))
        if step == 0:
            grad = _grad_norms(opts)
    return {"losses": losses, "grad": grad,
            "change": reflib.change_norms(theta0, reflib.leaves(params))}


def _grad_norms(opts) -> dict:
    return reflib.rms_grad_norms({"/actor": opts[0], "/critic": opts[1],
                                  "/log_alpha": opts[2]})


def program_observables(params, opt_state) -> tuple:
    """The system's state read the same way: ``(params leaves, gradient
    norms from the three Adam states)``."""
    return reflib.leaves(params), _grad_norms(opt_state)
