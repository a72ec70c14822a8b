"""Rollout samplers — WALL-E's N parallel sampler processors, JAX-native.

Three granularities of "parallel sampler":

* ``make_env_rollout`` — one sampler: a ``vmap``-batched environment swept
  ``T`` steps with ``lax.scan`` under the current policy. This is the unit
  of work one WALL-E sampler process performs per iteration.
* ``make_sharded_rollout`` — the TPU-native form: ``shard_map`` places one
  sampler per ``data``-axis mesh slice; trajectories are *born sharded* and
  the learner consumes them in place (the experience queue becomes zero
  movement; see DESIGN.md §2).
* ``make_lm_rollout`` — the sequence-model sampler: autoregressive decode
  against a synthetic reward model (``envs.lm_env``), i.e. the RLHF-style
  workload whose inner step ``decode_32k``/``long_500k`` lower.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.envs.base import Env, auto_reset
from repro.models import mlp_policy, transformer


# ============================================================ env sampler
def batched_reset(env: Env, key, batch: int):
    states, obs = jax.vmap(env.reset)(jax.random.split(key, batch))
    return states, obs


def batched_step(env: Env) -> Callable:
    """Batched step+auto-reset: ``step(state, actions, keys)`` over
    ``(B,)``-leading leaves.

    A ``VectorEnv`` supplies its fused fast-path (``batched_step``
    attribute — one kernel dispatch for the whole batch); a plain ``Env``
    gets the historical ``vmap(auto_reset(env))``, byte-for-byte the
    rollout bodies' previous inline expression. The two are
    bitwise-identical for matched keys (``tests/test_vector_env.py``), so
    which one a rollout traces is a scheduling choice, not a numerical
    one.
    """
    fast = getattr(env, "batched_step", None)
    if fast is not None:
        return fast
    step_fn = auto_reset(env)

    def step(state, actions, keys):
        return jax.vmap(step_fn)(state, actions, keys)

    return step


def make_env_rollout(env: Env, horizon: int) -> Callable:
    """Build ``rollout(params, carry, step_keys) -> (carry', traj)``.

    carry = (env_state pytree (B,...), obs (B,obs_dim), keys (B,) PRNG).
    traj arrays are time-major ``(T, B, ...)``; includes ``last_value``.
    Pure and jit/shard_map-compatible.
    """
    step_batch = batched_step(env)

    def rollout(params, carry, _unused=None):
        def body(carry, _):
            env_state, obs, keys = carry
            splits = jax.vmap(lambda k: jax.random.split(k, 3))(keys)
            keys2, ka, ke = splits[:, 0], splits[:, 1], splits[:, 2]
            actions, logp = jax.vmap(
                mlp_policy.sample_action, in_axes=(None, 0, 0))(
                    params, obs, ka)
            values = mlp_policy.value_apply(params, obs)
            env_state2, obs2, rewards, dones = step_batch(
                env_state, actions, ke)
            out = {"obs": obs, "actions": actions, "rewards": rewards,
                   "dones": dones, "logp": logp, "values": values}
            return (env_state2, obs2, keys2), out

        carry, traj = jax.lax.scan(body, carry, None, length=horizon)
        traj["last_value"] = mlp_policy.value_apply(params, carry[1])
        return carry, traj

    return rollout


def init_env_carry(env: Env, key, batch: int):
    k_reset, k_keys = jax.random.split(key)
    states, obs = batched_reset(env, k_reset, batch)
    keys = jax.random.split(k_keys, batch)
    return (states, obs, keys)


def make_algo_rollout(algo, env: Env, horizon: int) -> Callable:
    """Algorithm-generic rollout: actions come from ``algo.act``.

    ``algo.act(params, obs, key) -> (action, extras)`` is vmapped over the
    env batch; per-step ``extras`` (e.g. behaviour logp) land in the traj
    under their own keys. Off-policy algos (``algo.needs_next_obs``) get
    ``next_obs`` recorded so the learner can build replay transitions;
    ``algo.rollout_tail`` appends end-of-rollout values (e.g. the GAE
    bootstrap). Same carry/traj layout as ``make_env_rollout``, so every
    backend schedules it unchanged.
    """
    step_batch = batched_step(env)
    needs_next_obs = bool(getattr(algo, "needs_next_obs", False))

    def rollout(params, carry, _unused=None):
        def body(carry, _):
            env_state, obs, keys = carry
            splits = jax.vmap(lambda k: jax.random.split(k, 3))(keys)
            keys2, ka, ke = splits[:, 0], splits[:, 1], splits[:, 2]
            actions, extras = jax.vmap(
                algo.act, in_axes=(None, 0, 0))(params, obs, ka)
            env_state2, obs2, rewards, dones = step_batch(
                env_state, actions, ke)
            out = {"obs": obs, "actions": actions, "rewards": rewards,
                   "dones": dones, **extras}
            if needs_next_obs:
                out["next_obs"] = obs2
            return (env_state2, obs2, keys2), out

        carry, traj = jax.lax.scan(body, carry, None, length=horizon)
        traj.update(algo.rollout_tail(params, carry[1]))
        return carry, traj

    return rollout


# ====================================================== sharded (TPU) form
def make_sharded_rollout(env: Env, horizon: int, mesh,
                         data_axes=("data",), rollout: Callable = None,
                         step_keys: Tuple[str, ...] = ("obs", "actions",
                                                       "rewards", "dones",
                                                       "logp", "values"),
                         tail_keys: Tuple[str, ...] = ("last_value",)
                         ) -> Callable:
    """One WALL-E sampler per ``data``-axis slice via shard_map.

    Params are replicated (the policy broadcast = the paper's policy queue);
    env state / trajectories are sharded on the batch axis and never leave
    their shard — the learner's pjit consumes them with identical sharding.

    ``rollout`` defaults to the PPO-family ``make_env_rollout``; pass an
    algorithm rollout (``make_algo_rollout``) plus its ``step_keys`` /
    ``tail_keys`` to shard any algorithm's trajectory layout.
    """
    from jax.sharding import PartitionSpec as P

    if rollout is None:
        rollout = make_env_rollout(env, horizon)
    batch_spec = P(data_axes)                      # leading dim = env batch
    carry_spec = (batch_spec, batch_spec, batch_spec)
    # trajectory arrays are time-major (T, B, ...): batch is dim 1
    traj_spec = {k: P(None, data_axes) for k in step_keys}
    traj_spec.update({k: batch_spec for k in tail_keys})

    sharded = jax.shard_map(
        lambda p, c: rollout(p, c), mesh=mesh,
        in_specs=(P(), carry_spec), out_specs=(carry_spec, traj_spec),
        check_vma=False)
    return sharded


# ============================================================== LM sampler
def make_lm_rollout(cfg, lmenv, gen_len: int) -> Callable:
    """Sequence-policy sampler: prefill the prompt, then decode ``gen_len``
    tokens (the experience-collection inner loop), scoring with the token
    reward model. Returns time-major traj compatible with the PPO learner.
    """

    def rollout(params, prompt: jnp.ndarray, key) -> Dict[str, jnp.ndarray]:
        B, P = prompt.shape
        state, logits = transformer.prefill(cfg, params, prompt,
                                            gen_budget=gen_len)

        def body(carry, key_t):
            state, logits = carry
            tok = jax.random.categorical(key_t, logits)          # (B,)
            logp = jnp.take_along_axis(jax.nn.log_softmax(logits, -1),
                                       tok[:, None], axis=-1)[:, 0]
            state, logits2 = transformer.decode_step(cfg, params, state,
                                                     tok[:, None])
            return (state, logits2), (tok, logp)

        keys = jax.random.split(key, gen_len)
        (state, _), (tokens, logps) = jax.lax.scan(body, (state, logits),
                                                   keys)
        tokens = tokens.T                                       # (B, T)
        logps = logps.T
        rewards = lmenv.token_rewards(tokens)
        return {
            "tokens": tokens, "logp": logps, "rewards": rewards,
            "prompt": prompt,
        }

    return rollout


# ========================================================== the worker spec
@dataclasses.dataclass(frozen=True)
class WorkerSpec:
    """Everything a fresh *process* needs to become one rollout worker.

    Plain data only — registry names plus JSON-safe kwargs — so the spec
    pickles across a ``spawn`` boundary and the worker rebuilds its env,
    algorithm, jitted rollout and carry purely via the registry
    (``build``); no closures, params or tracers ever cross. ``seed`` is
    the *per-worker* seed (the parent passes ``schedule.seed + i``), so a
    process worker's carry is bitwise the carry the inline backend would
    have built for sampler ``i`` — the root of the ``process == inline``
    determinism rule (DESIGN.md §6).
    """
    env: str
    algo: str
    horizon: int
    batch: int                      # per-worker env batch
    seed: int                       # per-worker: schedule.seed + worker_id
    kernels: str = "auto"
    env_kwargs: Dict[str, Any] = dataclasses.field(default_factory=dict)
    algo_kwargs: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "WorkerSpec":
        return cls(**d)

    def build(self):
        """Rebuild ``(rollout, carry, params_template)`` in this process.

        ``rollout`` is the algorithm's unjitted rollout (callers jit it);
        ``carry`` the worker's initial env carry; ``params_template`` a
        freshly-initialized params pytree whose *structure* (not values)
        lets the worker unflatten leaves read from a ``ParamsChannel``.
        Sets the kernel-plane mode first so everything traced here sees
        the spec's implementation choice.
        """
        from repro import kernels as kernels_mod
        from repro import registry
        kernels_mod.set_kernel_mode(self.kernels)
        env = registry.make("env", self.env, **dict(self.env_kwargs))
        algo = registry.make("algo", self.algo, **dict(self.algo_kwargs))
        rollout = algo.make_rollout(env, self.horizon)
        carry = init_env_carry(env, jax.random.PRNGKey(self.seed),
                               self.batch)
        params, _ = algo.init(jax.random.PRNGKey(self.seed), env)
        return rollout, carry, params


# ===================================================== sample-count helper
def samples_per_rollout(batch: int, horizon: int) -> int:
    return batch * horizon


def split_batch(global_batch: int, num_samplers: int) -> int:
    """Per-sampler env batch (the paper divides 20000 samples across N).

    Raises ``ValueError`` when the split is not exact — silently
    truncating would collect fewer samples than the schedule promised.
    """
    if num_samplers < 1:
        raise ValueError(f"num_samplers={num_samplers} must be >= 1")
    if global_batch < 1:
        raise ValueError(f"global_batch={global_batch} must be >= 1")
    if global_batch % num_samplers != 0:
        lower = (global_batch // num_samplers) * num_samplers
        upper = lower + num_samplers
        raise ValueError(
            f"global_batch={global_batch} is not divisible by "
            f"num_samplers={num_samplers}; every sampler must get an "
            f"equal env batch — adjust global_batch (nearest multiples: "
            + (f"{lower} or {upper}" if lower >= num_samplers
               else f"{upper}") + ")")
    return global_batch // num_samplers
