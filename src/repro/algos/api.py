"""The ``Algorithm`` protocol — one seam between learners and the runtime.

Every algorithm the framework can train is an object with three methods:

    init(key, env)                  -> (params, opt_state)
    learn(params, opt_state, batch) -> (params, opt_state, metrics) [jittable]
    act(params, obs, key)           -> (action, extras)             [per-obs]

``batch`` is whatever the experiment's **experience buffer** sampled: the
whole merged trajectory for on-policy algorithms (``fifo`` pass-through),
a flat replay minibatch (with ``discounts``/``weights``/``indices``) for
off-policy ones. The plane hooks connect the two:

* ``observe(buffer, state, traj)`` / ``sample(buffer, state, key)`` —
  how the algorithm pushes collected experience into its buffer and draws
  learner batches back out; defaults delegate straight to the buffer.
* ``default_buffer`` — the buffer kind a spec gets when it names none
  (``fifo`` on-policy, ``uniform`` off-policy).
* ``updates_per_collect`` — gradient steps per collected trajectory.
* ``transition_example(env)`` — the per-transition storage schema
  off-policy buffers allocate from.

``make_train_step`` composes an algorithm with a buffer into the single
jittable ``(params, opt_state, plane, traj) -> (params, opt_state, plane,
metrics)`` function every runner drives, where ``plane = (buffer_state,
sample_key)`` is runner-owned — buffer storage no longer hides inside
``opt_state`` (DDPG's old ring did; it now rides the plane like SAC's).

Plus declarative attributes the runtime uses to schedule the collection:
``make_rollout(env, horizon)``, ``step_keys`` / ``tail_keys`` (trajectory
layout -> PartitionSpecs for the sharded backend), ``needs_next_obs``
(off-policy algorithms record full transitions).

``SyncRunner``, ``AsyncOrchestrator`` and ``FusedRunner`` consume any
conforming object through this seam — that is what lets every algo run on
every backend (``repro.experiment``). Adapters for PPO, TRPO, DDPG and
SAC are registered under the ``"algo"`` registry kind.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Protocol, Tuple, runtime_checkable

import jax
import jax.numpy as jnp

from repro import registry
from repro.algos.ddpg import DDPGConfig, ddpg_update, explore_action, init_ddpg
from repro.algos.ppo import PPOConfig, make_mlp_learner
from repro.algos.staleness import STALENESS_OFF, StalenessConfig
from repro.algos.staleness import decay_weights as _decay_weights
from repro.algos.trpo import TRPOConfig, make_trpo_learner
from repro.core import sampler as sampler_mod
from repro.models import mlp_policy
from repro.optim import adam


@runtime_checkable
class Algorithm(Protocol):
    """What a learner must provide to ride the unified runtime."""

    name: str

    def init(self, key, env) -> Tuple[Any, Any]:
        """Build (params, opt_state) for ``env``."""
        ...

    def learn(self, params, opt_state, batch) -> Tuple[Any, Any, Dict]:
        """One update from a sampled batch. Must be jittable."""
        ...

    def act(self, params, obs, key) -> Tuple[jnp.ndarray, Dict]:
        """Action (+ per-step extras) for a single observation."""
        ...


class AlgorithmBase:
    """Default runtime + experience-plane hooks shared by the adapters."""

    name = "base"
    on_policy = True
    needs_next_obs = False
    step_keys: Tuple[str, ...] = ("obs", "actions", "rewards", "dones")
    tail_keys: Tuple[str, ...] = ()
    default_buffer = "fifo"
    updates_per_collect = 1
    # safe to wrap in the shard_map data-parallel learner: the algorithm's
    # ``learn`` routes every gradient through ``grad_sync.value_and_grad``
    # (TRPO's conjugate-gradient line search does not, so it opts out)
    shardable = True
    # importance-weighted staleness correction (algos/staleness.py): the
    # algorithm can consume the async runtime's per-trajectory params-
    # version gap and down-weight stale experience. Off (an inert config)
    # unless the experiment enables it through ``enable_staleness``.
    supports_staleness = False
    staleness: StalenessConfig = STALENESS_OFF

    def enable_staleness(self, cfg) -> None:
        """Install a staleness-correction config (mode string / dict /
        ``StalenessConfig``). A disabled config is always accepted (and
        is a no-op); an enabled one requires ``supports_staleness``."""
        cfg = StalenessConfig.parse(cfg)
        if cfg.enabled and not self.supports_staleness:
            raise ValueError(
                f"algorithm {self.name!r} does not support staleness "
                f"correction (supports_staleness=False) — its update has "
                f"no importance-weighting seam; use staleness mode 'off' "
                f"or a supporting algorithm (ppo, ddpg, sac)")
        self.staleness = cfg

    def make_rollout(self, env, horizon: int):
        return sampler_mod.make_algo_rollout(self, env, horizon)

    def rollout_tail(self, params, final_obs) -> Dict[str, jnp.ndarray]:
        return {}

    # ------------------------------------------- experience-plane hooks
    def observe(self, buffer, state, traj):
        """Push one collected trajectory into the buffer. Jittable."""
        return buffer.add(state, traj)

    def sample(self, buffer, state, key):
        """Draw one learner batch from the buffer. Jittable."""
        return buffer.sample(state, key)


class OffPolicyAlgorithm(AlgorithmBase):
    """Shared plane wiring for replay-based learners (DDPG, SAC):
    full transitions recorded at collect time, a transition-schema hook
    for buffer allocation, and per-update learner RNG threaded through
    the sampled batch as ``batch["rng"]``.

    Staleness correction (when enabled): the per-trajectory
    params-version gap is converted to a per-transition weight at
    *ingest* time (``observe`` — the gap is fixed once the transition
    enters replay), stored alongside the transition, and multiplied
    into the buffer's importance weights at ``sample`` time; DDPG/SAC
    critic losses already honor ``batch["weights"]``. Disabled, none of
    these keys exist and the plane is byte-identical to before."""

    on_policy = False
    needs_next_obs = True
    default_buffer = "uniform"
    updates_per_collect = 4
    step_keys = ("obs", "actions", "rewards", "dones", "next_obs")
    tail_keys: Tuple[str, ...] = ()
    supports_staleness = True

    def transition_example(self, env) -> Dict[str, jnp.ndarray]:
        """One zeroed transition — the storage schema buffers allocate."""
        ex = {
            "obs": jnp.zeros((1, env.obs_dim)),
            "actions": jnp.zeros((1, env.act_dim)),
            "rewards": jnp.zeros((1,)),
            "next_obs": jnp.zeros((1, env.obs_dim)),
            "dones": jnp.zeros((1,), bool),
        }
        if self.staleness.enabled:
            ex["staleness_w"] = jnp.zeros((1,))
        return ex

    def observe(self, buffer, state, traj):
        if self.staleness.enabled:
            traj = dict(traj)
            gap = traj.pop("staleness_gap", None)
            traj["staleness_w"] = (
                jnp.ones_like(traj["rewards"], dtype=jnp.float32)
                if gap is None           # lock-step paths record no gap
                else _decay_weights(self.staleness, gap))
        return buffer.add(state, traj)

    def sample(self, buffer, state, key):
        k_buf, k_learn = jax.random.split(key)
        batch = buffer.sample(state, k_buf)
        if "staleness_w" in batch:
            sw = batch.pop("staleness_w")
            batch["weights"] = batch.get("weights", 1.0) * sw
        batch["rng"] = k_learn          # stochastic learners (SAC) draw here
        return batch


# ==================================================== the composed step
def make_train_step(algo, buffer) -> Callable:
    """Fuse ``algo`` and ``buffer`` into the one jittable step runners
    drive:

        step(params, opt_state, plane, traj)
            -> (params, opt_state, plane, metrics)

    with ``plane = (buffer_state, key)`` owned by the runner (carried
    across iterations device-side — inside the fused engine's donated
    scan, across the sync/async learners' jit calls). Per call: observe
    the trajectory, then ``algo.updates_per_collect`` sample->learn steps
    under ``lax.scan``; learners that report per-sample ``priorities``
    get them routed into ``buffer.update_priorities``.

    For pass-through buffers (``fifo``) with one update per collect the
    step collapses to exactly the historical ``learn(params, opt_state,
    traj)`` call — no scan, no PRNG consumption — which keeps ``ppo`` ×
    ``inline`` bitwise-identical to the pre-plane path.

    The device trace names each part by its scope: ``replay.add``,
    ``replay.sample``, ``replay.update_priorities`` and ``learner.update``.
    """
    updates = int(getattr(algo, "updates_per_collect", 1))

    def observe(buf_state, traj):
        with jax.named_scope("replay.add"):
            return algo.observe(buffer, buf_state, traj)

    def sample(buf_state, key):
        with jax.named_scope("replay.sample"):
            return algo.sample(buffer, buf_state, key)

    def learn(params, opt_state, batch):
        with jax.named_scope("learner.update"):
            return algo.learn(params, opt_state, batch)

    if getattr(buffer, "passthrough", False) and updates == 1:
        def step(params, opt_state, plane, traj):
            buf_state, key = plane
            buf_state = observe(buf_state, traj)
            batch = sample(buf_state, key)
            params, opt_state, metrics = learn(params, opt_state, batch)
            return params, opt_state, (buf_state, key), metrics
        return step

    def step(params, opt_state, plane, traj):
        buf_state, key = plane
        buf_state = observe(buf_state, traj)
        keys = jax.random.split(key, updates + 1)

        def one(carry, k):
            params, opt_state, buf_state = carry
            batch = sample(buf_state, k)
            params, opt_state, metrics = learn(params, opt_state, batch)
            metrics = dict(metrics)
            priorities = metrics.pop("priorities", None)
            if priorities is not None:
                with jax.named_scope("replay.update_priorities"):
                    buf_state = buffer.update_priorities(
                        buf_state, batch["indices"], priorities)
            return (params, opt_state, buf_state), metrics

        (params, opt_state, buf_state), metrics = jax.lax.scan(
            one, (params, opt_state, buf_state), keys[1:])
        return (params, opt_state, (buf_state, keys[0]),
                jax.tree.map(jnp.mean, metrics))

    return step


# ======================================================== PPO-family base
class GaussianMLPAlgorithm(AlgorithmBase):
    """Shared hooks for algorithms on the paper's Gaussian-MLP policy +
    value model (PPO, TRPO): same params structure, same trajectory
    layout (behaviour logp + values + GAE bootstrap), same rollout."""

    step_keys = ("obs", "actions", "rewards", "dones", "logp", "values")
    tail_keys = ("last_value",)

    hidden: int = 64

    def _init_policy(self, key, env):
        return mlp_policy.init_policy(key, env.obs_dim, env.act_dim,
                                      hidden=self.hidden)

    def act(self, params, obs, key):
        action, logp = mlp_policy.sample_action(params, obs, key)
        return action, {"logp": logp,
                        "values": mlp_policy.value_apply(params, obs)}

    def make_rollout(self, env, horizon: int):
        # the historical rollout, verbatim: keeps ppo x inline bitwise-
        # identical to the pre-refactor SyncRunner path
        return sampler_mod.make_env_rollout(env, horizon)

    def rollout_tail(self, params, final_obs):
        return {"last_value": mlp_policy.value_apply(params, final_obs)}


# ===================================================================== PPO
class PPOAlgorithm(GaussianMLPAlgorithm):
    """Clipped-surrogate PPO with the paper's Gaussian-MLP policy."""

    name = "ppo"
    supports_staleness = True

    def __init__(self, lr: float = 3e-4, hidden: int = 64, **cfg_kwargs):
        self.cfg = PPOConfig(lr=lr, **cfg_kwargs)
        self.hidden = hidden
        self._opt = adam(self.cfg.lr)
        self._learn = make_mlp_learner(self._opt, self.cfg)

    def enable_staleness(self, cfg) -> None:
        super().enable_staleness(cfg)
        if self.staleness.enabled:      # weighted advantage path
            self._learn = make_mlp_learner(self._opt, self.cfg,
                                           staleness=self.staleness)

    def init(self, key, env):
        params = self._init_policy(key, env)
        return params, self._opt.init(params)

    def learn(self, params, opt_state, traj):
        return self._learn(params, opt_state, traj)


# ==================================================================== TRPO
class TRPOAlgorithm(GaussianMLPAlgorithm):
    """Natural-gradient TRPO; same policy/value model and trajectory
    layout as PPO, so it shares the PPO rollout."""

    name = "trpo"
    shardable = False               # CG/line-search grads bypass grad_sync

    def __init__(self, lr: float = None, hidden: int = 64, **cfg_kwargs):
        if lr is not None:
            cfg_kwargs.setdefault("vf_lr", lr)
        self.cfg = TRPOConfig(**cfg_kwargs)
        self.hidden = hidden
        self._learn = make_trpo_learner(self.cfg)

    def init(self, key, env):
        return self._init_policy(key, env), None   # no optimizer state

    def learn(self, params, opt_state, traj):
        return self._learn(params, opt_state, traj)


# ==================================================================== DDPG
class DDPGAlgorithm(OffPolicyAlgorithm):
    """Off-policy DDPG on the experience plane: the collect path records
    full transitions (``next_obs``) and each ``learn`` call consumes one
    replay minibatch the plane sampled (uniform or prioritized, any
    ``n_step``).

    ``opt_state`` is now *only* the two Adam states — the replay ring it
    used to smuggle lives in the runner-owned plane state, so capacity /
    batch size / n-step are experiment-level choices
    (``ExperimentSpec.buffer_kwargs``), not algorithm constructor args.
    """

    name = "ddpg"

    def __init__(self, lr: float = None, hidden: int = 64,
                 updates_per_collect: int = 4, **cfg_kwargs):
        if lr is not None:
            cfg_kwargs.setdefault("actor_lr", lr)
            cfg_kwargs.setdefault("critic_lr", lr)
        self.cfg = DDPGConfig(**cfg_kwargs)
        self.hidden = hidden
        self.updates_per_collect = updates_per_collect
        self._a_opt = adam(self.cfg.actor_lr)
        self._c_opt = adam(self.cfg.critic_lr)

    def init(self, key, env):
        params = init_ddpg(key, env.obs_dim, env.act_dim,
                           hidden=self.hidden)
        return params, (self._a_opt.init(params["actor"]),
                        self._c_opt.init(params["critic"]))

    def learn(self, params, opt_state, batch):
        params, opt_state, metrics = ddpg_update(
            params, opt_state, batch, self.cfg, self._a_opt, self._c_opt)
        return params, opt_state, metrics

    def act(self, params, obs, key):
        return explore_action(params, obs, key, self.cfg), {}


def _make_sac(**kwargs):
    # lazy so api <-> sac imports never cycle (sac subclasses
    # OffPolicyAlgorithm from this module)
    from repro.algos.sac import SACAlgorithm
    return SACAlgorithm(**kwargs)


registry.register("algo", "ppo", PPOAlgorithm)
registry.register("algo", "trpo", TRPOAlgorithm)
registry.register("algo", "ddpg", DDPGAlgorithm)
registry.register("algo", "sac", _make_sac)
