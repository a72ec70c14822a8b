"""The fused engine: collect -> GAE -> PPO, one dispatch per chunk.

The stepped runners pay a host<->device round-trip per sampler per
iteration (dispatch the rollout, block, merge, dispatch the update, block).
On the workloads the paper measures that dispatch overhead is pure loss —
rollout, GAE and the minibatched PPO update are all jittable already. The
fused engine rolls the *entire* iteration into the body of one
``lax.scan`` over ``chunk`` iterations under a single ``jit`` with donated
buffers, so the whole collect->learn loop stays resident on the device and
the host pays one dispatch per chunk instead of ~2N per iteration
(DESIGN.md §2).

With vector collection (``schedule.env_batch`` — the env plane,
DESIGN.md §7) the rollout inside the scan steps a device-resident
``VectorEnv`` batch through the fused ``env_step`` kernels, so env
stepping included, a whole collect->GAE->learn iteration is one donated
dispatch.

``make_fused_train_loop`` builds the raw jitted chunk function;
``FusedRunner`` wraps it in the runner interface (``run`` ->
``IterationLog`` list) so launch/examples/benchmarks treat it like any
other backend.
"""
from __future__ import annotations

import time
from functools import partial
from typing import Any, Callable, Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import sampler as sampler_mod
from repro.core.backends import BackendCloseMixin
from repro.data import trajectory


class TrainState(NamedTuple):
    """Everything the fused loop carries across iterations, device-side.

    ``plane_state`` is the experience plane's ``(buffer_state, key)`` —
    replay rings and sum-trees live *inside* the donated scan carry, so
    off-policy training updates its buffer in place on device across
    chunks with zero host round-trips.
    """
    params: Any
    opt_state: Any
    env_carry: Any
    plane_state: Any = None


def make_fused_train_loop(env, learn: Optional[Callable], horizon: int,
                          chunk: int,
                          rollout: Optional[Callable] = None,
                          train_step: Optional[Callable] = None) -> Callable:
    """Build ``train_chunk(state) -> (state', metrics)``.

    ``learn`` is a jittable ``(params, opt_state, traj) -> (params,
    opt_state, metrics)`` (e.g. ``make_mlp_learner``: GAE + epochs of
    minibatched PPO). One call runs ``chunk`` full collect->learn
    iterations on device; metrics come back stacked ``(chunk, ...)`` with
    per-iteration ``mean_return``. The state argument is donated, so
    params/optimizer/env buffers are updated in place across chunks.

    ``rollout`` defaults to the PPO-family ``make_env_rollout``; pass an
    ``Algorithm``'s rollout to fuse any algo's collect->learn iteration.
    Pass ``train_step`` (``algos.api.make_train_step``) instead of
    ``learn`` to fuse the whole experience plane — observe -> sample ->
    learn with ``state.plane_state`` threaded through the scan carry.
    """
    if rollout is None:
        rollout = sampler_mod.make_env_rollout(env, horizon)

    def one_iteration(state: TrainState, _):
        env_carry, traj = rollout(state.params, state.env_carry)
        if train_step is not None:
            params, opt_state, plane_state, metrics = train_step(
                state.params, state.opt_state, state.plane_state, traj)
        else:
            params, opt_state, metrics = learn(state.params,
                                               state.opt_state, traj)
            plane_state = state.plane_state
        metrics = dict(metrics)
        metrics["mean_return"] = trajectory.episode_returns(traj)
        return TrainState(params, opt_state, env_carry, plane_state), metrics

    @partial(jax.jit, donate_argnums=(0,))
    def train_chunk(state: TrainState):
        return jax.lax.scan(one_iteration, state, None, length=chunk)

    return train_chunk


class FusedRunner(BackendCloseMixin):
    """Runner-shaped driver over the fused loop; ``close`` is the
    mixin's no-op (nothing host-side to release).

    The fused engine has no host-visible collect/learn boundary — that is
    the point — so ``IterationLog.collect_time``/``collect_time_serial``
    are 0.0 and ``learn_time`` carries the whole fused iteration's share
    of the chunk's wall time (DESIGN.md §2).

    ``overlap=True`` trades the single fused dispatch for a
    double-buffered two-dispatch pipeline: collect and learn become
    separate donated jits so iteration k+1's rollout executes while
    iteration k's update runs on the learner mesh (DESIGN.md §11). The
    scan ``chunk`` is ignored in this mode — the host must see the
    collect/learn boundary to pipeline across it. Overlapped collects
    act with params one update behind; the consuming iteration's log
    stamps ``staleness=1.0`` and ``overlap_saved_s`` reports the learn
    time hidden under the collect.
    """

    def __init__(self, env, learn: Optional[Callable], params: Any,
                 opt_state: Any, env_carry: Any, horizon: int,
                 chunk: Optional[int] = None,
                 rollout: Optional[Callable] = None,
                 train_step: Optional[Callable] = None,
                 plane_state: Any = None,
                 overlap: bool = False):
        assert learn is not None or train_step is not None
        self.env = env
        self.learn = learn
        self.train_step = train_step
        self.horizon = horizon
        self.chunk = chunk
        self.rollout = rollout
        self.overlap = overlap
        self._overlap_fns_cache = None
        self._overlap_clock = None        # created on first overlapped run;
        self._overlap_done = 0            # warmup is per-runner, not per
        #                                   run() call
        # the chunk fn donates its input state; copy so the caller's
        # params/opt_state/carry/plane buffers survive the first dispatch
        self.state = jax.tree.map(
            jnp.copy, TrainState(params, opt_state, env_carry, plane_state))
        self.num_samplers = 1
        self.logs: List = []
        self._loops: Dict[int, Callable] = {}
        self._samples_per_iter = sampler_mod.samples_per_rollout(
            env_carry[1].shape[0], horizon)      # obs is (B, obs_dim)
        from repro.core.timing import PhaseTimer
        self.timer = PhaseTimer()

    @property
    def params(self):
        return self.state.params

    @property
    def opt_state(self):
        return self.state.opt_state

    @property
    def plane_state(self):
        return self.state.plane_state

    @property
    def buffer_state(self):
        return (None if self.state.plane_state is None
                else self.state.plane_state[0])

    def loop_for(self, chunk: int) -> Callable:
        """The jitted, donating function that runs ``chunk`` fused
        iterations: ``loop_for(c).lower(runner.state)`` is the program."""
        if chunk not in self._loops:
            self._loops[chunk] = make_fused_train_loop(
                self.env, self.learn, self.horizon, chunk,
                rollout=self.rollout, train_step=self.train_step)
        return self._loops[chunk]

    # ----------------------------------------------------------- overlap
    def _overlap_fns(self):
        """(collect_fn, learn_fn) for the pipelined mode.

        ``collect_fn`` donates the env carry (serial chain); ``learn_fn``
        donates opt_state / plane_state / the consumed trajectory —
        params are NOT donated, the concurrent collect still reads
        them — and computes ``mean_return`` inside the trace, before
        the trajectory buffer is reclaimed for iteration k+2.
        """
        if self._overlap_fns_cache is not None:
            return self._overlap_fns_cache
        rollout = self.rollout or sampler_mod.make_env_rollout(
            self.env, self.horizon)
        train_step, learn = self.train_step, self.learn

        def learn_body(params, opt_state, plane_state, traj):
            if train_step is not None:
                params, opt_state, plane_state, metrics = train_step(
                    params, opt_state, plane_state, traj)
            else:
                params, opt_state, metrics = learn(params, opt_state, traj)
            metrics = dict(metrics)
            metrics["mean_return"] = trajectory.episode_returns(traj)
            return params, opt_state, plane_state, metrics

        self._overlap_fns_cache = (
            jax.jit(rollout, donate_argnums=(1,)),
            jax.jit(learn_body, donate_argnums=(1, 2, 3)))
        return self._overlap_fns_cache

    _OVERLAP_WARMUP = 2         # it 0 pays compilation, it 1 gives learn_ref

    def _run_overlapped(self, iterations: int) -> List:
        from repro.core.orchestrator import (
            IterationLog, OverlapClock, record_log, tree_ready)
        collect_fn, learn_fn = self._overlap_fns()
        if self._overlap_clock is None:
            self._overlap_clock = OverlapClock()
        clock = self._overlap_clock
        params, opt_state, env_carry, plane_state = self.state
        done0 = len(self.logs)

        t0 = time.perf_counter()
        env_carry, traj = collect_fn(params, env_carry)
        jax.block_until_ready(traj)
        collect_dur = time.perf_counter() - t0      # prologue collect
        stale = 0.0

        for it in range(iterations):
            data_dur, data_stale = collect_dur, stale
            t0 = time.perf_counter()
            out = learn_fn(params, opt_state, plane_state, traj)
            traj = None
            saved = 0.0
            warm, self._overlap_done = (self._overlap_done,
                                        self._overlap_done + 1)
            if warm < self._OVERLAP_WARMUP:
                # serial: block the learn, then collect with fresh params
                jax.block_until_ready(out[0])
                window = time.perf_counter() - t0
                if warm > 0:        # iteration 0 includes compilation
                    clock.note_serial(window)
                params, opt_state, plane_state, metrics = out
                if it + 1 < iterations:
                    tc = time.perf_counter()
                    env_carry, traj = collect_fn(params, env_carry)
                    jax.block_until_ready(traj)
                    collect_dur, stale = time.perf_counter() - tc, 0.0
            else:
                # pipelined: the collect acts with the pre-update params
                # while the dispatched learn runs on the learner mesh
                if it + 1 < iterations:
                    tc = time.perf_counter()
                    env_carry, traj = collect_fn(params, env_carry)
                    jax.block_until_ready(traj)
                    next_dur = time.perf_counter() - tc
                    saved = clock.saved(next_dur, tree_ready(out[0]))
                    collect_dur, stale = next_dur, 1.0
                params, opt_state, plane_state, metrics = out
                jax.block_until_ready(params)
                window = time.perf_counter() - t0
            record_log(self.logs, self.timer, IterationLog(
                iteration=done0 + it,
                collect_time=data_dur,
                collect_time_serial=data_dur,
                learn_time=max(0.0, window - saved),
                mean_return=float(metrics["mean_return"]),
                samples=self._samples_per_iter,
                staleness=data_stale,
                overlap_saved_s=saved,
            ))
        self.state = TrainState(params, opt_state, env_carry, plane_state)
        return self.logs

    def run(self, iterations: int) -> List:
        from repro.core.orchestrator import IterationLog, record_log
        if self.overlap:
            return self._run_overlapped(iterations)
        done = 0
        while done < iterations:
            c = min(self.chunk or iterations, iterations - done)
            loop = self.loop_for(c)
            t0 = time.perf_counter()
            self.state, metrics = loop(self.state)
            jax.block_until_ready(self.state.params)
            per_iter = (time.perf_counter() - t0) / c
            returns = jax.device_get(metrics["mean_return"])
            for j in range(c):
                record_log(self.logs, self.timer, IterationLog(
                    iteration=done + j,
                    collect_time=0.0,
                    collect_time_serial=0.0,
                    learn_time=per_iter,
                    mean_return=float(returns[j]),
                    samples=self._samples_per_iter,
                ))
            done += c
        return self.logs
