"""WALL-E's agent processor: runners as thin drivers over sampler backends.

* ``SyncRunner`` — collect (via a ``SamplerBackend``) -> learn -> repeat.
  With the default ``InlineBackend`` and ``num_samplers=1`` this is exactly
  the paper's N=1 baseline; with N > 1 per-sampler critical-path time is
  still measurable on a single host (see DESIGN.md §2 on measurement).
* ``AsyncOrchestrator`` — the paper's architecture: N sampler threads
  generating experience with the freshest published policy (possibly
  stale), a learner thread consuming the experience queue and publishing
  new parameters to the policy store. Device work stays jitted; threads
  orchestrate, matching the paper's process roles.

Both runners assemble their ``IterationLog`` through the same helpers
(``timed_learn`` + ``assemble_log``) so the collect/learn accounting that
feeds Figs 4-7 has exactly one definition.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax

from repro.core.backends import (
    BackendCloseMixin,
    InlineBackend,
    SamplerBackend,
    merge_trajs,
    timed_rollout,
)
from repro.core.queues import Experience, ExperienceQueue, PolicyStore
from repro.core.timing import PhaseTimer
from repro.data import trajectory


@dataclasses.dataclass
class IterationLog:
    iteration: int
    collect_time: float          # critical-path (parallel) collection time
    collect_time_serial: float   # sum over samplers (1-process equivalent)
    learn_time: float
    mean_return: float
    samples: int
    staleness: float = 0.0       # params-staleness: mean (learner version -
                                 # version the sampler acted with)
    queue_drops: int = 0         # async: cumulative experiences dropped on
                                 # queue overflow (backpressure signal)
    worker_utilization: float = 1.0   # fraction of worker wall time spent
                                      # actually rolling out (vs waiting on
                                      # params/slots); < 1 only measurable
                                      # for free-running process workers
    respawns: int = 0            # cumulative supervised worker respawns
    active_workers: int = 0      # pool size this iteration (elastic mode)
    overlap_saved_s: float = 0.0  # overlap pipeline: wall-clock hidden by
                                  # running this learn under the next
                                  # collect, vs the serial schedule (0 on
                                  # serial iterations; under overlap,
                                  # learn_time is the *exposed* learn cost
                                  # so collect+learn+saved ~= serial wall)

    def as_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


# ====================================================== shared helpers
def _maybe_jit_step(train_step: Optional[Callable]) -> Optional[Callable]:
    """Runners jit the plane step themselves — except a mesh step that
    manages its own jit and input placement (``ShardedLearner`` with
    D > 1 sets ``self_jitted``): re-jitting it would infer device
    placement from the arguments, and a device-0 trajectory next to
    FSDP-sharded params is an incompatible-devices error."""
    if train_step is None:
        return None
    if getattr(getattr(train_step, "__self__", None), "self_jitted", False):
        return train_step
    return jax.jit(train_step)


def timed_learn(learn: Callable, params, opt_state, merged):
    """One jitted learner update, blocked and timed."""
    t0 = time.perf_counter()
    params, opt_state, metrics = learn(params, opt_state, merged)
    jax.block_until_ready(params)
    return params, opt_state, metrics, time.perf_counter() - t0


def timed_train_step(train_step: Callable, params, opt_state, plane_state,
                     merged):
    """One jitted plane step (observe -> sample -> learn), blocked and
    timed; buffer state stays device-resident inside ``plane_state``."""
    t0 = time.perf_counter()
    params, opt_state, plane_state, metrics = train_step(
        params, opt_state, plane_state, merged)
    jax.block_until_ready(params)
    return params, opt_state, plane_state, metrics, time.perf_counter() - t0


def assemble_log(iteration: int, per_sampler_seconds: Sequence[float],
                 learn_time: float, merged, samples: Optional[int] = None,
                 staleness: float = 0.0,
                 queue_drops: int = 0,
                 worker_utilization: float = 1.0,
                 respawns: int = 0,
                 active_workers: int = 0,
                 overlap_saved_s: float = 0.0) -> IterationLog:
    """The single definition of per-iteration accounting (sync + async)."""
    return IterationLog(
        iteration=iteration,
        collect_time=max(per_sampler_seconds),
        collect_time_serial=sum(per_sampler_seconds),
        learn_time=learn_time,
        mean_return=float(trajectory.episode_returns(merged)),
        samples=(samples if samples is not None
                 else trajectory.num_samples(merged)),
        staleness=staleness,
        queue_drops=queue_drops,
        worker_utilization=worker_utilization,
        respawns=respawns,
        active_workers=active_workers,
        overlap_saved_s=overlap_saved_s,
    )


def tree_ready(tree) -> bool:
    """True iff every device array in ``tree`` has finished computing
    (``jax.Array.is_ready``) — a non-blocking probe used by the overlap
    pipeline to tell whether the in-flight learn was still running when
    the concurrent collect finished. A device error raises here rather
    than reading as "not ready"."""
    return all(bool(leaf.is_ready()) for leaf in jax.tree.leaves(tree)
               if hasattr(leaf, "is_ready"))


class OverlapClock:
    """Accounting for the double-buffered pipeline (DESIGN.md §11).

    ``overlap_saved_s`` is the learn wall-clock hidden under the
    concurrent collect, i.e. serial schedule minus pipelined schedule
    for this iteration. Two cases at the moment the collect returns:

    * the learn is **not** finished -> it ran under the entire collect,
      so the hidden portion is the whole collect duration;
    * the learn **is** finished -> the hidden portion is the learn's own
      duration, estimated by ``learn_ref`` — the fastest *serial* learn
      observed during warmup (post-compilation, so it is a clean
      reference), capped by the collect duration.
    """

    def __init__(self):
        self.learn_ref: Optional[float] = None

    def note_serial(self, learn_s: float) -> None:
        self.learn_ref = (learn_s if self.learn_ref is None
                          else min(self.learn_ref, learn_s))

    def saved(self, collect_s: float, learn_ready: bool) -> float:
        if not learn_ready:
            return collect_s
        ref = self.learn_ref if self.learn_ref is not None else collect_s
        return min(ref, collect_s)


def record_log(logs: List[IterationLog], timer: PhaseTimer,
               log: IterationLog) -> None:
    logs.append(log)
    timer.add("collect", log.collect_time)
    timer.add("learn", log.learn_time)


# ================================================================== sync
class SyncRunner(BackendCloseMixin):
    """collect (backend) -> learn -> repeat.

    Backward-compatible construction: pass ``(rollout, learn, params,
    opt_state, carries, num_samplers)`` and an ``InlineBackend`` is built —
    or pass ``backend=`` (any ``SamplerBackend``) and leave ``rollout`` /
    ``carries`` as None.

    Experience plane: pass ``train_step=`` (``algos.api.make_train_step``)
    plus its initial ``plane_state=(buffer_state, key)`` and the runner
    drives the composed observe -> sample -> learn step instead of raw
    ``learn``, owning the buffer state explicitly (``self.plane_state`` /
    ``self.buffer_state``) — it never hides inside ``opt_state``.

    Overlap (``overlap=True``, requires ``train_step``): after two serial
    warmup iterations (compile + a clean learn reference), each learn is
    *dispatched* without blocking and the **next** iteration's collect
    runs while it executes on the learner mesh — the collect acts with
    one-version-stale params (stamped ``staleness=1.0`` on the iteration
    that consumes it), and ``IterationLog.overlap_saved_s`` reports the
    learn time hidden under the collect (DESIGN.md §11).

    ``pin_params=True`` maintains a *second*, device-0 copy of the params
    for collection: an FSDP-sharded learn result fed straight to the
    single-device rollout would recompile it as a partitioned SPMD
    computation across the learner mesh (and under overlap put the
    collect on the very devices the learn is using). ``self.params``
    itself stays mesh-resident — it must match the mesh-committed
    opt_state at the next learn dispatch — so only the rollout reads the
    pinned copy.
    """

    def __init__(self, rollout: Optional[Callable],
                 learn: Optional[Callable],
                 params: Any, opt_state: Any,
                 carries: Optional[List[Any]] = None,
                 num_samplers: Optional[int] = None, *,
                 backend: Optional[SamplerBackend] = None,
                 train_step: Optional[Callable] = None,
                 plane_state: Any = None,
                 overlap: bool = False,
                 pin_params: bool = False):
        if backend is None:
            assert rollout is not None and carries is not None
            backend = InlineBackend(rollout, carries)
        if num_samplers is not None:
            assert backend.num_samplers == num_samplers
        assert learn is not None or train_step is not None
        self.backend = backend
        self.learn = jax.jit(learn) if learn is not None else None
        self._train_step = _maybe_jit_step(train_step)
        self.plane_state = plane_state
        self.params = params
        self.opt_state = opt_state
        self.num_samplers = backend.num_samplers
        if overlap and train_step is None:
            raise ValueError(
                "overlap=True requires the experience-plane train_step "
                "(the raw learn path has no buffer to double-buffer)")
        self.overlap = overlap
        self.pin_params = pin_params
        self._collect_params = None       # device-0 copy (pin_params mode)
        self._overlap_clock = OverlapClock()
        self._overlap_done = 0            # pipeline-lifetime iteration
        #                                   count: warmup is paid once per
        #                                   runner, not once per run() call
        self.timer = PhaseTimer()
        self.logs: List[IterationLog] = []
        self.metrics: Dict[str, Any] = {}  # last learner step's metrics
        #                                    (loss terms), device-resident

    @property
    def buffer_state(self):
        return None if self.plane_state is None else self.plane_state[0]

    def _pin(self) -> None:
        if self.pin_params:
            self._collect_params = jax.device_put(self.params,
                                                  jax.devices()[0])

    def _rollout_params(self):
        return (self._collect_params if self._collect_params is not None
                else self.params)

    def run(self, iterations: int) -> List[IterationLog]:
        if self.overlap:
            return self._run_overlapped(iterations)
        for it in range(iterations):
            merged, stats = self.backend.collect(self._rollout_params())
            if self._train_step is not None:
                (self.params, self.opt_state, self.plane_state, self.metrics,
                 learn_time) = timed_train_step(
                     self._train_step, self.params, self.opt_state,
                     self.plane_state, merged)
            else:
                (self.params, self.opt_state, self.metrics,
                 learn_time) = timed_learn(
                    self.learn, self.params, self.opt_state, merged)
            self._pin()
            record_log(self.logs, self.timer,
                       assemble_log(it, stats.per_sampler_seconds,
                                    learn_time, merged, stats.samples,
                                    respawns=stats.respawns,
                                    active_workers=stats.active_workers))
        return self.logs

    # ----------------------------------------------------------- overlap
    _OVERLAP_WARMUP = 2     # it 0 pays compilation, it 1 gives learn_ref

    def _run_overlapped(self, iterations: int) -> List[IterationLog]:
        """Double-buffered pipeline: dispatch iteration k's learn, run
        iteration k+1's collect while it executes, then block. The first
        ``_OVERLAP_WARMUP`` iterations stay fully serial, so short runs
        (``iterations <= warmup``) are identical to ``overlap=False``.
        Numerics are unchanged vs serial except that overlapped collects
        act with params one learn behind (staleness 1.0 on the consuming
        iteration's log) — the same staleness the async orchestrator
        already accounts for."""
        clock = self._overlap_clock
        pending = None          # (merged, stats, staleness) pre-collected
        for it in range(iterations):
            if pending is None:
                merged, stats = self.backend.collect(self._rollout_params())
                stale = 0.0
            else:
                merged, stats, stale = pending
                pending = None
            warm, self._overlap_done = (self._overlap_done,
                                        self._overlap_done + 1)
            if warm < self._OVERLAP_WARMUP:
                (self.params, self.opt_state, self.plane_state, self.metrics,
                 learn_time) = timed_train_step(
                     self._train_step, self.params, self.opt_state,
                     self.plane_state, merged)
                if warm > 0:    # iteration 0 includes compilation
                    clock.note_serial(learn_time)
                self._pin()
                record_log(self.logs, self.timer,
                           assemble_log(it, stats.per_sampler_seconds,
                                        learn_time, merged, stats.samples,
                                        staleness=stale,
                                        respawns=stats.respawns,
                                        active_workers=stats.active_workers))
                continue
            # dispatch the learn; do NOT block — self.params still refers
            # to the pre-update arrays, which is exactly the one-version-
            # stale policy the pipelined collect is specified to act with
            t0 = time.perf_counter()
            out = self._train_step(self.params, self.opt_state,
                                   self.plane_state, merged)
            saved = 0.0
            if it + 1 < iterations:
                # _rollout_params() was last pinned *before* this learn
                # dispatched — the one-version-stale policy by construction
                nxt, nstats = self.backend.collect(self._rollout_params())
                saved = clock.saved(max(nstats.per_sampler_seconds),
                                    tree_ready(out[0]))
                pending = (nxt, nstats, 1.0)
            self.params, self.opt_state, self.plane_state, self.metrics = out
            jax.block_until_ready(self.params)
            window = time.perf_counter() - t0
            self._pin()
            # window spans the overlapped collect; subtracting the hidden
            # portion leaves the *exposed* learn cost, so per iteration
            # collect_time + learn_time + overlap_saved_s ~= serial wall
            record_log(self.logs, self.timer,
                       assemble_log(it, stats.per_sampler_seconds,
                                    max(0.0, window - saved), merged,
                                    stats.samples, staleness=stale,
                                    respawns=stats.respawns,
                                    active_workers=stats.active_workers,
                                    overlap_saved_s=saved))
        return self.logs

    def close(self) -> None:
        """Release the backend (thread pools, worker processes, shm)."""
        close = getattr(self.backend, "close", None)
        if close is not None:        # pre-protocol custom backends
            close()


# ================================================================= async
class AsyncOrchestrator(BackendCloseMixin):
    """The paper's architecture (Fig 2): N sampler threads + learner thread.

    Sampler i loop:  params <- PolicyStore (latest, maybe stale)
                     traj   <- jitted rollout
                     ExperienceQueue.put(traj, version)
    Learner loop:    drain >= min_batches experiences
                     params <- jitted PPO update
                     PolicyStore.publish(params)

    Two sampler substrates: the in-process form above (threads + host
    queues), and — pass ``pool=`` (an ``ipc.ProcessWorkerPool``) — true
    worker *processes* collecting continuously into the shared-memory
    trajectory ring while this process's learner drains it. In pool mode
    the policy queue is the shared-memory ``ParamsChannel`` (one publish
    per update, no pickling), backpressure is the ring itself (a worker
    blocks once its slots are unconsumed — nothing is dropped), and
    ``IterationLog`` additionally reports ``worker_utilization`` (rollout
    time / worker loop wall time, windowed per iteration).

    Robustness (DESIGN.md §10): pass ``supervisor=`` (a
    ``core.supervisor.WorkerSupervisor`` over the same pool) and worker
    death/hangs are detected and respawned mid-run instead of killing
    the learner, with ``autoscale`` nudging the fleet size against the
    utilization band between updates. Pass ``staleness=`` (an enabled
    ``algos.staleness.StalenessConfig``) and every consumed trajectory
    is stamped with its params-version gap for the algo-side
    importance-weighted correction; disabled (default) attaches nothing.
    """

    def __init__(self, rollout: Optional[Callable],
                 learn: Optional[Callable],
                 params: Any, opt_state: Any, carries: Optional[List[Any]],
                 num_samplers: int, min_batches_per_update: int = 1,
                 queue_size: int = 64, *,
                 train_step: Optional[Callable] = None,
                 plane_state: Any = None, pool=None,
                 supervisor=None, staleness=None):
        self.pool = pool
        self.supervisor = supervisor      # core.supervisor.WorkerSupervisor
        self.staleness = staleness        # algos.staleness.StalenessConfig
        if pool is None:
            assert rollout is not None and carries is not None
            self.rollout = jax.jit(rollout)
        else:
            self.rollout = None
            num_samplers = pool.num_workers
        assert learn is not None or train_step is not None
        self.learn = jax.jit(learn) if learn is not None else None
        self._train_step = _maybe_jit_step(train_step)
        self.plane_state = plane_state
        self.store = PolicyStore(params)
        self.expq = ExperienceQueue(maxsize=queue_size)
        self.opt_state = opt_state
        self.carries = carries
        self.num_samplers = num_samplers
        self.min_batches = min_batches_per_update
        self.timer = PhaseTimer()
        self.logs: List[IterationLog] = []
        self._stop = threading.Event()

    @property
    def buffer_state(self):
        return None if self.plane_state is None else self.plane_state[0]

    def _attach_gap(self, traj, gap: float, np_mod):
        """Stamp the params-version gap onto every timestep of one
        trajectory (a (T, B) float32 leaf keyed ``staleness_gap``) so the
        algo-side correction can weight it after merging. Only called
        when staleness correction is enabled — with it off no key is
        added and every bitwise-parity guarantee is untouched."""
        ref = traj["rewards"]
        traj = dict(traj)
        traj["staleness_gap"] = np_mod.full(
            ref.shape[:2], float(max(0.0, gap)), dtype="float32")
        return traj

    # ------------------------------------------------------------ threads
    def _sampler_loop(self, i: int) -> None:
        while not self._stop.is_set():
            params, version = self.store.read()
            self.carries[i], traj, dt = timed_rollout(
                self.rollout, params, self.carries[i])
            # on overflow the experience is dropped and counted
            # (ExperienceQueue.drop_count -> IterationLog.queue_drops)
            if (not self.expq.put(Experience(traj, version, i, dt),
                                  timeout=5.0)
                    and self._stop.is_set()):
                return

    def _learner_loop(self, updates: int) -> None:
        import queue as _q
        for it in range(updates):
            exps: List[Experience] = []
            t_wait0 = time.perf_counter()
            while len(exps) < self.min_batches and not self._stop.is_set():
                try:
                    exps.append(self.expq.get(self.store.version,
                                              timeout=1.0))
                except _q.Empty:
                    continue
            if self._stop.is_set() and not exps:
                return
            wait = time.perf_counter() - t_wait0
            if self.staleness is not None and self.staleness.enabled:
                import jax.numpy as jnp
                trajs = [self._attach_gap(
                    e.traj, self.store.version - e.policy_version, jnp)
                    for e in exps]
            else:
                trajs = [e.traj for e in exps]
            merged = merge_trajs(trajs)
            params, _ = self.store.read()
            if self._train_step is not None:
                (params, self.opt_state, self.plane_state, _,
                 learn_time) = timed_train_step(
                     self._train_step, params, self.opt_state,
                     self.plane_state, merged)
            else:
                params, self.opt_state, _, learn_time = timed_learn(
                    self.learn, params, self.opt_state, merged)
            self.store.publish(params)
            record_log(self.logs, self.timer,
                       assemble_log(it, [e.collect_seconds for e in exps],
                                    learn_time, merged,
                                    staleness=self.expq.mean_staleness(),
                                    queue_drops=self.expq.drop_count))
            self.timer.add("collect_wait", wait)

    # ------------------------------------------------- process-pool learner
    def _learner_loop_pool(self, updates: int, deadline: float) -> None:
        """Drain the shared-memory ring while worker processes free-run.
        Returns early (like the thread path's learner join) once
        ``deadline`` passes with workers alive but unproductive.

        Accounting is *windowed per iteration* (not cumulative over the
        run): ``staleness`` and ``worker_utilization`` reflect only the
        experiences consumed for *this* update, so the log tracks the
        live fleet — a worker dying and being respawned mid-run shows up
        in that iteration's numbers instead of being averaged away over
        the whole history. With a supervisor attached, draining,
        failure handling and (between iterations) elastic resizing all
        route through it."""
        import numpy as _np
        it0 = len(self.logs)
        source = self.supervisor if self.supervisor is not None else self.pool
        stale_on = self.staleness is not None and self.staleness.enabled
        for it in range(updates):
            exps, gaps = [], []
            collect_s = loop_s = 0.0         # this iteration's window only
            t_wait0 = time.perf_counter()
            while len(exps) < self.min_batches and not self._stop.is_set():
                if time.monotonic() > deadline:
                    return
                got = source.next_experience(timeout=1.0)
                if got is None:
                    continue
                exp, loop_dt = got
                exps.append(exp)
                collect_s += exp.collect_seconds
                loop_s += loop_dt
                gaps.append(max(0, self.pool.version - exp.policy_version))
            if self._stop.is_set() and not exps:
                return
            wait = time.perf_counter() - t_wait0
            trajs = [e.traj for e in exps]
            if stale_on:
                trajs = [self._attach_gap(t, g, _np)
                         for t, g in zip(trajs, gaps)]
            merged = merge_trajs(
                [{k: jax.numpy.asarray(v) for k, v in t.items()}
                 for t in trajs])
            params, _ = self.store.read()
            if self._train_step is not None:
                (params, self.opt_state, self.plane_state, _,
                 learn_time) = timed_train_step(
                     self._train_step, params, self.opt_state,
                     self.plane_state, merged)
            else:
                params, self.opt_state, _, learn_time = timed_learn(
                    self.learn, params, self.opt_state, merged)
            self.store.publish(params)
            self.pool.publish(params)
            util = collect_s / loop_s if loop_s > 0 else 1.0
            record_log(self.logs, self.timer,
                       assemble_log(it0 + it,
                                    [e.collect_seconds for e in exps],
                                    learn_time, merged,
                                    staleness=float(sum(gaps) / len(gaps)),
                                    worker_utilization=util,
                                    respawns=(self.supervisor.respawns
                                              if self.supervisor else 0),
                                    active_workers=self.pool.num_workers))
            self.timer.add("collect_wait", wait)
            if self.supervisor is not None:
                self.supervisor.autoscale(util)

    # ---------------------------------------------------------------- run
    def run(self, updates: int, timeout: float = 600.0) -> List[IterationLog]:
        if self.pool is not None:
            # worker processes are the sampler concurrency; the learner
            # runs right here (Ctrl-C propagates, experiment.run reaps);
            # the timeout bounds a wedged-but-alive worker exactly like
            # the thread path's learner join
            self.pool.start_freerun()
            self._learner_loop_pool(updates, time.monotonic() + timeout)
            return self.logs
        samplers = [threading.Thread(target=self._sampler_loop, args=(i,),
                                     daemon=True)
                    for i in range(self.num_samplers)]
        learner = threading.Thread(target=self._learner_loop,
                                   args=(updates,), daemon=True)
        for t in samplers:
            t.start()
        learner.start()
        learner.join(timeout=timeout)
        self._stop.set()
        for t in samplers:
            t.join(timeout=5.0)
        return self.logs

    def close(self) -> None:
        """Stop sampler threads / reap worker processes (idempotent).

        With a supervisor attached, worker death is a tolerated,
        recovered-from event — a fault or crash landing between the last
        drained experience and shutdown must not resurface as a spurious
        ``WorkerCrashed`` from ``close``.
        """
        self._stop.set()
        if self.pool is not None:
            self.pool.close(raise_on_crash=self.supervisor is None)

    @property
    def params(self):
        return self.store.read()[0]
