"""Sampler backends — the pluggable experience-collection seam.

WALL-E's runtime layer separates *what* a sampler does (one jitted rollout,
``core/sampler.py``) from *how* N of them are scheduled. A
``SamplerBackend`` owns the sampler carries and produces, per iteration,
one merged trajectory plus per-sampler timing (DESIGN.md §2). Runners
(``core/orchestrator.py``) and the fused engine (``core/fused.py``) are
thin drivers over this protocol.

Backends:

* ``InlineBackend``   — the serial N-sampler sweep: each sampler's rollout
  runs back-to-back on the local device and is timed individually, so the
  critical path of a truly parallel deployment (max over samplers) can be
  reported from a single host.
* ``ThreadedBackend`` — the fan-out/join form of ``AsyncOrchestrator``'s
  sampler loops: each sampler's jitted rollout is dispatched from its own
  thread (JAX releases the GIL during device execution), then joined and
  merged.
* ``ShardedBackend``  — the accelerator-native form: ``shard_map`` places
  one sampler per ``data``-axis mesh slice; the trajectory is *born
  sharded* and never merged on host.
* ``ProcessBackend``  — the paper's actual deployment shape: N worker
  *processes* (own interpreter, own XLA client — no GIL or dispatch-queue
  contention with the learner), rebuilt from serializable ``WorkerSpec``s
  and fed through shared-memory transport (``core/ipc.py``). Trajectories
  merge in deterministic worker-index order, so ``process == inline``
  exactly for matched per-worker seeds (DESIGN.md §6).

Every backend is a context manager; ``close()`` releases whatever it
holds (thread pools, worker processes, shared memory) and is idempotent.
"""
from __future__ import annotations

import dataclasses
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, List, Optional, Protocol, Sequence

import jax

from repro import registry
from repro.core import timing
from repro.data import trajectory


@dataclasses.dataclass
class CollectStats:
    """Per-iteration collection accounting shared by every backend."""
    per_sampler_seconds: List[float]
    samples: int
    respawns: int = 0        # cumulative supervised worker respawns
    active_workers: int = 0  # live fleet size (process backend only)

    @property
    def critical_path(self) -> float:
        """Max over samplers — what a parallel deployment would wait."""
        return max(self.per_sampler_seconds)

    @property
    def serial_equivalent(self) -> float:
        """Sum over samplers — what N=1 pays for the same experience."""
        return sum(self.per_sampler_seconds)


class SamplerBackend(Protocol):
    """collect(params) -> (merged_traj, stats); carries are backend-owned.
    ``close()`` releases backend-held resources (idempotent)."""

    num_samplers: int

    def collect(self, params: Any) -> tuple:
        ...

    def close(self) -> None:
        ...


class BackendCloseMixin:
    """Context-manager + no-op ``close`` shared by every backend, so
    ``experiment.run`` can unconditionally release any backend in its
    ``finally`` (threads, worker processes, shared memory — or nothing)."""

    def close(self) -> None:
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def timed_rollout(rollout: Callable, params: Any, carry: Any,
                  sampler: int = 0):
    """Run one jitted rollout to completion as the ``samplers.rollout``
    span, returning (carry', traj, dt)."""
    with timing.span("samplers.rollout", sampler=sampler) as span:
        carry, traj = rollout(params, carry)
        traj = jax.block_until_ready(traj)
    return carry, traj, span.seconds


def merge_trajs(trajs: Sequence[Any]) -> Any:
    with timing.span("samplers.merge"):
        return trajectory.merge(list(trajs)) if len(trajs) > 1 else trajs[0]


# ================================================================== inline
class InlineBackend(BackendCloseMixin):
    """Today's serial sweep: N logical samplers executed back-to-back."""

    def __init__(self, rollout: Callable, carries: List[Any]):
        self.rollout = jax.jit(rollout)
        self.carries = carries
        self.num_samplers = len(carries)

    def collect(self, params):
        trajs, times = [], []
        for i in range(self.num_samplers):
            self.carries[i], traj, dt = timed_rollout(
                self.rollout, params, self.carries[i], i)
            trajs.append(traj)
            times.append(dt)
        merged = merge_trajs(trajs)
        return merged, CollectStats(times, trajectory.num_samples(merged))


# ================================================================ threaded
class ThreadedBackend(BackendCloseMixin):
    """Fan-out/join over sampler threads (AsyncOrchestrator's sampler loop,
    made synchronous): each sampler dispatches its jitted rollout from its
    own thread; the critical path is genuinely the max over samplers."""

    def __init__(self, rollout: Callable, carries: List[Any],
                 max_workers: Optional[int] = None):
        self.rollout = jax.jit(rollout)
        self.carries = carries
        self.num_samplers = len(carries)
        self._pool = ThreadPoolExecutor(
            max_workers=max_workers or self.num_samplers)

    def _one(self, i: int, params):
        self.carries[i], traj, dt = timed_rollout(
            self.rollout, params, self.carries[i], i)
        return traj, dt

    def collect(self, params):
        one = timing.bind(self._one)       # spans land in the caller's record
        futures = [self._pool.submit(one, i, params)
                   for i in range(self.num_samplers)]
        results = [f.result() for f in futures]
        trajs = [r[0] for r in results]
        times = [r[1] for r in results]
        merged = merge_trajs(trajs)
        return merged, CollectStats(times, trajectory.num_samples(merged))

    def close(self) -> None:
        self._pool.shutdown(wait=False)


# ================================================================= sharded
class ShardedBackend(BackendCloseMixin):
    """One sampler per ``data``-axis mesh slice via ``make_sharded_rollout``.

    The carry holds the *global* env batch; shard_map splits it so each
    slice runs an independent sampler and the trajectory arrays come back
    already concatenated on the (sharded) batch axis — no host merge. One
    dispatch covers all samplers, so per-sampler time equals the critical
    path and there is no serial/parallel gap to report.
    """

    def __init__(self, sharded_rollout: Callable, carry: Any, mesh,
                 data_axis: str = "data"):
        self.rollout = jax.jit(sharded_rollout)
        self.carry = carry
        self.mesh = mesh
        self.num_samplers = mesh.shape[data_axis]

    def collect(self, params):
        with jax.sharding.use_mesh(self.mesh) if hasattr(
                jax.sharding, "use_mesh") else self.mesh:
            self.carry, traj, dt = timed_rollout(
                self.rollout, params, self.carry)
        stats = CollectStats([dt], trajectory.num_samples(traj))
        return traj, stats


# ================================================================= process
class ProcessBackend(BackendCloseMixin):
    """N rollout worker *processes* behind the ``collect`` contract.

    Each worker owns its own interpreter and XLA client — rollouts never
    contend with the learner for the GIL or the dispatch queue, which is
    the paper's actual N-sampler-process deployment (and what inline/
    threaded only approximate from one process). Params go out through a
    versioned shared-memory channel (one publish per ``collect``, not one
    pickle per worker); trajectories come back through the shared-memory
    ring and merge **in worker-index order**, so with matched per-worker
    seeds the merged trajectory is exactly the inline backend's
    (DESIGN.md §6). With a ``supervisor`` attached (the default through
    ``repro.experiment``), a worker that dies mid-sweep is respawned
    from its ``WorkerSpec`` and its command re-issued instead of killing
    the run; without one, worker death or an in-worker exception
    surfaces as ``ipc.WorkerCrashed`` from ``collect``. ``close`` reaps
    everything.
    """

    def __init__(self, pool, supervisor=None):
        self.pool = pool
        self.supervisor = supervisor
        # command workers one at a time instead of broadcasting: on hosts
        # with fewer cores than workers this removes peer preemption from
        # the per-worker timings (see ProcessWorkerPool.collect) — the
        # benchmark harness flips it for steady-state measurement
        self.staggered = False

    @property
    def num_samplers(self) -> int:
        return self.pool.num_workers

    def collect(self, params):
        self.pool.publish(params)
        source = self.supervisor if self.supervisor is not None else self.pool
        trajs, times, _loops = source.collect(staggered=self.staggered)
        merged = merge_trajs(trajs)
        return merged, CollectStats(
            times, trajectory.num_samples(merged),
            respawns=(self.supervisor.respawns if self.supervisor else 0),
            active_workers=self.pool.num_workers)

    def close(self) -> None:
        # supervised pools tolerate worker death by design — don't let a
        # fault landing after the final collect resurface from close()
        self.pool.close(raise_on_crash=self.supervisor is None)


def _build_inline(*, rollout: Callable, carries: List[Any], **_ignored):
    return InlineBackend(rollout, carries)


def _build_threaded(*, rollout: Callable, carries: List[Any],
                    max_workers: Optional[int] = None, **_ignored):
    return ThreadedBackend(rollout, carries, max_workers)


def _build_sharded(*, carries: List[Any], env=None,
                   horizon: Optional[int] = None, mesh=None,
                   rollout: Optional[Callable] = None,
                   step_keys=None, tail_keys=None, **_ignored):
    """Mesh over the host's devices, one sampler per ``data`` slice.

    ``rollout`` here is the *unjitted* per-sampler rollout (the same one
    inline/threaded schedule); it is re-wrapped in shard_map with specs
    derived from ``step_keys``/``tail_keys`` (defaults: the PPO-family
    trajectory layout).
    """
    import numpy as np
    from jax.sharding import Mesh
    from repro.core import sampler as sampler_mod
    assert env is not None and horizon is not None
    batch = sum(c[1].shape[0] for c in carries)
    if mesh is None:
        devs = np.asarray(jax.devices())
        assert batch % len(devs) == 0, (
            f"sharded backend: global env batch {batch} not divisible "
            f"by the {len(devs)} available devices; adjust "
            f"--global-batch or pass an explicit mesh")
        mesh = Mesh(devs.reshape(len(devs), 1), ("data", "model"))
    else:
        assert batch % mesh.shape["data"] == 0, (
            f"sharded backend: global env batch {batch} not divisible "
            f"by mesh data axis {mesh.shape['data']}")
    keys = {}
    if step_keys is not None:
        keys["step_keys"] = tuple(step_keys)
    if tail_keys is not None:
        keys["tail_keys"] = tuple(tail_keys)
    sharded = sampler_mod.make_sharded_rollout(env, horizon, mesh,
                                               rollout=rollout, **keys)
    carry = jax.tree.map(
        lambda *xs: jax.numpy.concatenate(xs, axis=0), *carries)
    return ShardedBackend(sharded, carry, mesh)


def build_worker_pool(*, rollout: Callable, carries: List[Any],
                      worker_specs: Sequence[Any], params: Any,
                      slots_per_worker: int = 1,
                      active_workers: Optional[Sequence[int]] = None,
                      fault_plan=None):
    """Spawn a ``ProcessWorkerPool`` for ``worker_specs``.

    ``rollout``/``carries`` are the *parent-side* builds of the same spec
    — used only under ``eval_shape`` to size the shared-memory ring (no
    rollout runs here); ``params`` sizes the params channel. The pool is
    provisioned for all ``worker_specs`` but only ``active_workers``
    (default: all) start — the elastic headroom a supervisor grows into.
    """
    from repro.core import ipc
    traj_example = jax.eval_shape(
        lambda p, c: rollout(p, c)[1], params, carries[0])
    return ipc.ProcessWorkerPool(worker_specs, params, traj_example,
                                 slots_per_worker=slots_per_worker,
                                 active_workers=active_workers,
                                 fault_plan=fault_plan)


def _build_process(*, rollout: Callable, carries: List[Any],
                   worker_specs: Optional[Sequence[Any]] = None,
                   params: Any = None, fault_plan=None,
                   supervisor_cfg=None, **_ignored):
    assert worker_specs is not None and params is not None, (
        "the process backend is built from serializable WorkerSpecs plus "
        "the learner's params (to size the shared-memory channel); "
        "construct it through repro.experiment (backend='process')")
    pool = build_worker_pool(
        rollout=rollout, carries=carries, worker_specs=worker_specs,
        params=params, slots_per_worker=1, fault_plan=fault_plan)
    supervisor = None
    if supervisor_cfg is None or supervisor_cfg.max_respawns > 0:
        from repro.core.supervisor import WorkerSupervisor
        supervisor = WorkerSupervisor(pool, supervisor_cfg)
    return ProcessBackend(pool, supervisor=supervisor)


registry.register("backend", "inline", _build_inline)
registry.register("backend", "threaded", _build_threaded)
registry.register("backend", "sharded", _build_sharded)
registry.register("backend", "process", _build_process)


def make_backend(kind: str, rollout: Callable, carries: List[Any],
                 env=None, horizon: Optional[int] = None, mesh=None,
                 **kwargs):
    """Factory used by launch/train.py, examples and ``repro.experiment``.

    Thin shim over the unified registry (kind ``"backend"``): ``inline`` /
    ``threaded`` take the per-sampler ``carries`` list; ``sharded`` builds
    its mesh over the host's devices and a single global carry (the caller
    passes ``carries`` whose batches it concatenates). Extra ``kwargs``
    (e.g. ``step_keys``/``tail_keys`` for non-PPO trajectory layouts) are
    forwarded to the backend builder.
    """
    return registry.make("backend", kind, rollout=rollout, carries=carries,
                         env=env, horizon=horizon, mesh=mesh, **kwargs)
