"""Shared-memory IPC for the actor plane (DESIGN.md §6, §10).

WALL-E's sampler parallelism is *process*-level: N rollout workers, each
owning its own Python interpreter and XLA client, feed one learner. The
transport here moves trajectories and policy parameters across the
process boundary without pickling arrays per iteration:

* ``ShmRing`` — a slotted trajectory ring: one
  ``multiprocessing.shared_memory`` block per trajectory leaf (numpy
  views, zero-copy on the writer side) plus seqlock-style slot headers
  (sequence counter: odd = write in progress, even = stable; an ``ack``
  counter lets the producer block until its previous slot was consumed).
  Writers stamp their pid into the header *before* touching the payload,
  so a slot left mid-write by a dead worker names its writer; ``read``
  is deadline-bounded (``RingSlotStuck``) and ``reclaim`` repairs such
  slots instead of deadlocking the consumer.
* ``ParamsChannel`` — a versioned params cell generalizing
  ``core.queues.PolicyStore`` across processes: the learner publishes
  flattened param leaves into fixed shared blocks; workers poll a version
  word and copy only when it changed, so params cross the boundary once
  per *publish*, not once per rollout.
* ``Heartbeat`` — one monotonic-clock timestamp word per worker slot in
  shared memory. Workers stamp it every loop; the supervisor reads
  ``age`` to distinguish a wedged-but-alive worker (process up, beats
  stopped) from a merely slow one. CLOCK_MONOTONIC is system-wide on
  Linux, so cross-process timestamps are directly comparable.
* ``ProcessWorkerPool`` — spawns workers (``spawn`` start method; no
  closures cross the boundary — each worker rebuilds its jitted rollout
  from a serializable ``core.sampler.WorkerSpec`` purely via the
  registry), drives them in lock-step (``collect``) or free-running mode
  (``start_freerun``/``next_experience``), surfaces worker crashes as
  ``WorkerCrashed``, and reaps everything on ``close``. The pool is
  *elastic*: it is provisioned for ``max_workers`` specs/slots up front
  but only the ``active`` subset runs; ``grow``/``shrink``/``respawn``
  re-use the pre-sized ring and params channel, so resizing never
  reallocates shared memory. ``core.supervisor.WorkerSupervisor`` layers
  failure detection and respawn policy on top of the primitives exposed
  here (``poll_msg``/``dead_workers``/``heartbeat_age``/
  ``reclaim_worker_slots``/``read_slot_checked``).

Memory-ordering note: the seqlock headers are consistency *checks*; the
ordering guarantee producers rely on is the command/result queue
handshake (a pipe write/read pair is a full barrier), so the protocol
does not depend on fenced stores into the mmap.
"""
from __future__ import annotations

import atexit
import collections
import contextlib
import dataclasses
import json
import os
import queue as _queue
import signal
import sys
import time
import traceback
import uuid
from multiprocessing import shared_memory
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

# slot header layout: int64 words per slot ...
_H_SEQ, _H_ACK, _H_VERSION, _H_WORKER, _H_PID = 0, 1, 2, 3, 4
_HDR_I = 5
# ... plus float64 words per slot
_H_COLLECT_S, _H_LOOP_S = 0, 1
_HDR_F = 2


class WorkerCrashed(RuntimeError):
    """A rollout worker process died or raised; message carries details."""


class RingSlotStuck(WorkerCrashed):
    """A ring slot's seqlock never stabilized within the read deadline —
    its writer almost certainly died mid-write. Carries ``slot``,
    ``writer_pid``, ``worker_id`` and the stuck ``seq`` so a supervisor
    can reclaim exactly what is stuck."""

    def __init__(self, msg: str, *, slot: int, writer_pid: int,
                 worker_id: int, seq: int):
        super().__init__(msg)
        self.slot = slot
        self.writer_pid = writer_pid
        self.worker_id = worker_id
        self.seq = seq


class StaleSlotMessage(RuntimeError):
    """A queued trajectory message references a slot whose seqlock moved
    past the message's recorded ``seq`` — the slot was reclaimed and
    rewritten after the original writer died. The message must be
    discarded, never read: consuming it would double-count the slot's
    *new* contents."""


# Resource-tracker note: Python 3.10 registers every ``SharedMemory``
# with the resource tracker even when attaching (``create=False``). That
# is benign here — worker processes are spawned by ``multiprocessing`` and
# therefore share the *parent's* tracker, whose cache is a name-keyed set:
# a child's attach-registration is a no-op add, and the parent's ``unlink``
# unregisters the name exactly once. (Explicitly unregistering in children
# would instead strip the parent's registration and raise KeyErrors in the
# tracker at shutdown.)


@dataclasses.dataclass(frozen=True)
class LeafSpec:
    """Shape/dtype of one pytree leaf inside a shared block (picklable)."""
    key: str
    shape: Tuple[int, ...]
    dtype: str


@dataclasses.dataclass(frozen=True)
class RingSpec:
    """Everything a fresh process needs to attach to a ``ShmRing``."""
    prefix: str
    slots: int
    leaves: Tuple[LeafSpec, ...]


def _leaf_specs(example: Dict[str, Any]) -> Tuple[LeafSpec, ...]:
    """Sorted-key leaf specs from a dict of arrays/ShapeDtypeStructs."""
    return tuple(
        LeafSpec(key=k, shape=tuple(example[k].shape),
                 dtype=np.dtype(example[k].dtype).str)
        for k in sorted(example))


class ShmRing:
    """Slotted trajectory ring over one shared block per trajectory leaf.

    Slot ``s`` of leaf ``k`` is the numpy view ``self.views[k][s]``; the
    header block carries per-slot ``(seq, ack, policy_version, worker_id,
    writer_pid)`` int64 words and ``(collect_seconds, loop_seconds)``
    float64 words. Writers bump ``seq`` to odd and stamp their identity
    before touching the payload, and bump ``seq`` to even after; readers
    copy then re-check ``seq``. ``ack`` is written by the consumer
    (``ack(slot)``) so a producer can wait until its previous write was
    drained (``is_free``) — the ring's only backpressure.

    Failure repair: a writer that dies mid-write leaves ``seq`` odd
    forever. ``read`` gives up after ``timeout`` with ``RingSlotStuck``
    (naming slot, writer pid and seqlock state), and ``reclaim`` makes
    such a slot writable again without ever presenting torn payload data
    to the consumer.
    """

    def __init__(self, spec: RingSpec, create: bool):
        self.spec = spec
        self._shms: List[shared_memory.SharedMemory] = []
        self.views: Dict[str, np.ndarray] = {}
        for i, leaf in enumerate(spec.leaves):
            nbytes = (spec.slots * int(np.prod(leaf.shape, dtype=np.int64))
                      * np.dtype(leaf.dtype).itemsize)
            shm = self._open(f"{spec.prefix}-l{i}", create, max(nbytes, 8))
            self.views[leaf.key] = np.ndarray(
                (spec.slots, *leaf.shape), dtype=leaf.dtype, buffer=shm.buf)
        hdr_bytes = spec.slots * (_HDR_I * 8 + _HDR_F * 8)
        shm = self._open(f"{spec.prefix}-hdr", create, hdr_bytes)
        self._hdr_i = np.ndarray((spec.slots, _HDR_I), dtype=np.int64,
                                 buffer=shm.buf, offset=0)
        self._hdr_f = np.ndarray((spec.slots, _HDR_F), dtype=np.float64,
                                 buffer=shm.buf,
                                 offset=spec.slots * _HDR_I * 8)
        if create:
            self._hdr_i.fill(0)
            self._hdr_f.fill(0.0)

    def _open(self, name: str, create: bool,
              size: int) -> shared_memory.SharedMemory:
        shm = shared_memory.SharedMemory(
            name=name, create=create, size=size if create else 0)
        self._shms.append(shm)
        return shm

    @classmethod
    def create(cls, example: Dict[str, Any], slots: int,
               prefix: str) -> "ShmRing":
        return cls(RingSpec(prefix=prefix, slots=slots,
                            leaves=_leaf_specs(example)), create=True)

    @classmethod
    def attach(cls, spec: RingSpec) -> "ShmRing":
        return cls(spec, create=False)

    # ------------------------------------------------------------- producer
    def write(self, slot: int, traj: Dict[str, np.ndarray], *,
              worker_id: int, policy_version: int,
              collect_seconds: float, loop_seconds: float) -> int:
        """Seqlocked write of one trajectory; returns the slot's new
        (even) ``seq`` — the writer reports it alongside the slot index
        so the consumer can verify the slot still holds *this* write."""
        seq = int(self._hdr_i[slot, _H_SEQ])
        self._hdr_i[slot, _H_SEQ] = seq + 1          # odd: write in progress
        # identity first: a writer that dies mid-payload is still named
        self._hdr_i[slot, _H_WORKER] = worker_id
        self._hdr_i[slot, _H_PID] = os.getpid()
        for leaf in self.spec.leaves:
            self.views[leaf.key][slot][...] = traj[leaf.key]
        self._hdr_i[slot, _H_VERSION] = policy_version
        self._hdr_f[slot, _H_COLLECT_S] = collect_seconds
        self._hdr_f[slot, _H_LOOP_S] = loop_seconds
        self._hdr_i[slot, _H_SEQ] = seq + 2          # even: stable
        return seq + 2

    def begin_torn_write(self, slot: int, worker_id: int) -> None:
        """Start a write (seq to odd, identity stamped) and never finish
        it — the fault-injection hook behind ``FaultPlan``'s ``torn``
        kind: the worker calls this then SIGKILLs itself, leaving exactly
        the stuck-mid-write header a real mid-write death leaves."""
        seq = int(self._hdr_i[slot, _H_SEQ])
        self._hdr_i[slot, _H_SEQ] = seq + 1
        self._hdr_i[slot, _H_WORKER] = worker_id
        self._hdr_i[slot, _H_PID] = os.getpid()

    def is_free(self, slot: int) -> bool:
        """True when the consumer acked everything written to ``slot``."""
        return int(self._hdr_i[slot, _H_ACK]) == int(
            self._hdr_i[slot, _H_SEQ])

    def seq(self, slot: int) -> int:
        return int(self._hdr_i[slot, _H_SEQ])

    # ------------------------------------------------------------- consumer
    def read(self, slot: int, timeout: float = 5.0
             ) -> Tuple[Dict[str, np.ndarray], Dict[str, Any]]:
        """Copy one slot out; retries on a torn seqlock read but gives up
        after ``timeout`` seconds with ``RingSlotStuck`` instead of
        spinning forever behind a dead writer."""
        deadline = time.monotonic() + timeout
        while True:
            s1 = int(self._hdr_i[slot, _H_SEQ])
            if s1 % 2 == 0:                           # stable: copy out
                traj = {leaf.key: np.array(self.views[leaf.key][slot])
                        for leaf in self.spec.leaves}
                meta = {
                    "policy_version": int(self._hdr_i[slot, _H_VERSION]),
                    "worker_id": int(self._hdr_i[slot, _H_WORKER]),
                    "collect_seconds": float(
                        self._hdr_f[slot, _H_COLLECT_S]),
                    "loop_seconds": float(self._hdr_f[slot, _H_LOOP_S]),
                }
                if int(self._hdr_i[slot, _H_SEQ]) == s1:
                    return traj, meta
            if time.monotonic() > deadline:
                pid = int(self._hdr_i[slot, _H_PID])
                wid = int(self._hdr_i[slot, _H_WORKER])
                raise RingSlotStuck(
                    f"trajectory ring slot {slot} stuck mid-write for "
                    f"{timeout:.1f}s: seqlock seq={s1} "
                    f"({'odd = write in progress' if s1 % 2 else 'kept moving'}), "
                    f"writer pid {pid} (worker #{wid}) — the writer likely "
                    f"died mid-write; the slot must be reclaimed, not read",
                    slot=slot, writer_pid=pid, worker_id=wid, seq=s1)
            time.sleep(1e-4)

    def ack(self, slot: int) -> None:
        self._hdr_i[slot, _H_ACK] = self._hdr_i[slot, _H_SEQ]

    def reclaim(self, slot: int) -> Optional[str]:
        """Make a dead worker's slot writable again. Returns what was
        found: ``"torn"`` (seqlock odd — the writer died mid-write; the
        payload is garbage and is *not* surfaced), ``"unread"`` (a stable
        write nobody will ever consume — its result message was lost with
        the producer), or ``None`` (slot already free). Only call for
        slots whose writer is known dead and whose pending result
        messages have been drained — reclaiming a live writer's slot
        races its write."""
        seq = int(self._hdr_i[slot, _H_SEQ])
        ack = int(self._hdr_i[slot, _H_ACK])
        if seq % 2:                       # died mid-write: finish the seq
            self._hdr_i[slot, _H_SEQ] = seq + 1
            self._hdr_i[slot, _H_ACK] = seq + 1
            return "torn"
        if ack != seq:                    # stable but orphaned
            self._hdr_i[slot, _H_ACK] = seq
            return "unread"
        return None

    # ------------------------------------------------------------ lifecycle
    def close(self, unlink: bool = False) -> None:
        # drop numpy views before closing the mmaps they point into
        self.views = {}
        self._hdr_i = self._hdr_f = None
        for shm in self._shms:
            try:
                shm.close()
                if unlink:
                    shm.unlink()
            except FileNotFoundError:
                pass
        self._shms = []


class Heartbeat:
    """One shared monotonic-clock timestamp per worker slot.

    Workers ``beat(i)`` every service-loop pass (including inside
    backpressure waits); the supervisor's ``age(i)`` is the seconds since
    worker ``i`` last beat — ``inf`` before the first beat. The parent
    beats on behalf of a worker at spawn so import/jit warmup never reads
    as a hang. A single jitted rollout cannot beat mid-flight, so hang
    timeouts must exceed the longest legitimate rollout (DESIGN.md §10).
    """

    def __init__(self, name: str, slots: int = 0, create: bool = False):
        self.name = name
        self._shm = shared_memory.SharedMemory(
            name=name, create=create, size=slots * 8 if create else 0)
        # attach side derives capacity from the (page-rounded) block size
        self._view = np.ndarray((self._shm.size // 8,), dtype=np.float64,
                                buffer=self._shm.buf)
        if create:
            self._view.fill(0.0)

    def beat(self, i: int) -> None:
        self._view[i] = time.monotonic()

    def age(self, i: int) -> float:
        t = float(self._view[i])
        return float("inf") if t == 0.0 else time.monotonic() - t

    def close(self, unlink: bool = False) -> None:
        self._view = None
        try:
            self._shm.close()
            if unlink:
                self._shm.unlink()
        except FileNotFoundError:
            pass


@dataclasses.dataclass(frozen=True)
class ChannelSpec:
    """Attach info for a ``ParamsChannel`` (picklable).

    Also JSON round-trippable (``to_json``/``from_json``): worker
    processes receive the spec over the spawn boundary, but a *serving*
    replica (``repro.serve``) may be launched independently of the
    learner — the learner drops the spec as a handoff file and the
    replica attaches from it (``launch/serve_policy.py
    --channel-spec``).
    """
    prefix: str
    leaves: Tuple[LeafSpec, ...]

    def to_json(self) -> str:
        return json.dumps({
            "prefix": self.prefix,
            "leaves": [dataclasses.asdict(l) for l in self.leaves],
        })

    @classmethod
    def from_json(cls, text: str) -> "ChannelSpec":
        d = json.loads(text)
        return cls(prefix=d["prefix"], leaves=tuple(
            LeafSpec(key=l["key"], shape=tuple(l["shape"]),
                     dtype=l["dtype"]) for l in d["leaves"]))


class ParamsChannel:
    """Versioned cross-process params cell — ``PolicyStore`` over shm.

    One shared block per flattened param leaf plus a single seqlock word:
    ``publish`` bumps it to odd, overwrites every leaf, bumps to even;
    ``version == seq // 2`` counts publishes. Readers (``read``) spin
    until the version moves past ``min_version``, copy, and re-check —
    so workers always act with the freshest published policy (possibly
    stale, never torn) and pay the copy only when it actually changed.
    """

    def __init__(self, spec: ChannelSpec, create: bool):
        self.spec = spec
        self._shms: List[shared_memory.SharedMemory] = []
        self._views: List[np.ndarray] = []
        for i, leaf in enumerate(spec.leaves):
            nbytes = (int(np.prod(leaf.shape, dtype=np.int64))
                      * np.dtype(leaf.dtype).itemsize)
            shm = self._open(f"{spec.prefix}-l{i}", create, max(nbytes, 8))
            self._views.append(np.ndarray(leaf.shape, dtype=leaf.dtype,
                                          buffer=shm.buf))
        shm = self._open(f"{spec.prefix}-hdr", create, 8)
        self._hdr = np.ndarray((1,), dtype=np.int64, buffer=shm.buf)
        if create:
            self._hdr[0] = 0

    def _open(self, name: str, create: bool,
              size: int) -> shared_memory.SharedMemory:
        shm = shared_memory.SharedMemory(
            name=name, create=create, size=size if create else 0)
        self._shms.append(shm)
        return shm

    @classmethod
    def create(cls, leaves: Sequence[np.ndarray],
               prefix: str) -> "ParamsChannel":
        spec = ChannelSpec(prefix=prefix, leaves=tuple(
            LeafSpec(key=str(i), shape=tuple(x.shape),
                     dtype=np.dtype(x.dtype).str)
            for i, x in enumerate(leaves)))
        return cls(spec, create=True)

    @classmethod
    def attach(cls, spec: ChannelSpec) -> "ParamsChannel":
        return cls(spec, create=False)

    @property
    def version(self) -> int:
        return int(self._hdr[0]) // 2

    def publish(self, leaves: Sequence[np.ndarray]) -> int:
        if len(leaves) != len(self._views):
            raise ValueError(
                f"params channel holds {len(self._views)} leaves, "
                f"publish got {len(leaves)}")
        seq = int(self._hdr[0])
        self._hdr[0] = seq + 1
        for view, leaf in zip(self._views, leaves):
            view[...] = leaf
        self._hdr[0] = seq + 2
        return (seq + 2) // 2

    def read(self, min_version: int = 0, last_version: int = -1,
             should_stop: Optional[Callable[[], bool]] = None,
             poll: float = 1e-4
             ) -> Tuple[Optional[List[np.ndarray]], int]:
        """Block until ``version >= min_version``; return
        ``(leaf_copies, version)`` — leaves are ``None`` when the version
        equals ``last_version`` (nothing new to copy) or when
        ``should_stop()`` fired (version reported as -1)."""
        while True:
            s1 = int(self._hdr[0])
            if s1 % 2 == 0 and s1 // 2 >= min_version:
                version = s1 // 2
                if version == last_version:
                    return None, version
                out = [np.array(v) for v in self._views]
                if int(self._hdr[0]) == s1:
                    return out, version
                continue                              # torn read: retry
            if should_stop is not None and should_stop():
                return None, -1
            time.sleep(poll)

    def close(self, unlink: bool = False) -> None:
        self._views = []
        self._hdr = None
        for shm in self._shms:
            try:
                shm.close()
                if unlink:
                    shm.unlink()
            except FileNotFoundError:
                pass
        self._shms = []


@contextlib.contextmanager
def _worker_env():
    """Environment adjustments around ``Process.start()`` only (children
    inherit the environment at spawn; the parent's own, already-
    initialized client is unaffected):

    * rollout workers are host-side sampler processes and always run on
      the CPU client: an accelerator belongs to one process at a time,
      and the learner (this process) already holds it
    * limit each worker's XLA CPU intra-op pool to one thread: N workers
      x one multi-threaded eigen pool oversubscribes small hosts and
      *slows* collection as N grows (bitwise-neutral for rollout-sized
      ops — asserted by the process==inline parity tests, which run the
      parent multi-threaded)
    """
    saved = {k: os.environ.get(k) for k in ("JAX_PLATFORMS", "XLA_FLAGS")}
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = saved["XLA_FLAGS"] or ""
    if "intra_op_parallelism_threads" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_cpu_multi_thread_eigen=false "
            "intra_op_parallelism_threads=1").strip()
    try:
        yield
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


# ======================================================= the worker process
def _worker_main(spec_dict: Dict[str, Any], ring_spec: RingSpec,
                 chan_spec: ChannelSpec, hb_name: str, worker_id: int,
                 incarnation: int, slot_base: int, num_slots: int,
                 fault_plan_dict: Optional[Dict[str, Any]], cmd_q,
                 res_q) -> None:
    """Entry point of one rollout worker process.

    Rebuilds env/algo/rollout from the serialized ``WorkerSpec`` purely
    via the registry (nothing else crossed the boundary), then serves:

      ("collect", v) — one rollout under params version >= v, write slot,
                       report;  the lock-step mode ``ProcessBackend`` uses
      ("freerun", v) — roll continuously with the freshest published
                       params, blocking only when the ring slot has not
                       been consumed; the ``AsyncOrchestrator`` mode
      ("stop",)      — exit cleanly

    Trajectory reports carry the slot's post-write seqlock value:
    ("traj", id, slot, seq, version, collect_s, loop_s). The consumer
    matches seq against the live header before reading, which is what
    makes slot reclamation safe — a message from a dead incarnation can
    never alias a respawned worker's fresh write.

    ``incarnation`` counts this worker id's spawns; it keys the fault
    plan's PRNG stream (a respawned worker draws a fresh deterministic
    schedule) and is otherwise inert. The worker stamps ``hb_name``'s
    heartbeat slot every service-loop pass so a supervisor can tell
    wedged from slow.

    Any exception is reported upstream as ("error", id, traceback) and
    surfaces in the parent as ``WorkerCrashed``.
    """
    try:
        # spread workers round-robin over the host's cores: deterministic
        # placement avoids the migration thrash the kernel scheduler adds
        # when workers outnumber cores (a worker never fights more peers
        # than ceil(N / cores) for its core); a no-op gain when cores >= N
        if hasattr(os, "sched_setaffinity"):
            try:
                cores = sorted(os.sched_getaffinity(0))
                os.sched_setaffinity(
                    0, {cores[worker_id % len(cores)]})
            except OSError:
                pass
        import jax
        import jax.numpy as jnp

        from repro.core.faults import FaultPlan, decide
        from repro.core.sampler import WorkerSpec

        plan = FaultPlan.from_dict(fault_plan_dict)
        spec = WorkerSpec.from_dict(spec_dict)
        rollout, carry, params_template = spec.build()
        rollout = jax.jit(rollout)
        t_leaves, treedef = jax.tree_util.tree_flatten(params_template)
        ring = ShmRing.attach(ring_spec)
        chan = ParamsChannel.attach(chan_spec)
        hb = Heartbeat(hb_name)
        if len(t_leaves) != len(chan.spec.leaves):
            raise RuntimeError(
                f"worker {worker_id}: rebuilt params have "
                f"{len(t_leaves)} leaves, channel carries "
                f"{len(chan.spec.leaves)} — WorkerSpec and learner params "
                f"disagree")
        hb.beat(worker_id)
        res_q.put(("ready", worker_id, jax.devices()[0].platform))

        params, last_version = None, -1
        freerunning, counter, stop = False, 0, False
        while not stop:
            hb.beat(worker_id)
            if freerunning:
                try:
                    cmd = cmd_q.get_nowait()
                except _queue.Empty:
                    cmd = ("step", 0)
            else:
                try:                     # bounded waits keep the beat alive
                    cmd = cmd_q.get(timeout=0.25)
                except _queue.Empty:
                    continue
            op = cmd[0]
            if op == "stop":
                break
            if op == "freerun":
                freerunning = True
                continue
            # op is "collect" (lock-step) or "step" (free-running)
            fault = decide(plan, worker_id, incarnation, counter)
            if fault == "kill":          # clean death: nothing in flight
                os.kill(os.getpid(), signal.SIGKILL)
            elif fault == "hang":        # wedged: alive, beats never again
                while True:
                    time.sleep(0.05)
            elif fault == "delay":       # straggler, not a failure
                time.sleep(plan.delay_ms / 1e3)
            min_version = cmd[1] if len(cmd) > 1 else 0
            t_loop0 = time.perf_counter()
            np_leaves, version = chan.read(min_version=min_version,
                                           last_version=last_version)
            if np_leaves is not None:
                params = treedef.unflatten(
                    [jnp.asarray(x) for x in np_leaves])
                last_version = version
            t0 = time.perf_counter()
            carry, traj = rollout(params, carry)
            traj = jax.block_until_ready(traj)
            dt = time.perf_counter() - t0
            traj_np = {k: np.asarray(v) for k, v in traj.items()}
            slot = slot_base + (counter % num_slots)
            while not ring.is_free(slot):      # learner behind: back off
                hb.beat(worker_id)
                try:
                    nxt = cmd_q.get(timeout=0.002)
                    if nxt[0] == "stop":
                        stop = True
                        break
                except _queue.Empty:
                    pass
            if stop:
                break
            loop_dt = time.perf_counter() - t_loop0
            if fault == "torn":          # die mid-write: seqlock left odd
                ring.begin_torn_write(slot, worker_id)
                os.kill(os.getpid(), signal.SIGKILL)
            seq = ring.write(slot, traj_np, worker_id=worker_id,
                             policy_version=last_version,
                             collect_seconds=dt, loop_seconds=loop_dt)
            res_q.put(("traj", worker_id, slot, seq, last_version, dt,
                       time.perf_counter() - t_loop0))
            counter += 1
        ring.close()
        chan.close()
        hb.close()
    except Exception:
        try:
            res_q.put(("error", worker_id, traceback.format_exc()))
        except Exception:
            pass


# ============================================================ the worker pool
class ProcessWorkerPool:
    """Rollout worker processes + the shared-memory transport between
    them and this (learner) process.

    The pool is provisioned for ``max_workers = len(worker_specs)``
    workers up front — ring slots, heartbeat slots and per-worker specs
    all exist from construction — but only the ``active`` subset
    (``active_workers``, default: all) is actually running. Construction
    publishes the initial params (version 1), spawns the active workers
    and blocks until every one reports ready — a worker that dies while
    importing/building surfaces immediately as ``WorkerCrashed``.

    Two driving modes:

    * ``collect()`` — lock-step: broadcast one ("collect", version)
      command, await N results, return per-worker trajectories **in
      worker-index order** (the determinism rule that makes
      ``process == inline`` exact for matched per-worker seeds).
    * ``start_freerun()`` + ``next_experience()`` — the async mode:
      workers roll continuously against the freshest published params;
      the learner drains finished slots as ``core.queues.Experience``
      records. Backpressure is the ring itself (``slots_per_worker``
      unconsumed rollouts per worker, then the worker blocks), so
      nothing is ever dropped.

    Fleet primitives (``respawn``/``grow``/``shrink``/``kill_worker``,
    ``poll_msg``/``drain_pending``/``dead_workers``/``heartbeat_age``,
    ``reclaim_worker_slots``/``read_slot_checked``) are mechanism only —
    *when* to respawn, back off, or resize is
    ``core.supervisor.WorkerSupervisor`` policy.

    Workers are daemonic and additionally reaped by an ``atexit`` hook,
    so Ctrl-C in the learner never leaves orphan samplers behind.
    ``close`` distinguishes workers it stopped itself from workers that
    crashed during shutdown: the latter raise ``WorkerCrashed`` chained
    (via ``__cause__``) onto any crash already surfaced, and never mask
    an exception already propagating.
    """

    def __init__(self, worker_specs: Sequence[Any], params: Any,
                 traj_example: Dict[str, Any], slots_per_worker: int = 1,
                 start_timeout: float = 300.0,
                 collect_timeout: float = 600.0,
                 active_workers: Optional[Sequence[int]] = None,
                 fault_plan: Optional[Any] = None):
        import jax
        import multiprocessing as mp

        self.max_workers = len(worker_specs)
        self._specs = list(worker_specs)
        self.slots_per_worker = int(slots_per_worker)
        self.collect_timeout = collect_timeout
        self.fault_plan = fault_plan
        self._closed = False
        self._freerunning = False
        self._stash: collections.deque = collections.deque()
        self._terminated: set = set()       # wids we stopped on purpose
        self._crash_surfaced: set = set()   # crashes already raised
        self._last_crash: Optional[WorkerCrashed] = None
        self._ctx = mp.get_context("spawn")
        prefix = f"walle-{os.getpid()}-{uuid.uuid4().hex[:6]}"
        leaves = [np.asarray(jax.device_get(x))
                  for x in jax.tree_util.tree_leaves(params)]
        self.channel = ParamsChannel.create(leaves, prefix + "-p")
        self.version = self.channel.publish(leaves)
        self.ring = ShmRing.create(
            traj_example, self.max_workers * self.slots_per_worker,
            prefix + "-t")
        self.heartbeat = Heartbeat(prefix + "-hb", self.max_workers,
                                   create=True)
        self._res = self._ctx.Queue()
        self._cmd: List[Optional[Any]] = [None] * self.max_workers
        self._procs: List[Optional[Any]] = [None] * self.max_workers
        self._retired: List[Any] = []       # cmd queues of dead incarnations
        self._incarnation = [0] * self.max_workers
        self.active: List[int] = sorted(
            active_workers if active_workers is not None
            else range(self.max_workers))
        if not self.active:
            raise ValueError("worker pool needs at least one active worker")
        if self.active[0] < 0 or self.active[-1] >= self.max_workers:
            raise ValueError(
                f"active_workers {self.active} out of range for "
                f"{self.max_workers} specs")
        # each started worker's own report of its JAX platform ("cpu")
        self.worker_platforms: Dict[int, str] = {}
        self._atexit = lambda: self.close(raise_on_crash=False)
        atexit.register(self._atexit)
        try:
            for i in self.active:
                self._spawn(i)
            while len(self.worker_platforms) < len(self.active):
                msg = self._get(timeout=start_timeout)
                if msg[0] == "ready":
                    self.worker_platforms[msg[1]] = msg[2]
        except BaseException:
            self.close(raise_on_crash=False)
            raise

    # ---------------------------------------------------------------- sizing
    @property
    def num_workers(self) -> int:
        return len(self.active)

    # ------------------------------------------------------------- plumbing
    def _spawn(self, i: int) -> None:
        """(Re)start worker ``i`` under a fresh incarnation: new command
        queue (the old one may hold commands consumed-but-unexecuted by
        the dead incarnation), heartbeat pre-beaten by the parent so
        import/jit warmup never reads as a hang."""
        if self._cmd[i] is not None:
            self._retired.append(self._cmd[i])
        self._incarnation[i] += 1
        q = self._ctx.Queue()
        self._cmd[i] = q
        self.heartbeat.beat(i)
        plan_dict = (self.fault_plan.to_dict()
                     if self.fault_plan is not None else None)
        p = self._ctx.Process(
            target=_worker_main, name=f"walle-worker-{i}", daemon=True,
            args=(self._specs[i].to_dict(), self.ring.spec,
                  self.channel.spec, self.heartbeat.name, i,
                  self._incarnation[i], i * self.slots_per_worker,
                  self.slots_per_worker, plan_dict, q, self._res))
        self._procs[i] = p
        with _worker_env():
            p.start()

    def _check_alive(self) -> None:
        dead = [(i, self._procs[i].exitcode) for i in self.active
                if self._procs[i] is not None
                and not self._procs[i].is_alive()]
        if dead:
            for i, _ in dead:
                self._crash_surfaced.add(i)
            err = WorkerCrashed(
                "rollout worker(s) died: " + ", ".join(
                    f"#{i} (exitcode={code})" for i, code in dead))
            self._last_crash = err
            raise err

    def _get(self, timeout: float):
        """Next result-queue message (stashed messages first); raises
        ``WorkerCrashed`` on worker error/death and ``TimeoutError`` past
        ``timeout``."""
        deadline = time.monotonic() + timeout
        while True:
            if self._stash:
                msg = self._stash.popleft()
            else:
                try:
                    msg = self._res.get(timeout=0.25)
                except _queue.Empty:
                    self._check_alive()
                    if time.monotonic() > deadline:
                        raise TimeoutError(
                            f"no worker result within {timeout:.0f}s")
                    continue
            if msg[0] == "error":
                err = WorkerCrashed(
                    f"rollout worker #{msg[1]} raised:\n{msg[2]}")
                self._crash_surfaced.add(msg[1])
                self._last_crash = err
                raise err
            return msg

    def _read_slot(self, slot: int):
        traj, meta = self.ring.read(slot)
        self.ring.ack(slot)
        return traj, meta

    # ----------------------------------------------- supervisor primitives
    def poll_msg(self, timeout: float = 0.25):
        """One raw result message (stash first) or ``None`` on timeout.
        No liveness check, no error translation — supervisor's job."""
        if self._stash:
            return self._stash.popleft()
        try:
            return self._res.get(timeout=timeout)
        except _queue.Empty:
            return None

    def drain_pending(self) -> None:
        """Move every already-queued result message into the stash. A
        producer SIGKILLed mid-``put`` can leave a partially-pickled
        message; deserialization errors end the drain (nothing after a
        torn message is trustworthy this pass — the next drain retries)."""
        while True:
            try:
                self._stash.append(self._res.get_nowait())
            except _queue.Empty:
                return
            except Exception:
                return

    def dead_workers(self) -> List[Tuple[int, Optional[int]]]:
        """Active workers whose process has exited: [(wid, exitcode)]."""
        return [(i, self._procs[i].exitcode) for i in self.active
                if self._procs[i] is not None
                and not self._procs[i].is_alive()]

    def heartbeat_age(self, i: int) -> float:
        return self.heartbeat.age(i)

    def kill_worker(self, i: int) -> None:
        """SIGKILL worker ``i`` (wedged workers ignore gentler signals)."""
        p = self._procs[i]
        if p is not None and p.is_alive():
            p.kill()
        if p is not None:
            p.join(timeout=5.0)

    def respawn(self, i: int) -> None:
        """Replace worker ``i`` with a fresh incarnation of the same
        ``WorkerSpec`` (same seed — a deterministic restart; only the
        fault stream differs, keyed by incarnation). Re-enters freerun
        if the pool is free-running. The caller reclaims slots *before*
        respawning (``reclaim_worker_slots``) so the new incarnation is
        never blocked by its predecessor's unacked writes."""
        self.kill_worker(i)
        self._spawn(i)
        if self._freerunning:
            self._cmd[i].put(("freerun",))

    def reclaim_worker_slots(self, i: int) -> List[Tuple[int, str]]:
        """Repair dead worker ``i``'s ring slots, *except* slots with a
        pending ("traj", ...) message — those hold completed rollouts the
        supervisor will still consume (seq-checked). Returns
        [(slot, kind)] for what was actually reclaimed."""
        self.drain_pending()
        pending = {m[2] for m in self._stash
                   if m[0] == "traj" and m[1] == i}
        out = []
        base = i * self.slots_per_worker
        for slot in range(base, base + self.slots_per_worker):
            if slot in pending:
                continue
            kind = self.ring.reclaim(slot)
            if kind is not None:
                out.append((slot, kind))
        return out

    def read_slot_checked(self, slot: int, seq: int):
        """Read+ack ``slot`` only if its seqlock still equals ``seq`` (the
        value the reporting message recorded at write time); otherwise the
        slot was reclaimed/rewritten after its writer died and the message
        is stale — raise ``StaleSlotMessage`` so the caller discards it
        instead of double-consuming the slot's new contents."""
        cur = self.ring.seq(slot)
        if cur != seq:
            raise StaleSlotMessage(
                f"ring slot {slot}: message recorded seq {seq} but the "
                f"slot is now at seq {cur} — reclaimed and rewritten "
                f"since; discarding the stale message")
        return self._read_slot(slot)

    def send(self, wid: int, cmd: Tuple) -> None:
        self._cmd[wid].put(cmd)

    # --------------------------------------------------------------- sizing
    def grow(self) -> Optional[int]:
        """Activate the lowest inactive worker id (its spec, ring slots
        and heartbeat slot were provisioned at construction). Returns the
        id, or ``None`` at capacity. The new worker reads the current
        params from the channel on its first rollout — joiners are never
        behind by more than one publish."""
        inactive = [i for i in range(self.max_workers)
                    if i not in self.active]
        if not inactive:
            return None
        i = inactive[0]
        self._terminated.discard(i)
        self._crash_surfaced.discard(i)
        for slot in range(i * self.slots_per_worker,
                          (i + 1) * self.slots_per_worker):
            self.ring.reclaim(slot)
        self._spawn(i)
        self.active = sorted(self.active + [i])
        if self._freerunning:
            self._cmd[i].put(("freerun",))
        return i

    def shrink(self) -> Optional[int]:
        """Deactivate the highest active worker id (stop, join, terminate
        stragglers). Returns the id, or ``None`` at the floor of one."""
        if len(self.active) <= 1:
            return None
        i = self.active[-1]
        self.active = self.active[:-1]
        self._terminated.add(i)
        try:
            self._cmd[i].put_nowait(("stop",))
        except Exception:
            pass
        p = self._procs[i]
        if p is not None:
            p.join(timeout=5.0)
            if p.is_alive():
                p.terminate()
                p.join(timeout=3.0)
        # release anything it left unconsumed so a later grow() starts clean
        for slot in range(i * self.slots_per_worker,
                          (i + 1) * self.slots_per_worker):
            self.ring.reclaim(slot)
        return i

    # ------------------------------------------------------------ lock-step
    def publish(self, params: Any) -> int:
        import jax
        self.version = self.channel.publish(
            [np.asarray(jax.device_get(x))
             for x in jax.tree_util.tree_leaves(params)])
        return self.version

    def collect(self, staggered: bool = False
                ) -> Tuple[List[Dict[str, np.ndarray]], List[float],
                           List[float]]:
        """One lock-step sweep: every active worker rolls once under the
        current params version; trajectories come back in worker-index
        order.

        ``staggered=True`` commands workers one at a time, awaiting each
        result before waking the next. On hosts with fewer cores than
        workers the default broadcast makes every worker's self-timed
        rollout include preemption by its peers (they time-slice the same
        cores), so the per-worker times — and the critical-path throughput
        derived from them — measure scheduler contention, not sampler
        work. Staggering serializes the sweep so each worker runs
        uncontended, recovering the per-sampler steady-state timing the
        inline backend's serial sweep reports (DESIGN.md §2's
        methodology). Trajectories, merge order and determinism are
        identical either way — only the wall-clock overlap changes.
        """
        if self._closed:
            raise RuntimeError("worker pool is closed")
        if self._freerunning:
            raise RuntimeError(
                "pool is free-running (async mode); lock-step collect() "
                "would interleave with unsolicited rollouts")
        version = self.channel.version
        got: Dict[int, Tuple[int, int, float, float]] = {}
        if staggered:
            for i in self.active:
                self._cmd[i].put(("collect", version))
                wid, entry = self._next_traj(self.collect_timeout)
                got[wid] = entry
        else:
            for i in self.active:
                self._cmd[i].put(("collect", version))
            while len(got) < len(self.active):
                wid, entry = self._next_traj(self.collect_timeout)
                got[wid] = entry
        trajs, times, loops = [], [], []
        for i in self.active:                    # deterministic merge order
            slot, seq, dt, loop_dt = got[i]
            traj, _meta = self.read_slot_checked(slot, seq)
            trajs.append(traj)
            times.append(dt)
            loops.append(loop_dt)
        return trajs, times, loops

    def _next_traj(self, timeout: float):
        """Next ("traj", ...) message as (wid, (slot, seq, dt, loop_dt));
        skips stray ("ready", ...) reports from respawned workers."""
        deadline = time.monotonic() + timeout
        while True:
            msg = self._get(max(1e-3, deadline - time.monotonic()))
            if msg[0] != "traj":
                continue
            _, wid, slot, seq, _v, dt, loop_dt = msg
            return wid, (slot, seq, dt, loop_dt)

    # ------------------------------------------------------------- freerun
    def start_freerun(self) -> None:
        if self._freerunning:
            return
        self._freerunning = True
        for i in self.active:
            self._cmd[i].put(("freerun",))

    def next_experience(self, timeout: float = 1.0):
        """Drain one finished rollout as ``(Experience, loop_seconds)``;
        ``None`` if nothing finished within ``timeout``."""
        from repro.core.queues import Experience
        deadline = time.monotonic() + timeout
        while True:
            try:
                msg = self._get(max(1e-3, deadline - time.monotonic()))
            except TimeoutError:
                return None
            if msg[0] != "traj":
                if time.monotonic() > deadline:
                    return None
                continue
            _, wid, slot, seq, version, dt, _loop = msg
            traj, meta = self.read_slot_checked(slot, seq)
            return (Experience(traj=traj, policy_version=version,
                               sampler_id=wid, collect_seconds=dt),
                    meta["loop_seconds"])

    # ------------------------------------------------------------ lifecycle
    def close(self, raise_on_crash: bool = True) -> None:
        """Stop, join (terminate stragglers) and unlink all shared state.
        Idempotent; also runs from ``atexit`` so Ctrl-C reaps workers.

        Workers found already dead with a nonzero exitcode — that we did
        not stop ourselves and whose crash was not already surfaced as a
        ``WorkerCrashed`` — crashed *during shutdown*. When nothing else
        is propagating, that raises ``WorkerCrashed`` (chained onto the
        earlier crash via ``__cause__`` when one exists); when an
        exception is already in flight, close stays silent so it never
        masks the original error."""
        if self._closed:
            return
        self._closed = True
        for i in self.active:
            if self._cmd[i] is not None:
                try:
                    self._cmd[i].put_nowait(("stop",))
                except Exception:
                    pass
        procs = [(i, p) for i, p in enumerate(self._procs) if p is not None]
        for _, p in procs:
            p.join(timeout=3.0)
        for i, p in procs:
            if p.is_alive():
                self._terminated.add(i)
                p.terminate()
        for _, p in procs:
            p.join(timeout=3.0)
        shutdown_crashes = [
            (i, p.exitcode) for i, p in procs
            if p.exitcode not in (0, None)
            and i not in self._terminated
            and i not in self._crash_surfaced]
        for q in [q for q in self._cmd if q is not None] + self._retired + [
                self._res]:
            try:
                q.close()
                q.cancel_join_thread()
            except Exception:
                pass
        self.ring.close(unlink=True)
        self.channel.close(unlink=True)
        self.heartbeat.close(unlink=True)
        try:
            atexit.unregister(self._atexit)
        except Exception:
            pass
        if (shutdown_crashes and raise_on_crash
                and sys.exc_info()[1] is None):
            err = WorkerCrashed(
                "rollout worker(s) crashed during shutdown: " + ", ".join(
                    f"#{i} (exitcode={code})"
                    for i, code in shutdown_crashes))
            if self._last_crash is not None:
                raise err from self._last_crash
            raise err

    def __enter__(self) -> "ProcessWorkerPool":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
