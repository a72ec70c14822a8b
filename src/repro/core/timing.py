"""The program's tracer: the runners' phases as spans on the profiler's
clock, and per-iteration counts of what each phase cost.

A runner opens one ``iteration`` per logged iteration (a fused runner
one per chunk) and a ``span`` around each phase inside it. A span

* enters ``jax.profiler.TraceAnnotation`` (the iteration's root a
  ``StepTraceAnnotation``; every span of an iteration carries its
  ``step_num``), so under an active profiler the phase lands on the
  host plane on the same clock as the device's ops, and without one it
  costs about a microsecond;
* adds its ``perf_counter`` duration, by name, to the iteration's
  ``Record``: the seconds its ``IterationLog`` reports.

Counts go to the same record under ``<counter>@<innermost span>``, or
``<counter>@outside`` where a record is open but no span is:
``compiles`` (XLA backend compiles, seen through ``jax.monitoring``)
and ``host_pulls`` (the runner's own device-to-host reads, ``pull``).

Spans stay at phase granularity: never one per env-step, per minibatch
or inside a jitted body. A record is two small dicts however long the
run; nothing else is kept.
"""
from __future__ import annotations

import dataclasses
import functools
import threading
import time
from contextlib import contextmanager
from typing import Callable, Dict, Iterator, List, Optional

import jax

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
OUTSIDE = "outside"

clock = time.perf_counter       # tests substitute a fake clock


@dataclasses.dataclass
class Record:
    """One iteration's seconds per span name and counts per key."""
    spans: Dict[str, float] = dataclasses.field(default_factory=dict)
    counts: Dict[str, int] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Span:
    """An open or closed span: its parent is the span that enclosed it
    when it opened (in its own thread, or the one ``bind`` handed
    over)."""
    name: str
    parent: Optional["Span"]
    step_num: Optional[int]
    seconds: float = 0.0
    child_seconds: float = 0.0

    @property
    def self_seconds(self) -> float:
        """Duration less the summed durations of its children."""
        return self.seconds - self.child_seconds


class _State(threading.local):
    def __init__(self):
        self.record: Optional[Record] = None
        self.stack: List[Span] = []


_state = _State()
_listening = False
# a threaded backend's samplers add to one record from their own threads
_lock = threading.Lock()


def _on_duration(event: str, _secs: float, **_kw) -> None:
    if event == COMPILE_EVENT:
        _count("compiles")


def _count(counter: str) -> None:
    record = _state.record
    if record is not None:
        where = _state.stack[-1].name if _state.stack else OUTSIDE
        key = f"{counter}@{where}"
        with _lock:
            record.counts[key] = record.counts.get(key, 0) + 1


def _listen() -> None:
    """Register the compile listener, once per process."""
    global _listening
    with _lock:
        if not _listening:
            jax.monitoring.register_event_duration_secs_listener(
                _on_duration)
            _listening = True


def current() -> Optional[Span]:
    """The innermost open span of this thread."""
    return _state.stack[-1] if _state.stack else None


@contextmanager
def recording(record: Record) -> Iterator[Record]:
    """Make ``record`` the one this thread's spans and counts go to."""
    _listen()
    prev, _state.record = _state.record, record
    try:
        yield record
    finally:
        _state.record = prev


@contextmanager
def span(name: str, *, step_num: Optional[int] = None,
         **args) -> Iterator[Span]:
    """Time the block as ``name`` under the innermost open span; yields
    the ``Span``, whose ``seconds`` are set when the block ends."""
    parent = current()
    if step_num is None and parent is not None:
        step_num = parent.step_num
    s = Span(name, parent, step_num)
    kind = jax.profiler.TraceAnnotation
    if step_num is not None:
        args["step_num"] = step_num
        if parent is None:
            kind = jax.profiler.StepTraceAnnotation
    annotation = kind(name, **args)
    record = _state.record
    _state.stack.append(s)
    t0 = clock()
    try:
        with annotation:
            yield s
    finally:
        s.seconds = clock() - t0
        _state.stack.pop()
        with _lock:
            if record is not None:
                record.spans[name] = record.spans.get(name, 0.0) + s.seconds
            if parent is not None:
                parent.child_seconds += s.seconds


@contextmanager
def iteration(name: str, step_num: int) -> Iterator[Record]:
    """One iteration: a fresh ``Record`` and its root span."""
    with recording(Record()) as record, span(name, step_num=step_num):
        yield record


def bind(fn: Callable) -> Callable:
    """``fn``, to run in another thread under this thread's record and
    innermost span (a threaded backend's samplers)."""
    record, parent = _state.record, current()

    @functools.wraps(fn)
    def bound(*args, **kwargs):
        prev = _state.record, _state.stack
        _state.record = record
        _state.stack = [parent] if parent is not None else []
        try:
            return fn(*args, **kwargs)
        finally:
            _state.record, _state.stack = prev
    return bound


def pull(tree):
    """``jax.device_get(tree)``, counted as ``host_pulls``."""
    _count("host_pulls")
    return jax.device_get(tree)
