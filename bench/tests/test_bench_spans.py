"""The readers of the program's own spans, counters and scopes, on
hand-made logs and traces whose answers are counted by hand."""
from types import SimpleNamespace

import pytest

from tiny_cells import ROOT

from bench import harness, tracing

MS = 1e6  # nanoseconds in a millisecond


def _reader(name):
    return harness.load_module(ROOT / "bench" / "metrics" / f"{name}.py").read


def _log(spans=None, counts=None):
    return SimpleNamespace(spans=spans or {}, counts=counts or {})


def _untraced_log():
    """A log of a program without the tracer."""
    return SimpleNamespace(learn_time=0.1, collect_time_serial=0.2)


def _ctx(logs=(), window=None, trace=None, traffic=None):
    return tracing.MetricContext(
        cell=SimpleNamespace(traffic=traffic or {}), window=window or {},
        logs=list(logs), trace=trace, bench=ROOT / "bench",
        device_kind="TPU v5 lite")


def test_log_ms_is_the_mean_runner_log_span():
    logs = [_log({"runner.log": s, "learner.step": 1.0})
            for s in (0.08, 0.10, 0.12)]
    read = _reader("log_ms.sync")
    assert read(_ctx(logs)) == pytest.approx(100.0)
    assert read(_ctx([_untraced_log()] * 3)) is None


def test_compiles_per_iter_counts_every_span():
    logs = [_log(counts={"compiles@runner.log": 1,
                         "host_pulls@runner.log": 1}),
            _log(counts={"compiles@runner.log": 1,
                         "compiles@samplers.merge": 2})]
    read = _reader("compiles_per_iter.sync")
    assert read(_ctx(logs, {"iterations": 2})) == 2.0
    warm = [_log(counts={"host_pulls@runner.log": 1})] * 4
    assert read(_ctx(warm, {"iterations": 4})) == 0.0
    assert read(_ctx([_untraced_log()] * 2, {"iterations": 2})) is None


def test_pull_ms_is_per_call():
    # two calls of a chunk of 2: the call-level spans sit on each head
    logs = [_log({"runner.pull": 0.002, "runner.chunk": 0.03}), _log(),
            _log({"runner.pull": 0.004, "runner.chunk": 0.03}), _log()]
    read = _reader("pull_ms")
    assert read(_ctx(logs, {"calls": 2, "iterations": 4})) == \
        pytest.approx(3.0)
    assert read(_ctx([_untraced_log()] * 4, {"calls": 2})) is None


FIND = ('%replay.find.1 = s32[256]{0} custom-call(f32[256]{0} %a, '
        'f32[2097151]{0} %b), custom_call_target="tpu_custom_call"')


def _replay_trace():
    """One chip over a 10 ms window: the descent 0-2 ms (a scoped op
    inside it, 1-1.5 ms, counts once), a relayout copy whose op_name
    carries the gather's scope 2-3 ms, the learner's matmul 3-4 ms, an op
    whose only mention of replay is its source file 4-5 ms, and a scoped
    op after the window."""
    dev = [(FIND, 0, 2 * MS, ""),
           ("%fusion.7 = f32[256]{0} fusion(%c)", 1 * MS, 1.5 * MS,
            "jit(train_chunk)/while/body/replay.sample/replay.find/add"),
           ("%copy.123 = f32[1048576,1]{1,0} copy(%d)", 2 * MS, 3 * MS,
            'op_name="jit(train_chunk)/while/body/replay.sample/'
            'replay.gather/reshape"'),
           ("%fusion.9 = bf16[256,256]{1,0} fusion(%e)", 3 * MS, 4 * MS,
            "jit(train_chunk)/while/body/learner.update/dot_general"),
           ("%fusion.11 = f32[64]{0} fusion(%f)", 4 * MS, 5 * MS,
            "src/repro/data/replay.py:88"),
           ("%replay.update.2 = f32[8]{0} scatter(%g)", 12 * MS, 13 * MS,
            "")]
    return tracing.Trace({"/device:TPU:0": dev},
                         [("bench.window", 0, 10 * MS)])


def test_replay_ms_reads_scoped_device_time_per_update():
    read = _reader("replay_ms")
    ctx = _ctx(window={"iterations": 1}, trace=_replay_trace(),
               traffic={"updates_per_collect": 2})
    assert read(ctx) == pytest.approx(1.5)        # (2 + 1) ms / 2 updates


GATHER_UNSCOPED = ('%closed_call.299 = f32[256,14]{1,0} custom-call('
                   's32[256]{0} %a, f32[1048576,14]{1,0} %b), '
                   'custom_call_target="tpu_custom_call"')


def test_replay_ms_reads_nothing_where_no_op_carries_the_scope():
    dev = [(GATHER_UNSCOPED, 0, 2 * MS, "jit(train_chunk)/pallas_call"),
           ("%copy.1 = f32[1048576,1]{1,0} copy(%d)", 2 * MS, 3 * MS,
            "src/repro/data/replay.py:40")]
    trace = tracing.Trace({"/device:TPU:0": dev},
                          [("bench.window", 0, 10 * MS)])
    ctx = _ctx(window={"iterations": 1}, trace=trace,
               traffic={"updates_per_collect": 256})
    assert _reader("replay_ms")(ctx) is None
