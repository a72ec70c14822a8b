"""The reduction from a trace to the per-layer numbers, on hand-made
spans whose answers are counted by hand."""
import pytest

from tiny_cells import ROOT

from bench import harness, tracing

MS = 1e6  # nanoseconds in a millisecond


def test_union_length_merges_overlaps_and_clips():
    spans = [(0, 10), (5, 15), (20, 30), (25, 26), (40, 50)]
    assert tracing.union_length(spans, 0, 100) == 15 + 10 + 10
    assert tracing.union_length(spans, 8, 45) == 7 + 10 + 5
    assert tracing.union_length([], 0, 10) == 0


def test_gaps_are_the_uncovered_stretches():
    spans = [(5, 10), (8, 12), (20, 25)]
    assert tracing.gaps(spans, 0, 30) == [(0, 5), (12, 20), (25, 30)]
    assert tracing.gaps([(0, 30)], 0, 30) == []


def test_innermost_host_event_names_a_gap():
    host = [("bench.window", 0, 100), ("bench.call", 10, 90),
            ("PjitFunction(train_chunk)", 40, 60)]
    assert tracing.innermost(host, 50) == "PjitFunction(train_chunk)"
    assert tracing.innermost(host, 20) == "bench.call"
    assert tracing.innermost(host, 200) == "host: no event"


def _trace():
    """Two chips over a 10 ms window: chip 0 busy 6 ms (a 2 ms Pallas
    kernel twice, a 1 ms fusion twice), chip 1 busy 2 ms with an
    all-reduce; events outside the window do not count."""
    k = "custom-call.1"
    kernel_text = "jit(step)/pallas_call _gae_kernel"
    dev0 = [(k, 0 * MS, 2 * MS, kernel_text),
            ("fusion.3", 2 * MS, 3 * MS, "jit(step)/add"),
            (k, 5 * MS, 7 * MS, kernel_text),
            ("fusion.3", 7 * MS, 8 * MS, "jit(step)/add"),
            ("fusion.9", 12 * MS, 13 * MS, "")]
    dev1 = [("all-reduce.2", 1 * MS, 3 * MS, "all-reduce")]
    host = [("bench.window", 0, 10 * MS), ("bench.call", 0, 4 * MS),
            ("bench.call", 4 * MS, 10 * MS),
            ("PjitFunction(step)", 3 * MS, 4.5 * MS)]
    return tracing.Trace({"/device:TPU:0": dev0, "/device:TPU:1": dev1},
                         host)


def test_busy_idle_and_kernel_time():
    t = _trace()
    assert t.window_s == pytest.approx(0.010)
    assert t.busy_by_device() == {"/device:TPU:0": pytest.approx(0.006),
                                  "/device:TPU:1": pytest.approx(0.002)}
    assert t.busy_s == pytest.approx(0.004)
    assert t.kernel_seconds("_gae_kernel") == pytest.approx(0.004)
    assert t.kernel_seconds("_find_kernel") == 0.0
    assert t.op_seconds(lambda n, text: "all-reduce" in n) == \
        pytest.approx(0.002)


def test_breakdown_names_ops_and_gaps():
    b = _trace().breakdown()
    ops = dict(b["device_ops"])
    assert ops["custom-call.1"] == pytest.approx(0.002)   # 4 ms / 2 chips
    assert ops["fusion.3"] == pytest.approx(0.001)
    assert "fusion.9" not in ops                          # after the window
    gaps = b["idle_gaps"]
    assert [round(s * 1e3, 6) for _, s in gaps] == [2.0, 2.0]
    assert gaps[0][0] in ("PjitFunction(step)", "bench.call")
    assert len(b["device_ops"]) <= tracing.TOP
    assert len(gaps) <= tracing.TOP


def test_a_trace_without_the_window_span_is_refused():
    t = tracing.Trace({"/device:TPU:0": []}, [("other", 0, 1)])
    with pytest.raises(ValueError):
        _ = t.window


GATHER = ('%closed_call.299 = f32[256,14]{1,0:T(8,128)S(1)} custom-call('
          's32[256]{0:T(256)S(1)} %get-tuple-element.5685, '
          'f32[1048576,14]{1,0:T(8,128)} %get-tuple-element.6370), '
          'custom_call_target="tpu_custom_call", operand_layout_constraints='
          '{s32[256]{0}, f32[1048576,14]{1,0}}, '
          'frontend_attributes={kernel_metadata={}}')


def test_op_labels_and_pallas_operands_from_hlo_text():
    assert tracing.label(GATHER) == \
        "closed_call.299 custom-call:tpu_custom_call f32[256,14]"
    assert tracing.pallas_operands(GATHER) == [("s32", (256,)),
                                               ("f32", (1048576, 14))]
    fusion = "%fusion.3 = bf16[4096,64]{1,0:T(8,128)} fusion(%a), kind=kLoop"
    assert tracing.label(fusion) == "fusion.3 fusion bf16[4096,64]"
    assert tracing.pallas_operands(fusion) is None


def test_kernel_found_by_signature_where_the_trace_names_none():
    ms = 1e6
    t = tracing.Trace({"/device:TPU:0": [
        (GATHER, 0, 2 * ms, ""), ("%fusion.1 = f32[8] fusion(%a)", 2 * ms,
                                  3 * ms, "")]},
        [("bench.window", 0, 10 * ms)])
    gather = harness.load_module(ROOT / "bench" / "metrics"
                                 / "ring_gather_roofline.py").signature
    find = harness.load_module(ROOT / "bench" / "metrics"
                               / "sumtree_find_roofline.py").signature
    assert t.kernel_seconds("_gather_kernel", gather) == \
        pytest.approx(0.002)
    assert t.kernel_seconds("_find_kernel", find) == 0.0
    assert t.kernel_seconds("_gather_kernel") == 0.0
