"""Op work, model FLOPs, peaks and the roofline share, against counts
made by hand."""
import types

import pytest

from tiny_cells import ROOT

from bench import harness, rooflines, tracing

WORK = ROOT / "bench" / "work"


def _work(op):
    return harness.load_module(WORK / f"{op}.py")


def _cell(name):
    return harness.Cell(harness.load_json(ROOT / "BENCHMARK.json"), name,
                        ROOT)


def test_env_step_work_at_4096():
    cell = _cell("ppo-fused-b4096")
    w = _work("env_step").work(cell.config, cell.traffic)
    # read 15 state words + 6 actions + 15 reset words + 14 reset obs,
    # write 15 state words + 14 obs + 1 reward (4 bytes each), 1 done byte
    assert w["bytes"] == 4096 * (4 * (15 + 6 + 15 + 14 + 15 + 14 + 1) + 1)
    assert w["calls"] == 16 and w["flops"] > 0


def test_gae_work_at_16_by_4096():
    cell = _cell("ppo-fused-b4096")
    w = _work("gae").work(cell.config, cell.traffic)
    assert w["bytes"] == 16 * 4096 * (4 + 4 + 1 + 4 + 4) + 4 * 4096
    assert w["flops"] == 16 * 4096 * 9 and w["calls"] == 1
    sync = _cell("ppo-sync-n10")
    assert _work("gae").work(sync.config, sync.traffic)["bytes"] == \
        1000 * 20 * 17 + 4 * 20


def test_replay_work_at_2_20_rows():
    cell = _cell("sac256-per-1m")
    find = _work("sumtree_find").work(cell.config, cell.traffic)
    # 20 levels; per sample: mass in, one f32 node per level, index out
    assert find["bytes"] == 256 * (4 + 20 * 4 + 4)
    assert find["flops"] == 4 * 20 * 256 and find["calls"] == 256
    gather = _work("ring_gather").work(cell.config, cell.traffic)
    # 36 f32 per row (obs 14, act 6, reward, next obs 14, discount) read
    # and written, one int32 index each
    assert gather["bytes"] == 256 * (4 + 2 * 4 * 36)
    assert gather["calls"] == 256


def test_model_flops_by_hand():
    ppo = _cell("ppo-fused-b4096")
    pi = 2 * (14 * 64 + 64 * 64 + 64 * 6)
    vf = 2 * (14 * 64 + 64 * 64 + 64 * 1)
    xgrad = 2 * (64 * 64 + 64 * 6) + 2 * (64 * 64 + 64 * 1)
    want = pi + vf + vf / 16 + 4 * (2 * (pi + vf) + xgrad)
    assert _work("ppo").flops_per_env_step(ppo.config, ppo.traffic) == \
        pytest.approx(want)
    sac = _cell("sac256-per-1m")
    a = 2 * (14 * 256 + 256 * 256 + 256 * 12)
    q = 2 * (20 * 256 + 256 * 256 + 256 * 1)
    per_row = 3 * a + 10 * q + 2 * 2 * (256 * 256 + 256) \
        + 2 * (256 * 256 + 256 * 12)
    want = a + per_row * 256 * 256 / (64 * 4)
    assert _work("sac").flops_per_env_step(sac.config, sac.traffic) == \
        pytest.approx(want)


def test_peaks_table_and_unknown_device():
    p = rooflines.peaks(ROOT / "bench", "TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        rooflines.peaks(ROOT / "bench", "TPU v9 imaginary")


def test_roofline_share_and_silence():
    cell = _cell("ppo-fused-b4096")
    w = _work("gae").work(cell.config, cell.traffic)
    least = w["bytes"] / 819e9               # bytes bound this op
    ms = 1e6
    # 10 iterations, each one GAE call of 4x the least time
    ops = [("custom-call.7", i * ms, i * ms + 4 * least * 1e9,
            "_gae_kernel") for i in range(10)]
    trace = tracing.Trace({"/device:TPU:0": ops},
                          [("bench.window", 0, 20 * ms)])
    ctx = types.SimpleNamespace(cell=cell, trace=trace, bench=ROOT / "bench",
                                window={"iterations": 10},
                                device_kind="TPU v5 lite")
    assert rooflines.share(ctx, "gae", "_gae_kernel") == pytest.approx(25.0)
    assert rooflines.share(ctx, "gae", "_no_such_kernel") is None
    metric = harness.load_module(ROOT / "bench" / "metrics"
                                 / "gae_roofline.py")
    assert metric.read(ctx) == pytest.approx(25.0)
