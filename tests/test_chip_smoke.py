"""``chip_smoke.py``'s phases on CPU at a tiny size.

The script itself refuses any platform but TPU; these tests call its
phase functions directly, with small sizes and the Pallas kernels in
interpret mode, so the wiring, the checks and the records are exercised
on every CI run.
"""
import importlib.util
import json
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
_spec = importlib.util.spec_from_file_location("chip_smoke",
                                               ROOT / "chip_smoke.py")
chip_smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(chip_smoke)


def _passed(rec, phase):
    assert rec["phase"] == phase
    assert rec["platform"] == "cpu" and rec["device_kind"]
    assert rec["checks"] and all(c["ok"] for c in rec["checks"])
    for key in ("compile_s_cold", "compile_s_warm", "run_s"):
        assert rec[key] >= 0.0


@pytest.fixture(scope="module")
def ckpt_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("paper_ckpt")
    _passed(chip_smoke.phase_paper(d, num_samplers=2, horizon=8,
                                   iterations=2), "paper")
    return d


def test_paper_phase_trains_and_checkpoints(ckpt_dir):
    assert any(p.name.startswith("ckpt_") for p in ckpt_dir.iterdir())


def test_env_plane_phase_with_interpreted_kernels():
    _passed(chip_smoke.phase_env_plane(env_batch=32, horizon=4,
                                       iterations=2, kernels="pallas"),
            "env_plane")


def test_off_policy_phase_with_interpreted_kernels():
    rec = chip_smoke.phase_off_policy(capacity=256, batch_size=16,
                                      global_batch=2, horizon=16,
                                      iterations=2, kernels="pallas")
    _passed(rec, "off_policy")
    assert rec["resolved"]["sum_tree.find"] == ["pallas", True]


def test_actors_phase_workers_report_cpu():
    _passed(chip_smoke.phase_actors(workers=2, global_batch=2, horizon=8,
                                    iterations=2), "actors")


def test_serve_phase_answers_every_request(ckpt_dir):
    _passed(chip_smoke.phase_serve(ckpt_dir, slots=4, requests=12,
                                   clients=3), "serve")


def test_learner_d4_phase_on_four_host_devices():
    """The four-chip phase on four forced host devices (a subprocess: the
    device count is fixed before JAX initialises)."""
    script = (
        "import json, sys\n"
        f"sys.path.insert(0, {str(ROOT)!r})\n"
        "import chip_smoke\n"
        "print('REC ' + json.dumps(chip_smoke.phase_learner_d4(\n"
        "    num_samplers=4, global_batch=8, horizon=16, iterations=2)))\n")
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", script], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    rec = json.loads(r.stdout.split("REC ", 1)[1])
    _passed(rec, "learner_d4")
    assert rec["checks"][1]["value"] == [0, 1, 2, 3]
    assert rec["gap_default_precision"] <= chip_smoke.LEARNER_TOL


def test_failed_check_raises():
    ph = chip_smoke.Phase("demo")
    with pytest.raises(chip_smoke.SmokeFailure, match="demo: gap failed"):
        ph.require("gap", False, tol={"atol": 1e-6}, value=1.0)


def test_main_refuses_cpu_without_ok_line(capsys):
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out and out.strip() == ""
