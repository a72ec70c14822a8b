"""A reference that follows the program: the harness hands it exactly
the check calls' trajectories of a stepped cell, the window runs the
runner's own ``_collect``, and the cells whose reference is called as
before (fused, SAC) read the same numbers as before the hook, bit for
bit (``numbers-before-following.json``, recorded on the tree before it
at the seed it names)."""
import json

import jax
import numpy as np
import pytest

from tiny_cells import ROOT, run, tiny_cell

from bench import harness

BEFORE = json.loads(
    (ROOT / "bench" / "tests" / "numbers-before-following.json").read_text())


def test_reference_follows_the_check_calls(monkeypatch):
    from repro.core import orchestrator
    collected = []
    real_collect = orchestrator.SyncRunner._collect

    def recording(self):
        merged, stats = real_collect(self)
        collected.append(jax.device_get(merged))
        return merged, stats

    monkeypatch.setattr(orchestrator.SyncRunner, "_collect", recording)
    cell = tiny_cell("ppo-sync-n10")
    module = cell.reference()
    handed = {}
    real_run = module.run

    def run_spy(*args, **kwargs):
        handed["program"] = kwargs.get("program")
        return real_run(*args, **kwargs)

    monkeypatch.setattr(module, "run", run_spy)
    monkeypatch.setattr(cell, "reference", lambda: module)
    real_measure = harness.measure

    def measure(caller, seconds, annotate=None):
        handed["wrapped_in_window"] = "_collect" in vars(caller.runner)
        handed["collects_before_window"] = len(collected)
        return real_measure(caller, seconds, annotate)

    monkeypatch.setattr(harness, "measure", measure)
    out = run(cell)
    assert out["correct"], out["checks"]
    assert handed["wrapped_in_window"] is False
    assert handed["collects_before_window"] == harness.CHECK_STEPS
    assert len(collected) > harness.CHECK_STEPS
    program = handed["program"]
    assert len(program) == harness.CHECK_STEPS
    for got, want in zip(program, collected):
        assert set(got) == set(want)
        for key in want:
            np.testing.assert_array_equal(got[key], want[key])
    assert {"env_gap", "logp_gap", "value_gap"} <= set(out["numbers"])


@pytest.mark.parametrize("name", sorted(BEFORE["cells"]))
def test_unfollowed_cells_read_as_before(name):
    out = run(tiny_cell(name), BEFORE["seed"])
    assert out["numbers"] == BEFORE["cells"][name]["numbers"]
    assert out["checks"] == BEFORE["cells"][name]["checks"]


def test_fused_reference_reads_as_before():
    cell = tiny_cell("ppo-fused-b4096")
    got = cell.reference().run(cell.config, cell.traffic, BEFORE["seed"],
                               harness.CHECK_STEPS)
    assert got == BEFORE["fused_reference"]
