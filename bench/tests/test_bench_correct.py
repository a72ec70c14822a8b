"""``correct`` on the CPU at a size a test run holds: a sound run of each
one-chip cell passes its limits, and the control (the reference computed
in bfloat16 in the system's place, followed by a reference that follows
the program as the program would be) fails them."""
import jax.numpy as jnp
import pytest

from tiny_cells import run, tiny_cell

from bench import harness, reflib

ONE_CHIP = ["ppo-fused-b4096", "ppo-sync-n10", "sac256-per-1m"]


@pytest.mark.parametrize("name", ONE_CHIP)
def test_sound_run_is_correct(name):
    out = run(tiny_cell(name))
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    assert out["failed"] == 0 and out["attempted"] > 0
    cell = tiny_cell(name)
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert "setup_s" in out["metrics"] and len(out["metrics"]) == 2
    assert set(out["checks"]) == set(cell.limits)


@pytest.mark.parametrize("name", ONE_CHIP)
def test_control_fails_the_limits(name):
    cell = tiny_cell(name)
    ref = cell.reference()
    seed = 2 ** 31 + 5
    control = ref.run(cell.config, cell.traffic, seed, 3,
                      dtype=jnp.bfloat16, precision="default")
    want = harness.run_reference(ref, cell.config, cell.traffic, seed,
                                 control)
    numbers = reflib.compare(control, want)
    assert any(numbers[k] > limit for k, limit in cell.limits.items()), \
        numbers
