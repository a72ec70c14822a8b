"""A whole run on the CPU with the timed path broken underneath reports
``correct`` false: a step that returns its state unchanged, half of the
batch left out (the mean taken over the rest). The cells run on one
chip and exchange nothing between chips."""
import jax
import pytest

from tiny_cells import run, tiny_cell

ONE_CHIP = ["ppo-fused-b4096", "ppo-sync-n10", "sac256-per-1m"]


def _unchanged(monkeypatch):
    from repro import experiment
    real = experiment.make_train_step

    def make(algo, buffer):
        step = real(algo, buffer)

        def frozen(params, opt_state, plane, traj):
            _, _, plane, metrics = step(params, opt_state, plane, traj)
            return params, opt_state, plane, metrics
        return frozen

    monkeypatch.setattr(experiment, "make_train_step", make)


def _half_batch(monkeypatch):
    from repro.data import buffers

    def halve_trajectory(self, state, key):
        return {k: v[: v.shape[0] // 2] if k == "last_value"
                else v[:, : v.shape[1] // 2] for k, v in state.items()}

    real = buffers.PrioritizedBuffer.sample

    def halve_draw(self, state, key):
        return jax.tree.map(lambda x: x[: x.shape[0] // 2],
                            real(self, state, key))

    monkeypatch.setattr(buffers.FifoBuffer, "sample", halve_trajectory)
    monkeypatch.setattr(buffers.PrioritizedBuffer, "sample", halve_draw)


FAULTS = {"unchanged": _unchanged, "half_batch": _half_batch}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("name", ONE_CHIP)
def test_fault_makes_correct_false(name, fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    out = run(tiny_cell(name))
    assert out["correct"] is False, out["checks"]
