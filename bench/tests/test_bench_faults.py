"""A whole run on the CPU with the timed path broken underneath reports
``correct`` false: a step that returns its state unchanged, half of the
batch left out (the mean taken over the rest). The cells run on one
chip and exchange nothing between chips. In the stepped cell, whose
reference follows the program's draws, also: the behaviour log-prob
computed with a wrong log-std, one cheetah physics term (the
neighbour coupling) dropped from the env step, and GAE with gamma and
lambda swapped."""
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


def _logp_wrong_std(monkeypatch):
    from repro.models import mlp_policy

    def sample_action(params, obs, key):
        mean, std = mlp_policy.policy_dist(params, obs)
        action = mean + std * jax.random.normal(key, mean.shape)
        return action, mlp_policy.gaussian_logp(mean, std * 1.1, action)

    monkeypatch.setattr(mlp_policy, "sample_action", sample_action)


def _physics_term_dropped(monkeypatch):
    from repro.kernels.env_step import ref
    monkeypatch.setattr(ref, "CHEETAH_COUPLING", 0.0)


def _gae_swapped(monkeypatch):
    from repro.algos import gae
    real = gae.gae

    def swapped(rewards, values, dones, last_value, gamma=0.99, lam=0.95,
                **kw):
        return real(rewards, values, dones, last_value, lam, gamma, **kw)

    monkeypatch.setattr(gae, "gae", swapped)


FAULTS = {"unchanged": _unchanged, "half_batch": _half_batch,
          "logp_wrong_std": _logp_wrong_std,
          "physics_term_dropped": _physics_term_dropped,
          "gae_swapped": _gae_swapped}
CASES = [(name, fault) for name in ONE_CHIP
         for fault in ("half_batch", "unchanged")] + [
    ("ppo-sync-n10", fault)
    for fault in ("gae_swapped", "logp_wrong_std", "physics_term_dropped")]


@pytest.mark.parametrize("name,fault", CASES)
def test_fault_makes_correct_false(name, fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    out = run(tiny_cell(name))
    assert out["correct"] is False, out["checks"]
