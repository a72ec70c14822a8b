"""Dispatching public op for the env-step kernel family.

``env_step(name, ...)`` is the one batched, auto-reset-fused environment
step the env plane drives (``envs.base.auto_reset_batch`` via each env's
``batch_step`` closure). It accepts the reference layout — state leaves
batched on their leading ``(B,)`` axis, actions ``(B, act_dim)``, reset
candidates in the same layout — and selects the implementation through
``kernels.select`` (``impl=`` overrides per call):

* ref    — ``ref.<env>_step_batch_ref``: the envs' historical physics
  expressions batched + a single ``where`` over the batch. The CPU
  default, and bitwise-identical to ``vmap`` of the single-instance
  step under ``auto_reset``.
* pallas — the fused step+auto-reset kernel (``env_step_pallas``),
  interpret mode off-accelerator.

The kernels are float32-only (the envs' default dtype); experiments
running an env under another dtype fall back to the ref path so the
dispatcher never changes numerics, only scheduling.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp

from repro.kernels import select
from repro.kernels.env_step import env_step_pallas, ref

ENV_NAMES: Tuple[str, ...] = tuple(ref.STEP_BATCH_REF)


def env_step(name: str, state, actions, reset_state, reset_obs, *,
             dtype=jnp.float32, impl: Optional[str] = None, **params):
    """Fused batched physics step + auto-reset select for env ``name``.

    Returns ``(next_state, obs, rewards, dones)`` with the reset
    candidates substituted leafwise wherever ``dones`` is set (rewards
    stay the terminal transition's — the ``auto_reset`` contract).
    ``params`` are the env's static ``make`` kwargs (horizon, scales).
    """
    if name not in ref.STEP_BATCH_REF:
        raise KeyError(f"no env_step kernels for env {name!r}; "
                       f"choose from {sorted(ref.STEP_BATCH_REF)}")
    impl_name, interpret = select.resolve(impl, "env_step.step")
    if impl_name == "pallas" and jnp.dtype(dtype) == jnp.float32:
        return env_step_pallas.STEP_BATCH_PALLAS[name](
            state, actions, reset_state, reset_obs,
            interpret=interpret, **params)
    return ref.STEP_BATCH_REF[name](
        state, actions, reset_state, reset_obs, dtype=dtype, **params)
