"""Dispatching public ops for the GAE kernel family.

``gae`` / ``discounted_returns`` accept the reference layout — time-major
``(T, ...)`` with an arbitrary batch shape — and select the
implementation through ``kernels.select`` (``impl=`` overrides per call).
The ref path forwards the original arrays untouched, so the CPU-default
resolution is the historical ``algos/gae.py`` recurrence bit for bit;
the pallas path flattens the batch dims to one lane axis for the kernel
and restores the caller's shape on the way out.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp

from repro.kernels import select
from repro.kernels.gae.gae_pallas import (
    discounted_returns_pallas,
    gae_pallas,
)
from repro.kernels.gae.ref import discounted_returns_ref, gae_ref


def _flatten_batch(x: jnp.ndarray) -> jnp.ndarray:
    """(T, ...) -> (T, prod(...)); a scalar batch becomes one column."""
    return x.reshape(x.shape[0], -1)


def gae(rewards: jnp.ndarray, values: jnp.ndarray, dones: jnp.ndarray,
        last_value: jnp.ndarray, gamma: float = 0.99, lam: float = 0.95,
        *, impl: Optional[str] = None
        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Advantages + returns; see ``ref.gae_ref`` for semantics."""
    name, interpret = select.resolve(impl, "gae.gae")
    if name == "ref":
        return gae_ref(rewards, values, dones, last_value, gamma, lam)
    nonterm = 1.0 - dones.astype(jnp.float32)
    adv, ret = gae_pallas(
        _flatten_batch(rewards), _flatten_batch(values),
        _flatten_batch(nonterm), last_value.reshape(-1),
        gamma=gamma, lam=lam, interpret=interpret)
    return adv.reshape(rewards.shape), ret.reshape(rewards.shape)


def discounted_returns(rewards: jnp.ndarray, dones: jnp.ndarray,
                       last_value: jnp.ndarray, gamma: float = 0.99,
                       *, impl: Optional[str] = None) -> jnp.ndarray:
    """Discounted returns-to-go; see ``ref.discounted_returns_ref``."""
    name, interpret = select.resolve(impl, "gae.discounted_returns")
    if name == "ref":
        return discounted_returns_ref(rewards, dones, last_value, gamma)
    nonterm = 1.0 - dones.astype(jnp.float32)
    ret = discounted_returns_pallas(
        _flatten_batch(rewards), _flatten_batch(nonterm),
        last_value.reshape(-1), gamma=gamma, interpret=interpret)
    return ret.reshape(rewards.shape)
