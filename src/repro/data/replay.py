"""Uniform replay buffer (ring, preallocated, jittable) — DDPG substrate.

The scatter-insert and minibatch-gather hot paths dispatch through the
kernel plane (``repro.kernels.replay_ring``): with the ref selection —
the CPU default — they are the historical XLA scatter/gather bit for
bit. On TPU (``--kernels auto``) the insert stays XLA's scatter
(``kernels.select.TPU_REFUSED``) and the gather is one fused Pallas
launch per ``(capacity, width)`` leaf, with the other leaves (rewards,
discounts) gathered in place by XLA.
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.replay_ring import ring_gather, ring_insert


class ReplayState(NamedTuple):
    storage: Dict[str, jnp.ndarray]   # each (capacity, ...)
    index: jnp.ndarray                # next write slot
    size: jnp.ndarray                 # filled entries


def init_replay(capacity: int, example: Dict[str, jnp.ndarray]) -> ReplayState:
    storage = {k: jnp.zeros((capacity,) + v.shape[1:], v.dtype)
               for k, v in example.items()}
    return ReplayState(storage, jnp.zeros((), jnp.int32),
                       jnp.zeros((), jnp.int32))


def add_batch(state: ReplayState, batch: Dict[str, jnp.ndarray]
              ) -> ReplayState:
    """Insert (N, ...) transitions at the ring head (wraps around)."""
    cap = next(iter(state.storage.values())).shape[0]
    n = next(iter(batch.values())).shape[0]
    storage = ring_insert(state.storage, batch, state.index)
    return ReplayState(storage, (state.index + n) % cap,
                       jnp.minimum(state.size + n, cap))


def ensure_nonempty(state: ReplayState) -> None:
    """Eager form of the sampling invariant: callers must ``add_batch``
    before sampling (``size >= 1``). An empty ring used to silently yield
    zero-filled slot-0 transitions; outside a trace the violation now
    raises, and under jit the index clamp in ``sample_indices`` keeps
    draws in ``[0, max(size, 1))`` so the documented invariant is the
    only defense — the composed train step
    (``algos.api.make_train_step``) upholds it by always observing a
    trajectory before sampling."""
    if not isinstance(state.size, jax.core.Tracer) and int(state.size) == 0:
        raise ValueError(
            "sample() on an empty replay buffer — add_batch at least one "
            "transition first (an empty ring would yield zero-filled "
            "slot-0 transitions)")


def sample_indices(state: ReplayState, key, batch_size: int) -> jnp.ndarray:
    """Uniform slot indices over the filled prefix (guarded; the one
    index-draw both ``sample`` and the plane's uniform buffer use)."""
    ensure_nonempty(state)
    return jax.random.randint(key, (batch_size,), 0,
                              jnp.maximum(state.size, 1))


def sample(state: ReplayState, key, batch_size: int
           ) -> Dict[str, jnp.ndarray]:
    """Draw ``batch_size`` uniform transitions from the filled prefix."""
    idx = sample_indices(state, key, batch_size)
    return ring_gather(state.storage, idx)
