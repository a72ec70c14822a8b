"""Dispatching public ops for the replay-ring kernel family.

Dict-of-leaves layout, exactly as ``data/replay.py`` stores it: each
leaf is ``(capacity, ...)``. The pallas insert flattens trailing dims to
one feature axis per leaf and launches one fused kernel per leaf. The
pallas gather launches its row-block kernel on each ``(capacity, width)``
leaf only: reshaping any other leaf to that form is a relayout of the
whole leaf on TPU (a one-wide ``(capacity,)`` field becomes
``(capacity, 1)`` padded to 128 lanes, 128x its bytes, per draw), so
those leaves are gathered in place by XLA's own ``v[idx]``. The ref
path forwards to the oracle scatter/gather untouched, keeping the
CPU-default resolution bitwise-identical to the pre-plane behavior.
The ops run under the ``replay.insert`` / ``replay.gather`` scopes on
either path, so the device trace names them.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.kernels import select
from repro.kernels.replay_ring.ref import ring_gather_ref, ring_insert_ref
from repro.kernels.replay_ring.replay_ring_pallas import (
    ring_gather_pallas,
    ring_insert_pallas,
)


def _as2d(x: jnp.ndarray) -> jnp.ndarray:
    return x.reshape(x.shape[0], -1)


def ring_insert(storage: Dict[str, jnp.ndarray],
                batch: Dict[str, jnp.ndarray], start: jnp.ndarray, *,
                impl: Optional[str] = None) -> Dict[str, jnp.ndarray]:
    """Scatter-insert (N, ...) transitions at the ring head (wraps)."""
    name, interpret = select.resolve(impl, "replay_ring.insert")
    with jax.named_scope("replay.insert"):
        if name == "ref":
            return ring_insert_ref(storage, batch, start)
        return {
            k: ring_insert_pallas(_as2d(storage[k]),
                                  _as2d(batch[k]).astype(storage[k].dtype),
                                  start, interpret=interpret)
            .reshape(storage[k].shape)
            for k in storage
        }


def ring_gather(storage: Dict[str, jnp.ndarray], idx: jnp.ndarray, *,
                impl: Optional[str] = None) -> Dict[str, jnp.ndarray]:
    """Draw the rows at ``idx`` (B,) from every leaf: the Pallas kernel
    on ``(capacity, width)`` leaves, XLA's gather in place on the rest."""
    name, interpret = select.resolve(impl, "replay_ring.gather")
    with jax.named_scope("replay.gather"):
        if name == "ref":
            return ring_gather_ref(storage, idx)
        return {
            k: (ring_gather_pallas(v, idx, interpret=interpret)
                if v.ndim == 2 else v[idx])
            for k, v in storage.items()
        }
