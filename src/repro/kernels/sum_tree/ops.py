"""Dispatching public ops for the sum-tree kernel family.

The state type stays the registry-visible ``SumTree`` (a tuple of
per-level arrays — the pytree every buffer carry already flows through);
the pallas path flattens it to the kernels' concatenated layout at the
call boundary and splits the result back. Selection follows
``kernels.select`` (``impl=`` overrides per call); the ref path forwards
to the oracles untouched, keeping the CPU default bitwise-identical to
the historical ``data/buffers.py`` descent.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.kernels import select
from repro.kernels.sum_tree.ref import (
    SumTree,
    sumtree_find_batch_ref,
    sumtree_update_ref,
)
from repro.kernels.sum_tree.sum_tree_pallas import (
    level_offsets,
    level_sizes,
    sumtree_find_pallas,
    sumtree_update_pallas,
)


def tree_flatten(tree: SumTree) -> jnp.ndarray:
    """Concatenate levels leaves-first into the kernels' flat layout."""
    return jnp.concatenate(list(tree.levels))


def tree_unflatten(flat: jnp.ndarray, capacity: int) -> SumTree:
    sizes = level_sizes(capacity)
    offsets = level_offsets(sizes)
    return SumTree(tuple(flat[off:off + size]
                         for off, size in zip(offsets, sizes)))


def sumtree_find_batch(tree: SumTree, masses: jnp.ndarray, *,
                       impl: Optional[str] = None) -> jnp.ndarray:
    """Stratified descent for a batch of masses -> leaf indices (B,)."""
    name, interpret = select.resolve(impl, "sum_tree.find")
    if name == "ref":
        return sumtree_find_batch_ref(tree, masses)
    capacity = tree.levels[0].shape[0]
    return sumtree_find_pallas(tree_flatten(tree), masses,
                               capacity=capacity, interpret=interpret)


def sumtree_update(tree: SumTree, idx: jnp.ndarray,
                   leaf_values: jnp.ndarray, *,
                   impl: Optional[str] = None) -> SumTree:
    """Batched leaf write-back + parent recomputation."""
    name, interpret = select.resolve(impl, "sum_tree.update")
    if name == "ref":
        return sumtree_update_ref(tree, idx, leaf_values)
    capacity = tree.levels[0].shape[0]
    flat = sumtree_update_pallas(
        tree_flatten(tree), jnp.atleast_1d(idx), jnp.atleast_1d(leaf_values),
        capacity=capacity, interpret=interpret)
    return tree_unflatten(flat, capacity)
