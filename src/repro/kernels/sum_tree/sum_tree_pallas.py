"""Fused sum-tree Pallas kernels for TPU.

Prioritized replay's two hot paths, each as one kernel launch over a
*flat* tree layout (all levels concatenated leaves-first — offsets are
static, derived from the capacity):

* ``sumtree_find_pallas``  — the full stratified root-to-leaf descent
  for a batch of B masses. The tree stays in HBM, so capacity is not
  capped by VMEM; masses and the walked indices live in SMEM (scalar
  prefetch). Each level, a group of samples DMAs the 1024-element run
  of the flat tree holding its left child into SMEM (1024 elements is
  the smallest slice a DMA may take of a 1-D f32 array in HBM) and
  steps down, so the launch does B·log2(cap) dependent scalar steps
  with the DMAs of a group in flight together.
* ``sumtree_update_pallas`` — the batched priority write-back: a
  sequential last-write-wins leaf scatter (matching XLA's in-order
  ``.at[idx].set`` semantics under duplicates) followed by a pairwise
  rebuild of every parent level while the leaves are still in VMEM.
  Assumes the input tree is consistent (every parent the pairwise sum of
  its children — guaranteed by construction), in which case the rebuild
  is bitwise-identical to the reference's touched-path recomputation.
  The TPU compiler refuses its lane-strided slices, so it runs only in
  interpret mode (``kernels.select.TPU_REFUSED``).

Both kernels evaluate the reference expressions exactly, so parity tests
assert equality, not closeness.
"""
from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# a 1-D f32 array in HBM is tiled in runs of 1024 elements: the
# smallest slice a DMA may take of the flat tree
_CHUNK = 1024
# samples whose level reads are in flight together
_GROUP = 8


def level_sizes(capacity: int) -> Tuple[int, ...]:
    """Static per-level lengths of the flat layout, leaves first."""
    if capacity & (capacity - 1):
        raise ValueError(f"sum-tree capacity must be a power of two, "
                         f"got {capacity}")
    sizes = []
    n = capacity
    while n >= 1:
        sizes.append(n)
        if n == 1:
            break
        n //= 2
    return tuple(sizes)


def level_offsets(sizes: Sequence[int]) -> Tuple[int, ...]:
    offs, off = [], 0
    for s in sizes:
        offs.append(off)
        off += s
    return tuple(offs)


def _find_kernel(m_ref, flat_hbm, idx_ref, mass_ref, buf_ref, sem, *,
                 offsets, groups: int):
    def init(j, _):
        idx_ref[j] = jnp.int32(0)
        mass_ref[j] = m_ref[j]
        return 0

    jax.lax.fori_loop(0, groups * _GROUP, init, 0)

    def chunk_copy(s, base):
        return pltpu.make_async_copy(
            flat_hbm.at[pl.ds(base, _CHUNK)],
            buf_ref.at[pl.ds(s * _CHUNK, _CHUNK)], sem)

    def descend(g, _):
        for off in offsets[-2::-1]:          # root's children -> leaves
            for s in range(_GROUP):
                pos = off + 2 * idx_ref[g * _GROUP + s]
                chunk_copy(s, pl.multiple_of(pos // _CHUNK * _CHUNK,
                                             _CHUNK)).start()
            for s in range(_GROUP):
                chunk_copy(s, 0).wait()
            for s in range(_GROUP):
                j = g * _GROUP + s
                idx = 2 * idx_ref[j]
                left = buf_ref[s * _CHUNK + (off + idx) % _CHUNK]
                mass = mass_ref[j]
                go_right = mass >= left
                mass_ref[j] = jnp.where(go_right, mass - left, mass)
                idx_ref[j] = jnp.where(go_right, idx + 1, idx)
        return 0

    jax.lax.fori_loop(0, groups, descend, 0)


def _update_kernel(flat_ref, idx_ref, vals_ref, out_ref, *, sizes, offsets,
                   batch: int):
    cap = sizes[0]
    pos = jax.lax.broadcasted_iota(jnp.int32, (1, cap), 1)
    leaves = flat_ref[:, 0:cap]

    def write(j, leaves):
        return jnp.where(pos == idx_ref[0, j], vals_ref[0, j], leaves)

    leaves = jax.lax.fori_loop(0, batch, write, leaves)
    out_ref[:, 0:cap] = leaves
    child = leaves
    for k in range(1, len(sizes)):
        child = child[:, 0::2] + child[:, 1::2]
        out_ref[:, offsets[k]:offsets[k] + sizes[k]] = child


def sumtree_find_pallas(flat: jnp.ndarray, masses: jnp.ndarray, *,
                        capacity: int, interpret: bool = True
                        ) -> jnp.ndarray:
    """flat (2*cap-1,) f32 (leaves-first levels), masses (B,) f32
    -> leaf indices (B,) int32."""
    offsets = level_offsets(level_sizes(capacity))
    (total,) = flat.shape
    B = masses.shape[0]
    groups = pl.cdiv(B, _GROUP)
    bp = groups * _GROUP
    flat = jnp.pad(flat, (0, pl.cdiv(total, _CHUNK) * _CHUNK - total))
    masses = jnp.pad(masses.astype(jnp.float32), (0, bp - B))
    kernel = functools.partial(_find_kernel, offsets=offsets, groups=groups)
    idx = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
            scratch_shapes=[pltpu.SMEM((bp,), jnp.float32),
                            pltpu.SMEM((_GROUP * _CHUNK,), jnp.float32),
                            pltpu.SemaphoreType.DMA(())]),
        out_shape=jax.ShapeDtypeStruct((bp,), jnp.int32),
        interpret=interpret,
    )(masses, flat)
    return idx[:B]


def sumtree_update_pallas(flat: jnp.ndarray, idx: jnp.ndarray,
                          leaf_values: jnp.ndarray, *, capacity: int,
                          interpret: bool = True) -> jnp.ndarray:
    """flat (2*cap-1,) f32, idx (B,) int32, leaf_values (B,) f32
    -> updated flat tree."""
    sizes = level_sizes(capacity)
    offsets = level_offsets(sizes)
    (total,) = flat.shape
    B = idx.shape[0]
    kernel = functools.partial(_update_kernel, sizes=sizes,
                               offsets=offsets, batch=B)
    out = pl.pallas_call(
        kernel,
        grid=(1,),
        in_specs=[pl.BlockSpec((1, total), lambda i: (0, 0)),
                  pl.BlockSpec((1, B), lambda i: (0, 0)),
                  pl.BlockSpec((1, B), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((1, total), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, total), jnp.float32),
        interpret=interpret,
    )(flat[None, :], idx[None, :].astype(jnp.int32),
      leaf_values[None, :].astype(jnp.float32))
    return out[0]
