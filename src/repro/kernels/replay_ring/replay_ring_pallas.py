"""Fused replay-ring Pallas kernels for TPU.

The uniform ring's two hot paths as single kernel launches per storage
leaf (leaves are 2D ``(capacity, features)`` tiles; the ops layer
flattens trailing dims):

* ``ring_insert_pallas`` — scatter-insert N transitions at the write
  head with wraparound, rows streamed through VMEM in one launch instead
  of an XLA scatter per leaf. Sequential row writes make duplicate
  positions (N > capacity) resolve last-write-wins, matching the
  reference's in-order scatter. It holds the whole ring in VMEM, so the
  TPU compiler refuses it at 10^6 rows, and below that it moves the
  whole ring per insert; on TPU the reference runs instead
  (``kernels.select.TPU_REFUSED``).
* ``ring_gather_pallas`` — the stratified/uniform minibatch draw: one
  grid step per drawn row. The drawn indices are scalar-prefetched into
  SMEM, and the ring stays in HBM: each step's ``index_map`` reads the
  index and DMAs the one sublane-aligned block of rows that holds it
  (rows narrower than 128 lanes cannot be DMAed one at a time), and the
  kernel copies the row out of that block.

Both kernels only move bytes — no arithmetic — so parity with the
reference is exact for every dtype.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _insert_kernel(start_ref, storage_ref, batch_ref, out_ref, *,
                   cap: int, n: int):
    out_ref[...] = storage_ref[...]
    start = start_ref[0, 0]

    def write(j, _):
        pos = (start + j) % cap
        out_ref[pl.ds(pos, 1), :] = batch_ref[pl.ds(j, 1), :]
        return 0

    jax.lax.fori_loop(0, n, write, 0)


def _gather_kernel(idx_ref, block_ref, out_ref, *, rows: int):
    j = pl.program_id(0)
    out_ref[pl.ds(j % rows, 1), :] = block_ref[pl.ds(idx_ref[j] % rows, 1), :]


def _sublane_rows(dtype) -> int:
    """Rows in one (sublane x 128-lane) tile of ``dtype``: 8 for 32-bit,
    16 for 16-bit, 32 for 8-bit elements."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def ring_insert_pallas(storage: jnp.ndarray, batch: jnp.ndarray,
                       start: jnp.ndarray, *, interpret: bool = True
                       ) -> jnp.ndarray:
    """storage (cap, D), batch (n, D) same dtype, start scalar int ->
    updated storage."""
    cap, feat = storage.shape
    n = batch.shape[0]
    kernel = functools.partial(_insert_kernel, cap=cap, n=n)
    return pl.pallas_call(
        kernel,
        grid=(1,),
        in_specs=[pl.BlockSpec((1, 1), lambda i: (0, 0)),
                  pl.BlockSpec((cap, feat), lambda i: (0, 0)),
                  pl.BlockSpec((n, feat), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((cap, feat), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((cap, feat), storage.dtype),
        # the ring is the canonical donate-in-place buffer: alias storage
        # (operand 1) to the output so the update never doubles HBM
        input_output_aliases={1: 0},
        interpret=interpret,
    )(jnp.asarray(start, jnp.int32).reshape(1, 1), storage, batch)


def ring_gather_pallas(storage: jnp.ndarray, idx: jnp.ndarray, *,
                       interpret: bool = True) -> jnp.ndarray:
    """storage (cap, D), idx (B,) int32 -> rows (B, D)."""
    cap, feat = storage.shape
    B = idx.shape[0]
    rows = _sublane_rows(storage.dtype)
    bp = pl.cdiv(B, rows) * rows
    idx = jnp.pad(idx.astype(jnp.int32), (0, bp - B))
    out = pl.pallas_call(
        functools.partial(_gather_kernel, rows=rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bp,),
            in_specs=[pl.BlockSpec((rows, feat),
                                   lambda j, idx: (idx[j] // rows, 0))],
            out_specs=pl.BlockSpec((rows, feat),
                                   lambda j, idx: (j // rows, 0))),
        out_shape=jax.ShapeDtypeStruct((bp, feat), storage.dtype),
        interpret=interpret,
    )(idx, storage)
    return out[:B]
