"""Kernel-plane implementation selection: ``ref`` | ``pallas`` | ``auto``.

Every RL hot-loop kernel family (``gae``, ``sum_tree``, ``replay_ring``,
``env_step``) ships a pure-JAX reference and a Pallas kernel behind one
``ops.py`` dispatcher. Which implementation a dispatcher traces is
decided here:

* ``ref``    — always the pure-JAX oracle. The default resolution on
  CPU, and the implementation every bitwise guarantee in the test suite
  (``ppo`` × ``inline`` legacy identity, ``fused == stepped``) is stated
  against.
* ``pallas`` — always the Pallas kernel. On TPU the kernel compiles
  through Mosaic; on any other platform it runs in interpret mode (a
  correctness harness, not a timing one), so parity tests exercise the
  real kernel bodies on CPU CI.
* ``auto``   — ``pallas`` compiled on TPU, ``ref`` elsewhere, except the
  kernels listed in ``TPU_REFUSED``, which stay on ``ref`` on TPU too.

The selection table (platform × mode -> implementation):

    mode     cpu / gpu          tpu
    ref      ref                ref
    pallas   pallas+interpret   pallas compiled (TPU_REFUSED: error)
    auto     ref                pallas compiled (TPU_REFUSED: ref)

The mode is process-global and read at **trace time**: dispatchers
branch when a train step is traced, so already-jitted callables keep the
implementation they were traced with. Set it before building an
experiment (``ExperimentSpec.kernels`` does this in ``experiment.build``,
``launch/train.py`` exposes it as ``--kernels``), or override per call
with the dispatchers' ``impl=`` argument (how the parity tests and
benchmarks pin both sides).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax

MODES = ("ref", "pallas", "auto")

# the one platform whose Pallas lowering (Mosaic) the kernels are written
# for; they use TPU memory spaces (SMEM scalar prefetch, HBM refs)
COMPILED_PLATFORMS = ("tpu",)

# Kernels with no Pallas form that compiles on TPU at the replay sizes
# the system runs, keyed by ``<family>.<op>``, with the compiler's reason
# (checked for a described v5e chip). ``auto`` resolves them to
# ``ref`` on TPU (XLA's own scatter, in place under donation); ``pallas``
# on TPU raises with the reason. Both stay testable in interpret mode.
TPU_REFUSED = {
    "sum_tree.update": (
        "Mosaic refuses the pairwise parent rebuild: 'Shape mismatch in "
        "input, indices and output' (the child[:, 0::2] + child[:, 1::2] "
        "lane-strided slices), at every capacity"),
    "replay_ring.insert": (
        "the kernel holds the whole ring as one VMEM block: it compiles "
        "up to (131072, 14) f32 but asks for 512 MB at (10^6, 17) "
        "against 128 MiB of VMEM, and where it compiles it streams the "
        "whole ring through VMEM on every insert, where the reference "
        "updates n rows in place; a per-row HBM DMA of a ring narrower "
        "than 128 lanes is refused: 'Slice shape along dimension 1 must "
        "be aligned to tiling (128)'"),
}

_mode = "auto"


def set_kernel_mode(mode: str) -> str:
    """Set the process-global selection mode; returns the previous one."""
    global _mode
    if mode not in MODES:
        raise ValueError(f"unknown kernel mode {mode!r}; choose from {MODES}")
    prev, _mode = _mode, mode
    return prev


def kernel_mode() -> str:
    return _mode


def resolve(impl: Optional[str] = None,
            kernel: Optional[str] = None) -> Tuple[str, bool]:
    """Resolve a per-call override (or the global mode) for ``kernel``
    (``<family>.<op>``) to a concrete implementation: ``("ref", False)``
    or ``("pallas", interpret)``.

    ``interpret`` is True whenever the Pallas kernel would run on a
    platform with no native lowering — the interpreter executes the
    kernel body with real JAX ops, so the result is exact but the timing
    is meaningless. On TPU the kernels compile, and a kernel in
    ``TPU_REFUSED`` resolves to ``ref`` under ``auto`` and raises under
    ``pallas``.
    """
    mode = impl if impl is not None else _mode
    if mode not in MODES:
        raise ValueError(f"unknown kernel impl {mode!r}; choose from {MODES}")
    compiled = jax.default_backend() in COMPILED_PLATFORMS
    refused = compiled and kernel in TPU_REFUSED
    if mode == "auto":
        mode = "pallas" if compiled and not refused else "ref"
    if mode == "ref":
        return "ref", False
    if refused:
        raise ValueError(
            f"kernel {kernel!r} has no Pallas form that compiles on TPU "
            f"({TPU_REFUSED[kernel]}); use kernels='auto' or 'ref'")
    return "pallas", not compiled
