"""Roofline share of one op from its semantic work and its kernel's
device time: the least time the chip could take for the op's FLOPs and
bytes (the larger of FLOPs over peak FLOP/s and bytes over peak HBM
bandwidth), over the time the kernel took, summed over every chip."""
from __future__ import annotations

import json
from typing import Optional


def peaks(bench, device_kind: str) -> dict:
    """The peak row for ``device_kind``; an unknown device is an error."""
    with open(bench / "peaks.json") as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (known: {sorted(table)})")
    return table[device_kind]


def share(ctx, op: str, kernel: str, signature=None) -> Optional[float]:
    """Percent of the roofline the op's kernel reached over the traced
    window, or None where the trace shows no such kernel. The kernel is
    found by its name, or, where the trace does not carry names, by its
    operands (``signature``, see ``tracing.Trace.kernel_seconds``)."""
    from bench import harness
    seconds = ctx.trace.kernel_seconds(kernel, signature)
    if seconds <= 0.0:
        return None
    work = harness.load_module(ctx.bench / "work" / f"{op}.py").work(
        ctx.cell.config, ctx.cell.traffic)
    peak = peaks(ctx.bench, ctx.device_kind)
    least = max(work["flops"] / peak["bf16_flops_per_s"],
                work["bytes"] / peak["hbm_bytes_per_s"])
    calls = work["calls"] * ctx.window["iterations"]
    return 100.0 * least * calls / seconds
