"""Roofline share of the batched cheetah env step (``work/env_step.py``)
in its Pallas kernel ``_cheetah_kernel``. The TPU trace names a custom
call ``closed_call.N`` without its kernel, so the kernel is also known by
its operands: twelve lane-major (rows, batch) blocks (state, actions,
reset candidates), two of them int32 step counters, the last the 14-row
reset observation."""
from bench import rooflines


def signature(operands):
    return (len(operands) == 12
            and all(len(dims) == 2 for _, dims in operands)
            and [d for d, _ in operands].count("s32") == 2
            and operands[-1][0] == "f32" and operands[-1][1][0] == 14)


def read(ctx):
    return rooflines.share(ctx, "env_step", "_cheetah_kernel", signature)
