"""Roofline share of GAE (``work/gae.py``) in its Pallas kernel
``_gae_kernel``, known in the trace (which names no kernel) also by its
operands: rewards, values and nonterminal flags as three f32 (T, B)
blocks, and the (1, B) bootstrap values."""
from bench import rooflines


def signature(operands):
    return (len(operands) == 4 and all(d == "f32" for d, _ in operands)
            and all(len(dims) == 2 for _, dims in operands)
            and operands[0][1] == operands[1][1] == operands[2][1]
            and operands[3][1][0] == 1)


def read(ctx):
    return rooflines.share(ctx, "gae", "_gae_kernel", signature)
