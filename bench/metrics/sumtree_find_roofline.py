"""Roofline share of the prioritized replay's sum-tree descent
(``work/sumtree_find.py``) in its Pallas kernel ``_find_kernel``, known
in the trace (which names no kernel) also by its operands: the f32
masses and the flat f32 tree, both one-dimensional."""
from bench import rooflines


def signature(operands):
    return (len(operands) == 2
            and all(d == "f32" and len(dims) == 1 for d, dims in operands))


def read(ctx):
    return rooflines.share(ctx, "sumtree_find", "_find_kernel", signature)
