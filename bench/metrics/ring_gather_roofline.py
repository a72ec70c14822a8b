"""Roofline share of the replay ring's minibatch gather
(``work/ring_gather.py``) in its Pallas kernel ``_gather_kernel``, one
launch per ring field, known in the trace (which names no kernel) also
by its operands: int32 row indices and one (capacity, width) field."""
from bench import rooflines


def signature(operands):
    return (len(operands) == 2 and operands[0][0] == "s32"
            and len(operands[0][1]) == 1 and len(operands[1][1]) == 2)


def read(ctx):
    return rooflines.share(ctx, "ring_gather", "_gather_kernel", signature)
