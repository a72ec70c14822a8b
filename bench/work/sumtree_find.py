"""Work of one batched sum-tree descent: ``batch`` masses walk from the
root to a leaf of a tree over ``capacity`` leaves (``log2(capacity)``
levels). From the op's semantics each sample reads its mass and one
node per level (the left child, f32), and writes one leaf index (int32);
per level it compares, subtracts, selects the mass and the index, and
doubles the index (4 operations).
"""


def capacity(config: dict) -> int:
    return 1 << (int(config["buffer_kwargs"]["capacity"]) - 1).bit_length()


def work(config: dict, traffic: dict) -> dict:
    """``flops`` and ``bytes`` of one call, and ``calls`` per iteration."""
    levels = capacity(config).bit_length() - 1
    batch = int(config["buffer_kwargs"]["batch_size"])
    return {"flops": 4 * levels * batch,
            "bytes": batch * (4 + 4 * levels + 4),
            "calls": int(traffic["updates_per_collect"])}
