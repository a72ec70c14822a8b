"""Work of one replay-ring gather: ``batch`` rows drawn by index from
every field of the ring. From the op's semantics it reads the indices
(int32) and each drawn row, and writes each row: a row holds obs (14),
action (6), reward (1), next obs (14) and discount (1), all f32.
"""

ROW_WORDS = 14 + 6 + 1 + 14 + 1


def work(config: dict, traffic: dict) -> dict:
    """``flops`` and ``bytes`` of one call, and ``calls`` per iteration."""
    batch = int(config["buffer_kwargs"]["batch_size"])
    return {"flops": 0,
            "bytes": batch * (4 + 2 * 4 * ROW_WORDS),
            "calls": int(traffic["updates_per_collect"])}
