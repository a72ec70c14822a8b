"""Work of one GAE call over the iteration's ``(T, B)`` trajectory, from
the op's semantics: it reads rewards and values (f32), done flags
(1 byte) and the bootstrap values (``B`` f32), and writes advantages and
returns (f32). Per element the recurrence is ``nonterm = 1 - done``,
``delta = r + gamma * v_next * nonterm - v`` (4), ``adv = delta +
(gamma lambda) * nonterm * adv_next`` (3), ``ret = adv + v`` (1).
"""

FLOPS_PER_ELEMENT = 1 + 4 + 3 + 1
BYTES_PER_ELEMENT = 4 + 4 + 1 + 4 + 4


def work(config: dict, traffic: dict) -> dict:
    """``flops`` and ``bytes`` of one call, and ``calls`` per iteration."""
    batch = int(traffic.get("env_batch") or traffic["global_batch"])
    elements = int(traffic["horizon"]) * batch
    return {"flops": FLOPS_PER_ELEMENT * elements,
            "bytes": BYTES_PER_ELEMENT * elements + 4 * batch,
            "calls": 1}
