"""Work of one batched cheetah ``env_step`` (physics step + auto-reset
select) at the cell's env batch, from the op's semantics: what it must
read and write, whatever implements it.

Per instance it reads the state (6 angles, 6 velocities, body velocity,
pitch as f32, the step counter as int32: 15 words), 6 actions, and the
reset candidates (15 state words, 14 observation values); it writes the
next state (15 words), the observation (14), the reward (f32) and the
done flag (1 byte).
"""

STATE_WORDS = 15
OBS = 14
ACT = 6

BYTES_PER_INSTANCE = 4 * (STATE_WORDS + ACT + STATE_WORDS + OBS
                          + STATE_WORDS + OBS + 1) + 1

# arithmetic per instance, counted from the equations: clip (2 x 6),
# neighbour coupling (2 x 6), joint velocity update (8 x 6), angle update
# (2 x 6), thrust (4 x 5 terms, a 5-term mean: 6), body velocity (4),
# pitch (a 6-term mean: 7, then 3), step count (1), reward (6 squares,
# 5 adds, 2), done test (1), and the reset select (29 values)
FLOPS_PER_INSTANCE = (12 + 12 + 48 + 12 + 26 + 4 + 10 + 1 + 13 + 1 + 29)


def work(config: dict, traffic: dict) -> dict:
    """``flops`` and ``bytes`` of one call, and ``calls`` per iteration."""
    batch = int(traffic["env_batch"])
    return {"flops": FLOPS_PER_INSTANCE * batch,
            "bytes": BYTES_PER_INSTANCE * batch,
            "calls": int(traffic["horizon"])}
