"""Model FLOPs of SAC per env-step trained, from the configuration's
widths: matrix multiplications only (2 m n k), nothing recomputed.

Per env-step the rollout runs the actor forward once. Each update of
``batch`` rows runs, per row: for the critic loss, the actor forward on
the next observation, both target critics and both critics forward, and
the critics' backward (weight gradients, input gradients of all layers
but the first); for the actor loss, the actor forward, both fresh
critics forward and backward to their action input (input gradients of
every layer), and the actor's backward (weight gradients, input
gradients of all layers but the first). Updates per env-step are
``updates_per_collect * batch`` rows over the ``env_batch * horizon``
steps collected.
"""


def _flops(sizes, skip_first=False):
    layers = list(zip(sizes[:-1], sizes[1:]))
    return sum(2 * i * o for i, o in layers[1 if skip_first else 0:])


def flops_per_env_step(config: dict, traffic: dict) -> float:
    h = int(config["model"]["hidden"])
    obs, act = 14, 6
    actor = [obs, h, h, 2 * act]
    critic = [obs + act, h, h, 1]
    a, q = _flops(actor), _flops(critic)
    per_row = (3 * a + 10 * q + 2 * _flops(critic, True)
               + _flops(actor, True))
    rows = (int(traffic["updates_per_collect"])
            * int(config["buffer_kwargs"]["batch_size"]))
    steps = int(traffic["env_batch"]) * int(traffic["horizon"])
    return a + per_row * rows / steps
