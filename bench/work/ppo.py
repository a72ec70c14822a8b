"""Model FLOPs of PPO per env-step trained, from the configuration's
widths: matrix multiplications only (2 m n k), nothing recomputed.

Per env-step the rollout runs the policy and value nets forward once,
and the value net once more per instance at the end of the horizon for
the bootstrap. The learner sees each row in ``epochs`` minibatch steps:
each runs both nets forward, then backward: every layer's weight
gradient, and the input gradient of every layer but the first (nothing
needs the gradient of the observation).
"""


def _layers(sizes):
    return list(zip(sizes[:-1], sizes[1:]))


def flops_per_env_step(config: dict, traffic: dict) -> float:
    hidden = int(config["model"]["hidden"])
    obs, act = 14, 6
    nets = [[obs, hidden, hidden, act], [obs, hidden, hidden, 1]]
    forward = sum(2 * i * o for s in nets for i, o in _layers(s))
    value = sum(2 * i * o for i, o in _layers(nets[1]))
    input_grads = sum(2 * i * o for s in nets for i, o in _layers(s)[1:])
    epochs = int(config["algo_kwargs"]["epochs"])
    rollout = forward + value / int(traffic["horizon"])
    return rollout + epochs * (2 * forward + input_grads)
