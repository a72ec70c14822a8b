"""Building blocks the plain references share, and the comparison that
decides ``correct``.

Nothing here imports the system under test. The references build their
parameters and optimizer states in the same pytree layout as the system
(a dict of MLP layer lists, Adam's ``(step, mu, nu)``), so one function
reads a leaf by its tree path on either side.
"""
from __future__ import annotations

import math
from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8


# ----------------------------------------------------------------- layers
def mlp_init(key, sizes, dtype=jnp.float32):
    """Truncated-normal fan-in weights, zero biases, one key per layer."""
    keys = jax.random.split(key, len(sizes) - 1)
    return [{"w": (jax.random.truncated_normal(k, -3.0, 3.0, (i, o),
                                               jnp.float32)
                   * i ** -0.5).astype(dtype),
             "b": jnp.zeros((o,), dtype)}
            for k, i, o in zip(keys, sizes[:-1], sizes[1:])]


def mlp_apply(net, x, precision):
    """tanh between layers, none after the last."""
    for i, layer in enumerate(net):
        x = jnp.matmul(x, layer["w"], precision=precision) + layer["b"]
        if i < len(net) - 1:
            x = jnp.tanh(x)
    return x


def gaussian_logp(mean, std, x):
    z = (x - mean) / std
    return jnp.sum(-0.5 * z ** 2 - jnp.log(std)
                   - 0.5 * math.log(2 * math.pi), axis=-1)


# -------------------------------------------------------------- optimizer
class AdamState(NamedTuple):
    step: jnp.ndarray
    mu: object
    nu: object


def adam_init(params) -> AdamState:
    zeros = jax.tree.map(jnp.zeros_like, params)
    return AdamState(jnp.zeros((), jnp.int32), zeros,
                     jax.tree.map(jnp.zeros_like, params))


def adam_step(grads, state: AdamState, params, lr):
    """One Adam step (no weight decay): ``(params', state')``."""
    step = state.step + 1
    t = step.astype(jnp.float32)
    bc1 = 1.0 - ADAM_B1 ** t
    bc2 = 1.0 - ADAM_B2 ** t
    mu = jax.tree.map(lambda m, g: ADAM_B1 * m + (1 - ADAM_B1) * g,
                      state.mu, grads)
    nu = jax.tree.map(lambda v, g: ADAM_B2 * v + (1 - ADAM_B2) * g * g,
                      state.nu, grads)
    params = jax.tree.map(
        lambda p, m, v: p - lr * ((m / bc1) / (jnp.sqrt(v / bc2)
                                               + ADAM_EPS)).astype(p.dtype),
        params, mu, nu)
    return params, AdamState(step, mu, nu)


def clip_by_global_norm(grads, max_norm):
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, max_norm / (norm + 1e-9))
    return jax.tree.map(lambda g: (g.astype(jnp.float32) * scale
                                   ).astype(g.dtype), grads)


# ------------------------------------------------------- reading leaves
def leaves(tree, prefix: str = "") -> Dict[str, np.ndarray]:
    """``{path: float64 array}`` for every leaf of ``tree``."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = prefix + "".join(
            f"/{getattr(k, 'key', getattr(k, 'idx', getattr(k, 'name', k)))}"
            for k in path)
        out[name] = np.asarray(jax.device_get(leaf), np.float64)
    return out


def rms_grad_norms(states: Dict[str, AdamState]) -> Dict[str, float]:
    """Per leaf, the norm of the gradient as Adam saw it, worked out from
    its state: ``sqrt(nu / (1 - b2^t))``. After one update that is the
    gradient's magnitude exactly; after ``t`` updates it is their root
    mean square (b2 = 0.999 weighs them almost alike)."""
    out = {}
    for prefix, st in states.items():
        t = int(jax.device_get(st.step))
        bc2 = 1.0 - ADAM_B2 ** max(t, 1)
        for name, nu in leaves(st.nu, prefix).items():
            out[name] = float(np.linalg.norm(np.sqrt(np.maximum(nu, 0.0)
                                                     / bc2)))
    return out


def change_norms(before: Dict[str, np.ndarray],
                 after: Dict[str, np.ndarray]) -> Dict[str, float]:
    return {k: float(np.linalg.norm(after[k] - before[k])) for k in before}


# ------------------------------------------------------------ comparison
# a leaf whose reference gradient is under this share of the median
# leaf's moves under Adam by round-off alone, and is left out of the
# parameter-change number
STILL_LEAF_SHARE = 1e-3


def _leaf_gaps(prog: Dict[str, float], ref: Dict[str, float],
               names) -> np.ndarray:
    """Each leaf's ``|p - r|`` over the larger of its reference norm and
    the median leaf's."""
    names = list(names)
    median = float(np.median([ref[n] for n in names]))
    return np.array([abs(prog[n] - ref[n]) / max(ref[n], median, 1e-30)
                     for n in names])


def scaled_gap(prog, ref) -> float:
    """The worst ``|p - r|`` over the larger of ``|r|`` and the median
    ``|r|``. A quantity that passes through zero (a joint angle, a
    velocity, a value near a sign change) would turn round-off into a
    huge gap against its own size; against its typical size it reads as
    round-off, while a gap that matters still reads near 1."""
    p = np.asarray(prog, np.float64)
    r = np.asarray(ref, np.float64)
    scale = np.maximum(np.abs(r), max(float(np.median(np.abs(r))), 1e-30))
    return float(np.max(np.abs(p - r) / scale))


def compare(prog: dict, ref: dict) -> Dict[str, float]:
    """The numbers ``correct`` can be decided on, each a relative gap
    (a cell's limits file names the ones it compares):

    * ``loss_gap``: the worst of the steps' losses, ``|p - r| / |r|``;
      ``loss_gap_first``: the first step's;
    * ``grad_gap``: the worst leaf's gradient norm after the first step,
      against the larger of that leaf's reference norm and the median
      leaf's; ``grad_gap_median``: the median leaf's;
    * ``change_gap``, ``change_gap_median``: the same for each leaf's
      change over the steps, over the leaves the reference's gradient
      moves;
    * whatever a reference that follows the program checked step by step
      itself (``ref["numbers"]``; ``harness`` states the rule), passed
      through as it is.
    """
    losses = [abs(p - r) / max(abs(r), 1e-30)
              for p, r in zip(prog["losses"], ref["losses"])]
    grad = _leaf_gaps(prog["grad"], ref["grad"], ref["grad"])
    median_grad = float(np.median(list(ref["grad"].values())))
    moving = [n for n in ref["change"]
              if ref["grad"].get(n, median_grad)
              >= STILL_LEAF_SHARE * median_grad]
    change = _leaf_gaps(prog["change"], ref["change"], moving)
    return {"loss_gap": float(max(losses)),
            "loss_gap_first": float(losses[0]),
            "grad_gap": float(grad.max()),
            "grad_gap_median": float(np.median(grad)),
            "change_gap": float(change.max()),
            "change_gap_median": float(np.median(change)),
            **ref.get("numbers", {})}
