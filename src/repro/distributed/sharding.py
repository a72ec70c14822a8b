"""Logical-axis sharding rules with divisibility fallback.

Placement policy (MaxText-style 2-D sharding, adapted per DESIGN.md §5):

* weight matrices: contracting/input dim -> FSDP axes (``pod``+``data``),
  output dim -> ``model`` (Megatron column-parallel); the reverse for
  output projections (row-parallel), so weights are ~world-way sharded.
* batch dims of activations / trajectories -> ``pod``+``data`` (each data
  slice is one WALL-E sampler).
* decode KV caches: sequence dim -> ``model`` (flash-decoding: each model
  shard owns a KV slice; XLA's distributed softmax does the m/l combine).
* SSM states: d_inner -> ``model``.

Every placement goes through ``shard_axes`` which *falls back to
replication* (returns a smaller axis set or None) when the dim is not
divisible — never silent padding; the dry-run records the choice.
"""
from __future__ import annotations

import math
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# ------------------------------------------------------------------ axes
def fsdp_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    return fsdp_axes(mesh)


def axes_size(mesh: Mesh, axes: Sequence[str]) -> int:
    return math.prod(mesh.shape[a] for a in axes) if axes else 1


def shard_axes(size: int, axes: Sequence[str], mesh: Mesh
               ) -> Optional[Tuple[str, ...]]:
    """Largest prefix-reduced axis set that divides ``size`` (else None)."""
    axes = tuple(axes)
    candidates = [axes]
    if len(axes) > 1:
        candidates += [axes[1:], axes[:1]]
    for cand in candidates:
        n = axes_size(mesh, cand)
        if n > 1 and size % n == 0:
            return cand if len(cand) > 1 else cand[0]
    return None


# ------------------------------------------------------------ param specs
_COLUMN = {"wq", "wk", "wv", "w1", "w3", "in_proj", "x_proj", "dt_proj",
           "router"}
_ROW = {"wo", "w2", "out_proj"}
_MODEL_VEC = {"bq", "bk", "bv", "conv_b", "dt_bias", "D"}


def _param_spec(path, leaf, cfg, mesh: Mesh, mode: str = "train") -> P:
    """mode="train": FSDP x TP 2-D layout (optimizer state shards with it).
    mode="serve": the decode-fleet layout — contracting dim on `model` so
    single-token matmuls psum tiny activations instead of streaming weight
    shards (EXPERIMENTS.md §Perf, llama3-405b x decode_32k). A disaggregated
    deployment reshards the checkpoint once when loading the decode fleet.
    """
    names = [_key(p) for p in path]
    name = names[-1]
    shape = leaf.shape
    fs = fsdp_axes(mesh)
    in_layers = "layers" in names
    lead = (None,) if in_layers else ()
    dims = shape[1:] if in_layers else shape

    def fsdp(n):
        return shard_axes(n, fs, mesh)

    def model(n):
        return shard_axes(n, ("model",), mesh)

    col_in, col_out = (fsdp, model) if mode == "train" else (model, fsdp)
    row_in, row_out = (model, fsdp) if mode == "train" else (fsdp, model)

    if "embed" in names and name == "table":
        if mode == "serve":
            return P(fsdp(shape[0]), model(shape[1]))
        return P(model(shape[0]), fsdp(shape[1]))
    if "lm_head" in names and name == "w":
        if mode == "serve":
            return P(model(shape[0]), fsdp(shape[1]))
        return P(fsdp(shape[0]), model(shape[1]))
    if "value_head" in names or name == "scale" or name == "meta_tokens":
        return P()
    if name in _COLUMN and len(dims) == 2:
        return P(*lead, col_in(dims[0]), col_out(dims[1]))
    if name in _ROW and len(dims) == 2:
        return P(*lead, row_in(dims[0]), row_out(dims[1]))
    # MoE expert weights: 2-D sharded storage (D on fsdp, F on model); the
    # block explicitly re-gathers the D shards per layer so the expert
    # einsums run fully local (EXPERIMENTS.md §Perf, mixtral iteration 2).
    # Serve layout: contracting dim on `model` — decode psums tiny buffers.
    if name in ("w1", "w3") and len(dims) == 3:        # MoE (E, D, F)
        if mode == "serve":
            return P(*lead, None, model(dims[1]), fsdp(dims[2]))
        return P(*lead, None, fsdp(dims[1]), model(dims[2]))
    if name == "w2" and len(dims) == 3:                # MoE (E, F, D)
        if mode == "serve":
            return P(*lead, None, fsdp(dims[1]), model(dims[2]))
        return P(*lead, None, model(dims[1]), fsdp(dims[2]))
    if name == "conv_w":                               # (W, Di)
        return P(*lead, None, model(dims[1]))
    if name == "A_log":                                # (Di, N)
        return P(*lead, model(dims[0]), None)
    if name in _MODEL_VEC and len(dims) == 1:
        return P(*lead, model(dims[0]))
    # RL MLP dense weights (models/mlp_policy, ddpg/sac actor+critic
    # stacks): generic ``w`` is (in, out) with ``x @ w`` contraction, so
    # the contracting dim goes on the fsdp axes (ZeRO-3 storage layout);
    # 1-D biases / log_std stay replicated — they are tiny and the
    # per-layer all-gather schedule never pays for them.
    if name == "w" and len(dims) == 2:
        return P(*lead, col_in(dims[0]), col_out(dims[1]))
    if name == "b":                                    # generic bias
        return P(*lead, *([None] * len(dims)))
    return P(*lead, *([None] * len(dims)))


def _key(p) -> str:
    for attr in ("key", "name", "idx"):
        if hasattr(p, attr):
            return str(getattr(p, attr))
    return str(p)


def fsdp_leaf_dim(path, leaf, mesh: Mesh) -> Optional[int]:
    """Which dim of this leaf the train-mode ``_param_spec`` layout puts on
    the **full** fsdp axis product — the learner plane's FSDP storage rule.

    Returns the dim index, or None when the leaf stays replicated. Unlike
    raw ``_param_spec`` (whose ``shard_axes`` may fall back to a *subset*
    of the fsdp axes when only that subset divides the dim), the learner
    shards over all of ``("pod", "data")`` or not at all: a uniform shard
    count keeps the gather / reduce-scatter schedule identical for every
    sharded leaf, and partial-divisibility falls back to replicated
    exactly as the plain non-divisible case does.
    """
    fs = fsdp_axes(mesh)
    n = axes_size(mesh, fs)
    if n <= 1:
        return None
    spec = _param_spec(path, leaf, None, mesh, "train")
    full = fs if len(fs) > 1 else fs[0]
    for d, entry in enumerate(spec):
        if entry == full:
            return d
    return None


def param_specs(cfg, params_shape: Any, mesh: Mesh, mode: str = "train"):
    """PartitionSpec pytree matching ``init_params`` output.

    ``params_shape`` may be real params or ``jax.eval_shape`` structs.
    """
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: _param_spec(path, leaf, cfg, mesh, mode),
        params_shape)


# ------------------------------------------------------------ batch specs
def batch_spec(size: int, mesh: Mesh) -> P:
    return P(shard_axes(size, batch_axes(mesh), mesh))


def train_batch_specs(cfg, batch_shapes: dict, mesh: Mesh) -> dict:
    """Specs for the PPO train batch dict (tokens/targets/... (B,S))."""
    out = {}
    for k, v in batch_shapes.items():
        shape = v.shape
        if k == "positions" and len(shape) == 3:       # (3, B, S) M-RoPE
            out[k] = P(None, batch_spec(shape[1], mesh)[0], None)
        else:
            b = batch_spec(shape[0], mesh)[0]
            out[k] = P(b, *([None] * (len(shape) - 1)))
    return out


def decode_state_specs(cfg, state_shapes: dict, mesh: Mesh) -> dict:
    """Specs for the decode cache/state dict (flash-decoding layout)."""
    out = {}
    for k, v in state_shapes.items():
        shape = v.shape
        if k in ("k", "v"):            # (L, B, Sc, K, hd): seq -> model
            b = batch_spec(shape[1], mesh)[0]
            out[k] = P(None, b, shard_axes(shape[2], ("model",), mesh),
                       None, None)
        elif k == "conv":              # (L, B, W, Di)
            b = batch_spec(shape[1], mesh)[0]
            out[k] = P(None, b, None,
                       shard_axes(shape[3], ("model",), mesh))
        elif k == "ssm":               # (L, B, Di, N)
            b = batch_spec(shape[1], mesh)[0]
            out[k] = P(None, b, shard_axes(shape[2], ("model",), mesh),
                       None)
        else:                          # pos scalar / cache_pos (Sc,)
            out[k] = P(*([None] * len(shape)))
    return out


def to_shardings(mesh: Mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))
