"""The multi-device learner plane: shard_map data-parallel training.

``ShardedLearner`` wraps any shardable ``Algorithm``'s composed train
step (``algos.api.make_train_step``) in ``jax.shard_map`` over a
learner mesh:

* trajectories / replay minibatches shard along the mesh's batch axes
  (``pod``+``data`` — each data slice consumes one collection slice);
* by default params and optimizer state stay **replicated**: every
  gradient inside the step is pmean'd across shards by the ``grad_sync``
  context, so the (identical) clip + optimizer update is recomputed per
  shard and replication is preserved without a post-step broadcast — one
  psum all-reduce per loss is the entire collective schedule;
* with ``fsdp=True`` params and Adam moments are instead **stored
  sharded** along the fsdp axes per the ``_param_spec`` layout rules
  (``sharding.fsdp_leaf_dim`` — weight contracting dims on
  ``pod``+``data``, non-divisible leaves replicated): the body
  all-gathers param leaves per layer at entry, ``grad_sync``
  reduce-scatters each sharded leaf's gradient into storage layout,
  moments update fully locally, and the body exit slices params back to
  their shards (DESIGN.md §11);
* ``pods > 1`` splits the shard count over a ``(pod, data, model)`` mesh
  — the same axis names as ``launch.mesh.make_production_mesh``'s
  multi-pod mesh, so the identical step lowers across the DCN boundary;
* buffer state rides the plane sharded (``replay_sharded``): per-shard
  rings / sum-trees with a psum'd global root, so off-policy algorithms
  sample without a gather;
* gradient-accumulation microbatching (``microbatches > 1``) scans the
  per-shard batch in M slices inside ``grad_sync.value_and_grad``, so the
  global batch scales past per-device memory.

The wrapped step has the exact ``(params, opt_state, plane, traj)``
signature every runner drives, so inline/threaded/process backends and
the fused scan carry thread it through unchanged — selection happens
once, in ``experiment.build`` (``Schedule.learner_devices`` /
``train.py --learner-devices``). With ``learner_devices=1`` the build
bypasses this module entirely (bitwise guarantee); a 1-device mesh
through this wrapper is also bitwise (tests), since every collective is
over a singleton axis. ``fsdp=False`` leaves the replicated schedule
bitwise-untouched.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.algos.api import make_train_step
from repro.distributed import grad_sync
from repro.distributed.replay_sharded import shard_buffer
from repro.distributed.sharding import (
    _key,
    axes_size,
    batch_axes,
    fsdp_leaf_dim,
)


def learner_mesh(num_devices: int, pods: int = 1,
                 offset: int = 0) -> Mesh:
    """A ``(data, model)`` — or, with ``pods > 1``, ``(pod, data,
    model)`` — mesh over ``num_devices`` devices starting at ``offset``
    (``launch.mesh.make_learner_mesh``)."""
    from repro.launch.mesh import make_learner_mesh
    return make_learner_mesh(num_devices, pods=pods, offset=offset)


def _local_shape(shape: tuple, dim: int, n: int) -> tuple:
    return shape[:dim] + (shape[dim] // n,) + shape[dim + 1:]


class ShardedLearner:
    """Builds and owns the shard_map-wrapped train step.

    ``train_step`` is a drop-in for ``make_train_step(algo, buffer)``;
    ``buffer`` (possibly wrapped sharded) must be used for plane init so
    sharded leaves are allocated at global (tiled) size.
    """

    def __init__(self, algo, buffer, num_devices: int = 1,
                 microbatches: int = 1, mesh: Optional[Mesh] = None,
                 fsdp: bool = False, pods: int = 1, offset: int = 0):
        self.algo = algo
        self.microbatches = max(1, int(microbatches))
        if mesh is None and num_devices > 1:
            mesh = learner_mesh(num_devices, pods=pods, offset=offset)
        self.mesh = mesh
        self.axes: Tuple[str, ...] = batch_axes(mesh) if mesh else ()
        self.num_shards = axes_size(mesh, self.axes) if mesh else 1
        self.fsdp = bool(fsdp) and self.num_shards > 1
        if self.num_shards > 1 and not getattr(algo, "shardable", False):
            raise ValueError(
                f"algorithm {getattr(algo, 'name', algo)!r} does not "
                f"support the sharded learner (shardable=False)")
        if self.num_shards > 1:
            self.buffer = shard_buffer(buffer, self.num_shards, self.axes)
        else:
            self.buffer = buffer
        self._step = make_train_step(algo, self.buffer)
        self._wrapped = None
        self._jitted = None
        self._shardings = None
        self._fsdp_info: Optional[grad_sync.FsdpInfo] = None
        # runners must NOT re-jit a mesh step that manages its own jit +
        # input placement (orchestrator._maybe_jit_step reads this): a
        # plain jit would infer device placement from the arguments, and
        # mixing a device-0 trajectory with mesh-sharded params/opt-state
        # is exactly the incompatible-devices error placement preempts
        self.self_jitted = self.num_shards > 1

    # ------------------------------------------------------------- specs
    def _traj_spec(self, tree):
        """Batch-axis specs by trajectory layout: step keys are time-major
        ``(T, B, ...)`` (batch = dim 1), tail keys are ``(B, ...)``."""
        tail = set(getattr(self.algo, "tail_keys", ()) or ())
        return {k: (P(self.axes) if k in tail else P(None, self.axes))
                for k in tree}

    def _plane_spec(self, buf_state):
        if hasattr(self.buffer, "state_spec"):
            return self.buffer.state_spec(buf_state)
        return self._traj_spec(buf_state)          # fifo: stored trajectory

    # -------------------------------------------------------- FSDP layout
    def fsdp_layout(self, params) -> dict:
        """``(leaf name, full shape) -> storage dim`` for every sharded
        param leaf, per ``sharding.fsdp_leaf_dim`` over the full shapes.

        Two degradations keep shape-keyed in-trace lookups unambiguous
        (a local slice's shape alone can't prove it was scattered):

        * two leaves sharing ``(name, shape)`` but resolving to different
          dims are both replicated (cannot happen for the registered RL
          param trees — the rule keys on terminal name + shape — but the
          layout must stay sound for any tree);
        * a sharded leaf whose *local* key would collide with a
          replicated leaf's key is replicated instead.
        """
        entries = {}            # (name, full shape) -> dim | None
        n = self.num_shards

        def collect(path, leaf):
            key = (_key(path[-1]), tuple(leaf.shape))
            dim = fsdp_leaf_dim(path, leaf, self.mesh)
            if key in entries and entries[key] != dim:
                dim = None      # conflicting rules: replicate
            entries[key] = dim

        jax.tree_util.tree_map_with_path(collect, params)
        changed = True
        while changed:
            changed = False
            repl = {k for k, d in entries.items() if d is None}
            for (name, shape), dim in list(entries.items()):
                if dim is None:
                    continue
                if (name, _local_shape(shape, dim, n)) in repl:
                    entries[(name, shape)] = None
                    changed = True
        return {k: d for k, d in entries.items() if d is not None}

    def _fsdp_tables(self, params) -> grad_sync.FsdpInfo:
        full = self.fsdp_layout(params)
        local = {(nm, _local_shape(shp, d, self.num_shards)): d
                 for (nm, shp), d in full.items()}
        return grad_sync.FsdpInfo(axes=self.axes, size=self.num_shards,
                                  full_table=full, local_table=local)

    def _storage_spec(self, info: Optional[grad_sync.FsdpInfo], tree):
        """Per-leaf PartitionSpecs for params/opt-state storage. Moments
        share the params' leaf names and shapes, so the same table gives
        each Adam moment exactly its param's layout; everything else
        (step counters, non-matching leaves) is replicated ``P()``."""
        if info is None:
            return P()

        def one(path, leaf):
            dim = info.full_table.get((_key(path[-1]), tuple(leaf.shape)))
            if dim is None:
                return P()
            parts = [None] * len(leaf.shape)
            parts[dim] = self.axes if len(self.axes) > 1 else self.axes[0]
            return P(*parts)

        return jax.tree_util.tree_map_with_path(one, tree)

    # -------------------------------------------------------------- step
    def _build(self, params, opt_state, plane, traj):
        buf_spec = self._plane_spec(plane[0])
        plane_spec = (buf_spec, P())               # sample key replicated
        traj_spec = self._traj_spec(traj)
        axes = self.axes
        micro = self.microbatches
        step = self._step
        info = self._fsdp_tables(params) if self.fsdp else None
        self._fsdp_info = info
        pspec = self._storage_spec(info, params)
        ospec = self._storage_spec(info, opt_state)

        def local_step(params, opt_state, plane, traj):
            with grad_sync.activate(axes, micro, fsdp=info):
                if info is not None:
                    # per-layer all-gather: algorithm code sees full
                    # params (target nets, polyak, forward passes);
                    # moments stay local through the whole step
                    params = grad_sync.gather_params(params)
                params, opt_state, plane, metrics = step(
                    params, opt_state, plane, traj)
                if info is not None:
                    params = grad_sync.shard_params(params)
            # scalar diagnostics; per-sample priorities were already
            # consumed inside the step by update_priorities
            metrics = jax.tree.map(
                lambda x: jax.lax.pmean(x, axes), metrics)
            return params, opt_state, plane, metrics

        self._shardings = tuple(
            self._sharding_tree(s, t)
            for s, t in zip((pspec, ospec, plane_spec, traj_spec),
                            (params, opt_state, plane, traj)))
        wrapped = jax.shard_map(
            local_step, mesh=self.mesh,
            in_specs=(pspec, ospec, plane_spec, traj_spec),
            out_specs=(pspec, ospec, plane_spec, P()), check_vma=False)
        self._jitted = jax.jit(wrapped)
        return wrapped

    def _sharding_tree(self, spec, tree):
        """Per-leaf ``NamedSharding``s for one argument: either broadcast
        a single ``P`` over the tree or map a matching spec tree."""
        if isinstance(spec, P):
            return jax.tree.map(
                lambda _: NamedSharding(self.mesh, spec), tree)
        return jax.tree.map(lambda s: NamedSharding(self.mesh, s), spec,
                            is_leaf=lambda x: isinstance(x, P))

    def train_step(self, params, opt_state, plane, traj):
        if self.num_shards <= 1:
            # microbatch-accumulation only: no mesh, no collectives
            with grad_sync.activate(None, self.microbatches):
                return self._step(params, opt_state, plane, traj)
        if self._wrapped is None:
            self._wrapped = self._build(params, opt_state, plane, traj)
        if isinstance(jax.tree.leaves(params)[0], jax.core.Tracer):
            # inside a caller's trace (the fused scan): the whole
            # iteration is one computation and the mesh placement is
            # exactly what we want — pass straight through
            return self._wrapped(params, opt_state, plane, traj)
        # eager (runner) path: place every input onto its mesh sharding
        # first — params/opt-state/plane already match after the first
        # step (no-op), the freshly collected trajectory is a real
        # device-0 -> mesh transfer — then run the cached jit; placement
        # rather than jit inference is what lets a device-0 trajectory
        # coexist with FSDP-sharded params
        params, opt_state, plane, traj = (
            jax.device_put(a, s)
            for a, s in zip((params, opt_state, plane, traj),
                            self._shardings))
        params, opt_state, plane, metrics = self._jitted(
            params, opt_state, plane, traj)
        # hand the (re-assembled) params back to the default device:
        # collection (inline/threaded rollout jit, process-worker
        # publish) is single-device, and a mesh-committed params array
        # would recompile the rollout as a partitioned SPMD computation
        # (pathological on forced host devices). Opt state (and under
        # FSDP its sharded moments) stays mesh-resident — only the
        # rollout needs host-side params. Under FSDP the runner keeps a
        # separate pinned copy instead (pin_params), so sharded params
        # stay sharded here.
        if not self.fsdp:
            params = jax.device_put(params, jax.devices()[0])
        return params, opt_state, plane, metrics
