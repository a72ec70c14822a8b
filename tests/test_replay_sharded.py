"""Sharding-aware replay (distributed/replay_sharded): per-shard rings and
sum-trees whose sampled distribution must match the single-buffer
reference — the DESIGN.md §9 protocol.

Fast tests drive the shard_map bodies on a 1-device mesh (bitwise vs the
reference buffers) and check the masked sum-tree update against the
unmasked reference. The D=4 exact-equality test runs in a subprocess so
it can force 8 host devices before jax initialises.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.data import replay
from repro.data.buffers import (
    FifoBuffer,
    PrioritizedBuffer,
    PrioritizedState,
    SumTree,
    UniformBuffer,
    sumtree_build,
)
from repro.distributed.replay_sharded import (
    ShardedPrioritizedBuffer,
    ShardedUniformBuffer,
    shard_buffer,
)
from repro.kernels.sum_tree import sumtree_update_masked
from repro.kernels.sum_tree.ref import sumtree_update_ref

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))


def _run(args, env=ENV, timeout=420):
    return subprocess.run([sys.executable] + args, cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=timeout)


def _paired_states(capacity: int, d: int, seed: int = 0):
    """A full sharded PrioritizedState (D local trees/rings concatenated)
    plus the reference single-tree state over the *same* global leaves and
    storage — global leaf ``s*C_loc + i`` is shard ``s``'s local leaf
    ``i`` by construction."""
    rng = np.random.RandomState(seed)
    leaves = rng.uniform(0.1, 2.0, capacity).astype(np.float32)
    storage = {
        "obs": jnp.arange(capacity, dtype=jnp.float32)[:, None],
        "rewards": jnp.asarray(rng.randn(capacity).astype(np.float32)),
    }
    c_loc = capacity // d
    local_trees = [sumtree_build(jnp.asarray(leaves[s * c_loc:(s + 1) * c_loc]))
                   for s in range(d)]
    tree_sh = SumTree(tuple(
        jnp.concatenate([t.levels[k] for t in local_trees])
        for k in range(len(local_trees[0].levels))))
    # ring index/size are replicated leaves and hold the *local* values
    ring_sh = replay.ReplayState(storage, jnp.zeros((), jnp.int32),
                                 jnp.asarray(c_loc, jnp.int32))
    state_sh = PrioritizedState(ring_sh, tree_sh, jnp.ones((), jnp.float32))
    ring_ref = replay.ReplayState(storage, jnp.zeros((), jnp.int32),
                                  jnp.asarray(capacity, jnp.int32))
    state_ref = PrioritizedState(ring_ref, sumtree_build(jnp.asarray(leaves)),
                                 jnp.ones((), jnp.float32))
    return state_sh, state_ref, leaves


# ======================================================= masked tree update
def test_sumtree_update_masked_all_true_matches_unmasked():
    leaves = jnp.asarray(
        np.random.RandomState(1).uniform(0.1, 1.0, 16), jnp.float32)
    tree = sumtree_build(leaves)
    idx = jnp.asarray([3, 7, 0, 12])
    vals = jnp.asarray([0.5, 2.0, 0.1, 1.5], jnp.float32)
    want = sumtree_update_ref(tree, idx, vals)
    got = sumtree_update_masked(tree, idx, vals,
                                jnp.ones((4,), jnp.bool_))
    for a, b in zip(want.levels, got.levels):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_sumtree_update_masked_partial_rows_untouched():
    leaves = jnp.asarray(
        np.random.RandomState(2).uniform(0.1, 1.0, 16), jnp.float32)
    tree = sumtree_build(leaves)
    idx = jnp.asarray([3, 7, 0, 12])
    vals = jnp.asarray([0.5, 2.0, 0.1, 1.5], jnp.float32)
    mask = jnp.asarray([True, False, True, False])
    got = sumtree_update_masked(tree, idx, vals, mask)
    want = sumtree_update_ref(tree, jnp.asarray([3, 0]),
                              jnp.asarray([0.5, 0.1], jnp.float32))
    for a, b in zip(want.levels, got.levels):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# =================================================== dispatch + validation
def test_shard_buffer_dispatch_and_validation():
    assert isinstance(shard_buffer(UniformBuffer(64, 16), 4, ("data",)),
                      ShardedUniformBuffer)
    assert isinstance(shard_buffer(PrioritizedBuffer(64, 16), 4, ("data",)),
                      ShardedPrioritizedBuffer)
    fifo = FifoBuffer()
    assert shard_buffer(fifo, 4, ("data",)) is fifo       # trajectory kind
    with pytest.raises(ValueError, match="power-of-two"):
        ShardedPrioritizedBuffer(PrioritizedBuffer(64, 16), 3, ("data",))
    with pytest.raises(ValueError, match="batch_size"):
        ShardedUniformBuffer(UniformBuffer(64, 15), 4, ("data",))
    with pytest.raises(ValueError, match="capacity"):
        ShardedUniformBuffer(UniformBuffer(66, 16), 4, ("data",))


# ==================================================== 1-device mesh bitwise
def _mesh1():
    return Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))


def test_sharded_prioritized_d1_mesh_bitwise():
    cap, batch = 32, 16
    buf = ShardedPrioritizedBuffer(PrioritizedBuffer(cap, batch), 1,
                                   ("data",))
    state_sh, state_ref, _ = _paired_states(cap, 1)
    spec = buf.state_spec(state_sh)
    out_spec = {k: P(("data",))
                for k in ("obs", "rewards", "indices", "weights")}
    sample = jax.shard_map(buf.sample, mesh=_mesh1(), in_specs=(spec, P()),
                           out_specs=out_spec, check_vma=False)
    key = jax.random.PRNGKey(7)
    got = sample(state_sh, key)
    want = PrioritizedBuffer(cap, batch).sample(state_ref, key)
    for k in want:
        np.testing.assert_array_equal(np.asarray(got[k]),
                                      np.asarray(want[k]), err_msg=k)


def test_sharded_uniform_d1_mesh_bitwise():
    cap, batch = 32, 16
    buf = ShardedUniformBuffer(UniformBuffer(cap, batch), 1, ("data",))
    rng = np.random.RandomState(3)
    storage = {
        "obs": jnp.arange(cap, dtype=jnp.float32)[:, None],
        "rewards": jnp.asarray(rng.randn(cap).astype(np.float32)),
    }
    state = replay.ReplayState(storage, jnp.zeros((), jnp.int32),
                               jnp.asarray(cap, jnp.int32))
    spec = buf.state_spec(state)
    out_spec = {k: P(("data",))
                for k in ("obs", "rewards", "indices", "weights")}
    sample = jax.shard_map(buf.sample, mesh=_mesh1(), in_specs=(spec, P()),
                           out_specs=out_spec, check_vma=False)
    key = jax.random.PRNGKey(11)
    got = sample(state, key)
    want = UniformBuffer(cap, batch).sample(state, key)
    for k in want:
        np.testing.assert_array_equal(np.asarray(got[k]),
                                      np.asarray(want[k]), err_msg=k)


def test_sharded_prioritized_d1_mesh_update_priorities():
    cap, batch = 32, 16
    buf = ShardedPrioritizedBuffer(PrioritizedBuffer(cap, batch), 1,
                                   ("data",))
    state_sh, state_ref, _ = _paired_states(cap, 1)
    spec = buf.state_spec(state_sh)
    idx = jnp.asarray(np.random.RandomState(4).permutation(cap)[:batch])
    pri = (idx.astype(jnp.float32) % 7 + 1.0) * 0.3
    upd = jax.shard_map(buf.update_priorities, mesh=_mesh1(),
                        in_specs=(spec, P(("data",)), P(("data",))),
                        out_specs=spec, check_vma=False)
    got = upd(state_sh, idx, pri)
    want = PrioritizedBuffer(cap, batch).update_priorities(
        state_ref, idx, pri)
    np.testing.assert_array_equal(np.asarray(got.tree.levels[0]),
                                  np.asarray(want.tree.levels[0]))
    np.testing.assert_array_equal(np.asarray(got.max_priority),
                                  np.asarray(want.max_priority))


# ============================================== D=4 exact-equality (slow)
@pytest.mark.slow
def test_sharded_prioritized_d4_matches_reference():
    """On 8 forced host devices: 4-shard stratified sampling draws the
    *exact same* global leaf indices as the single-tree reference over the
    same leaf masses, the per-shard roots psum to the reference total, the
    realized per-shard draw counts equal the exact interval allocation of
    the stratified masses, and the priority write-back lands on the same
    leaves with the same values."""
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from repro.data import replay
from repro.data.buffers import PrioritizedBuffer, PrioritizedState, \
    SumTree, sumtree_build
from repro.distributed.replay_sharded import ShardedPrioritizedBuffer

cap, batch, d = 32, 16, 4
c_loc = cap // d
rng = np.random.RandomState(0)
leaves = rng.uniform(0.1, 2.0, cap).astype(np.float32)
storage = {"obs": jnp.arange(cap, dtype=jnp.float32)[:, None],
           "rewards": jnp.asarray(rng.randn(cap).astype(np.float32))}
local_trees = [sumtree_build(jnp.asarray(leaves[s*c_loc:(s+1)*c_loc]))
               for s in range(d)]
tree_sh = SumTree(tuple(jnp.concatenate([t.levels[k] for t in local_trees])
                        for k in range(len(local_trees[0].levels))))
state_sh = PrioritizedState(
    replay.ReplayState(storage, jnp.zeros((), jnp.int32),
                       jnp.asarray(c_loc, jnp.int32)),
    tree_sh, jnp.ones((), jnp.float32))
state_ref = PrioritizedState(
    replay.ReplayState(storage, jnp.zeros((), jnp.int32),
                       jnp.asarray(cap, jnp.int32)),
    sumtree_build(jnp.asarray(leaves)), jnp.ones((), jnp.float32))

buf = ShardedPrioritizedBuffer(PrioritizedBuffer(cap, batch), d, ("data",))
ref = PrioritizedBuffer(cap, batch)
mesh = Mesh(np.asarray(jax.devices()[:d]).reshape(d, 1), ("data", "model"))
spec = buf.state_spec(state_sh)
out_spec = {k: P(("data",)) for k in ("obs", "rewards", "indices",
                                      "weights")}
sample = jax.shard_map(buf.sample, mesh=mesh, in_specs=(spec, P()),
                       out_specs=out_spec, check_vma=False)

key = jax.random.PRNGKey(7)
got = sample(state_sh, key)
want = ref.sample(state_ref, key)

# exact leaf-index equality: the per-shard descent is the exact tail of
# the reference root descent (depth-log2(D) subtree factoring)
np.testing.assert_array_equal(np.asarray(got["indices"]),
                              np.asarray(want["indices"]))
np.testing.assert_array_equal(np.asarray(got["obs"]),
                              np.asarray(want["obs"]))
np.testing.assert_allclose(np.asarray(got["weights"]),
                           np.asarray(want["weights"]), rtol=1e-5)

# root invariant: per-shard roots sum (the psum'd global root) == ref total
roots = np.asarray([float(t.total) for t in local_trees])
np.testing.assert_allclose(roots.sum(), float(state_ref.tree.total),
                           rtol=1e-6)

# exact-count allocation: realized draws per shard == the interval counts
# of the replicated stratified masses over the shard prefix offsets
b = batch
u = np.asarray((jnp.arange(b, dtype=jnp.float32)
                + jax.random.uniform(key, (b,))) / b)
m = u * roots.sum()
prefix = np.concatenate([[0.0], np.cumsum(roots)])
owner = np.clip(np.searchsorted(prefix, m, side="right") - 1, 0, d - 1)
realized = np.bincount(np.asarray(got["indices"]) // c_loc, minlength=d)
np.testing.assert_array_equal(realized, np.bincount(owner, minlength=d))

# priority write-back: same leaves, same values, same max_priority
idx = jnp.asarray(want["indices"])
pri = (idx.astype(jnp.float32) % 7 + 1.0) * 0.3
upd = jax.shard_map(buf.update_priorities, mesh=mesh,
                    in_specs=(spec, P(("data",)), P(("data",))),
                    out_specs=spec, check_vma=False)
got_st = upd(state_sh, idx, pri)
want_st = ref.update_priorities(state_ref, idx, pri)
np.testing.assert_array_equal(np.asarray(got_st.tree.levels[0]),
                              np.asarray(want_st.tree.levels[0]))
np.testing.assert_array_equal(np.asarray(got_st.max_priority),
                              np.asarray(want_st.max_priority))
print("SHARDED_REPLAY_OK")
"""
    r = _run(["-c", script])
    assert "SHARDED_REPLAY_OK" in r.stdout, r.stdout + r.stderr
