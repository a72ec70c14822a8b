"""Kernel-plane parity + selection tests.

Every RL hot-loop family (gae / sum_tree / replay_ring) must be
*exactly* equal between its Pallas kernel (interpret mode on CPU — the
real kernel bodies, executed by the interpreter) and its pure-JAX
reference — these assert equality, not closeness, across the T/B/
capacity edge cases (T=1, B=1, capacity not a power of two, all-done
trajectories, duplicate scatter indices). Plus the selection seam:
``kernels.select`` modes, ``ExperimentSpec.kernels``, and the
``"kernel"`` registry kind.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import registry
from repro.algos import gae as algo_gae
from repro.data.buffers import PrioritizedBuffer
from repro.experiment import ExperimentSpec
from repro.kernels import gae as gae_k
from repro.kernels import replay_ring as ring_k
from repro.kernels import select
from repro.kernels import sum_tree as tree_k

KEY = jax.random.PRNGKey(11)


@pytest.fixture(autouse=True)
def _restore_kernel_mode():
    prev = select.kernel_mode()
    yield
    select.set_kernel_mode(prev)


def assert_trees_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for xa, xb in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(xa), np.asarray(xb))


def _dones(T, B, mode, key):
    if mode == "none":
        return jnp.zeros((T, B), bool)
    if mode == "all":
        return jnp.ones((T, B), bool)
    return jax.random.bernoulli(key, 0.3, (T, B))


# ===================================================================== gae
GAE_SHAPES = [(1, 1), (2, 1), (1, 7), (5, 3), (64, 8), (130, 4)]


@pytest.mark.parametrize("T,B", GAE_SHAPES)
@pytest.mark.parametrize("done_mode", ["none", "random", "all"])
def test_gae_pallas_matches_ref_exactly(T, B, done_mode):
    ks = jax.random.split(jax.random.fold_in(KEY, T * 1000 + B), 4)
    r = jax.random.normal(ks[0], (T, B))
    v = jax.random.normal(ks[1], (T, B))
    d = _dones(T, B, done_mode, ks[2])
    lv = jax.random.normal(ks[3], (B,))
    adv_r, ret_r = gae_k.gae(r, v, d, lv, impl="ref")
    adv_p, ret_p = gae_k.gae(r, v, d, lv, impl="pallas")
    np.testing.assert_array_equal(np.asarray(adv_r), np.asarray(adv_p))
    np.testing.assert_array_equal(np.asarray(ret_r), np.asarray(ret_p))


@pytest.mark.parametrize("T,B", GAE_SHAPES)
@pytest.mark.parametrize("done_mode", ["none", "random", "all"])
def test_returns_pallas_matches_ref_exactly(T, B, done_mode):
    ks = jax.random.split(jax.random.fold_in(KEY, T * 991 + B), 3)
    r = jax.random.normal(ks[0], (T, B))
    d = _dones(T, B, done_mode, ks[1])
    lv = jax.random.normal(ks[2], (B,))
    ret_r = gae_k.discounted_returns(r, d, lv, impl="ref")
    ret_p = gae_k.discounted_returns(r, d, lv, impl="pallas")
    np.testing.assert_array_equal(np.asarray(ret_r), np.asarray(ret_p))


def test_gae_entry_point_default_is_bitwise_ref():
    """``algos.gae.gae`` with the default selection (auto, off-TPU)
    is the historical sequential recurrence bit for bit."""
    ks = jax.random.split(KEY, 4)
    r = jax.random.normal(ks[0], (16, 2))
    v = jax.random.normal(ks[1], (16, 2))
    d = jax.random.bernoulli(ks[2], 0.2, (16, 2))
    lv = jax.random.normal(ks[3], (2,))
    adv, ret = algo_gae.gae(r, v, d, lv)
    adv_ref, ret_ref = gae_k.gae_ref(r, v, d, lv)
    np.testing.assert_array_equal(np.asarray(adv), np.asarray(adv_ref))
    np.testing.assert_array_equal(np.asarray(ret), np.asarray(ret_ref))


def test_gae_trailing_batch_dims_roundtrip():
    """The pallas path flattens (T, B1, B2) batches and restores them."""
    ks = jax.random.split(KEY, 4)
    r = jax.random.normal(ks[0], (9, 2, 3))
    v = jax.random.normal(ks[1], (9, 2, 3))
    d = jax.random.bernoulli(ks[2], 0.2, (9, 2, 3))
    lv = jax.random.normal(ks[3], (2, 3))
    adv_r, _ = gae_k.gae(r, v, d, lv, impl="ref")
    adv_p, _ = gae_k.gae(r, v, d, lv, impl="pallas")
    assert adv_p.shape == (9, 2, 3)
    np.testing.assert_array_equal(np.asarray(adv_r), np.asarray(adv_p))


# ================================================================ sum_tree
CAPS = [1, 2, 8, 64, 1024]


@pytest.mark.parametrize("cap", CAPS)
def test_sumtree_find_pallas_matches_ref_exactly(cap):
    leaves = jnp.abs(jax.random.normal(jax.random.fold_in(KEY, cap),
                                       (cap,)))
    # zero-mass slots exercise the unfilled-capacity case
    leaves = leaves.at[:: max(cap // 4, 1)].set(0.0)
    tree = tree_k.sumtree_build(leaves)
    B = 32
    u = (jnp.arange(B, dtype=jnp.float32) + 0.5) / B
    masses = u * tree.total
    idx_r = tree_k.sumtree_find_batch(tree, masses, impl="ref")
    idx_p = tree_k.sumtree_find_batch(tree, masses, impl="pallas")
    np.testing.assert_array_equal(np.asarray(idx_r), np.asarray(idx_p))
    assert np.asarray(idx_p).max() < cap
    # the batched descent is elementwise the scalar descent
    scalar = jnp.stack([tree_k.sumtree_find(tree, m) for m in masses[:4]])
    np.testing.assert_array_equal(np.asarray(scalar),
                                  np.asarray(idx_r[:4]))


@pytest.mark.parametrize("cap", CAPS)
def test_sumtree_update_pallas_matches_ref_exactly(cap):
    leaves = jnp.abs(jax.random.normal(jax.random.fold_in(KEY, cap + 1),
                                       (cap,)))
    tree = tree_k.sumtree_build(leaves)
    # duplicates on purpose: both impls must resolve last-write-wins
    idx = jnp.asarray([0, cap - 1, 0, cap // 2, 0])[: max(3, min(5, cap))]
    idx = idx % cap
    vals = jnp.asarray([1.5, 2.0, 0.25, 3.0, 0.125])[: idx.shape[0]]
    t_r = tree_k.sumtree_update(tree, idx, vals, impl="ref")
    t_p = tree_k.sumtree_update(tree, idx, vals, impl="pallas")
    assert_trees_equal(t_r, t_p)
    # and the updated tree descends identically
    masses = (jnp.arange(8, dtype=jnp.float32) + 0.5) / 8 * t_r.total
    np.testing.assert_array_equal(
        np.asarray(tree_k.sumtree_find_batch(t_r, masses, impl="ref")),
        np.asarray(tree_k.sumtree_find_batch(t_p, masses, impl="pallas")))


def test_sumtree_flatten_roundtrip():
    tree = tree_k.sumtree_build(jnp.arange(16.0))
    flat = tree_k.tree_flatten(tree)
    assert flat.shape == (31,)
    assert_trees_equal(tree, tree_k.tree_unflatten(flat, 16))


# ============================================================= replay_ring
@pytest.mark.parametrize("cap,n,start", [
    (17, 5, 0),        # capacity not a power of two
    (17, 5, 15),       # wraparound
    (12, 12, 7),       # exactly one full ring, offset start
    (8, 11, 3),        # n > capacity: self-overwrite, last write wins
    (1, 1, 0),         # degenerate ring
])
def test_ring_insert_pallas_matches_ref_exactly(cap, n, start):
    ks = jax.random.split(jax.random.fold_in(KEY, cap * 100 + n), 2)
    storage = {"obs": jax.random.normal(ks[0], (cap, 3)),
               "rewards": jnp.zeros((cap,))}
    batch = {"obs": jax.random.normal(ks[1], (n, 3)),
             "rewards": jnp.arange(float(n))}
    s_r = ring_k.ring_insert(storage, batch, jnp.int32(start), impl="ref")
    s_p = ring_k.ring_insert(storage, batch, jnp.int32(start),
                             impl="pallas")
    assert_trees_equal(s_r, s_p)


@pytest.mark.parametrize("cap,B", [(17, 6), (1, 1), (64, 64)])
def test_ring_gather_pallas_matches_ref_exactly(cap, B):
    ks = jax.random.split(jax.random.fold_in(KEY, cap * 7 + B), 4)
    # every stored form: (cap, width) takes the Pallas kernel; rank-1 and
    # rank-3 leaves take XLA's gather in place
    storage = {"obs": jax.random.normal(ks[0], (cap, 2, 2)),
               "rewards": jax.random.normal(ks[1], (cap,)),
               "actions": jax.random.normal(ks[2], (cap, 3)),
               "steps": jax.random.randint(ks[3], (cap,), -50, 50)}
    idx = jax.random.randint(jax.random.fold_in(KEY, B), (B,), 0, cap)
    g_r = ring_k.ring_gather(storage, idx, impl="ref")
    g_p = ring_k.ring_gather(storage, idx, impl="pallas")
    assert g_p["obs"].shape == (B, 2, 2)
    assert g_p["actions"].shape == (B, 3)
    assert g_p["steps"].dtype == jnp.int32
    assert_trees_equal(g_r, g_p)


# ============================================== buffer-level end-to-end
def _traj(T, B):
    t = jnp.broadcast_to(jnp.arange(T, dtype=jnp.float32)[:, None, None],
                         (T, B, 3))
    return {"obs": t, "actions": jnp.zeros((T, B, 2)),
            "rewards": jnp.ones((T, B)),
            "dones": jnp.zeros((T, B), bool), "next_obs": t + 1.0}


def _example():
    return {"obs": jnp.zeros((1, 3)), "actions": jnp.zeros((1, 2)),
            "rewards": jnp.zeros((1,)), "next_obs": jnp.zeros((1, 3)),
            "dones": jnp.zeros((1,), bool)}


def test_prioritized_buffer_pallas_matches_ref_end_to_end():
    """add -> update_priorities -> sample through the whole buffer, once
    per kernel mode: same tree, same drawn indices, same weights."""
    outs = {}
    for mode in ("ref", "pallas"):
        select.set_kernel_mode(mode)
        buf = PrioritizedBuffer(capacity=64, batch_size=32)
        state = buf.add(buf.init(_example()), _traj(8, 4))
        state = buf.update_priorities(state, jnp.arange(8),
                                      jnp.linspace(0.1, 3.0, 8))
        outs[mode] = (state, buf.sample(state, jax.random.PRNGKey(0)))
    assert_trees_equal(outs["ref"][0], outs["pallas"][0])
    for k in outs["ref"][1]:
        np.testing.assert_array_equal(np.asarray(outs["ref"][1][k]),
                                      np.asarray(outs["pallas"][1][k]))


# ========================================================= selection seam
def test_kernel_mode_validation_and_resolution():
    with pytest.raises(ValueError, match="kernel mode"):
        select.set_kernel_mode("cuda")
    with pytest.raises(ValueError, match="kernel impl"):
        select.resolve("cuda")
    assert select.resolve("ref") == ("ref", False)
    name, interpret = select.resolve("pallas")
    assert name == "pallas"
    compiled = jax.default_backend() in select.COMPILED_PLATFORMS
    assert interpret == (not compiled)  # off-accelerator pallas interprets
    assert select.resolve("auto") == (("pallas", False) if compiled
                                      else ("ref", False))


def test_set_kernel_mode_returns_previous():
    prev = select.set_kernel_mode("ref")
    assert select.kernel_mode() == "ref"
    assert select.set_kernel_mode(prev) == "ref"


def test_spec_kernels_field_roundtrip_and_validation():
    spec = ExperimentSpec(kernels="pallas")
    assert ExperimentSpec.from_dict(spec.to_dict()) == spec
    assert ExperimentSpec().kernels == "auto"
    from repro import experiment
    with pytest.raises(ValueError, match="kernel mode"):
        experiment.build(ExperimentSpec(kernels="nope"))


def test_registry_kernel_kind_lists_families():
    names = registry.choices("kernel")
    assert {"gae", "sum_tree", "replay_ring", "env_step"} <= set(names)
    ops = registry.make("kernel", "gae")
    assert hasattr(ops, "gae") and hasattr(ops, "gae_ref")
