"""Explicit cross-shard primitives (shard_map) for the serving path.

``flash_decode_shardmap`` is the hand-written form of the flash-decoding
combine that GSPMD derives implicitly from the seq-sharded KV cache: each
``model`` shard computes streaming-softmax stats (acc, m, l) over its KV
slice, and the shards combine with a max/psum pair — numerically identical
to a single-device softmax (tests/test_collectives.py proves it). Useful
when you want the collective schedule pinned rather than left to the
partitioner, and as the reference semantics for the decode_attention
Pallas kernel's cross-chip composition.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def _local_stats(q, k, v, valid):
    """q (B,H,hd); k/v (B,Sl,K,hd); valid (Sl,) -> (acc, m, l) f32."""
    B, H, hd = q.shape
    K = k.shape[2]
    G = H // K
    qg = q.reshape(B, K, G, hd)
    s = jnp.einsum("bkgh,bskh->bkgs", qg, k,
                   preferred_element_type=jnp.float32) * (hd ** -0.5)
    s = jnp.where(valid[None, None, None, :], s, -jnp.inf)
    m = jnp.max(s, axis=-1)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.where(valid[None, None, None, :], jnp.exp(s - m_safe[..., None]),
                  0.0)
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bkgs,bskh->bkgh", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return acc, jnp.where(jnp.isfinite(m), m, -jnp.inf), l


def flash_decode_shardmap(mesh: Mesh, axis: str = "model"):
    """Build ``f(q, k_cache, v_cache, valid) -> o`` with the KV cache
    sharded along its sequence dim over ``axis``.

    q (B,H,hd) replicated over ``axis``; k/v (B,Sc,K,hd) seq-sharded;
    valid (Sc,) seq-sharded. Output (B,H,hd) replicated.
    """

    def local(q, k, v, valid):
        acc, m, l = _local_stats(q, k, v, valid)
        g_m = jax.lax.pmax(m, axis)                      # global row max
        m_safe = jnp.where(jnp.isfinite(g_m), g_m, 0.0)
        scale = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        num = jax.lax.psum(acc * scale[..., None], axis)
        den = jax.lax.psum(l * scale, axis)
        den = jnp.where(den == 0.0, 1.0, den)
        o = (num / den[..., None]).astype(q.dtype)
        B, K, G, hd = o.shape
        return o.reshape(B, K * G, hd)

    in_specs = (P(), P(None, axis, None, None), P(None, axis, None, None),
                P(axis))
    return jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                         out_specs=P(), check_vma=False)
