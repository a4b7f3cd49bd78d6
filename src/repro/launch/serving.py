"""Context-parallel decode: flash-merge attention over a sequence-sharded
KV cache.

The long-context decode cells (`long_500k`, batch=1) shard the KV cache on
the sequence dim over the `data` axis (launch/sharding.py `cache_specs`).
Under auto-SPMD the softmax over a sharded sequence makes XLA gather
logits; this module is the explicit alternative: every shard computes a
partial attention over its local cache slice and the shards merge with
the flash identity

    m  = pmax(m_i)
    l  = psum(l_i · exp(m_i − m))
    o  = psum(o_i · exp(m_i − m)) / l

so the wire traffic per layer is O(B·H·hd) instead of O(B·H·S/shards).
The cache write lands only on the owning shard. Numerics are pinned
against layers.attn_decode in tests/test_serving.py.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.layers import AttnDims, _qkv


def _local_attend(q, k, v, valid, scale):
    """q:[B,1,H,hd]; k,v:[B,S_loc,KV,hd]; valid:[S_loc] bool.
    Returns (o [B,1,H,hd] f32 unnormalized, m [B,1,H], l [B,1,H])."""
    groups = q.shape[2] // k.shape[2]
    kq = jnp.repeat(k, groups, axis=2)
    vq = jnp.repeat(v, groups, axis=2)
    s = jnp.einsum("bthk,bshk->bhts", q, kq.astype(q.dtype),
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(valid[None, None, None, :], s, -jnp.inf)
    m = s.max(-1)  # [B,H,1]
    m_safe = jnp.where(jnp.isfinite(m), m, -1e30)
    p = jnp.where(jnp.isfinite(s), jnp.exp(s - m_safe[..., None]), 0.0)
    l = p.sum(-1)
    o = jnp.einsum("bhts,bshk->bthk", p.astype(vq.dtype), vq,
                   preferred_element_type=jnp.float32)
    return o, m_safe.transpose(0, 2, 1), l.transpose(0, 2, 1)


def make_cp_decode_attention(dims: AttnDims, seq_axis: str = "data"):
    """Build the shard_map body for one decode-attention layer with a
    seq-sharded cache. Returns fn(p, x, cache_k, cache_v, cur_len) →
    (attn_out [B,1,d], new_k, new_v); call inside shard_map/jit with
    cache specs P(batch?, seq_axis, None, None)."""
    scale = 1.0 / math.sqrt(dims.d_head)

    def attend(p, x, cache_k, cache_v, cur_len):
        nshard = jax.lax.axis_size(seq_axis)
        rank = jax.lax.axis_index(seq_axis)
        S_loc = cache_k.shape[1]
        offset = rank * S_loc

        pos = jnp.full((x.shape[0], 1), cur_len, jnp.int32)
        q, k, v = _qkv(p, x, dims, pos)

        # cache write: only the owning shard applies the update
        local = jnp.clip(cur_len - offset, 0, S_loc - 1)
        owns = (cur_len >= offset) & (cur_len < offset + S_loc)
        upd_k = jax.lax.dynamic_update_slice_in_dim(
            cache_k, k.astype(cache_k.dtype), local, axis=1)
        upd_v = jax.lax.dynamic_update_slice_in_dim(
            cache_v, v.astype(cache_v.dtype), local, axis=1)
        new_k = jnp.where(owns, upd_k, cache_k)
        new_v = jnp.where(owns, upd_v, cache_v)

        valid = (jnp.arange(S_loc) + offset) <= cur_len
        o, m, l = _local_attend(q, new_k, new_v, valid, scale)

        # flash merge across shards: O(B·H·hd) on the wire
        m_g = jax.lax.pmax(m, seq_axis)
        c = jnp.exp(m - m_g)
        l_g = jax.lax.psum(l * c, seq_axis)
        o_g = jax.lax.psum(o * c[..., None], seq_axis)
        out = (o_g / jnp.maximum(l_g, 1e-30)[..., None]).astype(x.dtype)
        return jnp.einsum("bthk,hkd->btd", out, p["wo"]), new_k, new_v

    return attend


def cp_decode_attention(p, x, cache_k, cache_v, cur_len, dims: AttnDims,
                        mesh, *, seq_axis: str = "data", batch_axes: tuple = ()):
    """Convenience jit'able wrapper: shard_map over `mesh` with the cache
    sequence dim on `seq_axis` (the long_500k layout)."""
    attend = make_cp_decode_attention(dims, seq_axis)
    b = tuple(batch_axes) if batch_axes else None
    cache_spec = P(b, seq_axis, None, None)
    xspec = P(b, None, None)
    pspec = jax.tree.map(lambda _: P(), p)
    return jax.shard_map(
        attend,
        mesh=mesh,
        check_vma=False,
        in_specs=(pspec, xspec, cache_spec, cache_spec, P()),
        out_specs=(xspec, cache_spec, cache_spec),
    )(p, x, cache_k, cache_v, cur_len)
