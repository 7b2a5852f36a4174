"""Sequence-parallel flash decode: KV cache sharded along *sequence*.

For decode, the KV cache dominates memory and the per-step attention is a
(1 x S) softmax — bandwidth-bound.  When kv-head count < model-axis size
(qwen2.5/yi have 8 kv heads on a 16-way axis), head sharding wastes chips.
Instead we shard the cache on the sequence dim: every chip scans its S/n
slice and the partials combine with the online-softmax identity:

    m = pmax(m_i),  den = psum(den_i * e^{m_i - m}),
    out = psum(num_i * e^{m_i - m}) / den

Three scalar-ish collectives replace an all-gather of the whole cache —
this is the beyond-paper optimization used by the decode hillclimb.
Runs inside ``shard_map`` (see ``sp_decode_attention``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


__all__ = ["sp_decode_attention", "sp_attention_shardmap"]

NEG = -1e30


def sp_decode_attention(q, k_shard, v_shard, valid_shard, axis: str,
                        scale: float):
    """Partial-softmax decode attention inside shard_map.

    q:        (B, H, D)       replicated over ``axis``
    k_shard:  (B, T/n, KV, D) local slice
    v_shard:  (B, T/n, KV, D)
    valid_shard: (B, T/n) bool
    Returns (B, H, D).
    """
    b, h, d = q.shape
    kv = k_shard.shape[2]
    g = h // kv
    qg = q.reshape(b, kv, g, d).astype(jnp.float32)
    s = jnp.einsum("bkgd,btkd->bkgt", qg,
                   k_shard.astype(jnp.float32)) * scale
    s = jnp.where(valid_shard[:, None, None, :], s, NEG)
    m_loc = jnp.max(s, axis=-1)                       # (B,KV,G)
    p = jnp.exp(s - m_loc[..., None])
    den_loc = jnp.sum(p, axis=-1)
    num_loc = jnp.einsum("bkgt,btkd->bkgd", p,
                         v_shard.astype(jnp.float32))
    m = jax.lax.pmax(m_loc, axis)
    corr = jnp.exp(m_loc - m)
    den = jax.lax.psum(den_loc * corr, axis)
    num = jax.lax.psum(num_loc * corr[..., None], axis)
    out = num / jnp.maximum(den, 1e-30)[..., None]
    return out.reshape(b, h, d)


def sp_attention_shardmap(mesh, axis: str = "model"):
    """Build a jit-friendly wrapper: caller passes globally-sharded arrays
    (cache seq dim on ``axis``), gets full attention out."""

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(), P(None, axis, None, None),
                  P(None, axis, None, None), P(None, axis), P()),
        out_specs=P(),
    )
    def run(q, k, v, valid, scale):
        return sp_decode_attention(q, k, v, valid, axis, scale[0])

    return run
