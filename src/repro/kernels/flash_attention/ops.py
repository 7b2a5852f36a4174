"""Public flash-attention op with padding + backend policy."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import use_interpret
from repro.kernels.flash_attention.kernel import flash_attention_fwd

__all__ = ["flash_attention"]


@functools.partial(
    jax.jit, static_argnames=("causal", "bq", "bk", "interpret")
)
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    bq: int = 128,
    bk: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """(B, Hq, Sq, D) x (B, Hkv, Skv, D) -> (B, Hq, Sq, D), seq padded."""
    if interpret is None:
        interpret = use_interpret()
    b, hq, sq, d = q.shape
    skv = k.shape[2]
    bq_ = min(bq, max(8, sq))
    bk_ = min(bk, max(8, skv))
    pq = (-sq) % bq_
    pkv = (-skv) % bk_
    # padded kv columns lie above the causal diagonal of every real query;
    # without causality nothing would mask them, so refuse that shape
    if not causal and pkv:
        raise ValueError(
            f"non-causal flash attention needs the kv length {skv} to be a "
            f"multiple of the kv block {bk_}")
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pkv), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pkv), (0, 0)))
    out = flash_attention_fwd(
        qp, kp, vp, bq=bq_, bk=bk_, causal=causal, interpret=interpret,
        scale=d ** -0.5,
    )
    return out[:, :, :sq, :]
