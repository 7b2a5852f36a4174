"""Public RMSNorm op: flattens leading dims, pads rows, backend policy."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import use_interpret
from repro.kernels.rmsnorm.kernel import rmsnorm_fwd

__all__ = ["rmsnorm"]


@functools.partial(jax.jit, static_argnames=("eps", "bm", "interpret"))
def rmsnorm(
    x: jax.Array,
    w: jax.Array,
    *,
    eps: float = 1e-6,
    bm: int = 256,
    interpret: bool | None = None,
) -> jax.Array:
    if interpret is None:
        interpret = use_interpret()
    shape = x.shape
    d = shape[-1]
    rows = 1
    for s in shape[:-1]:
        rows *= s
    x2 = x.reshape(rows, d)
    bm_ = min(bm, rows)
    pad = (-rows) % bm_
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    out = rmsnorm_fwd(x2, w, bm=bm_, eps=eps, interpret=interpret)
    return out[:rows].reshape(shape)
