"""Maxpool Pallas kernel — the SNAX max-pool accelerator on the VPU.

The paper's unit runs 8 parallel max-pool kernels behind 512-bit streamers.
On TPU the VPU reduces a (kh*kw)-unrolled window; the streamer program is
grid (n, channel-block) with a full-spatial VMEM tile (TinyML feature maps
are small; channel blocking keeps the lane dim at 128).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.streamer import Streamer

__all__ = ["maxpool"]


def _maxpool_body(x_ref, o_ref, *, k: int):
    x = x_ref[...]                       # (1, H, W, bc)
    if jnp.issubdtype(x.dtype, jnp.integer):
        # the TPU compiler has no int8 reductions: widen, reduce, narrow
        # (exact, max commutes with the widening)
        x = x.astype(jnp.int32)
    _, h, w, bc = x.shape
    x = x.reshape(1, h // k, k, w // k, k, bc)
    o_ref[...] = jnp.max(x, axis=(2, 4)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("k", "bc", "interpret"))
def maxpool(
    x: jax.Array, *, k: int = 2, bc: int = 128, interpret: bool = False
) -> jax.Array:
    """Non-overlapping NHWC maxpool (kernel = stride = k)."""
    n, h, w, c = x.shape
    assert h % k == 0 and w % k == 0, (x.shape, k)
    assert c % bc == 0, (c, bc)
    ho, wo = h // k, w // k

    s_in = Streamer("I", (1, h, w, bc), advance=("n", None, None, "c"),
                    elem_bits=x.dtype.itemsize * 8)
    s_out = Streamer("O", (1, ho, wo, bc), advance=("n", None, None, "c"),
                     elem_bits=x.dtype.itemsize * 8)
    grid_loops = ("n", "c")

    return pl.pallas_call(
        functools.partial(_maxpool_body, k=k),
        grid=(n, c // bc),
        in_specs=[s_in.to_block_spec(grid_loops)],
        out_specs=s_out.to_block_spec(grid_loops),
        out_shape=jax.ShapeDtypeStruct((n, ho, wo, c), x.dtype),
        interpret=interpret,
    )(x)
