"""Public GeMM ops: padding, dtype policy, interpret mode on the CPU.

These are the ``compute_fns`` registered for the GeMM accelerator: plain
matmul, dense (FC) and conv2d lowered to implicit GEMM via im2col — the
paper's GeMM accelerator is "optimized for CNN kernels" in exactly this way.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import use_interpret
from repro.kernels.gemm import ref
from repro.kernels.gemm.kernel import gemm

__all__ = ["matmul", "dense", "conv2d_as_gemm"]


def _pad_to(x: jax.Array, mults: tuple[int, int]) -> jax.Array:
    pads = [(0, (-x.shape[i]) % mults[i]) for i in range(2)]
    if any(p[1] for p in pads):
        x = jnp.pad(x, pads)
    return x


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bk", "out_dtype", "interpret")
)
def matmul(
    a: jax.Array,
    b: jax.Array,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    out_dtype=None,
    interpret: bool | None = None,
) -> jax.Array:
    if interpret is None:
        interpret = use_interpret()
    m, n = a.shape[0], b.shape[1]
    ap = _pad_to(a, (bm, bk))
    bp = _pad_to(b, (bk, bn))
    out = gemm(ap, bp, bm=bm, bn=bn, bk=bk, out_dtype=out_dtype,
               interpret=interpret)
    return out[:m, :n]


def dense(attrs: dict, x: jax.Array, w: jax.Array) -> jax.Array:
    """FC layer for the cluster compiler (attrs may carry block sizes)."""
    return matmul(
        x, w,
        bm=attrs.get("bm", 128),
        bn=attrs.get("bn", 128),
        bk=attrs.get("bk", 128),
        out_dtype=attrs.get("out_dtype"),
    )


def conv2d_as_gemm(attrs: dict, x: jax.Array, w: jax.Array) -> jax.Array:
    """Conv2d on the GeMM accelerator: im2col (streamer loop nest) + GEMM."""
    stride = attrs.get("stride", 1)
    padding = attrs.get("padding", 0)
    kh, kw, cin, cout = w.shape
    cols, (n, ho, wo) = ref.im2col(x, kh, kw, stride, padding)
    out = matmul(cols, w.reshape(kh * kw * cin, cout),
                 out_dtype=attrs.get("out_dtype"))
    return out.reshape(n, ho, wo, cout)
