"""Mamba2 SSD chunked-scan Pallas kernel.

TPU adaptation of the SSD algorithm: the quadratic intra-chunk term runs as
(Q x Q) MXU matmuls on VMEM-resident blocks, and the inter-chunk state
S (N x P) lives in VMEM *scratch carried across the innermost grid
dimension* — the TPU-idiomatic replacement for the GPU kernel's
SM-persistent state.  One grid step = one (batch, head, chunk) block; the
chunk axis is innermost so the recurrence is honored.

Inputs are the pre-projected SSD operands (the surrounding projections /
conv / gating stay in XLA where they fuse well):
    xdt  (B, H, nc, Q, P)   dt-weighted inputs
    bmat (B, nc, Q, N)      input projections  B_t
    cmat (B, nc, Q, N)      output projections C_t
    lcum (B, H, nc, Q)      within-chunk inclusive cumsum of log-decay
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ssd_fwd"]

NEG = -1e30


def _ssd_body(xdt_ref, b_ref, c_ref, lrow_ref, lcol_ref, o_ref, s_ref, *,
              q: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    xdt = xdt_ref[0, 0, 0].astype(jnp.float32)        # (Q, P)
    b = b_ref[0, 0].astype(jnp.float32)               # (Q, N)
    c = c_ref[0, 0].astype(jnp.float32)               # (Q, N)
    # the log-decays arrive as a row and as a column, so that no lane <->
    # sublane transpose is needed in the kernel
    lrow = lrow_ref[0, 0, 0].astype(jnp.float32)      # (1, Q)
    lcol = lcol_ref[0, 0, 0].astype(jnp.float32)      # (Q, 1)
    llast = lcol[q - 1, 0]                            # scalar

    # intra-chunk: scores[i, j] = (C_i . B_j) * exp(lc_i - lc_j), j <= i
    cb = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (Q,Q)
    ldiff = lcol - lrow
    mask = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    dec = jnp.exp(jnp.where(mask, ldiff, NEG))
    y_intra = jax.lax.dot_general(
        cb * dec, xdt, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)           # (Q, P)

    # inter-chunk: y_i += exp(lc_i) * C_i . S_prev
    s_prev = s_ref[...]                               # (N, P)
    y_inter = jnp.exp(lcol) * jax.lax.dot_general(
        c, s_prev, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    o_ref[0, 0, 0] = (y_intra + y_inter).astype(o_ref.dtype)

    # state update: S = exp(lc_Q) * S_prev + B^T (exp(lc_Q - lc_j) * xdt)
    tail = jnp.exp(llast - lcol)                      # (Q, 1)
    s_new = s_prev * jnp.exp(llast) + jax.lax.dot_general(
        b, tail * xdt, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    s_ref[...] = s_new


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_fwd(xdt, bmat, cmat, lcum, *, interpret: bool = False):
    bsz, h, nc, q, p = xdt.shape
    n = bmat.shape[-1]
    grid = (bsz, h, nc)
    return pl.pallas_call(
        functools.partial(_ssd_body, q=q),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1, q, p),
                         lambda b, hh, c_: (b, hh, c_, 0, 0)),
            pl.BlockSpec((1, 1, q, n), lambda b, hh, c_: (b, c_, 0, 0)),
            pl.BlockSpec((1, 1, q, n), lambda b, hh, c_: (b, c_, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, q),
                         lambda b, hh, c_: (b, hh, c_, 0, 0)),
            pl.BlockSpec((1, 1, 1, q, 1),
                         lambda b, hh, c_: (b, hh, c_, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, q, p),
                               lambda b, hh, c_: (b, hh, c_, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(xdt.shape, xdt.dtype),
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        interpret=interpret,
    )(xdt, bmat, cmat, lcum[..., None, :], lcum[..., :, None])
