"""Public SSD op: backend policy + operand preparation helper."""
from __future__ import annotations

import functools

import jax

from repro.kernels import use_interpret
from repro.kernels.ssd.kernel import ssd_fwd

__all__ = ["ssd_chunked"]


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_chunked(xdt, bmat, cmat, lcum, *, interpret: bool | None = None):
    """Chunked SSD scan. Shapes as in ``kernel.ssd_fwd``; the caller
    (``repro.models.mamba2``) prepares dt-weighted inputs and log-decays."""
    if interpret is None:
        interpret = use_interpret()
    return ssd_fwd(xdt, bmat, cmat, lcum, interpret=interpret)
