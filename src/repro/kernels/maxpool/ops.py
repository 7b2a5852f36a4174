"""Public maxpool op (compute_fn of the max-pool accelerator)."""
from __future__ import annotations

import jax

from repro.kernels import use_interpret
from repro.kernels.maxpool.kernel import maxpool


def maxpool2d(attrs: dict, x: jax.Array) -> jax.Array:
    k = attrs.get("k", 2)
    c = x.shape[-1]
    bc = attrs.get("bc", min(128, c))
    if c % bc:
        raise ValueError(
            f"maxpool accelerator: {c} channels of input {x.shape} are not "
            f"a multiple of the channel block {bc}")
    return maxpool(x, k=k, bc=bc, interpret=use_interpret())
