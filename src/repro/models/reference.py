"""Reference logits and the tolerances served logits are held to.

The reference is :func:`repro.models.lm.forward` over the whole token
sequence, with the weights widened to float32 and every matmul at
``"highest"`` precision (on a TPU a float32 matmul otherwise runs in
bfloat16 passes).  It shares the model code with the served path, so it
checks the cache, paging, batching and precision of serving, not the model
equations themselves.

Logits are compared, not sampled tokens: with random weights the largest
logit changes on rounding.  :func:`logit_error` is the relative RMS error
of one position's logit vector, worst position first.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models import lm

__all__ = ["LOGIT_TOL", "logit_error", "reference_logits"]

# Largest admitted logit_error, keyed by the config's compute dtype.
#  * float32: the served path and the reference differ only in the order
#    of float32 sums (chunked vs whole-sequence attention, gathered pages,
#    fused matmuls): ~1e-6 relative.  A step that ran in bfloat16 instead
#    (8-bit mantissa, unit roundoff 2**-9 ~ 2e-3 per rounding) lands at
#    1e-3 to 1e-2, so 1e-4 separates the two by an order of magnitude.
#  * bfloat16: weights and activations are rounded to bfloat16 at every
#    layer while the reference keeps float32 throughout; across a deep
#    stack the rounding errors add up to a few percent of the logit norm.
LOGIT_TOL = {"float32": 1e-4, "bfloat16": 6e-2}


def logit_error(got, ref) -> float:
    """max over positions of ||got - ref|| / ||ref|| (logits on the last
    axis, any leading shape)."""
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    if got.shape != ref.shape:
        raise ValueError(f"logit shapes differ: {got.shape} vs {ref.shape}")
    num = np.linalg.norm(got - ref, axis=-1)
    den = np.maximum(np.linalg.norm(ref, axis=-1), 1e-30)
    return float(np.max(num / den))


def reference_logits(cfg: ArchConfig, params, tokens) -> jax.Array:
    """(B, S, V) float32 logits of ``tokens`` (B, S) from the whole-sequence
    forward pass in float32 at highest matmul precision."""
    cfg32 = dataclasses.replace(cfg, param_dtype="float32",
                                compute_dtype="float32")
    params32 = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.float32)
        if jnp.issubdtype(x.dtype, jnp.floating) else x, params)
    with jax.default_matmul_precision("highest"):
        return _forward(cfg32, params32, jnp.asarray(tokens, jnp.int32))


@functools.partial(jax.jit, static_argnums=0)
def _forward(cfg: ArchConfig, params, tokens):
    return lm.forward(params, {"tokens": tokens}, cfg)[0]
