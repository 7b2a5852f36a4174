"""Pallas TPU kernels for the compute hot-spots.

Each kernel package has
  * ``kernel.py`` — the ``pl.pallas_call`` body with explicit ``BlockSpec``
    VMEM tiling (BlockSpecs generated from ``repro.core.streamer.Streamer``
    where the kernel realizes a SNAX accelerator datapath),
  * ``ops.py``    — the jit'd public wrapper (padding, dtype policy and the
    backend choice of :func:`use_interpret`),
  * ``ref.py``    — the pure-jnp oracle used by the allclose test sweeps.
"""
from __future__ import annotations

import jax

__all__ = ["use_interpret"]


def use_interpret() -> bool:
    """Whether Pallas kernels run in interpret mode: on the CPU backend
    (tests), and nowhere else.  The kernels are compiled for the TPU; any
    other backend is an error rather than a silent fallback."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels run compiled on 'tpu' or interpreted on 'cpu'; "
        f"the default JAX backend is {backend!r}")
