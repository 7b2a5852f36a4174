"""Production meshes.

Single pod: 256 chips as (data=16, model=16).  Multi-pod: 2 pods x 256 =
512 chips as (pod=2, data=16, model=16); the ``pod`` axis carries cross-pod
data parallelism (DCN-ish: gradient all-reduce, optionally compressed) while
``data``/``model`` stay intra-pod (ICI).

Functions, not module constants — importing this module must never touch
jax device state (the dry-run pins the device count before first jax use).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_mesh", "make_production_mesh", "make_local_mesh"]


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """``jax.make_mesh`` with Auto axes: the compiler propagates shardings
    from the jit in/out shardings, as the sharding rules expect
    (``jax.make_mesh`` defaults to Explicit axes, which demand per-op
    ``out_sharding`` annotations)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False,
                         split_model: int = 1):
    """``split_model=k`` factors the 16-way model axis into
    (model=16/k, model_b=k).  Sharding rules then place a tensor dim on the
    longest divisible prefix — e.g. 40 attention heads shard 8-way on
    ``model`` instead of replicating 16-way (the dense-train hillclimb)."""
    if split_model > 1:
        shape = ((2, 16, 16 // split_model, split_model) if multi_pod
                 else (16, 16 // split_model, split_model))
        axes = (("pod", "data", "model", "model_b") if multi_pod
                else ("data", "model", "model_b"))
        return make_mesh(shape, axes)
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(*, data: int | None = None, model: int = 1):
    """Development mesh over whatever devices exist (tests, examples)."""
    n = len(jax.devices())
    if data is None:
        data = n // model
    return make_mesh((data, model), ("data", "model"))
