"""Public paged-decode op: the block-diagonal query, the lengths, and the
backend policy."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import use_interpret
from repro.kernels.paged_decode.kernel import paged_decode_fwd

__all__ = ["paged_decode", "on_tpu"]

# bytes of K (and of V) one block of pages brings into VMEM
BLOCK_BYTES = 256 * 1024


def on_tpu() -> bool:
    """Whether the model's decode step takes the kernel: compiled, on the
    TPU.  Elsewhere it keeps its gather (``models.transformer``); the
    kernel's own tests run it in interpret mode on the CPU."""
    return jax.default_backend() == "tpu"


def paged_decode(q, k_pages, v_pages, page_table, lens, layer=None, *,
                 n_kv: int, interpret: bool | None = None):
    """Attention of one query token per slot over the slot's paged keys
    and values, the pages read in place.

    ``q`` ``(B, H, hd)``; ``k_pages`` / ``v_pages`` the pool leaves as the
    model carries them, ``(L, n_pages, P, W)`` with ``layer`` the index
    into ``L``, or ``(n_pages, P, W)`` with ``layer`` None; a page is a
    row per token of ``n_kv * hd`` numbers padded to ``W`` lanes
    (``transformer._page_shape``).  ``page_table`` ``(B, NP)`` int32 and
    ``lens`` ``(B,)`` int32: slot ``b`` attends to its positions ``[0,
    lens[b])``, which live in pages ``page_table[b, :ceil(lens[b] / P)]``;
    a slot with ``lens[b] == 0`` reads nothing and gives zeros.  Query
    head ``h`` attends with KV head ``h // (H // n_kv)``.

    Returns ``(B, H, hd)`` float32."""
    if interpret is None:
        interpret = use_interpret()
    b, h, hd = q.shape
    page, width = k_pages.shape[-2:]
    g = h // n_kv
    eye = jnp.eye(n_kv, dtype=bool)[None, :, None, :, None]
    qbd = jnp.where(eye, q.reshape(b, n_kv, g, 1, hd), 0).reshape(
        b, h, n_kv * hd)
    qbd = jnp.pad(qbd, ((0, 0), (0, 0), (0, width - n_kv * hd)))
    n_slot_pages = page_table.shape[1]
    ppb = max(1, min(n_slot_pages, BLOCK_BYTES // (
        page * width * k_pages.dtype.itemsize)))
    layer = jnp.zeros((1,), jnp.int32) if layer is None else jnp.reshape(
        layer, (1,)).astype(jnp.int32)
    out = paged_decode_fwd(
        qbd.astype(jnp.promote_types(q.dtype, k_pages.dtype)), k_pages,
        v_pages, page_table.astype(jnp.int32), lens.astype(jnp.int32),
        layer, ppb=ppb, scale=hd ** -0.5, interpret=interpret)
    # head h's output lies at its KV head's lanes
    out = out[..., :n_kv * hd].reshape(b, n_kv, g, n_kv, hd)
    return jnp.moveaxis(jnp.diagonal(out, axis1=1, axis2=3), -1, 1).reshape(
        b, h, hd)
