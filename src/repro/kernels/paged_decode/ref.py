"""Pure-jnp oracle: gather the slot's pages into a position-ordered view,
then a masked float32 softmax over it (the model's own gather path)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["paged_decode_ref"]


def paged_decode_ref(q, k_pages, v_pages, page_table, lens, layer=None, *,
                     n_kv: int):
    """Same contract as ``ops.paged_decode``."""
    b, h, hd = q.shape
    if layer is not None:
        k_pages, v_pages = k_pages[layer], v_pages[layer]
    safe = jnp.where(page_table < 0, 0, page_table)

    def view(pool):
        v = pool[safe].reshape(b, -1, pool.shape[-1])[..., :n_kv * hd]
        return v.reshape(b, -1, n_kv, hd).astype(jnp.float32)

    k, v = view(k_pages), view(v_pages)
    qg = q.reshape(b, n_kv, h // n_kv, hd).astype(jnp.float32)
    hi = jax.lax.Precision.HIGHEST
    s = jnp.einsum("bkgd,btkd->bkgt", qg, k, precision=hi) * hd ** -0.5
    valid = jnp.arange(k.shape[1])[None, :] < lens[:, None]
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    out = jnp.einsum("bkgt,btkd->bkgd", jax.nn.softmax(s, axis=-1), v,
                     precision=hi)
    return jnp.where((lens > 0)[:, None, None],
                     out.reshape(b, h, hd), 0.0)
