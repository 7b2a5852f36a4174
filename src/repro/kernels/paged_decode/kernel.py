"""Paged decode attention Pallas kernel: one query token per slot against
that slot's KV pages, read where they lie in the pool.

The pool stays in HBM (``memory_space=pl.ANY``).  The kernel reads only a
slot's live pages, ``[0, ceil(len / P))`` of its table row, by one async
copy per page into a VMEM block of ``ppb`` pages, two blocks in flight:
while one block is scored the next is on its way, across slot boundaries
too.  The blocks to read are listed once in SMEM at the start (slot,
block), so a slot with ``len == 0`` costs nothing.

A page is ``P`` rows of ``W`` lanes, a token's ``(n_kv, hd)`` numbers
padded to whole 128-lane rows.  The query arrives block-diagonal,
``(H, W)`` a slot: head ``h`` holds its ``hd`` numbers at the lanes of its
KV head and zeros elsewhere.  So one ``(H, W) x (bk, W)^T`` product scores
every head against one read of the block (GQA: a KV head's bytes serve all
its query heads), and ``p (H, bk) x (bk, W)`` gives each head its output
at its KV head's lanes (``ops.py`` takes those out).

The online softmax (running max ``m``, denominator ``l``, accumulator
``acc``) is carried in VMEM scratch from block to block in float32.  The
scores are exact products of the bf16 query and keys summed in float32
(a float32 pool's at ``HIGHEST`` precision), and ``p`` stays float32, at
``HIGHEST`` precision, in the product with the values.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["paged_decode_fwd"]

NEG = -1e30


def _body(layer_ref, table_ref, len_ref,             # scalar prefetch
          q_ref, k_hbm, v_hbm,                       # inputs
          o_ref,                                     # output
          slot_ref, blk_ref, k_buf, v_buf, sem, m_ref, l_ref, acc_ref,
          *, batch: int, n_slot_pages: int, page: int, ppb: int,
          scale: float, layered: bool):
    bk = ppb * page
    n_blocks = lambda b: (len_ref[b] + bk - 1) // bk     # noqa: E731

    # the work list: (slot, block) for every live block, in slot order
    def list_slot(b, n):
        def put(j, n):
            slot_ref[n] = b
            blk_ref[n] = j
            return n + 1
        return jax.lax.fori_loop(0, n_blocks(b), put, n)

    n = jax.lax.fori_loop(0, batch, list_slot, 0)

    def copies(i, buf):
        """(live, K copy, V copy) of each page of work item ``i``."""
        b, first = slot_ref[i], blk_ref[i] * ppb
        live = (len_ref[b] + page - 1) // page
        out = []
        for j in range(ppb):
            idx = jnp.minimum(first + j, n_slot_pages - 1)
            pid = jnp.maximum(table_ref[b * n_slot_pages + idx], 0)
            at = (layer_ref[0], pid) if layered else (pid,)
            dst = pl.ds(j * page, page)
            out.append((first + j < live,
                        pltpu.make_async_copy(k_hbm.at[at], k_buf.at[buf, dst],
                                              sem.at[0, buf]),
                        pltpu.make_async_copy(v_hbm.at[at], v_buf.at[buf, dst],
                                              sem.at[1, buf])))
        return out

    def start(i, buf):
        for live, ck, cv in copies(i, buf):
            @pl.when(live)
            def _():
                ck.start()
                cv.start()

    def wait(i, buf):
        for live, ck, cv in copies(i, buf):
            @pl.when(live)
            def _():
                ck.wait()
                cv.wait()

    o_ref[...] = jnp.zeros_like(o_ref)     # slots with nothing to read

    @pl.when(n > 0)
    def _():
        start(0, 0)

    def step(i, carry):
        buf = i % 2
        b, blk = slot_ref[i], blk_ref[i]
        length = len_ref[b]

        @pl.when(i + 1 < n)
        def _():
            start(i + 1, 1 - buf)

        @pl.when(blk == 0)
        def _():
            m_ref[...] = jnp.full_like(m_ref, NEG)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        wait(i, buf)
        q = q_ref[b]                                       # (H, W)
        k = k_buf[buf]                                     # (bk, W)
        if k.dtype != q.dtype:
            k = k.astype(q.dtype)
        s = jax.lax.dot_general(                          # (H, bk)
            q, k, (((1,), (1,)), ((), ())), precision=(
                jax.lax.Precision.HIGHEST if q.dtype == jnp.float32
                else None),
            preferred_element_type=jnp.float32) * scale
        pos = blk * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        s = jnp.where(pos < length, s, NEG)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
        # rows past `len` hold whatever the page held: never let them in
        row = blk * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)
        v = jnp.where(row < length, v_buf[buf].astype(jnp.float32), 0.0)
        acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

        @pl.when((blk + 1) * bk >= length)
        def _():
            o_ref[b] = acc_ref[...] / l_ref[...]

        return carry

    jax.lax.fori_loop(0, n, step, 0)


@functools.partial(jax.jit, static_argnames=("ppb", "scale", "interpret"))
def paged_decode_fwd(
    q: jax.Array,            # (B, H, W), block-diagonal over the KV heads
    k_pages: jax.Array,      # ([L,] n_pages, P, W)
    v_pages: jax.Array,
    page_table: jax.Array,   # (B, NP) int32, -1 = unassigned
    lens: jax.Array,         # (B,) int32, tokens to read per slot
    layer: jax.Array,        # (1,) int32, the index into L (unused without)
    *,
    ppb: int,
    scale: float,
    interpret: bool = False,
) -> jax.Array:
    """-> ``(B, H, W)`` float32: each head's softmax-weighted values, at
    every lane of the page row."""
    batch, heads, width = q.shape
    page = k_pages.shape[-2]
    n_slot_pages = page_table.shape[1]
    n_work = batch * pl.cdiv(n_slot_pages, ppb)
    bk = ppb * page
    body = functools.partial(
        _body, batch=batch, n_slot_pages=n_slot_pages, page=page, ppb=ppb,
        scale=scale, layered=k_pages.ndim == 4)
    whole = lambda i, *_: (0, 0, 0)                  # noqa: E731
    return pl.pallas_call(
        body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(1,),
            in_specs=[pl.BlockSpec(q.shape, whole),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(q.shape, whole),
            scratch_shapes=[
                pltpu.SMEM((n_work,), jnp.int32),
                pltpu.SMEM((n_work,), jnp.int32),
                pltpu.VMEM((2, bk, width), k_pages.dtype),
                pltpu.VMEM((2, bk, width), v_pages.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((heads, 1), jnp.float32),
                pltpu.VMEM((heads, 1), jnp.float32),
                pltpu.VMEM((heads, width), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct(q.shape, jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(layer, page_table.reshape(-1), lens, q, k_pages, v_pages)
