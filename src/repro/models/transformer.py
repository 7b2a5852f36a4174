"""Transformer building blocks: GQA attention (RoPE/M-RoPE, sliding window,
KV/ring caches), dense FFN (SwiGLU / GELU), pre-norm blocks, scanned stacks.

Attention impls:
  * ``einsum``    — materialized scores, for short sequences / smoke tests;
  * ``xla_flash`` — chunked online-softmax attention in pure jnp (lax.scan
    over KV chunks), O(S * chunk) memory: the XLA-level mirror of the Pallas
    flash kernel, used for long sequences and under SPMD where the Pallas
    path is TPU-only;
  * ``pallas``    — the Pallas kernel (TPU target).
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from repro.kernels.paged_decode import ops as paged_decode_ops
from repro.models.common import (
    apply_dense, apply_norm, cast, dense_init, gelu, mrope, norm_init,
    rope, swiglu_combine,
)

__all__ = [
    "AttnArgs", "attn_init", "attn_apply", "init_kv_cache",
    "reset_kv_slot", "install_kv_pages",
    "ffn_init", "ffn_apply", "block_init", "block_apply",
    "stack_init", "stack_apply",
]

NEG = -1e30


# ============================================================== attention ==
@dataclasses.dataclass(frozen=True)
class AttnArgs:
    n_heads: int
    n_kv: int
    hd: int
    causal: bool = True
    rope_theta: float = 1e6
    rotary_pct: float = 1.0
    use_rope: bool = True
    mrope_sections: tuple[int, int, int] | None = None
    sliding_window: int | None = None
    impl: str = "auto"        # einsum | xla_flash | pallas | auto


def attn_init(key, d_model: int, a: AttnArgs, *, qkv_bias=False,
              dtype=jnp.bfloat16, cross=False):
    ks = jax.random.split(key, 4)
    pq, sq = dense_init(ks[0], d_model, (a.n_heads, a.hd),
                        ("embed", "heads", "head"), bias=qkv_bias,
                        dtype=dtype)
    pk, sk = dense_init(ks[1], d_model, (a.n_kv, a.hd),
                        ("embed", "kv_heads", "head"), bias=qkv_bias,
                        dtype=dtype)
    pv, sv = dense_init(ks[2], d_model, (a.n_kv, a.hd),
                        ("embed", "kv_heads", "head"), bias=qkv_bias,
                        dtype=dtype)
    # output proj: (H, hd, d) contracted over (H, hd)
    w_o = (jax.random.normal(ks[3], (a.n_heads, a.hd, d_model), jnp.float32)
           / math.sqrt(a.n_heads * a.hd)).astype(dtype)
    params = {"q": pq, "k": pk, "v": pv, "o": {"w": w_o}}
    specs = {"q": sq, "k": sk, "v": sv,
             "o": {"w": ("heads", "head", "embed")}}
    return params, specs


def init_kv_cache(batch: int, max_len: int, a: AttnArgs, dtype,
                  *, ring: bool = False, quant: bool = False,
                  page_size: int = 0, n_pages: int = 0):
    """Decode cache with **per-slot** position counters.

    Two layouts share one calling convention:

    **Dense** (``page_size == 0``) — every slot owns a contiguous
    ``max_len`` strip:

      * ``k`` / ``v``      ``(batch, size, n_kv, hd)``
      * ``slot_pos``       ``(batch, size)`` int32 — absolute position of
        each entry, ``-1`` = empty (the mask that makes a row logically
        empty without zeroing it)
      * ``len``            ``(batch,)`` int32 — tokens cached so far

    **Paged** (``page_size > 0``) — slots share a fixed pool of
    ``page_size``-token pages and address them through a page table:

      * ``k_pages`` / ``v_pages``  ``(n_pages, *_page_shape(page_size,
        n_kv * hd, pad=not quant))`` — a page's tokens' ``(n_kv, hd)``
        blocks in position order, held as lane-dense rows: one row per
        token, ``n_kv * hd`` numbers padded with zeros to whole 128-lane
        rows (an int8 pool pads nothing: a width that does not fill whole
        rows holds the whole page as one row).  The chip lays out an
        unpadded ``(n_pages, P, n_kv * hd)`` or ``(n_pages, P, n_kv, hd)``
        pool with the page index on the lanes, where neither the token
        write nor the page read can use it, so every step would relay the
        whole pool out and back; the padded rows are what the paged-decode
        kernel (``repro.kernels.paged_decode``) reads page by page
      * ``page_table``  ``(batch, ceil(max_len / page_size))`` int32 —
        entry ``j`` of row ``b`` is the pool page holding row ``b``'s
        absolute positions ``[j * page_size, (j + 1) * page_size)``;
        ``-1`` = unassigned
      * ``len``         ``(batch,)`` int32

    Paged invariants (what makes prefix sharing safe):

      * positions ``< len[b]`` are contiguously valid — every one of them
        lives in an assigned page and has been written (by this slot or by
        the shared-prefix donor), so validity is pure arithmetic
        (``pos < len``) and no per-entry position map is needed;
      * a pool page referenced by more than one page table (a shared
        prefix page) is **full and immutable**: writes only ever target
        positions ``>= len[b]``, and admission only shares pages wholly
        below the recipient's starting ``len``;
      * page *allocation* is host-side (``repro.serving.PagePool`` owns
        refcounts and the free list) — the device only ever reads/writes
        through the table it was handed.

    Every batch row ("slot") carries its own length counter, so rows can
    hold sequences of different lengths, be prefilled/advanced
    independently, and be reset and reused without touching their
    neighbours — the substrate for continuous batching.

    A model's layer stack of paged caches (``lm.init_caches``) is updated
    in place: ``lm.decode_step`` carries the stacked pool leaves
    (``POOL_KEYS``) through its layer loop, and each layer writes and
    reads them at its own index (``_paged_cache_update``).  Callers that
    jit a step donate the caches, or the step copies the pool once a call
    to keep the caller's array.

    ``ring=True`` -> sliding-window ring buffer (dense only).
    ``quant=True`` -> int8 K/V with per-(token, head) f32 scales: halves
    the decode memory term (decode reads the whole cache every step)."""
    kv_dtype = jnp.int8 if quant else dtype
    if page_size:
        if ring:
            raise ValueError("paged KV cache does not support ring "
                             "(sliding-window) layout")
        n_slot_pages = -(-max_len // page_size)
        if not n_pages:
            n_pages = batch * n_slot_pages
        kv = (n_pages,) + _page_shape(page_size, a.n_kv * a.hd,
                                      pad=not quant)
        cache = {
            "k_pages": jnp.zeros(kv, kv_dtype),
            "v_pages": jnp.zeros(kv, kv_dtype),
            "page_table": jnp.full((batch, n_slot_pages), -1, jnp.int32),
            "len": jnp.zeros((batch,), jnp.int32),
        }
        if quant:
            sc = (n_pages,) + _page_shape(page_size, a.n_kv, pad=False)
            cache["k_scale_pages"] = jnp.zeros(sc, jnp.float32)
            cache["v_scale_pages"] = jnp.zeros(sc, jnp.float32)
        return cache
    size = min(max_len, a.sliding_window) if (ring and a.sliding_window) \
        else max_len
    cache = {
        "k": jnp.zeros((batch, size, a.n_kv, a.hd), kv_dtype),
        "v": jnp.zeros((batch, size, a.n_kv, a.hd), kv_dtype),
        # absolute position stored per (slot, entry); -1 = empty
        "slot_pos": jnp.full((batch, size), -1, jnp.int32),
        # tokens cached so far, per slot
        "len": jnp.zeros((batch,), jnp.int32),
    }
    if quant:
        cache["k_scale"] = jnp.zeros((batch, size, a.n_kv), jnp.float32)
        cache["v_scale"] = jnp.zeros((batch, size, a.n_kv), jnp.float32)
    return cache


# the leaves of a paged cache that hold the page pool (the rest are per-slot)
POOL_KEYS = ("k_pages", "v_pages", "k_scale_pages", "v_scale_pages")


def _page_shape(page_size: int, width: int, *, pad: bool) -> tuple:
    """One pool page of ``page_size`` tokens of ``width`` numbers: a row
    per token, ``width`` padded to whole 128-lane rows where ``pad``;
    unpadded, a ``width`` that does not fill whole rows holds the page as
    one row."""
    if pad:
        return (page_size, -(-width // 128) * 128)
    return (page_size, width) if width % 128 == 0 else (page_size * width,)


def _is_paged(cache) -> bool:
    return "page_table" in cache


def reset_kv_slot(cache, slot):
    """Make one batch row of a decode cache logically empty and reusable.

    ``slot`` may be a traced int32 — admission resets run jitted.

    Dense: the position map is what empties the row (``slot_pos = -1``
    masks every entry); K/V are zeroed too so a reset slot carries no
    stale data.

    Paged: only the row's ``page_table`` (set to -1) and ``len`` (0) are
    touched — the pool pages themselves may be shared with other slots or
    retained by the prefix tree, so reclaiming them is the host-side
    allocator's job (``PagePool.release``), never the device's.  Stale
    data in a freed page is harmless: it is unreachable until the page is
    re-installed in some table, and positions ``>= len`` never score.
    """
    if _is_paged(cache):
        return {**cache,
                "page_table": cache["page_table"].at[slot].set(-1),
                "len": cache["len"].at[slot].set(0)}
    out = {k: v.at[slot].set(0) for k, v in cache.items()}
    out["slot_pos"] = cache["slot_pos"].at[slot].set(-1)
    return out


def install_kv_pages(cache, slot, table_row, n_tokens):
    """Point slot ``slot`` of a paged cache at ``table_row`` pool pages and
    seed its length with ``n_tokens`` already-valid (shared-prefix) tokens.

    ``table_row`` is a ``(n_slot_pages,)`` int32 vector (``-1`` padded);
    its first ``ceil(n_tokens / page_size)`` entries must be pages whose
    first ``n_tokens`` positions hold valid K/V for this slot's token
    prefix — admission guarantees that by only sharing full, immutable
    prefix pages.  The remaining assigned entries are private, writable
    pages covering the slot's tail prefill + generation."""
    return {**cache,
            "page_table": cache["page_table"].at[slot].set(table_row),
            "len": cache["len"].at[slot].set(n_tokens)}


def migrate_kv_pages(src_cache, dst_cache, src_pages, dst_pages):
    """Copy page *contents* from one paged cache's pool into another's.

    ``src_pages``/``dst_pages`` are equal-length int32 page-id vectors
    into the source and destination pools (which may differ in
    ``n_pages`` and batch width — only ``page_size``/heads/head-dim must
    match).  This is the data plane of the prefill->decode handoff: the
    host-side custody move is ``repro.serving.handoff.transfer``; this
    gather/scatter lands the bytes.  Page tables and lengths are
    untouched — the caller installs the destination table separately
    (``install_kv_pages``), so a partially-migrated slot is never
    addressable.

    Index pairs may repeat (callers pad to a bucketed length by
    repeating a real pair): the duplicate scatter writes carry identical
    content, so last-write-wins is deterministic.
    """
    out = dict(dst_cache)
    for key in POOL_KEYS:
        if key in dst_cache:
            out[key] = dst_cache[key].at[dst_pages].set(
                src_cache[key][src_pages], mode="drop")
    return out


def _kv_quantize(x):
    """(B, 1, KV, hd) -> int8 values + per-head scale."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0 + 1e-9
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def _kv_dequant(q, scale):
    return q.astype(jnp.float32) * scale[..., None].astype(jnp.float32)


def _is_ring(cache, a: AttnArgs) -> bool:
    # the cache is a ring buffer iff it is smaller than what unbounded
    # attention would need, which only happens with a sliding window
    return (a.sliding_window is not None
            and cache["k"].shape[1] <= a.sliding_window)


def _apply_rope(x, positions, pos3, a: AttnArgs):
    if not a.use_rope:
        return x
    if a.mrope_sections is not None and pos3 is not None:
        return mrope(x, pos3, theta=a.rope_theta, sections=a.mrope_sections)
    return rope(x, positions, theta=a.rope_theta, rotary_pct=a.rotary_pct)


def _gqa_scores(q, k):
    """q (B,S,H,D), k (B,T,KV,D) -> scores (B,KV,G,S,T) without repeat."""
    b, s, h, d = q.shape
    kv = k.shape[2]
    g = h // kv
    qg = q.reshape(b, s, kv, g, d)
    return jnp.einsum("bskgd,btkd->bkgst", qg, k)


def _gqa_out(p, v):
    """p (B,KV,G,S,T), v (B,T,KV,D) -> (B,S,H,D)."""
    b, kv, g, s, t = p.shape
    out = jnp.einsum("bkgst,btkd->bskgd", p, v)
    return out.reshape(b, s, kv * g, v.shape[-1])


def _einsum_attn(q, k, v, mask, scale):
    s = _gqa_scores(q.astype(jnp.float32), k.astype(jnp.float32)) * scale
    s = jnp.where(mask, s, NEG)
    p = jax.nn.softmax(s, axis=-1)
    return _gqa_out(p, v.astype(jnp.float32)).astype(q.dtype)


def _xla_flash(q, k, v, scale, *, causal, window, q_chunk=512,
               kv_chunk=1024):
    """Chunked online-softmax attention in pure jnp (differentiable).

    Under ``flags.UNROLL`` (dry-run cost compiles) the chunk loops become
    python loops with fully-masked causal blocks skipped — flops are
    chunk-size invariant, so this is the loop-free twin XLA can cost.
    """
    from repro.models import flags
    b, s, h, d = q.shape
    t = k.shape[1]
    kv_heads = k.shape[2]
    g = h // kv_heads
    if flags.UNROLL:
        # fewer, larger chunks bound the unrolled HLO size
        q_chunk = max(q_chunk, s // 8)
        kv_chunk = max(kv_chunk, t // 8)
    qc = min(q_chunk, s)
    kc = min(kv_chunk, t)
    nq = -(-s // qc)
    nk = -(-t // kc)
    sp = nq * qc - s
    tp = nk * kc - t
    qq = jnp.pad(q, ((0, 0), (0, sp), (0, 0), (0, 0))) if sp else q
    kk = jnp.pad(k, ((0, 0), (0, tp), (0, 0), (0, 0))) if tp else k
    vv = jnp.pad(v, ((0, 0), (0, tp), (0, 0), (0, 0))) if tp else v
    qq = qq.reshape(b, nq, qc, kv_heads, g, d)
    kk = kk.reshape(b, nk, kc, kv_heads, d)
    vv = vv.reshape(b, nk, kc, kv_heads, d)

    q_pos = jnp.arange(nq * qc, dtype=jnp.int32).reshape(nq, qc)
    k_pos = jnp.arange(nk * kc, dtype=jnp.int32).reshape(nk, kc)
    k_valid = (jnp.arange(nk * kc) < t).reshape(nk, kc)

    def kv_update(carry, qb, qp, kb, vb, kp, kval):
        acc, m, l = carry
        # matmuls stay in the input dtype with f32 accumulation (MXU-native)
        # — upcasting q/k/v to f32 before the dot doubles HBM traffic
        sc = jnp.einsum(
            "bqkgd,btkd->bkgqt", qb, kb,
            preferred_element_type=jnp.float32) * scale
        msk = kval[None, :]
        if causal:
            msk = msk & (qp[:, None] >= kp[None, :])
        if window is not None:
            msk = msk & (qp[:, None] - kp[None, :] < window)
        sc = jnp.where(msk[None, None, None], sc, NEG)
        m_new = jnp.maximum(m, sc.max(-1))
        p = jnp.exp(sc - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bkgqt,btkd->bkgqd", p.astype(vb.dtype), vb,
            preferred_element_type=jnp.float32)
        return acc, m_new, l

    def q_step(qb, qp, qi=None):
        acc = jnp.zeros((b, kv_heads, g, qc, d), jnp.float32)
        m = jnp.full((b, kv_heads, g, qc), NEG, jnp.float32)
        l = jnp.zeros((b, kv_heads, g, qc), jnp.float32)
        if flags.UNROLL:
            for ki in range(nk):
                if causal and qi is not None and \
                        ki * kc > qi * qc + qc - 1:
                    continue            # fully-masked block: skip (flash)
                acc, m, l = kv_update(
                    (acc, m, l), qb, qp, kk[:, ki], vv[:, ki],
                    k_pos[ki], k_valid[ki])
        else:
            def body(carry, ki):
                return kv_update(carry, qb, qp, *ki), None

            (acc, m, l), _ = jax.lax.scan(
                body, (acc, m, l),
                (jnp.moveaxis(kk, 1, 0), jnp.moveaxis(vv, 1, 0),
                 k_pos, k_valid))
        out = acc / jnp.maximum(l[..., None], 1e-30)   # (B,KV,G,qc,D)
        out = jnp.einsum("bkgqd->bqkgd", out).reshape(b, qc, h, d)
        return out.astype(q.dtype)

    if flags.UNROLL:
        outs = [q_step(qq[:, qi], q_pos[qi], qi) for qi in range(nq)]
        out = jnp.stack(outs, axis=1)
    else:
        _, outs = jax.lax.scan(
            lambda _, qi: (None, q_step(qi[0], qi[1])), None,
            (jnp.moveaxis(qq, 1, 0), q_pos))
        out = jnp.moveaxis(outs, 0, 1)
    out = out.reshape(b, nq * qc, h, d)
    return out[:, :s]


def _paged_cache_update(cache, k_new, v_new, posq, token_valid, new_len,
                        a: AttnArgs):
    """Append ``k_new``/``v_new`` through the page table
    (``_paged_write``), then build the position-ordered attention view:
    gathering the slot's table rebuilds a contiguous ``(B, n_slot_pages *
    P, KV, hd)`` view in which view index == absolute position, so
    validity is ``t <= posq`` (causal) and ``t < new_len`` (written);
    unassigned table entries gather page 0 but are masked by the length
    test.

    Returns ``(new_cache, k_read, v_read, valid)`` with f32 read views.
    """
    new_cache = _paged_write(cache, k_new, v_new, posq, token_valid, new_len)
    n_kv, hd = k_new.shape[2:]
    lead = (cache["layer"],) if "layer" in cache else ()
    pt = cache["page_table"]
    safe_pt = jnp.where(pt < 0, 0, pt)

    def read(key, feat):
        pool = new_cache[key]
        _, row = _page_rows(pool.shape[len(lead) + 1:], math.prod(feat))
        view = pool[lead + (safe_pt,)].reshape(pt.shape[0], -1, row)
        return view[..., :math.prod(feat)].reshape(view.shape[:2] + feat)

    if "k_scale_pages" in cache:
        k_read = _kv_dequant(read("k_pages", (n_kv, hd)),
                             read("k_scale_pages", (n_kv,)))
        v_read = _kv_dequant(read("v_pages", (n_kv, hd)),
                             read("v_scale_pages", (n_kv,)))
    else:
        k_read = read("k_pages", (n_kv, hd)).astype(jnp.float32)
        v_read = read("v_pages", (n_kv, hd)).astype(jnp.float32)
    t_pos = jnp.arange(k_read.shape[1], dtype=jnp.int32)[None, None, :]
    valid = (t_pos <= posq[:, :, None]) & (t_pos < new_len[:, None, None])
    if a.sliding_window is not None:
        valid &= posq[:, :, None] - t_pos < a.sliding_window
    return new_cache, k_read, v_read, valid


def _page_rows(page_shape, width: int) -> tuple[int, int]:
    """``(tokens per page, numbers per row)`` of a pool page of
    ``_page_shape``: a row per token, or the whole page as one row of
    ``width`` numbers a token."""
    if len(page_shape) == 2:
        return page_shape
    return page_shape[0] // width, width


def _paged_write(cache, k_new, v_new, posq, token_valid, new_len):
    """Append ``k_new``/``v_new`` through the page table; returns the new
    cache with ``len`` set to ``new_len``.

    The pool leaves (``POOL_KEYS``, pages of ``_page_shape``) are one
    layer's ``(n_pages, *page)``, or, when the cache also holds a
    ``layer`` index, the whole layer-stacked ``(L, n_pages, *page)`` pool
    that ``lm.decode_step`` carries through its layer loop: the write and
    the read then address ``[layer, page]`` in it, so the loop updates the
    (donated) pool in place and no layer's pool is sliced out or stacked
    again.

    Token (b, i) at absolute position ``posq[b, i]`` lands in pool page
    ``page_table[b, posq // P]`` at offset ``posq % P``.  The pages a
    row's new tokens fall in are gathered, the tokens written into them,
    and the pages written back whole: the chip scatters whole rows of its
    tiles at once but a part of a row one token at a time.  Invalid tokens
    (beyond ``seq_lens``, beyond the table, or aimed at an unassigned
    ``-1`` entry) are dropped, and a page holding no valid new token is
    not written back — a slot can never write outside its own assigned
    pages, which is what keeps shared (refcount > 1) pages immutable.  A
    page written back holds the bytes it held wherever no token landed,
    so the pool is the one a token-by-token write gives; a padded row's
    padding stays zero.
    """
    lead = (cache["layer"],) if "layer" in cache else ()
    pt = cache["page_table"]                       # (B, NP)
    b, np_ = pt.shape
    s = k_new.shape[1]
    pool_k = cache["k_pages"]
    n_pages = pool_k.shape[len(lead)]
    page, _ = _page_rows(pool_k.shape[len(lead) + 1:],
                         math.prod(k_new.shape[2:]))
    # the pages row b's new tokens can fall in, from that of its first one
    first = posq[:, :1] // page
    n_touch = min(np_, (s + page - 2) // page + 1)
    touch = first + jnp.arange(n_touch, dtype=jnp.int32)[None, :]
    tpid = jnp.take_along_axis(pt, jnp.clip(touch, 0, np_ - 1), axis=1)
    # a page that holds no valid new token aims at `n_pages`: dropped
    written = ((touch < np_) & (touch * page < new_len[:, None])
               & (posq[:, :1] < new_len[:, None]) & (tpid >= 0))
    tpid = jnp.where(written, tpid, n_pages)
    # token (b, i) within its row's touched pages; invalid ones aim past
    # their end and are dropped
    at = jnp.where(token_valid, posq - first * page, n_touch * page)
    rows = jnp.arange(b, dtype=jnp.int32)[:, None]

    def write(key, new):
        pool = cache[key]
        new = new.reshape(b, s, -1)
        _, row = _page_rows(pool.shape[len(lead) + 1:], new.shape[-1])
        new = jnp.pad(new, ((0, 0), (0, 0), (0, row - new.shape[-1])))
        pages = pool[lead + (jnp.minimum(tpid, n_pages - 1),)]
        pages = pages.reshape(b, n_touch * page, row).at[rows, at].set(
            cast(new, pool.dtype), mode="drop").reshape(pages.shape)
        return pool.at[lead + (tpid,)].set(pages, mode="drop")

    if "k_scale_pages" in cache:
        k_q, k_s = _kv_quantize(k_new)
        v_q, v_s = _kv_quantize(v_new)
        new = {"k_pages": write("k_pages", k_q),
               "v_pages": write("v_pages", v_q),
               "k_scale_pages": write("k_scale_pages", k_s),
               "v_scale_pages": write("v_scale_pages", v_s)}
    else:
        new = {"k_pages": write("k_pages", k_new),
               "v_pages": write("v_pages", v_new)}
    return {**cache, **new, "len": new_len}


def _decode_kernel_serves(cache, a: AttnArgs, s: int) -> bool:
    """Whether a paged step reads its pages with the paged-decode kernel
    (``repro.kernels.paged_decode``) in place of the gathered view: one
    token a slot, bf16 or f32 pages (int8 pages keep the gather), no
    sliding window, on the TPU.  Chunked prefill (``s > 1``) and the CPU
    keep the gather."""
    return (s == 1 and "k_scale_pages" not in cache
            and a.sliding_window is None and paged_decode_ops.on_tpu())


def attn_apply(p, x, a: AttnArgs, *, kv_x=None, positions=None, pos3=None,
               cache=None, compute_dtype=jnp.bfloat16, is_cross=False,
               seq_lens=None):
    """One attention layer, with or without a decode cache.

    Shapes: ``x`` is ``(B, S, d_model)``; returns ``(y, new_cache)`` with
    ``y`` ``(B, S, d_model)`` in ``compute_dtype``.

    Modes:
      * ``cache is None``     — full self/cross attention (train/prefill);
        ``new_cache`` is returned as None.
      * ``cache is not None`` — cached step: S == 1 is the decode step,
        S > 1 is chunked/batched prefill through the same cache plumbing.
        Each batch row advances from its **own** ``cache["len"]`` counter;
        rows never share positions.  Token (b, i) is written at absolute
        position ``len[b] + i`` and attends to row b's positions
        ``[0, len[b] + i]`` (window-clipped when ``sliding_window`` is
        set); afterwards ``len[b] += seq_lens[b]``.

    The cache may be **dense** or **paged** (see ``init_kv_cache`` for the
    layouts and their invariants) — the layout is detected from the cache
    keys and the attention math is identical: a position-masked softmax
    over a per-row contiguous view.  Dense scatters into the row's own
    strip using the ``slot_pos`` map (ring-wrapped under a sliding
    window); paged scatters through the page table and can therefore
    start from a nonzero ``len`` whose K/V live in pages shared with
    other rows (prefix reuse).

    ``seq_lens`` (B,) int32, cache mode only: number of *valid* new tokens
    per row (<= S).  Rows beyond their count write nothing, advance nothing,
    and are masked out of attention — this is what makes idle slots and
    ragged prompts harmless to their neighbours in a serving batch.  None
    means all S tokens are valid for every row.
    """
    b, s, _ = x.shape
    src = x if kv_x is None else kv_x
    q = apply_dense(p["q"], x)                     # (B,S,H,hd)
    scale = a.hd ** -0.5

    if cache is None:
        k = apply_dense(p["k"], src)
        v = apply_dense(p["v"], src)
        if kv_x is None:                           # rope only for self-attn
            q = _apply_rope(q, positions, pos3, a)
            k = _apply_rope(k, positions, pos3, a)
        t = k.shape[1]
        impl = a.impl
        if impl == "auto":
            impl = "xla_flash" if max(s, t) > 1024 else "einsum"
        if impl == "xla_flash":
            y = _xla_flash(q, k, v, scale,
                           causal=a.causal and kv_x is None,
                           window=a.sliding_window)
        elif impl == "pallas":
            from repro.kernels.flash_attention.ops import flash_attention
            y = flash_attention(
                jnp.moveaxis(q, 2, 1), jnp.moveaxis(k, 2, 1),
                jnp.moveaxis(v, 2, 1),
                causal=a.causal and kv_x is None)
            y = jnp.moveaxis(y, 1, 2)
        else:
            q_pos = jnp.arange(s)
            k_pos = jnp.arange(t)
            mask = jnp.ones((s, t), bool)
            if a.causal and kv_x is None:
                mask &= q_pos[:, None] >= k_pos[None, :] + (s - t) * 0
            if a.sliding_window is not None and kv_x is None:
                mask &= q_pos[:, None] - k_pos[None, :] < a.sliding_window
            y = _einsum_attn(q, k, v, mask[None, None, None], scale)
        out = jnp.einsum("bshd,hde->bse", y.astype(jnp.float32),
                         p["o"]["w"].astype(jnp.float32))
        return out.astype(compute_dtype), cache

    # ------------- decode / chunked prefill against the cache -------------
    cur = cache.get("len")                         # (B,) tokens per slot
    if not is_cross:
        if seq_lens is None:
            seq_lens = jnp.full((b,), s, jnp.int32)
        seq_lens = jnp.minimum(seq_lens.astype(jnp.int32), s)
        offs = jnp.arange(s, dtype=jnp.int32)[None, :]
        posq = cur[:, None] + offs                 # (B, S) absolute pos
        token_valid = offs < seq_lens[:, None]     # (B, S) ragged mask
        q = _apply_rope(q, posq, pos3, a)
        k_new = apply_dense(p["k"], src)
        v_new = apply_dense(p["v"], src)
        k_new = _apply_rope(k_new, posq, pos3, a)
        new_len = cur + seq_lens
        if _is_paged(cache):
            if _decode_kernel_serves(cache, a, s):
                new_cache = _paged_write(cache, k_new, v_new, posq,
                                         token_valid, new_len)
                y = paged_decode_ops.paged_decode(
                    q[:, 0], new_cache["k_pages"], new_cache["v_pages"],
                    cache["page_table"],
                    jnp.where(token_valid[:, 0], new_len, 0),
                    cache.get("layer"), n_kv=a.n_kv)[:, None]
            else:
                new_cache, k_read, v_read, valid = _paged_cache_update(
                    cache, k_new, v_new, posq, token_valid, new_len, a)
                sc = _gqa_scores(q.astype(jnp.float32), k_read) * scale
                sc = jnp.where(valid[:, None, None, :, :], sc, NEG)
                pr = jax.nn.softmax(sc, axis=-1)
                y = _gqa_out(pr, v_read)
            out = jnp.einsum("bshd,hde->bse", y,
                             p["o"]["w"].astype(jnp.float32))
            return out.astype(compute_dtype), new_cache
        size = cache["k"].shape[1]
        if _is_ring(cache, a):
            if s > size:
                # a wider chunk could retire in-window keys mid-chunk
                # (early queries would silently lose keys they may attend,
                # including their own) — prefill ring caches in chunks of
                # at most the window size
                raise ValueError(
                    f"chunked write of {s} tokens exceeds the ring cache "
                    f"size {size}; split the prefill into <= {size}-token "
                    f"chunks")
            # ring: also drop tokens a later token of the same call would
            # overwrite, so scatter indices stay unique per row
            idx = posq % size
            keep = token_valid & (posq >= new_len[:, None] - size)
        else:
            idx = posq
            keep = token_valid & (posq < size)
        # invalid writes aim at row `size` and are dropped by the scatter
        idx = jnp.where(keep, idx, size)
        rows = jnp.arange(b, dtype=jnp.int32)[:, None]
        quant = "k_scale" in cache
        if quant:
            k_q, k_s = _kv_quantize(k_new)
            v_q, v_s = _kv_quantize(v_new)
            kc = cache["k"].at[rows, idx].set(k_q, mode="drop")
            vc = cache["v"].at[rows, idx].set(v_q, mode="drop")
            k_sc = cache["k_scale"].at[rows, idx].set(k_s, mode="drop")
            v_sc = cache["v_scale"].at[rows, idx].set(v_s, mode="drop")
            extra = {"k_scale": k_sc, "v_scale": v_sc}
            k_read = _kv_dequant(kc, k_sc)
            v_read = _kv_dequant(vc, v_sc)
        else:
            kc = cache["k"].at[rows, idx].set(
                cast(k_new, cache["k"].dtype), mode="drop")
            vc = cache["v"].at[rows, idx].set(
                cast(v_new, cache["v"].dtype), mode="drop")
            extra = {}
            k_read = kc.astype(jnp.float32)
            v_read = vc.astype(jnp.float32)
        slot_pos = cache["slot_pos"].at[rows, idx].set(posq, mode="drop")
        new_cache = {**cache, "k": kc, "v": vc, "slot_pos": slot_pos,
                     "len": new_len, **extra}
        # (B, S, T): query i of row b sees row b's entries at positions
        # [0, posq[b, i]]; empty entries (pos -1) never score.
        valid = (slot_pos >= 0)[:, None, :] & \
            (slot_pos[:, None, :] <= posq[:, :, None])
        if a.sliding_window is not None:
            valid &= posq[:, :, None] - slot_pos[:, None, :] \
                < a.sliding_window
        sc = _gqa_scores(q.astype(jnp.float32), k_read) * scale
        sc = jnp.where(valid[:, None, None, :, :], sc, NEG)
        pr = jax.nn.softmax(sc, axis=-1)
        y = _gqa_out(pr, v_read)
    else:
        # cross-attention decode: static precomputed K/V in the cache
        sc = _gqa_scores(q.astype(jnp.float32),
                         cache["k"].astype(jnp.float32)) * scale
        pr = jax.nn.softmax(sc, axis=-1)
        y = _gqa_out(pr, cache["v"].astype(jnp.float32))
        new_cache = cache
    out = jnp.einsum("bshd,hde->bse", y, p["o"]["w"].astype(jnp.float32))
    return out.astype(compute_dtype), new_cache


# ==================================================================== ffn ==
def ffn_init(key, d_model: int, d_ff: int, *, act="swiglu",
             dtype=jnp.bfloat16, bias=False):
    ks = jax.random.split(key, 3)
    if act == "swiglu":
        pg, sg = dense_init(ks[0], d_model, d_ff, ("embed", "mlp"),
                            dtype=dtype)
        pu, su = dense_init(ks[1], d_model, d_ff, ("embed", "mlp"),
                            dtype=dtype)
        pd, sd = dense_init(ks[2], d_ff, d_model, ("mlp", "embed"),
                            dtype=dtype)
        return ({"gate": pg, "up": pu, "down": pd},
                {"gate": sg, "up": su, "down": sd})
    pu, su = dense_init(ks[0], d_model, d_ff, ("embed", "mlp"),
                        bias=bias, dtype=dtype)
    pd, sd = dense_init(ks[1], d_ff, d_model, ("mlp", "embed"),
                        bias=bias, dtype=dtype)
    return {"up": pu, "down": pd}, {"up": su, "down": sd}


def ffn_apply(p, x, *, act="swiglu"):
    if act == "swiglu":
        h = swiglu_combine(apply_dense(p["gate"], x),
                           apply_dense(p["up"], x))
    else:
        h = gelu(apply_dense(p["up"], x))
    return apply_dense(p["down"], h)


# ================================================================== block ==
def block_init(key, d_model: int, d_ff: int, a: AttnArgs, *,
               qkv_bias=False, act="swiglu", norm="rms",
               dtype=jnp.bfloat16, cross=False, moe_cfg=None):
    ks = jax.random.split(key, 6)
    params, specs = {}, {}
    params["ln1"], specs["ln1"] = norm_init(d_model, kind=norm)
    params["attn"], specs["attn"] = attn_init(
        ks[0], d_model, a, qkv_bias=qkv_bias, dtype=dtype)
    if cross:
        params["ln_x"], specs["ln_x"] = norm_init(d_model, kind=norm)
        params["xattn"], specs["xattn"] = attn_init(
            ks[1], d_model, a, qkv_bias=qkv_bias, dtype=dtype, cross=True)
    params["ln2"], specs["ln2"] = norm_init(d_model, kind=norm)
    if moe_cfg is not None:
        from repro.models.moe import moe_init
        params["moe"], specs["moe"] = moe_init(
            ks[2], d_model, moe_cfg, dtype=dtype)
    else:
        params["ffn"], specs["ffn"] = ffn_init(
            ks[2], d_model, d_ff, act=act, dtype=dtype,
            bias=(norm == "ln"))
    return params, specs


def block_apply(p, x, a: AttnArgs, *, enc_out=None, positions=None,
                pos3=None, caches=None, act="swiglu", norm="rms",
                moe_cfg=None, compute_dtype=jnp.bfloat16, seq_lens=None):
    """Returns (x, new_caches, aux_loss)."""
    new_caches = dict(caches) if caches is not None else None
    h, c = attn_apply(
        p["attn"], apply_norm(p["ln1"], x, kind=norm), a,
        positions=positions, pos3=pos3,
        cache=None if caches is None else caches.get("self"),
        compute_dtype=compute_dtype, seq_lens=seq_lens)
    if new_caches is not None:
        new_caches["self"] = c
    x = x + h
    if "xattn" in p:
        h, c = attn_apply(
            p["xattn"], apply_norm(p["ln_x"], x, kind=norm),
            dataclasses.replace(a, causal=False, use_rope=False),
            kv_x=enc_out, is_cross=True,
            cache=None if caches is None else caches.get("cross"),
            compute_dtype=compute_dtype)
        if new_caches is not None:
            new_caches["cross"] = c
        x = x + h
    aux = jnp.zeros((), jnp.float32)
    y = apply_norm(p["ln2"], x, kind=norm)
    if moe_cfg is not None:
        from repro.models.moe import moe_apply
        h, aux = moe_apply(p["moe"], y, moe_cfg)
    else:
        h = ffn_apply(p["ffn"], y, act=act)
    return x + h, new_caches, aux


# ================================================================== stack ==
def stack_init(key, n_layers: int, init_one):
    """Stack homogeneous layers: init each, stack leaves on a leading dim."""
    keys = jax.random.split(key, n_layers)
    ps, ss = zip(*(init_one(k) for k in keys))
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *ps)
    specs = jax.tree_util.tree_map(
        lambda s: ("layers",) + s, ss[0],
        is_leaf=lambda s: isinstance(s, tuple) and all(
            isinstance(e, (str, type(None))) for e in s))
    return stacked, specs


def stack_apply(stacked, x, apply_one, *, remat=True):
    """lax.scan over the layer dim; apply_one(params_l, x) -> (x, aux)."""

    def body(carry, layer_params):
        x, aux = carry
        x, a = apply_one(layer_params, x)
        return (x, aux + a), None

    if remat:
        body = jax.checkpoint(body)
    (x, aux), _ = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32)), stacked)
    return x, aux
