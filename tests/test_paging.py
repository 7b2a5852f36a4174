"""Unit tests for the paged KV substrate: PagePool refcount invariants,
PrefixTree match/insert/evict semantics, and agreement of the paged cache
layout with the dense one at the ``lm`` level (including int8 KV
quantization and shared-prefix tail prefill).  The two layouts are
different programs, so their logits agree within the float32 tolerance
of ``repro.models.reference``, not bit for bit.  The stacked pool that the
serving steps update in place is held to the per-layer, token-by-token
update it replaced, bit for bit, and its compiled programs to holding no
copy of the pool.
"""
import contextlib
import dataclasses
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as configs
from repro.configs.base import reduce
from repro.models import lm
from repro.models.reference import LOGIT_TOL, logit_error
from repro.serving import PagePool, PrefixTree


# ============================================================== PagePool ==
def test_pool_alloc_is_all_or_nothing():
    pool = PagePool(4, 8)
    assert pool.alloc(5) is None
    assert pool.free_pages == 4          # failed alloc took nothing
    got = pool.alloc(3)
    assert len(got) == 3 and len(set(got)) == 3
    assert pool.free_pages == 1 and pool.used_pages == 3
    assert (pool.refs[got] == 1).all()


def test_pool_release_returns_pages_at_zero_refcount():
    pool = PagePool(4, 8)
    (a, b) = pool.alloc(2)
    pool.retain([a])                     # a now held twice
    assert pool.release([a, b]) == 1     # only b freed
    assert pool.refs[a] == 1 and pool.refs[b] == 0
    assert pool.release([a]) == 1
    assert pool.free_pages == 4


def test_pool_refuses_refcount_underflow_and_dead_retain():
    pool = PagePool(2, 8)
    (a,) = pool.alloc(1)
    pool.release([a])
    with pytest.raises(ValueError):
        pool.release([a])
    with pytest.raises(ValueError):
        pool.retain([a])


# ============================================================ PrefixTree ==
def _toks(*vals):
    return np.asarray(vals, np.int32)


def test_tree_match_walks_full_pages_and_caps_before_last_token():
    pool = PagePool(8, 2)
    tree = PrefixTree(pool)
    prompt = _toks(1, 2, 3, 4, 5, 6)
    pages = pool.alloc(3)
    tree.insert(prompt, pages)
    assert (pool.refs[pages] == 2).all()          # slot + tree
    # identical prompt: only 2 of its 3 cached pages may be shared —
    # the final token is always left for the tail prefill
    got, n = tree.match(prompt)
    assert got == pages[:2] and n == 4
    assert (pool.refs[pages[:2]] == 3).all()      # match retained for us
    # divergence mid-prompt stops the walk at the last matching page
    got2, n2 = tree.match(_toks(1, 2, 9, 9, 5, 6, 7))
    assert got2 == pages[:1] and n2 == 2


def test_tree_insert_dedupes_existing_runs():
    pool = PagePool(8, 2)
    tree = PrefixTree(pool)
    first = pool.alloc(2)
    assert tree.insert(_toks(1, 2, 3, 4), first) == 2
    dup = pool.alloc(2)                  # same tokens, private pages
    assert tree.insert(_toks(1, 2, 3, 4), dup) == 0
    assert tree.nodes == 2
    assert (pool.refs[dup] == 1).all()   # tree kept the canonical pages


def test_tree_evicts_lru_leaves_but_never_referenced_pages():
    pool = PagePool(4, 2)
    tree = PrefixTree(pool)
    hot = pool.alloc(2)                  # an "active request"'s pages
    tree.insert(_toks(1, 2, 3, 4), hot)  # refs == 2: slot + tree
    cold = pool.alloc(2)
    tree.insert(_toks(5, 6, 7, 8), cold)
    pool.release(cold)                   # its request retired: tree-only
    # pool is full (refs: hot 2,2 cold 1,1); evicting 10 can only
    # reclaim the two tree-only cold pages, deepest leaf first
    assert tree.evict(10) == 2
    assert tree.nodes == 2
    assert (pool.refs[hot] == 2).all()   # pinned pages survived
    assert pool.free_pages == 2
    # after the request retires, its subtree becomes evictable
    pool.release(hot)
    assert tree.evict(10) == 2
    assert tree.nodes == 0 and pool.free_pages == 4


def test_tree_eviction_prefers_least_recently_used():
    pool = PagePool(4, 2)
    tree = PrefixTree(pool)
    a = pool.alloc(1)
    tree.insert(_toks(1, 2), a)
    b = pool.alloc(1)
    tree.insert(_toks(3, 4), b)
    pool.release(a)
    pool.release(b)
    got, _ = tree.match(_toks(1, 2, 0))  # touch a: b becomes LRU
    pool.release(got)                    # drop the match's reference
    assert tree.evict(1) == 1
    assert pool.refs[b[0]] == 0          # b evicted, a kept
    assert pool.refs[a[0]] == 1


# ================================================== paged ~= dense logits ==
@pytest.fixture(scope="module")
def smollm():
    cfg = reduce(configs.get("smollm_135m"))
    params, _ = lm.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _seq_table(start, n):
    return jnp.asarray(list(range(start, start + n)), jnp.int32)


def _assert_logits_close(got, want):
    # float32 config: only the summation order differs between layouts
    err = logit_error(got, want)
    assert err <= LOGIT_TOL["float32"], err


def _decode_compare(cfg, params, dense, paged, steps, t0):
    tok = t0
    for _ in range(steps):
        ld, dense = lm.decode_step(params, tok, dense, cfg)
        lp, paged = lm.decode_step(params, tok, paged, cfg)
        _assert_logits_close(lp, ld)
        # both layouts are fed the same token, so a near-tie argmax
        # cannot send them down different sequences
        tok = jnp.argmax(ld[:, 0], -1)[:, None].astype(jnp.int32)


@pytest.mark.parametrize("quant", [False, True])
def test_paged_prefill_and_decode_match_dense_bitwise(smollm, quant):
    """Same tokens through the dense and the paged layout (max_len not a
    page multiple, so the paged view is wider) must produce the same
    logits, within the float32 tolerance, at prefill and every decode
    step."""
    import dataclasses
    cfg, params = smollm
    cfg = dataclasses.replace(cfg, kv_quant=quant)
    rng = np.random.default_rng(5)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 6)), jnp.int32)
    max_len = 13                          # ceil(13/4)=4 pages per slot
    dense = lm.init_caches(cfg, 2, max_len)
    paged = lm.init_caches(cfg, 2, max_len, paged=True, page_size=4,
                           n_pages=8)
    paged = lm.install_pages(paged, 0, _seq_table(0, 4), 0, cfg)
    paged = lm.install_pages(paged, 1, _seq_table(4, 4), 0, cfg)
    ld, dense = lm.prefill_into(params, toks, dense, cfg)
    lp, paged = lm.prefill_into(params, toks, paged, cfg)
    _assert_logits_close(lp, ld)
    _decode_compare(cfg, params, dense, paged, 5,
                    jnp.argmax(ld, -1)[:, None].astype(jnp.int32))


def test_shared_prefix_tail_prefill_matches_solo_dense(smollm):
    """Slot B seeded with slot A's full prefix pages and prefilled only on
    its tail must match a solo dense prefill of the whole prompt — and
    B's writes must not disturb the shared pages (A keeps decoding in
    step with the dense reference afterwards)."""
    cfg, params = smollm
    P = 4
    rng = np.random.default_rng(7)
    shared = rng.integers(0, cfg.vocab_size, 9).astype(np.int32)
    pa = np.concatenate([shared,
                         rng.integers(0, cfg.vocab_size, 3)]).astype(
        np.int32)                                           # 12 tokens
    pb = np.concatenate([shared,
                         rng.integers(0, cfg.vocab_size, 4)]).astype(
        np.int32)                                           # 13 tokens
    max_len = 20
    ref = lm.init_caches(cfg, 2, max_len)
    toks = np.zeros((2, 13), np.int32)
    toks[0, :12], toks[1] = pa, pb
    lr, ref = lm.prefill_into(params, jnp.asarray(toks), ref, cfg,
                              seq_lens=jnp.asarray([12, 13], jnp.int32))

    paged = lm.init_caches(cfg, 2, max_len, paged=True, page_size=P,
                           n_pages=12)
    paged = lm.install_pages(paged, 0, _seq_table(0, 5), 0, cfg)
    ta = np.zeros((2, 16), np.int32)
    ta[0, :12] = pa
    la, paged = lm.prefill_into(params, jnp.asarray(ta), paged, cfg,
                                seq_lens=jnp.asarray([12, 0], jnp.int32))
    _assert_logits_close(la[0], lr[0])
    # B shares A's first two pages (8 tokens), gets private tail pages
    paged = lm.install_pages(
        paged, 1, jnp.asarray([0, 1, 5, 6, 7], jnp.int32), 8, cfg)
    tb = np.zeros((2, 8), np.int32)
    tb[1, :5] = pb[8:]
    lb, paged = lm.prefill_into(params, jnp.asarray(tb), paged, cfg,
                                seq_lens=jnp.asarray([0, 5], jnp.int32))
    _assert_logits_close(lb[1], lr[1])
    # both rows keep decoding in lockstep with the dense reference
    t0 = jnp.stack([jnp.argmax(la[0]), jnp.argmax(lb[1])]).astype(
        jnp.int32)[:, None]
    _decode_compare(cfg, params, ref, paged, 4, t0)


def test_paged_reset_slot_clears_table_not_pool(smollm):
    """reset_slot on a paged cache empties ONE row's table/len and leaves
    the pool untouched — shared pages must survive a neighbour's reset."""
    cfg, params = smollm
    paged = lm.init_caches(cfg, 2, 8, paged=True, page_size=4, n_pages=4)
    paged = lm.install_pages(paged, 0, _seq_table(0, 2), 0, cfg)
    paged = lm.install_pages(paged, 1, _seq_table(2, 2), 0, cfg)
    toks = jnp.asarray(np.arange(8, dtype=np.int32).reshape(2, 4) + 1)
    _, paged = lm.prefill_into(params, toks, paged, cfg)
    before = np.asarray(paged["self"]["k_pages"]).copy()
    paged = lm.reset_slot(paged, 1, cfg)
    c = paged["self"]
    assert (np.asarray(c["len"])[:, 0] == 4).all()
    assert (np.asarray(c["len"])[:, 1] == 0).all()
    assert (np.asarray(c["page_table"])[:, 1] == -1).all()
    assert (np.asarray(c["page_table"])[:, 0, :2] == [0, 1]).all()
    np.testing.assert_array_equal(np.asarray(c["k_pages"]), before)


# ================================ in-place pool == today's per-layer rule ==
def _token_paged_update(cache, k_new, v_new, posq, token_valid, new_len, a):
    """The single-layer paged write and read as they were before the pool
    rode in the layer loop's carry, on one layer's pool: every valid token
    scattered into its page at its offset, then the slot's pages
    gathered."""
    from repro.models.transformer import _kv_dequant, _kv_quantize
    pt = cache["page_table"]
    b, np_ = pt.shape
    n_kv, hd = k_new.shape[2:]
    n_pages = cache["k_pages"].shape[0]
    # a row per token (its width padded to whole lanes) or a page per row
    page = (cache["k_pages"].shape[1] if cache["k_pages"].ndim == 3
            else cache["k_pages"].shape[1] // (n_kv * hd))
    pid = jnp.take_along_axis(pt, jnp.clip(posq // page, 0, np_ - 1), 1)
    keep = token_valid & (posq < np_ * page) & (pid >= 0)
    pid, off = jnp.where(keep, pid, n_pages), posq % page
    safe = jnp.where(pt < 0, 0, pt)
    out = {}

    def write_read(key, new):
        pool = cache[key].reshape(n_pages, page, -1)
        flat = new.reshape(new.shape[:2] + (-1,))
        w = flat.shape[-1]
        flat = jnp.pad(flat, ((0, 0), (0, 0), (0, pool.shape[-1] - w)))
        pool = pool.at[pid, off].set(flat.astype(pool.dtype), mode="drop")
        out[key] = pool.reshape(cache[key].shape)
        return jnp.take(pool, safe, axis=0).reshape(
            b, np_ * page, -1)[..., :w].reshape(
                (b, np_ * page) + new.shape[2:])

    if "k_scale_pages" in cache:
        (k_q, k_s), (v_q, v_s) = _kv_quantize(k_new), _kv_quantize(v_new)
        k_read = _kv_dequant(write_read("k_pages", k_q),
                             write_read("k_scale_pages", k_s))
        v_read = _kv_dequant(write_read("v_pages", v_q),
                             write_read("v_scale_pages", v_s))
    else:
        k_read = write_read("k_pages", k_new).astype(jnp.float32)
        v_read = write_read("v_pages", v_new).astype(jnp.float32)
    t = jnp.arange(np_ * page)[None, None, :]
    valid = (t <= posq[:, :, None]) & (t < new_len[:, None, None])
    return {**cache, **out, "len": new_len}, k_read, v_read, valid


def _layer_loop_oracle(params, tokens, caches, cfg, seq_lens):
    """The paged step as it was before the pool rode in the loop carry: a
    loop over the layers, each given its own slice of every cache leaf as
    a scanned input, written token by token (``_token_paged_update``) and
    stacked again as a scanned output.  Returns ``(logits, caches)``.

    The loop is ``lm._scan``, as the step's own: a ``lax.scan``, or a
    python loop under ``flags.UNROLL``.  A python loop checked against the
    scanned step would compare two program structures, and the CPU
    backend rounds a bfloat16 intermediate in one and not the other."""
    from repro.models import transformer
    a = lm.attn_args(cfg)

    def layer(x, inp):
        lp, c = inp
        x, nc, _ = transformer.block_apply(
            lp, x, a, caches={"self": c}, act=cfg.act, norm=cfg.norm,
            moe_cfg=cfg.moe, compute_dtype=lm._cdt(cfg), seq_lens=seq_lens)
        return x, nc["self"]

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(transformer, "_paged_cache_update", _token_paged_update)
        x, new = lm._scan(layer, lm._embed(params, tokens, cfg),
                          (params["layers"], caches["self"]))
    return lm._unembed(params, x, cfg), {"self": new}


def _step_case(cfg, params, case):
    """(caches, tokens, seq_lens) of one step after a ragged prefill that
    leaves rows mid-page (4-token pages): a decode of every row, a
    16-token tail prefill over another row's two full prefix pages while
    the other rows idle, or a ragged step with an idle row whose widest
    row writes into three pages."""
    B, P, max_len = 3, 4, 40
    n_slot = max_len // P
    caches = lm.init_caches(cfg, B, max_len, paged=True, page_size=P,
                            n_pages=B * n_slot + 4)
    for b in range(B):
        caches = lm.install_pages(caches, b, _seq_table(b * n_slot, n_slot),
                                  0, cfg)
    rng = np.random.default_rng(11)
    first = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, 12)), jnp.int32)
    _, caches = jax.jit(lambda c: lm.prefill_into(
        params, first, c, cfg,
        seq_lens=jnp.asarray([12, 9, 10], jnp.int32)))(caches)
    if case == "decode":
        tok = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, 1)), jnp.int32)
        return caches, tok, jnp.ones((B,), jnp.int32)
    if case == "tail":
        # row 2 starts over row 0's first two (full) pages: 8 shared tokens
        row = np.full(n_slot, -1, np.int32)
        row[:2] = [0, 1]
        row[2:7] = np.arange(2 * n_slot, 2 * n_slot + 5)
        caches = lm.install_pages(caches, 2, jnp.asarray(row), 8, cfg)
        tok = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, 16)),
                          jnp.int32)
        return caches, tok, jnp.asarray([0, 0, 16], jnp.int32)
    # row 0 writes positions 12..16 (pages 3, 4), row 1 9..16 (2, 3, 4)
    tok = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, 8)), jnp.int32)
    return caches, tok, jnp.asarray([5, 8, 0], jnp.int32)


# An int8 pool's float32 scales, and the float32 logits of its config, are
# held to an absolute 1e-6, not bit for bit: the CPU backend fuses each
# program's projections and quantisation (``_kv_quantize``) into different
# kernels and may keep a float32 intermediate unrounded in one of them, so
# a scale's last bits differ and the difference carries through the layers
# (at most 3.1e-8 on scales of ~1e-2 and 6.7e-7 on logits, measured on
# these cases).  Its int8 values, and the bf16 pool, cache and logits of
# the bf16 config, are held bit for bit.
INT8_TOL = 1e-6


@pytest.mark.parametrize("row", ["page", "token"])
@pytest.mark.parametrize("case", ["decode", "tail", "ragged"])
@pytest.mark.parametrize("pool", ["bf16", "int8"])
def test_in_place_pool_matches_per_layer_update(pool, case, row):
    """The donated serving steps, which carry the stacked pool through the
    layer loop and write whole pages in place, give the logits and every
    cache leaf of the same loop over per-layer slices of the pool written
    token by token; with the layers scanned and with them unrolled.  Two
    KV heads: ``n_kv * hd`` 32 holds an int8 page as one row and a bf16
    token as a row padded to 128 lanes, 128 a row per token."""
    from repro.launch.serve import serving_fns
    from repro.models import flags
    cfg = dataclasses.replace(reduce(configs.get("smollm_135m")), n_layers=3,
                              head_dim=16 if row == "page" else 64)
    cfg = (dataclasses.replace(cfg, compute_dtype="bfloat16")
           if pool == "bf16" else dataclasses.replace(cfg, kv_quant=True))
    params = lm.init_params(cfg, jax.random.PRNGKey(3))[0]
    caches, tok, sl = _step_case(cfg, params, case)
    assert caches["self"]["k_pages"].ndim == (
        3 if row == "page" and pool == "int8" else 4)
    for unroll in (False, True):
        with flags.unrolled() if unroll else contextlib.nullcontext():
            want, want_c = jax.jit(
                lambda c: _layer_loop_oracle(params, tok, c, cfg, sl))(caches)
            prefill, decode = serving_fns(cfg, donate=True)
            got, got_c = decode(params, tok, jax.tree_util.tree_map(
                jnp.copy, caches), sl)
            got_last, got_c2 = prefill(params, tok, jax.tree_util.tree_map(
                jnp.copy, caches), sl)
        want_last = jnp.take_along_axis(
            want, jnp.maximum(sl - 1, 0)[:, None, None], axis=1)[:, 0]
        tol = 0 if pool == "bf16" else INT8_TOL
        for g, w in ((got, want), (got_last, want_last)):
            np.testing.assert_allclose(np.asarray(g, np.float32),
                                       np.asarray(w, np.float32),
                                       rtol=0, atol=tol)
        for gc in (got_c, got_c2):
            assert sorted(gc["self"]) == sorted(want_c["self"])
            for key, leaf in want_c["self"].items():
                assert gc["self"][key].dtype == leaf.dtype, key
                np.testing.assert_allclose(
                    np.asarray(gc["self"][key]), np.asarray(leaf), rtol=0,
                    atol=tol if leaf.dtype == jnp.float32 else 0,
                    err_msg=key)


@pytest.mark.parametrize("row", ["page", "token"])
@pytest.mark.parametrize("pool", ["bf16", "int8"])
def test_layer_indexed_update_matches_one_layer_pool(pool, row):
    """``_paged_cache_update`` at ``[layer, page]`` of a stacked pool, and
    on one layer's pool with no index, gives from the same new keys and
    values the pool and the views of today's token-by-token write on that
    layer's slice (row 0 from a page start, row 1 idle, row 2 from
    mid-page across three pages)."""
    from repro.models.transformer import _paged_cache_update
    cfg = dataclasses.replace(reduce(configs.get("smollm_135m")), n_layers=3,
                              head_dim=16 if row == "page" else 64,
                              kv_quant=pool == "int8",
                              compute_dtype="bfloat16")
    a = lm.attn_args(cfg)
    caches = lm.init_caches(cfg, 3, 40, paged=True, page_size=4, n_pages=34)
    for b in range(3):
        caches = lm.install_pages(caches, b, _seq_table(b * 10, 10), 0, cfg)
    c = jax.tree_util.tree_map(lambda v: v + 1,
                               caches["self"])   # pages hold old bytes
    new = jnp.asarray(np.random.default_rng(2).normal(
        size=(3, 8, 2, cfg.hd)), jnp.bfloat16)
    posq = jnp.asarray([[0], [5], [3]], jnp.int32) + jnp.arange(8)
    valid = jnp.arange(8)[None] < jnp.asarray([[8], [0], [8]])
    new_len = posq[:, 0] + valid.sum(1).astype(jnp.int32)

    def per_layer(update, i, one):
        nc, k_read, v_read, _ = update(one, new * (i + 1), -new, posq, valid,
                                       new_len, a)
        return {k: nc[k] for k in nc if "pages" in k}, k_read, v_read

    def stacked(c):
        pool = {k: v for k, v in c.items() if "pages" in k}
        reads = []
        for i in range(3):
            one = {"page_table": c["page_table"][i], "len": c["len"][i],
                   **pool, "layer": jnp.int32(i)}
            pool, *r = per_layer(_paged_cache_update, i, one)
            reads.append(r)
        return pool, reads

    def sliced(update, c):
        pools, reads = [], []
        for i in range(3):
            one = jax.tree_util.tree_map(lambda v: v[i], c)
            p, *r = per_layer(update, i, one)
            pools.append(p)
            reads.append(r)
        return jax.tree_util.tree_map(lambda *v: jnp.stack(v), *pools), reads

    want = jax.jit(lambda c: sliced(_token_paged_update, c))(c)
    for got in (jax.jit(stacked)(c),
                jax.jit(lambda c: sliced(_paged_cache_update, c))(c)):
        for g, w in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def pool_copies(hlo: str, pool_shape) -> list[str]:
    """The ``copy`` ops of an optimised HLO text whose result holds as many
    elements as the stacked pool ``pool_shape`` or as one layer's pool,
    whatever their shape."""
    sizes = {math.prod(pool_shape), math.prod(pool_shape[1:])}
    return [ln for ln in hlo.splitlines()
            if (m := re.search(r"= \w+\[([\d,]+)\]\{[^}]*\} copy\(", ln))
            and math.prod(map(int, m.group(1).split(","))) in sizes]


@pytest.mark.parametrize("unroll", [False, True])
def test_donated_serving_steps_never_copy_the_pool(unroll):
    """The compiled, donated prefill and decode programs hold no copy of
    the stacked pool or of one layer's pool: each layer writes the pool
    the loop carries, in the donated buffer; also with the layer loop
    unrolled (``flags.UNROLL``)."""
    from repro.launch.serve import serving_fns
    from repro.models import flags
    cfg = reduce(configs.get("smollm_135m"))
    params = jax.eval_shape(lambda: lm.init_params(
        cfg, jax.random.PRNGKey(0))[0])
    # 40 pages of 4 against 4 rows of 8 x 4 tokens: no token block and no
    # view holds as many elements as a pool
    caches = jax.eval_shape(lambda: lm.init_caches(
        cfg, 4, 32, paged=True, page_size=4, n_pages=40))
    sl = jax.ShapeDtypeStruct((4,), jnp.int32)
    with flags.unrolled() if unroll else contextlib.nullcontext():
        for fn, width in zip(serving_fns(cfg, donate=True), (8, 1)):
            tok = jax.ShapeDtypeStruct((4, width), jnp.int32)
            hlo = fn.lower(params, tok, caches, sl).compile().as_text()
            copies = pool_copies(hlo, caches["self"]["k_pages"].shape)
            assert not copies, copies
