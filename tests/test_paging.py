"""Unit tests for the paged KV substrate: PagePool refcount invariants,
PrefixTree match/insert/evict semantics, and agreement of the paged cache
layout with the dense one at the ``lm`` level (including int8 KV
quantization and shared-prefix tail prefill).  The two layouts are
different programs, so their logits agree within the float32 tolerance
of ``repro.models.reference``, not bit for bit.
"""
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
