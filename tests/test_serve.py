"""Continuous-batching serving tests: paged KV with prefix-tree reuse.

The acceptance bar for the serving path is *bit-equivalence*: whatever mix
of staggered admissions, ragged prompt lengths, idle slots, microbatch
shards, slot reuse, prefix sharing, and pool eviction the server sees,
every request's greedy tokens must equal its single-request reference
decode exactly.  ``solo_reference`` runs on the DENSE cache layout while
``Server`` defaults to the paged one, so every assertion here is a
cross-layout oracle.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as configs
from repro.configs.base import reduce
from repro.launch.serve import Request, Server, drain, solo_reference
from repro.models import lm
from repro.models.reference import LOGIT_TOL, logit_error


@pytest.fixture(scope="module")
def smollm():
    cfg = reduce(configs.get("smollm_135m"))
    params, _ = lm.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _prompts(cfg, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
            for n in lengths]


def _drain(server, pending):
    return drain(server, pending, max_iters=500)


@pytest.mark.parametrize("microbatches", [1, 2])
def test_staggered_ragged_admission_bit_identical(smollm, microbatches):
    """Requests with different prompt lengths admitted mid-decode (one
    every 2 ticks) must each decode bit-identically to their solo
    reference — per-slot positions mean neighbours can't shift them."""
    cfg, params = smollm
    gen = 6
    lengths = [3, 9, 5, 2, 7]
    max_len = max(lengths) + gen + 2
    server = Server(cfg, params, batch=2, max_len=max_len,
                    microbatches=microbatches)
    pending = [Request(i, p, gen, arrival=2 * i)
               for i, p in enumerate(_prompts(cfg, lengths))]
    done = _drain(server, pending)
    assert len(done) == len(lengths)
    for r in sorted(done, key=lambda r: r.rid):
        ref = solo_reference(cfg, params, r.prompt, gen, max_len)
        assert r.out == ref, (r.rid, r.out, ref)


def test_slot_reuse_fixed_max_len_requests_exceed_batch(smollm):
    """requests >> batch through a cache sized for ONE sequence (no
    admission-wave scaling): slot reuse must reset per-slot positions, so
    late waves are bit-identical to their references too."""
    cfg, params = smollm
    gen, plen, n_req, batch = 5, 6, 9, 2
    max_len = plen + gen + 1          # deliberately wave-independent
    server = Server(cfg, params, batch=batch, max_len=max_len)
    pending = [Request(i, p, gen)
               for i, p in enumerate(_prompts(cfg, [plen] * n_req, seed=7))]
    done = _drain(server, pending)
    assert len(done) == n_req
    for r in done:
        ref = solo_reference(cfg, params, r.prompt, gen, max_len)
        assert r.out == ref, (r.rid, r.out, ref)


def test_eos_aware_retirement(smollm):
    """A request sampling ``eos_id`` retires immediately and frees its
    slot; its (truncated) tokens still match the reference prefix."""
    cfg, params = smollm
    gen = 8
    (prompt,) = _prompts(cfg, [5], seed=3)
    max_len = 5 + gen + 2
    ref = solo_reference(cfg, params, prompt, gen, max_len)
    eos = ref[3]                      # forces retirement mid-stream
    cut = ref.index(eos) + 1
    server = Server(cfg, params, batch=2, max_len=max_len, eos_id=eos)
    follower = _prompts(cfg, [4], seed=11)[0]
    done = _drain(server, [Request(0, prompt, gen),
                           Request(1, follower, gen)])
    by = {r.rid: r for r in done}
    assert by[0].out == ref[:cut]
    # the surviving neighbour is untouched by the early retirement
    ref1 = solo_reference(cfg, params, follower, gen, max_len,
                          eos_id=eos)
    assert by[1].out == ref1


def test_admit_rejects_oversized_request_loudly(smollm):
    """prompt + generation exceeding max_len must raise at admission —
    overflowing KV writes would otherwise be silently dropped (and the
    solo reference, truncating identically, couldn't catch it)."""
    cfg, params = smollm
    server = Server(cfg, params, batch=2, max_len=8)
    (prompt,) = _prompts(cfg, [6])
    with pytest.raises(ValueError, match="max_len"):
        server.admit(Request(0, prompt, max_new=4))   # needs 6 + 3 > 8


def test_idle_slots_frozen_between_admissions(smollm):
    """Slots with no request must not consume cache length while their
    shard decodes (the shared-position bug this PR removes)."""
    cfg, params = smollm
    server = Server(cfg, params, batch=2, max_len=16)
    (prompt,) = _prompts(cfg, [4])
    server.admit(Request(0, prompt, 6))
    for _ in range(3):
        server.tick()
    lens = np.asarray(server.caches[0]["self"]["len"])   # (L, B)
    assert (lens[:, 0] == 4 + 3).all()    # active slot advanced
    assert (lens[:, 1] == 0).all()        # idle slot untouched


def test_reset_slot_zeroes_one_row_only(smollm):
    cfg, params = smollm
    caches = lm.init_caches(cfg, 2, 12)
    toks = jnp.asarray(np.arange(8, dtype=np.int32).reshape(2, 4) + 1)
    _, caches = lm.prefill_into(params, toks, caches, cfg)
    caches = lm.reset_slot(caches, 1, cfg)
    c = caches["self"]
    assert (np.asarray(c["len"])[:, 0] == 4).all()
    assert (np.asarray(c["len"])[:, 1] == 0).all()
    assert (np.asarray(c["slot_pos"])[:, 1] == -1).all()
    assert np.asarray(c["k"], np.float32)[:, 0].any()        # row 0 kept
    assert not np.asarray(c["k"], np.float32)[:, 1].any()    # row 1 zeroed


def test_ring_cache_rejects_over_wide_chunk():
    """A chunked write wider than the ring would retire in-window keys
    mid-chunk; the cache plumbing must refuse it loudly."""
    from repro.models.transformer import AttnArgs, attn_init, attn_apply, \
        init_kv_cache
    a = AttnArgs(n_heads=2, n_kv=2, hd=8, sliding_window=4)
    params, _ = attn_init(jax.random.PRNGKey(0), 16, a)
    cache = init_kv_cache(1, 32, a, jnp.float32, ring=True)
    assert cache["k"].shape[1] == 4                  # window-sized ring
    x = jnp.zeros((1, 6, 16), jnp.float32)
    with pytest.raises(ValueError, match="ring cache"):
        attn_apply(params, x, a, cache=cache)


def test_prefix_reuse_bit_identical_prefills_tail_only(smollm):
    """Two requests sharing a long prefix: the second must decode
    bit-identically to its solo reference while its prefill covers only
    the unshared tail (observable via per-request/server stats)."""
    cfg, params = smollm
    gen, P = 6, 4
    rng = np.random.default_rng(2)
    shared = rng.integers(0, cfg.vocab_size, 9).astype(np.int32)
    pa = np.concatenate([shared, rng.integers(0, cfg.vocab_size, 3)
                         .astype(np.int32)])          # 12 tokens
    pb = np.concatenate([shared, rng.integers(0, cfg.vocab_size, 2)
                         .astype(np.int32)])          # 11 tokens
    max_len = len(pa) + gen + 2
    server = Server(cfg, params, batch=2, max_len=max_len, page_size=P)
    done = _drain(server, [Request(0, pa, gen),
                           Request(1, pb, gen, arrival=2)])
    by = {r.rid: r for r in done}
    for r in done:
        ref = solo_reference(cfg, params, r.prompt, gen, max_len)
        assert r.out == ref, (r.rid, r.out, ref)
    # request 0 primed the tree; request 1 shares floor(9 / 4) = 2 full
    # pages (8 tokens) and prefills only its 3-token tail
    assert by[0].shared_len == 0 and by[0].prefill_len == len(pa)
    assert by[1].shared_len == 8
    assert by[1].prefill_len == len(pb) - 8
    st = server.stats()
    assert st["prefix_hits"] == 1 and st["prefill_tokens_skipped"] == 8
    assert st["prefill_tokens"] == len(pa) + len(pb) - 8


def test_pool_exhaustion_defers_and_never_reclaims_referenced_pages(smollm):
    """Fill the page pool with an active request: the follower's admission
    must be deferred (its pages are pinned — refcounted pages are never
    evicted) and succeed only after retirement, still bit-identically."""
    cfg, params = smollm
    gen, P = 6, 4
    max_len = 12                      # 3 pages per slot worst-case
    pa, pb = _prompts(cfg, [6, 6], seed=13)
    # pool of 4: request A takes 3 pages (1 of them also retained by the
    # tree after insert), leaving 1 free — B needs 3 and must wait
    server = Server(cfg, params, batch=2, max_len=max_len, page_size=P,
                    pool_pages=4)
    done = _drain(server, [Request(0, pa, gen), Request(1, pb, gen)])
    assert server.deferred_admissions > 0
    for r in done:
        ref = solo_reference(cfg, params, r.prompt, gen, max_len)
        assert r.out == ref, (r.rid, r.out, ref)


def test_slot_churn_releases_pages(smollm):
    """Many short requests through few slots: retirement must release
    page references (the reset_slot page-leak fix) — afterwards the only
    pages still in use are the prefix tree's, and the pool never ran
    dry mid-run."""
    cfg, params = smollm
    gen, P, n_req = 3, 4, 12
    max_len = 10
    server = Server(cfg, params, batch=2, max_len=max_len, page_size=P)
    pending = [Request(i, p, gen)
               for i, p in enumerate(_prompts(cfg, [5] * n_req, seed=17))]
    done = _drain(server, pending)
    assert len(done) == n_req
    assert server.deferred_admissions == 0     # churn never starved
    # all slot references are gone; only tree-retained pages remain
    assert all(p is None for p in server.slot_pages)
    assert server.pages_in_use == sum(t.nodes for t in server.trees)
    for pool in server.pools:
        assert (pool.refs[pool.refs > 0] == 1).all()
    for r in done:
        ref = solo_reference(cfg, params, r.prompt, gen, max_len)
        assert r.out == ref, (r.rid, r.out, ref)


def test_dense_fallback_still_serves(smollm):
    """paged=False keeps the PR 2 dense path alive (and bit-identical)."""
    cfg, params = smollm
    gen = 5
    max_len = 6 + gen + 1
    server = Server(cfg, params, batch=2, max_len=max_len, paged=False)
    pending = [Request(i, p, gen)
               for i, p in enumerate(_prompts(cfg, [6, 4, 5], seed=23))]
    done = _drain(server, pending)
    assert not server.stats()["paged"]
    for r in done:
        ref = solo_reference(cfg, params, r.prompt, gen, max_len)
        assert r.out == ref, (r.rid, r.out, ref)


def test_prefill_into_matches_forward_last_logits(smollm):
    """The cache-writing batched prefill must agree with the full-sequence
    forward at the last position.  They are different programs (cached
    vs whole-sequence attention), so within the float32 tolerance."""
    cfg, params = smollm
    rng = np.random.default_rng(5)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 6)), jnp.int32)
    full, _ = jax.jit(lambda p, b: lm.forward(p, b, cfg, impl="einsum"))(
        params, {"tokens": toks})
    caches = lm.init_caches(cfg, 2, 16)
    last, _ = jax.jit(lambda p, t, c: lm.prefill_into(p, t, c, cfg))(
        params, toks, caches)
    err = logit_error(last, full[:, -1])
    assert err <= LOGIT_TOL["float32"], err
