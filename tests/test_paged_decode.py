"""The paged-decode kernel (``repro.kernels.paged_decode``) in interpret mode
against its gather reference (``ref.py``), at smollm-135m's widths (3 KV
heads of 64: a token's 192 numbers padded to a 256-lane row) and
stablelm-3b's (32 KV heads of 80: 2560, whole rows), on ragged lengths,
shared pages, unassigned table entries and a layer-stacked pool; one
decode step of the model with the kernel forced on against the step with
the gather it replaces on the TPU; and the decode step's embedding
lookup."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as configs
from repro.configs.base import reduce
from repro.kernels.paged_decode import ops
from repro.kernels.paged_decode.ref import paged_decode_ref
from repro.models import lm

P = 16

# (query heads, KV heads, head dim)
WIDTHS = {"smollm": (9, 3, 64), "stablelm": (32, 32, 80)}


def _case(widths, layers, seed=0):
    """Pools of random pages, and 6 slots of lengths 0, 1, a page, the
    table's whole ``max_len``, mid-page and two pages less one; slot 4
    starts on slot 3's first two pages (a shared prefix), and every table
    entry past a slot's length is -1."""
    h, n_kv, hd = WIDTHS[widths]
    width = -(-n_kv * hd // 128) * 128
    n_slot_pages, n_pages = 6, 26
    rng = np.random.default_rng(seed)
    shape = (layers, n_pages, P, width) if layers else (n_pages, P, width)
    k = jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
    lens = np.array([0, 1, P, n_slot_pages * P, 37, 2 * P - 1], np.int32)
    table = np.full((6, n_slot_pages), -1, np.int32)
    free = iter(rng.permutation(n_pages))
    for b, n in enumerate(lens):
        for j in range(-(-n // P)):
            table[b, j] = next(free)
    table[4, :2] = table[3, :2]
    q = jnp.asarray(rng.normal(size=(6, h, hd)), jnp.bfloat16)
    return q, k, v, jnp.asarray(table), jnp.asarray(lens), n_kv


@pytest.mark.parametrize("layers", [0, 3], ids=["one_layer", "stacked"])
@pytest.mark.parametrize("widths", sorted(WIDTHS))
def test_kernel_matches_gather(widths, layers):
    """Every slot's heads against the gathered view, float32 both; a
    stacked pool read at layer 2; a slot of length 0 reads zeros."""
    q, k, v, table, lens, n_kv = _case(widths, layers)
    layer = jnp.int32(2) if layers else None
    got = jax.jit(lambda *a: ops.paged_decode(*a, n_kv=n_kv))(
        q, k, v, table, lens, layer)
    want = paged_decode_ref(q, k, v, table, lens, layer, n_kv=n_kv)
    assert got.shape == q.shape and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert not np.asarray(got[0]).any()


def test_kernel_reads_only_live_pages():
    """Pages past a slot's length, and every page of a slot of length 0,
    may hold anything, non-finite numbers too: the kernel never reads
    them into its result."""
    q, k, v, table, lens, n_kv = _case("smollm", 0, seed=1)
    live = {int(p) for b, n in enumerate(np.asarray(lens))
            for p in np.asarray(table)[b, :-(-n // P)]}
    dead = np.array([p not in live for p in range(k.shape[0])])
    k_bad = jnp.where(dead[:, None, None], jnp.nan, k)
    v_bad = jnp.where(dead[:, None, None], jnp.inf, v)
    # the rows of the last live page past its slot's length too
    last = int(np.asarray(table)[4, 2])
    k_bad = k_bad.at[last, 37 % P:].set(jnp.nan)
    v_bad = v_bad.at[last, 37 % P:].set(jnp.nan)
    run = jax.jit(lambda *a: ops.paged_decode(*a, n_kv=n_kv))
    np.testing.assert_array_equal(np.asarray(run(q, k, v, table, lens)),
                                  np.asarray(run(q, k_bad, v_bad, table,
                                                 lens)))


@pytest.mark.parametrize("hd", [16, 64], ids=["padded_row", "whole_row"])
def test_decode_step_with_the_kernel_matches_the_gather(hd, monkeypatch):
    """One ``lm.decode_step`` of a float32 model over a layer-stacked pool
    after a ragged prefill, one slot on another's two prefix pages and one
    idle: the kernel's logits of every fed slot, and the keys and values
    the step writes (each layer's from the layers below), within float32
    summation order of the gather's; the same tables and lengths."""
    cfg = dataclasses.replace(reduce(configs.get("smollm_135m")),
                              n_layers=3, head_dim=hd)
    params = lm.init_params(cfg, jax.random.PRNGKey(7))[0]
    n_slot = 4
    caches = lm.init_caches(cfg, 4, n_slot * P, paged=True, page_size=P,
                            n_pages=20)
    for b in range(4):
        caches = lm.install_pages(
            caches, b, jnp.arange(b * n_slot, (b + 1) * n_slot,
                                  dtype=jnp.int32), 0, cfg)
    rng = np.random.default_rng(3)
    first = jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 40)), jnp.int32)
    _, caches = jax.jit(lambda c: lm.prefill_into(
        params, first, c, cfg,
        seq_lens=jnp.asarray([40, 17, 32, 5], jnp.int32)))(caches)
    # slot 2 starts over slot 0's first two (full) pages: 32 shared tokens
    caches = lm.install_pages(caches, 2, jnp.asarray([0, 1, 16, 17],
                                                     jnp.int32), 32, cfg)
    tok = jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 1)), jnp.int32)
    sl = jnp.asarray([1, 1, 1, 0], jnp.int32)

    def step():
        return jax.jit(lambda c: lm.decode_step(params, tok, c, cfg,
                                                seq_lens=sl))(caches)

    want, want_c = step()
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    got, got_c = step()
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got[:3]), np.asarray(want[:3]),
                               rtol=1e-5, atol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(got_c),
                    jax.tree_util.tree_leaves(want_c)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_a_decode_step_embeds_the_table_rows_exactly(dtype):
    """A single-token step looks its rows up as a one-hot product
    (``lm._embed``: the described-v5e decode step then holds no copy of
    the table, ``test_tpu_compile.py``): the table's rows bit for bit, as
    the gather of a longer step gives them."""
    cfg = dataclasses.replace(reduce(configs.get("smollm_135m")),
                              param_dtype=dtype, compute_dtype=dtype)
    params = lm.init_params(cfg, jax.random.PRNGKey(1))[0]
    tok = jnp.asarray([[3], [0], [cfg.vocab_size - 1], [77]], jnp.int32)
    got = jax.jit(lambda t: lm._embed(params, t, cfg))(tok)
    want = jnp.take(params["embed"]["w"], tok[:, 0], axis=0)[:, None]
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
