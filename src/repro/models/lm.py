"""Model facade: init / train loss / prefill / decode for every family.

Families:
  dense   — uniform GQA decoder (qwen2.5, stablelm, yi, smollm)
  moe     — decoder with MoE FFN (qwen2-moe, moonshot)
  vlm     — decoder with M-RoPE + patch-embedding merge (qwen2-vl backbone)
  hybrid  — Mamba2 layers + one *shared* attention block reused every k
            layers (zamba2)
  ssm     — alternating mLSTM/sLSTM blocks (xlstm)
  audio   — whisper-style enc-dec (conv frontend stubbed: encoder consumes
            precomputed frame embeddings per the assignment)

All stacks are scanned (homogeneous layer groups with stacked params) so the
60-layer configs compile to O(1)-size HLO; `remat` wraps scan bodies.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, ShapeCfg
from repro.models import flags
from repro.models import mamba2 as m2
from repro.models import xlstm as xl
from repro.models.common import apply_dense, apply_norm, embed_init, \
    make_positions, norm_init
from repro.models.transformer import (
    POOL_KEYS, AttnArgs, _is_paged, block_apply, block_init,
    init_kv_cache, install_kv_pages, migrate_kv_pages, reset_kv_slot,
    stack_init,
)

__all__ = [
    "init_params", "loss_fn", "prefill", "prefill_into", "decode_step",
    "init_caches", "reset_slot", "install_pages", "input_specs",
    "count_params", "attn_args",
]




def _scan(body, carry, xs, *, remat=False):
    """lax.scan, or an unrolled python loop under ``flags.UNROLL`` (used by
    the dry-run cost compiles; XLA cost_analysis counts loop bodies once).
    Semantics match lax.scan for (carry, ys) with pytree xs/ys."""
    if flags.UNROLL:
        n = jax.tree_util.tree_leaves(xs)[0].shape[0]
        ys = []
        for i in range(n):
            x_i = jax.tree_util.tree_map(lambda a: a[i], xs)
            carry, y = body(carry, x_i)
            ys.append(y)
        if all(y is None for y in ys):
            return carry, None
        ys = jax.tree_util.tree_map(lambda *zs: jnp.stack(zs), *ys)
        return carry, ys
    fn = jax.checkpoint(body) if remat else body
    return jax.lax.scan(fn, carry, xs)

# =========================================================== construction ==
def attn_args(cfg: ArchConfig, *, causal=True, window=None,
              impl="auto") -> AttnArgs:
    return AttnArgs(
        n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, hd=cfg.hd, causal=causal,
        rope_theta=cfg.rope_theta, rotary_pct=cfg.rotary_pct,
        use_rope=cfg.use_rope, mrope_sections=cfg.mrope_sections,
        sliding_window=window if window is not None else cfg.sliding_window,
        impl=impl,
    )


def _pdt(cfg):
    return jnp.dtype(cfg.param_dtype)


def _cdt(cfg):
    return jnp.dtype(cfg.compute_dtype)


def _decoder_block_init(cfg: ArchConfig, key, cross=False):
    return block_init(
        key, cfg.d_model, cfg.d_ff, attn_args(cfg), qkv_bias=cfg.qkv_bias,
        act=cfg.act, norm=cfg.norm, dtype=_pdt(cfg), cross=cross,
        moe_cfg=cfg.moe)


def init_params(cfg: ArchConfig, key):
    """Returns (params, specs) — specs mirror params with logical axes."""
    ks = jax.random.split(key, 8)
    params, specs = {}, {}
    params["embed"], specs["embed"] = embed_init(
        ks[0], cfg.vocab_size, cfg.d_model, dtype=_pdt(cfg))
    params["ln_f"], specs["ln_f"] = norm_init(cfg.d_model, kind=cfg.norm)
    if not cfg.tie_embeddings:
        from repro.models.common import dense_init
        params["lm_head"], specs["lm_head"] = dense_init(
            ks[1], cfg.d_model, cfg.vocab_size, ("embed", "vocab"),
            dtype=_pdt(cfg))

    fam = cfg.family
    if fam in ("dense", "moe", "vlm"):
        params["layers"], specs["layers"] = stack_init(
            ks[2], cfg.n_layers, lambda k: _decoder_block_init(cfg, k))
    elif fam == "hybrid":
        every = cfg.ssm.shared_attn_every
        n_groups = cfg.n_layers // every
        # mamba params: stacked (n_groups, every, ...)
        def group_init(k):
            return stack_init(k, every,
                              lambda kk: m2.mamba2_init(
                                  kk, cfg.d_model, cfg.ssm,
                                  dtype=_pdt(cfg)))
        params["mamba"], specs["mamba"] = stack_init(
            ks[2], n_groups, group_init)
        # ONE shared attention+FFN block (weights reused every invocation)
        params["shared"], specs["shared"] = block_init(
            ks[3], cfg.d_model, cfg.d_ff, attn_args(cfg),
            qkv_bias=cfg.qkv_bias, act=cfg.act, norm=cfg.norm,
            dtype=_pdt(cfg))
    elif fam == "ssm":
        pat = cfg.xlstm.pattern
        n_groups = cfg.n_layers // len(pat)

        def group_init(k):
            kk = jax.random.split(k, len(pat))
            ps, ss = {}, {}
            for i, kind in enumerate(pat):
                init = xl.mlstm_init if kind == "mlstm" else xl.slstm_init
                ps[f"{i}_{kind}"], ss[f"{i}_{kind}"] = init(
                    kk[i], cfg.d_model, cfg.xlstm, dtype=_pdt(cfg))
            return ps, ss

        params["groups"], specs["groups"] = stack_init(
            ks[2], n_groups, group_init)
    elif fam == "audio":
        enc_args = dataclasses.replace(
            attn_args(cfg, causal=False), use_rope=False)

        def enc_init(k):
            return block_init(k, cfg.d_model, cfg.d_ff, enc_args,
                              qkv_bias=True, act="gelu", norm="ln",
                              dtype=_pdt(cfg))

        def dec_init(k):
            return block_init(
                k, cfg.d_model, cfg.d_ff,
                dataclasses.replace(attn_args(cfg), use_rope=False),
                qkv_bias=True, act="gelu", norm="ln", dtype=_pdt(cfg),
                cross=True)

        params["encoder"], specs["encoder"] = stack_init(
            ks[2], cfg.encdec.n_enc_layers, enc_init)
        params["decoder"], specs["decoder"] = stack_init(
            ks[3], cfg.encdec.n_dec_layers, dec_init)
        params["ln_enc"], specs["ln_enc"] = norm_init(cfg.d_model, kind="ln")
    else:
        raise ValueError(f"unknown family {fam}")
    return params, specs


def abstract_params(cfg: ArchConfig):
    """(ShapeDtypeStruct tree, logical-axis specs) without allocating."""
    box = []

    def capture(k):
        p, s = init_params(cfg, k)
        box.append(s)
        return p

    shapes = jax.eval_shape(capture, jax.random.PRNGKey(0))
    return shapes, box[0]


# ============================================================ forward-fns ==
def _sinusoid(seq: int, d: int, dtype):
    pos = jnp.arange(seq, dtype=jnp.float32)[:, None]
    dim = jnp.arange(0, d, 2, dtype=jnp.float32)[None, :]
    ang = pos / jnp.power(10000.0, dim / d)
    pe = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)
    return pe.astype(dtype)


def _embed(params, tokens, cfg):
    w = params["embed"]["w"]
    if tokens.shape[1] == 1 and w.shape[1] % 128:
        # A decode step's few rows as a one-hot product, exact at any
        # dtype.  The chip lays out a table whose width does not fill
        # whole 128-lane rows with the vocabulary on the lanes, where a row
        # gather first copies the whole table into rows; the product reads
        # the table as it lies.
        e = jnp.einsum("bsv,vd->bsd", jax.nn.one_hot(tokens, w.shape[0],
                                                     dtype=w.dtype), w,
                       precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)
    else:
        e = jnp.take(w, tokens, axis=0)
    return e.astype(_cdt(cfg))


def _unembed(params, x, cfg):
    x = apply_norm(params["ln_f"], x, kind=cfg.norm)
    if cfg.tie_embeddings:
        w = params["embed"]["w"]
        return jax.lax.dot_general(
            x, w.astype(x.dtype), (((x.ndim - 1,), (1,)), ((), ())))
    return apply_dense(params["lm_head"], x)


def _run_decoder_stack(params, x, cfg: ArchConfig, *, positions=None,
                       pos3=None, impl="auto"):
    """Scanned uniform decoder (dense/moe/vlm). Returns (x, aux)."""
    a = attn_args(cfg, impl=impl)

    def body(carry, layer_params):
        x, aux = carry
        x, _, al = block_apply(
            layer_params, x, a, positions=positions, pos3=pos3,
            act=cfg.act, norm=cfg.norm, moe_cfg=cfg.moe,
            compute_dtype=_cdt(cfg))
        return (x, aux + al), None

    (x, aux), _ = _scan(body, (x, jnp.zeros((), jnp.float32)),
                        params["layers"], remat=cfg.remat)
    return x, aux


def _run_hybrid_stack(params, x, cfg: ArchConfig, *, positions,
                      impl="auto"):
    a = attn_args(cfg, impl=impl)
    every = cfg.ssm.shared_attn_every
    shared = params["shared"]

    def group_body(carry, group_params):
        x, aux = carry

        def mamba_body(xc, lp):
            return xc + m2.mamba2_apply(lp, xc, cfg.ssm), None

        x, _ = _scan(mamba_body, x, group_params)
        x, _, al = block_apply(
            shared, x, a, positions=positions, act=cfg.act, norm=cfg.norm,
            compute_dtype=_cdt(cfg))
        return (x, aux + al), None

    (x, aux), _ = _scan(group_body, (x, jnp.zeros((), jnp.float32)),
                        params["mamba"], remat=cfg.remat)
    return x, aux


def _run_ssm_stack(params, x, cfg: ArchConfig):
    pat = cfg.xlstm.pattern

    def group_body(carry, group_params):
        x, aux = carry
        for i, kind in enumerate(pat):
            p = group_params[f"{i}_{kind}"]
            if kind == "mlstm":
                x = x + xl.mlstm_apply(p, x, cfg.xlstm)
            else:
                x = x + xl.slstm_apply(p, x, cfg.xlstm)
        return (x, aux), None

    (x, aux), _ = _scan(group_body, (x, jnp.zeros((), jnp.float32)),
                        params["groups"], remat=cfg.remat)
    return x, aux


def _run_encoder(params, frames, cfg: ArchConfig, impl="auto"):
    x = frames.astype(_cdt(cfg)) + _sinusoid(
        frames.shape[1], cfg.d_model, _cdt(cfg))[None]
    a = dataclasses.replace(
        attn_args(cfg, causal=False, impl=impl), use_rope=False)

    def body(carry, lp):
        x, aux = carry
        x, _, _ = block_apply(lp, x, a, act="gelu", norm="ln",
                              compute_dtype=_cdt(cfg))
        return (x, aux), None

    (x, _), _ = _scan(body, (x, jnp.zeros((), jnp.float32)),
                      params["encoder"], remat=cfg.remat)
    return apply_norm(params["ln_enc"], x, kind="ln")


def _run_decoder_xattn(params, x, enc_out, cfg: ArchConfig, impl="auto"):
    a = dataclasses.replace(attn_args(cfg, impl=impl), use_rope=False)

    def body(carry, lp):
        x, aux = carry
        x, _, _ = block_apply(lp, x, a, enc_out=enc_out, act="gelu",
                              norm="ln", compute_dtype=_cdt(cfg))
        return (x, aux), None

    (x, _), _ = _scan(body, (x, jnp.zeros((), jnp.float32)),
                      params["decoder"], remat=cfg.remat)
    return x


def _merge_vlm(params, batch, cfg):
    """Patch embeddings (stub frontend) prepended to text embeddings."""
    text = _embed(params, batch["tokens"], cfg)
    patches = batch["patch_embeds"].astype(_cdt(cfg))
    return jnp.concatenate([patches, text], axis=1)


def forward(params, batch, cfg: ArchConfig, *, impl="auto"):
    """Full-sequence forward -> (logits, aux). Batch is family-specific."""
    x, aux = _backbone(params, batch, cfg, impl=impl)
    return _unembed(params, x, cfg), aux


def _backbone(params, batch, cfg: ArchConfig, *, impl="auto"):
    """Everything before the unembed: returns (hidden states, aux)."""
    fam = cfg.family
    if fam in ("dense", "moe"):
        tokens = batch["tokens"]
        x = _embed(params, tokens, cfg)
        positions = make_positions(*tokens.shape)
        x, aux = _run_decoder_stack(params, x, cfg, positions=positions,
                                    impl=impl)
    elif fam == "vlm":
        x = _merge_vlm(params, batch, cfg)
        positions = make_positions(x.shape[0], x.shape[1])
        x, aux = _run_decoder_stack(params, x, cfg, positions=positions,
                                    pos3=batch["pos3"], impl=impl)
    elif fam == "hybrid":
        tokens = batch["tokens"]
        x = _embed(params, tokens, cfg)
        positions = make_positions(*tokens.shape)
        x, aux = _run_hybrid_stack(params, x, cfg, positions=positions,
                                   impl=impl)
    elif fam == "ssm":
        x = _embed(params, batch["tokens"], cfg)
        x, aux = _run_ssm_stack(params, x, cfg)
    elif fam == "audio":
        enc_out = _run_encoder(params, batch["frames"], cfg, impl=impl)
        x = _embed(params, batch["dec_tokens"], cfg)
        x = x + _sinusoid(x.shape[1], cfg.d_model, x.dtype)[None]
        x = _run_decoder_xattn(params, x, enc_out, cfg, impl=impl)
        aux = jnp.zeros((), jnp.float32)
    else:
        raise ValueError(fam)
    return x, aux


def loss_fn(params, batch, cfg: ArchConfig, *, impl="auto", ce_chunk=0):
    """Next-token CE (labels = tokens shifted inside the batch dict).

    ``ce_chunk > 0``: compute the unembed + softmax in token chunks so the
    full (B, S, V) f32 logit tensor is never materialized — the memory-term
    optimization for large-vocab training (qwen2.5 hillclimb).
    """
    if ce_chunk:
        return _loss_chunked(params, batch, cfg, impl=impl,
                             ce_chunk=ce_chunk)
    logits, aux = forward(params, batch, cfg, impl=impl)
    labels = batch["labels"]
    if cfg.family == "vlm":
        # patches occupy the first n_patches positions; loss on text only
        logits = logits[:, cfg.n_patches:]
    logits = logits.astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    mask = (labels >= 0).astype(jnp.float32)
    loss = -(ll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    return loss + aux, {"ce": loss, "aux": aux}


def _loss_chunked(params, batch, cfg: ArchConfig, *, impl, ce_chunk):
    x, aux = _backbone(params, batch, cfg, impl=impl)
    labels = batch["labels"]
    if cfg.family == "vlm":
        x = x[:, cfg.n_patches:]
    b, s, d = x.shape
    t = b * s
    n = min(ce_chunk, t)
    assert t % n == 0, (t, n)
    xt = x.reshape(t // n, n, d)
    lt = labels.reshape(t // n, n)

    def body(carry, inp):
        xc, lc = inp
        logits = _unembed(params, xc[None], cfg)[0].astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lc[:, None], axis=-1)[:, 0]
        mask = (lc >= 0).astype(jnp.float32)
        tot, cnt = carry
        return (tot + ((lse - gold) * mask).sum(), cnt + mask.sum()), None

    (tot, cnt), _ = _scan(body, (jnp.zeros(()), jnp.zeros(())), (xt, lt),
                          remat=cfg.remat)
    loss = tot / jnp.maximum(cnt, 1.0)
    return loss + aux, {"ce": loss, "aux": aux}


# ================================================================= serve ==
def _paged_args(cfg: ArchConfig, batch: int, max_len: int, paged: bool,
                page_size: int, n_pages: int) -> dict:
    """Resolve the ``init_kv_cache`` paging kwargs for a family that
    supports paging (attention caches without a ring layout)."""
    if not paged:
        return {}
    ps = page_size or cfg.kv_page_size or 8
    n_slot_pages = -(-max_len // ps)
    return {"page_size": ps,
            "n_pages": n_pages or cfg.kv_pool_pages
            or batch * n_slot_pages}


def init_caches(cfg: ArchConfig, batch: int, max_len: int, *,
                enc_len: int = 0, prefilled: int = 0, paged: bool = False,
                page_size: int = 0, n_pages: int = 0):
    """Cache pytree (layer-stacked) for decode.

    Position counters are **per slot**: every attention cache carries a
    ``(layers, batch)`` length vector, so each batch row holds its own
    sequence and can be admitted/retired independently (``prefilled`` seeds
    every slot's counter).

    ``paged=True`` builds attention caches in the **paged** layout
    (per-layer page pool + per-slot page tables, see
    ``transformer.init_kv_cache``): ``page_size`` tokens per page
    (default ``cfg.kv_page_size`` or 8) and ``n_pages`` pool pages per
    layer (default ``cfg.kv_pool_pages`` or exactly enough for ``batch``
    dense-equivalent slots — give the pool headroom when a prefix tree
    should retain pages past slot retirement).  The page table has one
    entry per ``page_size`` positions up to ``max_len``; entry ``j`` of a
    row covers that row's absolute positions ``[j * P, (j + 1) * P)``.
    Recurrent families (hybrid/ssm) and ring (sliding-window) attention
    caches opt out and ignore ``paged`` — their state is per-slot by
    construction and is frozen via the ``seq_lens`` keep-mask path."""
    dt = _cdt(cfg)
    fam = cfg.family
    if fam in ("dense", "moe", "vlm"):
        a = attn_args(cfg)
        one = init_kv_cache(batch, max_len, a, dt, quant=cfg.kv_quant,
                            **_paged_args(cfg, batch, max_len, paged,
                                          page_size, n_pages))
        caches = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(
                x, (cfg.n_layers,) + x.shape).copy(), one)
        caches["len"] = jnp.full((cfg.n_layers, batch), prefilled,
                                 jnp.int32)
        return {"self": caches}
    if fam == "hybrid":
        every = cfg.ssm.shared_attn_every
        n_groups = cfg.n_layers // every
        ssm_one = m2.init_ssm_cache(batch, cfg.d_model, cfg.ssm, dt)
        ssm = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(
                x, (n_groups, every) + x.shape).copy(), ssm_one)
        a = attn_args(cfg, window=cfg.sliding_window)
        attn_one = init_kv_cache(batch, max_len, a, dt,
                                 ring=cfg.sliding_window is not None,
                                 quant=cfg.kv_quant)
        attn = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(
                x, (n_groups,) + x.shape).copy(), attn_one)
        attn["len"] = jnp.full((n_groups, batch), prefilled, jnp.int32)
        return {"ssm": ssm, "attn": attn}
    if fam == "ssm":
        pat = cfg.xlstm.pattern
        n_groups = cfg.n_layers // len(pat)
        group = {}
        for i, kind in enumerate(pat):
            init = (xl.init_mlstm_cache if kind == "mlstm"
                    else xl.init_slstm_cache)
            group[f"{i}_{kind}"] = init(batch, cfg.d_model, cfg.xlstm, dt)
        return jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(
                x, (n_groups,) + x.shape).copy(), group)
    if fam == "audio":
        a = attn_args(cfg)
        one = init_kv_cache(batch, max_len, a, dt, quant=cfg.kv_quant,
                            **_paged_args(cfg, batch, max_len, paged,
                                          page_size, n_pages))
        self_c = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(
                x, (cfg.encdec.n_dec_layers,) + x.shape).copy(), one)
        self_c["len"] = jnp.full((cfg.encdec.n_dec_layers, batch),
                                 prefilled, jnp.int32)
        cross = {
            "k": jnp.zeros((cfg.encdec.n_dec_layers, batch, enc_len,
                            cfg.n_kv_heads, cfg.hd), dt),
            "v": jnp.zeros((cfg.encdec.n_dec_layers, batch, enc_len,
                            cfg.n_kv_heads, cfg.hd), dt),
        }
        return {"self": self_c, "cross": cross}
    raise ValueError(fam)


def _keep_rows(new, old, keep, batch_axis):
    """Select rows of ``new`` where ``keep`` (B,) bool, else ``old`` —
    used to freeze recurrent state for idle serving slots."""

    def one(n, o):
        shape = [1] * n.ndim
        shape[batch_axis] = keep.shape[0]
        return jnp.where(keep.reshape(shape), n, o)

    return jax.tree_util.tree_map(one, new, old)


def decode_step(params, token, caches, cfg: ArchConfig, *, seq_lens=None):
    """New tokens ``token`` (B, S) int32 against the caches ->
    ``(logits (B, S, V), new caches)``.

    ``S == 1`` is the classic decode step; ``S > 1`` runs chunked prefill
    through the cache plumbing (attention families; recurrent families are
    single-token — use ``prefill_into`` for their prompt phase).

    Per-slot invariants (PR 2), both cache layouts:
      * row b's token i lands at absolute position ``len[b] + i`` and
        attends to row b's positions ``[0, len[b] + i]`` only — rows
        never share or shift each other's positions;
      * afterwards ``len[b] += seq_lens[b]`` (every layer agrees on the
        per-slot length).

    Page-table invariants (paged caches, see ``init_kv_cache``): writes
    go through ``page_table[b, pos // P]`` and are dropped when aimed at
    an unassigned (-1) entry, so a slot can only touch its own assigned
    pages; positions ``< len[b]`` may live in pages shared with other
    slots (prefix reuse) and those shared pages are full and immutable —
    the host must have installed enough private tail pages to cover
    ``len[b] + S`` before stepping.

    ``seq_lens`` (B,) int32: valid new tokens per row (0 freezes a row
    entirely — no KV writes, no recurrent-state update, no length advance),
    enabling ragged prompts and idle slots in a serving batch.  Logits at
    positions ``>= seq_lens[b]`` of row b are garbage and must be ignored
    by the caller (``prefill_into`` gathers each row's last valid one).

    A paged pool (dense/moe/vlm) is updated in place: the layer scan
    carries the stacked pool leaves (pages of the lane-dense rows that
    ``init_kv_cache`` describes) with the layer index, and each layer
    writes and then reads them at ``[layer, page]``, so the step holds no
    other pool-sized buffer.  Callers that jit the step donate the caches
    (``serving_fns(donate=True)``); an undonated pool is copied once a
    call, since the caller's array must survive.
    """
    fam = cfg.family
    x = _embed(params, token, cfg)
    if fam not in ("dense", "moe", "vlm", "audio") and token.shape[1] != 1:
        raise ValueError(
            f"{fam} decode is single-token recurrent; got S={token.shape[1]}"
            " (use prefill_into for multi-token prompts)")
    keep = None if seq_lens is None else seq_lens > 0
    if fam in ("dense", "moe", "vlm"):
        a = attn_args(cfg)
        self_c = caches["self"]
        # a paged pool rides in the carry, with the layer index; the small
        # per-slot leaves (and a dense cache whole) are scanned xs / ys
        pool = ({k: v for k, v in self_c.items() if k in POOL_KEYS}
                if _is_paged(self_c) else {})
        per_layer = {k: v for k, v in self_c.items() if k not in pool}

        def body(carry, inp):
            x, pool, layer = carry
            lp, cache = inp
            c = {**cache, **pool, "layer": layer} if pool else cache
            x, nc, _ = block_apply(lp, x, a, caches={"self": c},
                                   act=cfg.act, norm=cfg.norm,
                                   moe_cfg=cfg.moe, compute_dtype=_cdt(cfg),
                                   seq_lens=seq_lens)
            nc = nc["self"]
            return ((x, {k: nc[k] for k in pool}, layer + 1),
                    {k: nc[k] for k in cache})

        (x, pool, _), per_layer = _scan(
            body, (x, pool, jnp.zeros((), jnp.int32)),
            (params["layers"], per_layer))
        new_caches = {"self": {**per_layer, **pool}}
    elif fam == "hybrid":
        a = attn_args(cfg, window=cfg.sliding_window)
        shared = params["shared"]

        def group_body(x, inp):
            gp, ssm_c, attn_c = inp

            def mamba_body(xc, lp_c):
                lp, cache = lp_c
                y, nc = m2.mamba2_decode(lp, xc, cache, cfg.ssm)
                return xc + y, nc

            x, new_ssm = _scan(mamba_body, x, (gp, ssm_c))
            x, nc, _ = block_apply(shared, x, a, caches={"self": attn_c},
                                   act=cfg.act, norm=cfg.norm,
                                   compute_dtype=_cdt(cfg),
                                   seq_lens=seq_lens)
            return x, (new_ssm, nc["self"])

        x, (new_ssm, new_attn) = _scan(
            group_body, x, (params["mamba"], caches["ssm"],
                            caches["attn"]))
        if keep is not None:
            # ssm leaves are (n_groups, every, B, ...): freeze idle rows
            new_ssm = _keep_rows(new_ssm, caches["ssm"], keep, 2)
        new_caches = {"ssm": new_ssm, "attn": new_attn}
    elif fam == "ssm":
        pat = cfg.xlstm.pattern

        def group_body(x, inp):
            gp, gc = inp
            ncs = {}
            for i, kind in enumerate(pat):
                nm = f"{i}_{kind}"
                fn = xl.mlstm_decode if kind == "mlstm" else xl.slstm_decode
                y, ncs[nm] = fn(gp[nm], x, gc[nm], cfg.xlstm)
                x = x + y
            return x, ncs

        x, new_caches = _scan(group_body, x, (params["groups"], caches))
        if keep is not None:
            # xlstm leaves are (n_groups, B, ...): freeze idle rows
            new_caches = _keep_rows(new_caches, caches, keep, 1)
    elif fam == "audio":
        a = dataclasses.replace(attn_args(cfg), use_rope=False)
        cur = caches["self"]["len"][0]                       # (B,)
        pos = cur[:, None] + jnp.arange(token.shape[1], dtype=jnp.int32)
        x = x + jnp.take(_sinusoid(65536, cfg.d_model, x.dtype),
                         jnp.clip(pos, 0, 65535), axis=0)

        def body(x, inp):
            lp, self_c, ck, cv = inp
            c = {"self": self_c, "cross": {"k": ck, "v": cv,
                                           "len": self_c["len"]}}
            x, nc, _ = block_apply(lp, x, a, caches=c, act="gelu",
                                   norm="ln", compute_dtype=_cdt(cfg),
                                   seq_lens=seq_lens)
            return x, nc["self"]

        x, new_self = _scan(
            body, x, (params["decoder"], caches["self"],
                      caches["cross"]["k"], caches["cross"]["v"]))
        new_caches = {"self": new_self, "cross": caches["cross"]}
    else:
        raise ValueError(fam)
    return _unembed(params, x, cfg), new_caches


def reset_slot(caches, slot, cfg: ArchConfig):
    """Make slot ``slot``'s cache region logically empty across every
    layer/group so the batch row can be reused for a new request with a
    fixed-size cache.  ``slot`` may be a traced int32 (admission resets
    run jitted).

    Dense attention caches: the per-slot ``slot_pos`` map (set to -1)
    logically empties the row; K/V and recurrent state are zeroed so no
    stale data survives.

    Paged attention caches: only the slot's page-table row (-1) and
    length (0) are cleared — the K/V pool pages may be shared with other
    slots or retained by the prefix tree.  Returning them to the free
    list (and decrementing prefix-tree refcounts) is the **host-side
    server's** job at retirement (``PagePool.release``); a server that
    resets paged slots without releasing their pages leaks the pool.

    Either way the reset touches ONLY row ``slot`` — which is what makes
    it the fault-recovery primitive too: quarantining one poisoned slot
    and re-admitting its request (re-prefilling from prefix-tree cached
    pages) cannot perturb any neighbour's cache row, so survivors stay
    bit-identical under recovery (``tests/test_faults.py``)."""
    fam = cfg.family

    def attn_reset(c):
        # the single-layer reset invariant, vmapped over the layer/group
        # axis of the stacked cache
        return jax.vmap(reset_kv_slot, in_axes=(0, None))(c, slot)

    def zero_rows(tree, batch_axis):
        def one(x):
            return x.at[(slice(None),) * batch_axis + (slot,)].set(0)

        return jax.tree_util.tree_map(one, tree)

    if fam in ("dense", "moe", "vlm"):
        return {"self": attn_reset(caches["self"])}
    if fam == "hybrid":
        return {"ssm": zero_rows(caches["ssm"], 2),
                "attn": attn_reset(caches["attn"])}
    if fam == "ssm":
        return zero_rows(caches, 1)
    if fam == "audio":
        return {"self": attn_reset(caches["self"]),
                "cross": zero_rows(caches["cross"], 1)}
    raise ValueError(fam)


def install_pages(caches, slot, table_row, n_tokens, cfg: ArchConfig):
    """Assign pool pages to slot ``slot`` of a paged cache pytree.

    ``table_row`` is a ``(n_slot_pages,)`` int32 page-id vector (-1
    padded) and ``n_tokens`` the number of already-valid shared-prefix
    tokens it starts with; both may be traced (admission runs jitted).
    Page ids are layer-uniform — every layer's pool has the same shape,
    so one host-side allocation covers the whole stack and the same table
    row is installed at every layer (exactly like ``len``).  See
    ``transformer.install_kv_pages`` for the single-layer invariants.

    Re-admission after a fault recovery is the same call: the recovered
    request's table starts from whatever full prompt pages the prefix
    tree still caches (``n_tokens`` = the shared prefix), so recovery
    re-prefills only the prompt tail instead of starting cold."""
    fam = cfg.family

    def one(c):
        return jax.vmap(install_kv_pages,
                        in_axes=(0, None, None, None))(
            c, slot, table_row, n_tokens)

    if fam in ("dense", "moe", "vlm"):
        return {"self": one(caches["self"])}
    if fam == "audio":
        return {"self": one(caches["self"]), "cross": caches["cross"]}
    raise ValueError(
        f"family {fam} has no paged attention cache to install into")


def migrate_pages(src_caches, dst_caches, src_pages, dst_pages,
                  cfg: ArchConfig):
    """Copy KV page contents between two paged cache pytrees' pools.

    The data plane of the disaggregated prefill->decode handoff: the
    prefill worker's cache and a decode shard's cache are separate
    pytrees over separate page id spaces, and this lands the prompt's
    K/V bytes (``src_pages`` of the source pool) into the decode-side
    pages (``dst_pages``) that ``repro.serving.handoff.transfer`` just
    took custody of.  Page ids are layer-uniform, so the same index
    vectors apply at every layer; batch widths and pool sizes may
    differ between the two pytrees.  Returns the new destination pytree
    — page tables/lengths untouched, the caller installs them via
    :func:`install_pages` (a half-migrated slot is never addressable).
    """
    fam = cfg.family

    def one(s, d):
        return jax.vmap(migrate_kv_pages,
                        in_axes=(0, 0, None, None))(
            s, d, src_pages, dst_pages)

    if fam in ("dense", "moe", "vlm"):
        return {"self": one(src_caches["self"], dst_caches["self"])}
    if fam == "audio":
        return {"self": one(src_caches["self"], dst_caches["self"]),
                "cross": dst_caches["cross"]}
    raise ValueError(
        f"family {fam} has no paged attention cache to migrate")


def prefill_into(params, tokens, caches, cfg: ArchConfig, *, seq_lens=None):
    """Teacher-forced prefill of ``tokens`` (B, P) int32 into per-slot
    caches.

    Returns ``(last_logits (B, V), new caches)`` where ``last_logits[b]``
    is the logits at each row's final *valid* position — the distribution
    over its first generated token.  ``seq_lens`` (B,) gives the true
    prompt length per row (rows may be padded; rows with 0 are untouched
    and contribute their position-0 garbage logits, which callers must
    ignore).

    Rows start from their **current** cache length, not from zero: row b's
    tokens occupy absolute positions ``[len[b], len[b] + seq_lens[b])``.
    With a paged cache whose slot was seeded by ``install_pages`` this is
    what makes prefix-reuse admission a *tail* prefill — ``tokens[b]``
    holds only the suffix after the shared prefix, positions line up
    because ``len[b]`` was seeded with the shared token count, and the
    shared pages are read (never written) through the page table.

    Attention families run this as ONE cache-writing forward over the full
    prompt width; recurrent families (hybrid/ssm) scan the prompt token by
    token inside a single dispatch.
    """
    b, p = tokens.shape
    if seq_lens is None:
        seq_lens = jnp.full((b,), p, jnp.int32)
    seq_lens = jnp.asarray(seq_lens, jnp.int32)
    last_idx = jnp.maximum(seq_lens - 1, 0)[:, None, None]
    if cfg.family in ("dense", "moe", "vlm", "audio"):
        logits, caches = decode_step(params, tokens, caches, cfg,
                                     seq_lens=seq_lens)
        last = jnp.take_along_axis(logits, last_idx, axis=1)[:, 0]
        return last, caches

    def body(carry, t):
        tok = jax.lax.dynamic_slice_in_dim(tokens, t, 1, axis=1)
        lg, c = decode_step(params, tok, carry, cfg,
                            seq_lens=(t < seq_lens).astype(jnp.int32))
        return c, lg[:, 0]

    caches, logits = jax.lax.scan(body, caches, jnp.arange(p))
    last = jnp.take_along_axis(jnp.moveaxis(logits, 0, 1), last_idx,
                               axis=1)[:, 0]
    return last, caches


def encode_for_decode(params, frames, cfg: ArchConfig, *, impl="auto"):
    """Audio (enc-dec) serving prefill: run the encoder once and build the
    per-decoder-layer cross-attention K/V caches (the piece ``prefill``
    alone doesn't produce)."""
    assert cfg.family == "audio"
    enc_out = _run_encoder(params, frames, cfg, impl=impl)

    def layer_kv(carry, lp):
        k = apply_dense(lp["xattn"]["k"], enc_out)   # (B, S_enc, KV, hd)
        v = apply_dense(lp["xattn"]["v"], enc_out)
        return carry, (k, v)

    _, (ks, vs) = _scan(layer_kv, None, params["decoder"])
    return enc_out, {"k": ks, "v": vs}


def prefill(params, batch, cfg: ArchConfig, *, impl="auto", caches=None,
            seq_lens=None):
    """Full-sequence forward returning last-position logits (the dry-run
    prefill cell).  With ``caches`` it is the serving prefill: one batched
    cache-writing pass via ``prefill_into`` returning
    ``(last_logits, caches)`` with ragged ``seq_lens`` support."""
    if caches is not None:
        return prefill_into(params, batch["tokens"], caches, cfg,
                            seq_lens=seq_lens)
    logits, _ = forward(params, batch, cfg, impl=impl)
    return logits[:, -1]


# ================================================================ shapes ==
def input_specs(cfg: ArchConfig, shape: ShapeCfg):
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    s, b = shape.seq_len, shape.global_batch
    i32 = jnp.int32
    cd = _cdt(cfg)
    fam = cfg.family
    if shape.kind in ("train", "prefill"):
        if fam in ("dense", "moe", "hybrid", "ssm"):
            d = {"tokens": jax.ShapeDtypeStruct((b, s), i32)}
        elif fam == "vlm":
            np_ = cfg.n_patches
            d = {
                "tokens": jax.ShapeDtypeStruct((b, s - np_), i32),
                "patch_embeds": jax.ShapeDtypeStruct(
                    (b, np_, cfg.d_model), cd),
                "pos3": jax.ShapeDtypeStruct((3, b, s), i32),
            }
        elif fam == "audio":
            sd = s // cfg.encdec.dec_ratio
            d = {
                "frames": jax.ShapeDtypeStruct((b, s, cfg.d_model), cd),
                "dec_tokens": jax.ShapeDtypeStruct((b, sd), i32),
            }
        if shape.kind == "train":
            if fam == "audio":
                d["labels"] = jax.ShapeDtypeStruct(
                    (b, s // cfg.encdec.dec_ratio), i32)
            elif fam == "vlm":
                d["labels"] = jax.ShapeDtypeStruct((b, s - cfg.n_patches),
                                                   i32)
            else:
                d["labels"] = jax.ShapeDtypeStruct((b, s), i32)
        return d
    # decode: one token + caches
    token = jax.ShapeDtypeStruct((b, 1), i32)
    caches = jax.eval_shape(
        lambda: init_caches(
            cfg, b, s, enc_len=s if fam == "audio" else 0,
            prefilled=s - 1))
    return {"token": token, "caches": caches}


# ================================================================ params ==
def count_params(cfg: ArchConfig, active_only: bool = False) -> int:
    """Parameter count from eval_shape (excludes the embedding/lm_head for
    the 6*N*D convention used in EXPERIMENTS.md)."""
    params = jax.eval_shape(
        lambda k: init_params(cfg, k)[0], jax.random.PRNGKey(0))
    embed_like = {"embed", "lm_head"}

    def size(tree):
        return sum(
            math.prod(x.shape)
            for x in jax.tree_util.tree_leaves(tree))

    total = sum(size(v) for k, v in params.items()
                if k not in embed_like)
    if active_only and cfg.moe:
        # routed experts contribute top_k / n_routed of their params
        def experts_size(tree):
            out = 0
            if isinstance(tree, dict):
                for k, v in tree.items():
                    if k == "experts":
                        out += size(v)
                    else:
                        out += experts_size(v)
            return out

        e_sz = experts_size({k: v for k, v in params.items()
                             if k not in embed_like})
        total -= e_sz * (1.0 - cfg.moe.top_k / cfg.moe.n_routed)
    return int(total)
