"""Serving launcher: continuous batching over a paged KV-cache pool with
prefix-tree reuse, hardened against accelerator faults.

Requests are admitted into free cache slots and decoded in lockstep (one
fused ``decode_step`` per tick for the whole batch) — the standard TPU
serving shape (static batch, slot reuse) rather than a GPU-style dynamic
batcher.  See ``docs/serving.md`` for the full architecture; the short
version:

  * the KV cache is **paged**: a fixed per-layer page pool plus per-slot
    page tables (``models/transformer.init_kv_cache``), with host-side
    refcounted allocation (``repro.serving.PagePool``);
  * a **radix tree** over full-page token runs (``repro.serving.
    PrefixTree``) maps prompt prefixes to page runs, so admission starts
    each request from its longest cached prefix and prefills only the
    unshared tail — shared system prompts are stored and computed once;
  * retirement releases the slot's page references; pages retained only
    by the tree are LRU-evicted when the pool runs dry, and pages still
    referenced by an active slot are never reclaimed;
  * slots are truly independent: staggered arrivals, variable prompt
    lengths, prefix sharing, and slot reuse never shift another request's
    positions — every request's greedy tokens are bit-identical to a
    single-request reference decode (``solo_reference``, which runs on
    the *dense* cache layout, so ``--check`` is a cross-layout oracle).

**Fault tolerance** (see "Failure modes and recovery" in
``docs/serving.md``): every prefill/decode dispatch runs under bounded
retry with exponential backoff; NaN/Inf logits retire only the poisoned
slot; a faulted request's slot is quarantined and the request re-enters
admission, where the prefix tree lets it re-prefill from its cached
prompt pages instead of from scratch; per-request wall-clock deadlines
and a deferral cap bound how long a request can wait on a dry pool; and
a health state machine (``healthy -> degraded -> shedding``) sheds new
admissions with an explicit reason under sustained fault or pool
pressure instead of deferring silently.  ``--inject`` arms a seeded
:class:`~repro.runtime.faults.FaultPlan` so every one of those paths can
be exercised deterministically — with ``--check`` still holding every
*surviving* request bit-identical to its solo reference.

``--disagg`` swaps in the **disaggregated** prefill/decode runtime
(:mod:`repro.launch.disagg`): prompts prefill on a dedicated
compute-side worker with its own page pool and prefix tree, and their
KV pages are migrated + ownership-transferred into the decode shards'
pools — same narrow API, same oracle, phase-matched placement.

``microbatches > 1`` splits the slot pool into shards, each with its own
cache/pool/tree, and decodes them through the asynchronous pipeline: every
active shard's decode step is dispatched fire-and-forget on a
``DeviceQueue`` (riding JAX async dispatch, cache buffers donated per
shard), and the host synchronizes only when it reads the sampled tokens —
the serving-side mirror of the SNAX loose-control / tight-data execution
model.  Prefixes are shared within a shard (pools are per-shard arrays).

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch smollm_135m \
      --reduced --batch 4 --prompt-len 16 --gen 32 --microbatches 2 \
      --stagger 2 --vary-prompts --shared-prefix 9 --check \
      --inject "seed=3,raise:0.05,drop:0.05,nan:0.05,stall:0.05,pressure:0.1"
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

import repro.configs as configs
from repro import obs
from repro.configs.base import reduce as reduce_cfg
from repro.gateway.metrics import RingBuffer
from repro.launch.compile_cache import enable_compile_cache
from repro.models import lm
from repro.runtime.executor import DeviceQueue
from repro.runtime.faults import FaultError, FaultPlan
from repro.runtime.supervisor import StragglerMonitor
from repro.serving import PagePool, PrefixTree

__all__ = ["Server", "ServePolicy", "Request", "serving_fns",
           "solo_reference", "drain", "main"]

# families whose serving cache supports the paged layout (token-prompt
# attention models); recurrent families keep dense/recurrent state and
# opt out via the seq_lens keep-mask path
_PAGED_FAMILIES = ("dense", "moe", "vlm")

# terminal finish reasons that mean "served to completion" — only these
# requests are held to the --check bit-equivalence oracle
SURVIVOR_REASONS = ("length", "eos")


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray           # (prompt_len,) int32
    max_new: int
    arrival: int = 0             # tick at which the request becomes visible
    deadline_s: float | None = None   # wall-clock budget (None = policy's)
    out: list = dataclasses.field(default_factory=list)
    done: bool = False
    # why the request left the server: "length" | "eos" (survivors),
    # "deadline", "shed:<reason>", "rejected:<reason>", "failed:<reason>"
    finish_reason: str | None = None
    # filled in by paged admission: tokens actually prefilled (the
    # unshared tail) and tokens served from the prefix cache
    prefill_len: int = -1
    shared_len: int = 0
    # streaming cursor: how many of ``out`` the gateway has polled;
    # reset (with ``out``) by fault recovery so the stream restarts
    streamed: int = 0
    # fault-tolerance bookkeeping
    deferrals: int = 0           # pool-dry admission deferrals so far
    recoveries: int = 0          # quarantine/re-prefill round trips
    t_seen: float | None = None  # wall clock of first admission attempt


@dataclasses.dataclass
class ServePolicy:
    """Fault-tolerance knobs for :class:`Server` (see docs/serving.md).

    ``max_retries`` bounds per-dispatch retry (first retry waits
    ``backoff_s``, doubling each attempt); ``max_recoveries`` bounds how
    often a request may be quarantined and re-prefilled before it is
    retired as failed; ``defer_cap`` bounds pool-dry admission deferrals
    (the all-pages-pinned livelock guard); ``deadline_s`` is the default
    per-request wall-clock budget (None = unbounded).  The health state
    machine trips to ``shedding`` when the last ``health_window`` ticks
    saw ``shed_faults`` fault events or ``shed_deferrals`` deferrals.
    """
    max_retries: int = 3
    backoff_s: float = 0.005
    deadline_s: float | None = None
    defer_cap: int = 16
    max_recoveries: int = 3
    quarantine_ticks: int = 2
    health_window: int = 16
    shed_faults: int = 4
    shed_deferrals: int = 8


def _bucket(n: int, floor: int = 8) -> int:
    """Next power-of-two prompt width >= n (bounds prefill recompiles)."""
    b = floor
    while b < n:
        b *= 2
    return b


def serving_fns(cfg, *, donate: bool = False):
    """The one place serving callables are built: a jitted
    ``(prefill, decode)`` pair over ``lm.prefill_into`` /
    ``lm.decode_step``, both taking ``(params, tokens, caches,
    seq_lens)``.  The colocated :class:`Server`, the disaggregated
    prefill/decode workers (``repro.launch.disagg``), and the
    ``solo_reference`` oracle all compile *these* callables, so a
    ``--check`` divergence can never be an artifact of the server and
    the reference lowering different functions.  ``donate=True``
    donates the cache argument (the servers' steady-state path); the
    reference keeps its caches undonated so repeated checks can share
    executables."""
    kw: dict = {"donate_argnums": (2,)} if donate else {}

    # named, so a device trace shows jit_serve_prefill / jit_serve_decode
    def serve_prefill(p, t, c, sl):
        return lm.prefill_into(p, t, c, cfg, seq_lens=sl)

    def serve_decode(p, t, c, sl):
        return lm.decode_step(p, t, c, cfg, seq_lens=sl)

    return jax.jit(serve_prefill, **kw), jax.jit(serve_decode, **kw)


_REF_FNS: dict = {}

# span of one step dispatch, by ``Server._submit`` site
_DISPATCH_SPANS = {"prefill": "model.prefill_dispatch",
                   "decode": "model.decode_dispatch"}


def _ref_fns(cfg):
    """Per-config cached :func:`serving_fns` pair — repeated
    ``solo_reference`` calls (--check over many requests) reuse the same
    executables instead of recompiling per call."""
    if cfg not in _REF_FNS:
        _REF_FNS[cfg] = serving_fns(cfg)
    return _REF_FNS[cfg]


def solo_reference(cfg, params, prompt, max_new: int, max_len: int, *,
                   eos_id: int | None = None) -> list[int]:
    """Greedy tokens for ONE request decoded alone (batch=1) through the
    **dense** per-slot cache path — the bit-equivalence oracle for
    ``Server``.  A paged server being checked against a dense reference
    makes ``--check`` a cross-layout oracle: page indirection, prefix
    sharing, and pool reuse must all be invisible in the tokens."""
    prefill_fn, step = _ref_fns(cfg)
    caches = lm.init_caches(cfg, 1, max_len)
    p = len(prompt)
    toks = np.zeros((1, _bucket(p)), np.int32)   # server-matched padding
    toks[0, :p] = prompt
    logits, caches = prefill_fn(params, jnp.asarray(toks), caches,
                                jnp.asarray([p], np.int32))
    out = [int(jnp.argmax(logits[0]))]
    one = jnp.asarray([1], np.int32)
    while len(out) < max_new and (eos_id is None or out[-1] != eos_id):
        lg, caches = step(params, jnp.asarray([[out[-1]]], np.int32),
                          caches, one)
        out.append(int(jnp.argmax(lg[0, 0])))
    return out


def drain(server: "Server", pending: list[Request], *,
          max_iters: int | None = None) -> list[Request]:
    """Drive ``server`` until every request retires: admit requests as
    they arrive (``Request.arrival`` in ticks) and slots free up, tick,
    collect retirees.  The one canonical serving loop — main(), the
    serving benchmark, and the tests all drain through here.

    When ``max_iters`` is exceeded the error names exactly what is
    stuck — which requests, in which slots/shards, how far along — plus
    a ``stats()`` snapshot, so a hung soak run is diagnosable from the
    traceback alone.
    """
    pending = list(pending)
    done: list[Request] = []
    inflight: list[Request] = []
    clock = 0
    while pending or inflight:
        if max_iters is not None and clock >= max_iters:
            server.quiesce()
            raise RuntimeError(_stuck_report(server, pending, inflight,
                                             max_iters))
        while pending and pending[0].arrival <= clock \
                and server.admit(pending[0]):
            r = pending.pop(0)
            # a request can finish at admission (max_new == 1 / EOS /
            # shed / rejection)
            (done if r.done else inflight).append(r)
        server.tick()
        clock += 1
        for r in list(inflight):
            if r.done:
                inflight.remove(r)
                done.append(r)
    server.quiesce()
    if getattr(server, "verify_enabled", False):
        server.verify()          # raises AnalysisError on any violation
    return done


def _stuck_report(server: "Server", pending: list[Request],
                  inflight: list[Request], max_iters: int) -> str:
    """Human-readable account of a non-converging drain."""
    requeue = list(getattr(server, "requeue", ()))
    stuck = []
    for r in inflight:
        slot = next((i for i, s in enumerate(server.slots) if s is r),
                    None)
        if slot is not None:
            where = f"slot {slot} (shard {slot // server.mb})"
        elif r in requeue:
            where = f"queued for re-admission ({r.recoveries} recoveries)"
        else:
            where = "awaiting a slot"
        if r.t_seen is not None:
            where += f", waiting {time.monotonic() - r.t_seen:.2f}s"
        stuck.append(f"rid {r.rid}: {len(r.out)}/{r.max_new} tokens, "
                     f"{where}")
    return (f"server did not converge in {max_iters} iterations\n"
            f"  in flight: {'; '.join(stuck) or 'none'}\n"
            f"  never admitted: "
            f"{[r.rid for r in pending] or 'none'}\n"
            f"  requeue depth {len(requeue)}, oldest queued "
            f"{server.oldest_requeue_age_s():.2f}s\n"
            f"  stats: {server.stats()}")


class Server:
    """Continuous batching over a slot pool with paged, prefix-shared KV.

    Slots are partitioned into ``microbatches`` shards of ``batch //
    microbatches`` slots; each shard owns an independent cache (and, when
    paged, its own ``PagePool`` + ``PrefixTree``) and is decoded as one
    pipeline task per tick.  Admission matches the prompt against the
    shard's prefix tree, installs shared + freshly-allocated pages into
    the slot's page table, and prefills only the unshared tail in one
    dispatch; retirement (EOS or length) releases the slot's page
    references and frees the slot for immediate reuse.

    ``paged=False`` (or a non-attention family) falls back to the dense
    per-slot layout of PR 2 — same admission/tick flow, no sharing.

    Fault tolerance (``policy``, a :class:`ServePolicy`): dispatches
    retry with exponential backoff on :class:`~repro.runtime.faults.
    FaultError`; poisoned (NaN/Inf) logits retire only the affected
    slot; faulted requests are recovered through quarantine +
    re-admission, where the prefix tree supplies their already-computed
    prompt pages; deadlines and a deferral cap bound every wait; and the
    ``healthy -> degraded -> shedding`` state machine refuses new
    admissions with an explicit reason under sustained pressure.
    ``inject`` (a :class:`~repro.runtime.faults.FaultPlan` or spec
    string) arms deterministic chaos on the prefill/decode/pool sites.
    """

    def __init__(self, cfg, params, *, batch: int, max_len: int,
                 microbatches: int = 1, eos_id: int | None = None,
                 paged: bool | None = None, page_size: int = 0,
                 pool_pages: int = 0, verify: bool = False,
                 policy: ServePolicy | None = None,
                 inject: FaultPlan | str | None = None,
                 tick_window: int = 2048):
        if microbatches < 1:
            raise ValueError(f"microbatches must be >= 1, got {microbatches}")
        if batch % microbatches:
            raise ValueError(
                f"batch {batch} not divisible by microbatches {microbatches}")
        self.cfg, self.params = cfg, params
        self.batch, self.max_len = batch, max_len
        self.microbatches = microbatches
        self.eos_id = eos_id
        self.mb = batch // microbatches
        self.policy = policy or ServePolicy()
        self.inject = (FaultPlan.parse(inject) if isinstance(inject, str)
                       else inject)
        if paged is None:
            paged = cfg.family in _PAGED_FAMILIES
        elif paged and cfg.family not in _PAGED_FAMILIES:
            raise ValueError(
                f"family {cfg.family} does not support the paged KV cache")
        self.paged = paged
        # verify: record every pool operation so the serving-invariant
        # checker (repro.analysis.serving) can abstractly interpret the
        # control plane's behaviour — drain() re-verifies at the end
        self.verify_enabled = verify
        if paged:
            self.page_size = page_size or cfg.kv_page_size or 8
            self.n_slot_pages = -(-max_len // self.page_size)
            # default pool: 2x the dense-equivalent footprint, so the
            # prefix tree can retain shared prompts past retirement
            self.pool_pages = (pool_pages or cfg.kv_pool_pages
                               or 2 * self.mb * self.n_slot_pages)
            self.pools = [PagePool(self.pool_pages, self.page_size,
                                   record=verify)
                          for _ in range(microbatches)]
            self.trees = [PrefixTree(pool) for pool in self.pools]
        self.caches = [
            lm.init_caches(cfg, self.mb, max_len, paged=paged,
                           page_size=getattr(self, "page_size", 0),
                           n_pages=getattr(self, "pool_pages", 0))
            for _ in range(microbatches)]
        self.slots: list[Request | None] = [None] * batch
        # pages referenced by each slot's table (paged mode bookkeeping)
        self.slot_pages: list[list[int] | None] = [None] * batch
        self._prefill, self._decode = serving_fns(cfg, donate=True)

        def reset_slot(c, s):
            return lm.reset_slot(c, s, cfg)

        def install_pages(c, s, t, n):
            return lm.install_pages(c, s, t, n, cfg)

        self._reset = jax.jit(reset_slot, donate_argnums=(0,))
        self._install = jax.jit(install_pages, donate_argnums=(0,))
        self.queue = DeviceQueue("decode", injector=self.inject)
        self.ticks = 0               # ticks that dispatched a decode
        self.clock = 0               # every tick() call (drives timers)
        # observability: admission + prefix-cache counters, tick latencies
        self.admitted = 0
        self.prefix_hits = 0
        self.prefill_tokens = 0
        self.prefill_tokens_skipped = 0
        self.deferred_admissions = 0
        self.peak_pages_in_use = 0
        # bounded ring (not a list): a long-running serve keeps a window
        # of recent tick latencies, so memory is O(tick_window) and the
        # stats() percentiles are rolling, not lifetime
        self.tick_wall_s = RingBuffer(tick_window)
        self.straggler = StragglerMonitor()
        # fault tolerance state
        self.health = "healthy"      # healthy | degraded | shedding
        self._shed_reason = ""
        self.requeue: list[Request] = []         # awaiting re-admission
        self.quarantined: dict[int, int] = {}    # slot -> free at clock
        self._pressure_holds: list[tuple[int, int, list[int]]] = []
        self._fault_window: list[int] = []       # per-tick fault events
        self._defer_window: list[int] = []       # per-tick deferrals
        self._tick_faults = 0
        self._tick_defers = 0
        # fault/recovery counters (stats())
        self.faults_detected = 0
        self.retries = 0
        self.recoveries = 0
        self.recovered = 0
        self.failed = 0
        self.shed = 0
        self.rejected = 0
        self.cancelled = 0
        self.deadline_retired = 0
        self.slots_quarantined = 0

    # --------------------------------------------------- fault plumbing
    def _submit(self, site: str, fn, *args, queue: DeviceQueue | None = None):
        """Queue submit under the retry policy: an injected (or any
        :class:`FaultError`) dispatch failure is retried up to
        ``max_retries`` times with exponential backoff.  Faults fire
        *before* the kernel runs, so device state is untouched and the
        identical submit is safe to replay.  Returns None once retries
        are exhausted — the caller routes the affected request(s) into
        recovery.  ``queue`` overrides the default decode queue (the
        disaggregated server routes prefills through its prefill
        worker's own queue)."""
        q = queue if queue is not None else self.queue
        delay = self.policy.backoff_s
        for attempt in range(self.policy.max_retries + 1):
            try:
                with obs.span(_DISPATCH_SPANS[site]):
                    return q.submit(fn, *args, site=site)
            except FaultError:
                self.faults_detected += 1
                self._tick_faults += 1
                if attempt == self.policy.max_retries:
                    return None
                self.retries += 1
                time.sleep(delay)
                delay *= 2
        return None

    def _quarantine(self, slot: int):
        self.quarantined[slot] = self.clock + self.policy.quarantine_ticks
        self.slots_quarantined += 1

    def _is_quarantined(self, slot: int) -> bool:
        until = self.quarantined.get(slot)
        if until is None:
            return False
        if self.clock >= until:
            del self.quarantined[slot]
            return False
        return True

    def _recover(self, req: Request, slot: int, reason: str):
        """Pull ``req`` out of its (possibly poisoned) slot and route it
        back through admission.  The slot is quarantined for
        ``quarantine_ticks``; the request's pages are released (its
        prompt's full pages usually survive in the prefix tree, so the
        re-prefill starts from the cached prefix rather than from
        scratch); generation restarts so the recovered decode is exactly
        the deterministic greedy sequence the reference produces."""
        self.faults_detected += 1
        self._tick_faults += 1
        shard = slot // self.mb
        if self.paged and self.pools[shard].trace is not None:
            self.pools[shard].note("fault_recovery", rid=req.rid,
                                   slot=slot, reason=reason)
        if self.slots[slot] is req:
            self.slots[slot] = None
        self._release_slot(slot)
        self._quarantine(slot)
        req.out = []
        req.streamed = 0         # the gateway's stream restarts too
        req.prefill_len, req.shared_len = -1, 0
        req.recoveries += 1
        self.recoveries += 1
        if req.recoveries > self.policy.max_recoveries:
            req.done = True
            req.finish_reason = f"failed:{reason}"
            self.failed += 1
        else:
            self.requeue.append(req)

    def _effective_deadline(self, req: Request) -> float | None:
        return (req.deadline_s if req.deadline_s is not None
                else self.policy.deadline_s)

    def _update_health(self):
        w = self.policy.health_window
        self._fault_window.append(self._tick_faults)
        self._defer_window.append(self._tick_defers)
        del self._fault_window[:-w], self._defer_window[:-w]
        self._tick_faults = self._tick_defers = 0
        faults, defers = sum(self._fault_window), sum(self._defer_window)
        if faults >= self.policy.shed_faults:
            self.health, self._shed_reason = "shedding", "fault_rate"
        elif defers >= self.policy.shed_deferrals:
            self.health, self._shed_reason = "shedding", "pool_pressure"
        elif faults or defers or self.quarantined:
            self.health, self._shed_reason = "degraded", ""
        else:
            self.health, self._shed_reason = "healthy", ""

    # ------------------------------------------------------------- admit
    def _admission_gate(self, req: Request) -> bool:
        """Pre-slot admission policy, shared by every server flavour:
        capacity sanity (raises — an unservable request must fail loudly,
        not defer forever), wall-clock deadline while waiting, the
        deferral cap, and health-machine shedding.  Returns True when the
        request was *consumed* by the gate (``req.done`` set with a
        reason); False means proceed to slot placement."""
        need = len(req.prompt) + req.max_new - 1
        if need > self.max_len:
            raise ValueError(
                f"request {req.rid}: prompt {len(req.prompt)} + "
                f"{req.max_new} generated tokens need {need} cache "
                f"entries > max_len {self.max_len} — overflowing KV "
                f"writes would be silently dropped")
        now = time.monotonic()
        if req.t_seen is None:
            req.t_seen = now
        deadline = self._effective_deadline(req)
        if deadline is not None and now - req.t_seen > deadline:
            # expired while waiting for a slot / pool space
            req.done = True
            req.finish_reason = "rejected:deadline"
            self.rejected += 1
            return True
        if req.deferrals > self.policy.defer_cap:
            # the all-pages-pinned livelock guard: stop re-deferring
            req.done = True
            req.finish_reason = "rejected:defer_cap"
            self.rejected += 1
            return True
        if self.health == "shedding" and req.recoveries == 0:
            # shed NEW work loudly; recoveries keep their promise
            req.done = True
            req.finish_reason = f"shed:{self._shed_reason}"
            self.shed += 1
            return True
        return False

    def admit(self, req: Request) -> bool:
        """Place ``req`` into a free slot.

        Paged flow: match the prompt against the shard's prefix tree
        (longest run of full cached pages, capped so at least the final
        prompt token is left to prefill), retain the matched pages,
        allocate private pages for the tail + generation (LRU-evicting
        tree-only pages if the pool is dry), install the page table, and
        prefill **only the unshared tail** in ONE batched dispatch (rows
        of concurrent requests are masked by ``seq_lens``).  Afterwards
        the prompt's full pages are inserted into the tree so the next
        request can start from them.  Returns False when no slot is free
        or the shard's pool cannot currently hold the request.

        Returning True with ``req.done`` set means the request was
        *consumed* without being served: shed (health state), rejected
        (deferral cap / deadline expired while waiting), or finished at
        admission (max_new == 1 / EOS).  ``req.finish_reason`` says
        which."""
        if self._admission_gate(req):
            return True
        need = len(req.prompt) + req.max_new - 1
        for i, s in enumerate(self.slots):
            if s is not None or self._is_quarantined(i):
                continue
            shard, row = divmod(i, self.mb)
            if self.paged:
                # a dry pool defers only this shard — later free slots
                # (other shards, other pools) may still admit
                if self._admit_paged(req, i, shard, row, need):
                    return True
                continue
            self.slots[i] = req
            self.caches[shard] = self.queue.submit(
                self._reset, self.caches[shard], jnp.int32(row))
            p = len(req.prompt)
            req.prefill_len, req.shared_len = p, 0
            if self._dispatch_prefill(req, shard, row, req.prompt):
                self.admitted += 1
                self.prefill_tokens += p
            return True
        return False

    def _admit_paged(self, req: Request, slot: int, shard: int, row: int,
                     need: int) -> bool:
        pool, tree = self.pools[shard], self.trees[shard]
        n_total = -(-need // self.page_size)
        if n_total > self.pool_pages:
            raise ValueError(
                f"request {req.rid} needs {n_total} pages > pool capacity "
                f"{self.pool_pages} — it could never be admitted")
        with obs.span("server.prefix_match"):
            shared, shared_len = tree.match(req.prompt)
            n_priv = n_total - len(shared)
            if pool.free_pages < n_priv:
                tree.evict(n_priv - pool.free_pages)
            priv = pool.alloc(n_priv)
        if priv is None:
            # every evictable page is pinned by an active request: defer
            # admission (a later retirement will release pages)
            pool.release(shared)
            self.deferred_admissions += 1
            self._tick_defers += 1
            req.deferrals += 1
            return False
        table = shared + priv
        self.slots[slot] = req
        self.slot_pages[slot] = table
        with obs.span("server.page_install"):
            row_table = np.full((self.n_slot_pages,), -1, np.int32)
            row_table[:len(table)] = table
            self.caches[shard] = self.queue.submit(
                self._install, self.caches[shard], jnp.int32(row),
                jnp.asarray(row_table), jnp.int32(shared_len))
        tail = req.prompt[shared_len:]
        req.prefill_len, req.shared_len = len(tail), shared_len
        # retirement at admission (max_new == 1) must not release the
        # slot's pages before the tree has retained the prompt's full
        # pages — defer it past insert().  A FAILED prefill must never
        # reach insert(): its pages were never written, and caching them
        # would serve garbage K/V to every future match.  Content-wise
        # the insert is safe: the pages' K/V writes are queued ahead of
        # any later admission's reads by JAX dispatch order.
        ok = self._dispatch_prefill(req, shard, row, tail, slot_idx=slot,
                                    defer_retire=True)
        if ok:
            tree.insert(req.prompt, table)
            self.admitted += 1
            self.prefix_hits += shared_len > 0
            self.prefill_tokens += len(tail)
            self.prefill_tokens_skipped += shared_len
            self.peak_pages_in_use = max(self.peak_pages_in_use,
                                         self.pages_in_use)
            if req.done:             # finished at admission
                self.slots[slot] = None
                self._release_slot(slot)
        return True

    def _dispatch_prefill(self, req: Request, shard: int, row: int,
                          tail, slot_idx: int | None = None,
                          defer_retire: bool = False) -> bool:
        p = len(tail)
        with obs.span("server.prefill", rid=req.rid, tail=p,
                      shared=req.shared_len):
            toks = np.zeros((self.mb, _bucket(p)), np.int32)
            toks[row, :p] = tail
            sl = np.zeros((self.mb,), np.int32)
            sl[row] = p
            idx = slot_idx if slot_idx is not None \
                else shard * self.mb + row
            out = self._submit("prefill", self._prefill, self.params,
                               jnp.asarray(toks), self.caches[shard],
                               jnp.asarray(sl))
            if out is None:              # retries exhausted
                self._recover(req, idx, "prefill_failed")
                return False
            logits, self.caches[shard] = out
            with obs.span("server.prefill_readback"):
                row_logits = logits[row]
                finite = bool(jnp.isfinite(row_logits).all())
                # the prefill's final logits predict the first new token
                tok = int(jnp.argmax(row_logits)) if finite else None
            if tok is None:
                # poisoned prefill: only this request is damaged — the
                # cache writes themselves landed, but its seed token is
                # garbage
                self._recover(req, idx, "nan_logits")
                return False
            self._append(req, idx, tok, defer_retire=defer_retire)
            return True

    # ---------------------------------------------------------- retire
    def _release_slot(self, slot: int):
        """Return the slot's page references to its shard's pool —
        the page-leak fix: without this, slot reuse pins every page a
        retired request ever touched until the pool exhausts."""
        pages = self.slot_pages[slot]
        if pages is not None:
            self.pools[slot // self.mb].release(pages)
            self.slot_pages[slot] = None

    def _append(self, req: Request, slot: int, tok: int, *,
                defer_retire: bool = False):
        req.out.append(tok)
        if self.eos_id is not None and tok == self.eos_id:
            req.done, req.finish_reason = True, "eos"
        elif len(req.out) >= req.max_new:
            req.done, req.finish_reason = True, "length"
        if req.done:
            if req.recoveries:
                self.recovered += 1      # survived at least one fault
            if not defer_retire:
                self.slots[slot] = None      # retire -> slot reusable
                self._release_slot(slot)

    def _retire(self, req: Request, slot: int, reason: str):
        """Forcibly retire an active request with an explicit reason
        (deadline enforcement); its partial output is kept."""
        req.done = True
        req.finish_reason = reason
        self.slots[slot] = None
        self._release_slot(slot)

    # --------------------------------------- gateway-facing narrow API
    # The network front-end (repro.gateway) drives the server through
    # exactly three verbs — submit / poll / cancel — so the serving loop,
    # fault tolerance, and the --check oracle stay intact underneath it.
    def submit(self, req: Request) -> bool:
        """Try to place ``req`` now (one admission attempt).  Returns
        False when no slot/pool space is currently available — the
        caller requeues and retries a later step.  True means the
        request was *consumed*: it is decoding in a slot, or it already
        retired at admission with a ``finish_reason`` (shed, rejected,
        finished) — check ``req.done``."""
        with obs.span("server.submit", rid=req.rid):
            return self.admit(req)

    def poll(self, req: Request) -> list[int]:
        """Tokens generated since the last poll (the streaming delta).

        The cursor lives on the request, so one poller per request is
        the contract.  Fault recovery resets both ``out`` and the
        cursor: after a recovery, poll() re-streams from the first
        token — callers detect the restart by the cursor moving
        backwards (``repro.gateway`` emits a ``restart`` chunk)."""
        new = list(req.out[req.streamed:])
        req.streamed = len(req.out)
        return new

    def cancel(self, req: Request) -> list[int] | None:
        """Cancel a submitted request mid-flight.

        Returns the page ids its slot held (``[]`` for dense/queued
        requests) so the caller can verify the release against the pool
        trace, or ``None`` when the request is not in the server (never
        submitted, or already retired).  A cancelled in-slot request
        releases exactly the page references it held — the GWY004
        invariant — and frees the slot immediately; partial output is
        kept with ``finish_reason="cancelled"``."""
        if req in self.requeue:           # awaiting re-admission: no slot
            self.requeue.remove(req)
            req.done, req.finish_reason = True, "cancelled"
            self.cancelled += 1
            return []
        for i, s in enumerate(self.slots):
            if s is req:
                pages = list(self.slot_pages[i] or [])
                shard = i // self.mb
                if self.paged and self.pools[shard].trace is not None:
                    self.pools[shard].note("cancel", rid=req.rid, slot=i)
                self._retire(req, i, "cancelled")
                self.cancelled += 1
                return pages
        return None

    # ----------------------------------------------------- tick helpers
    def _expire_pressure(self, *, all_holds: bool = False):
        for until, shard, pages in list(self._pressure_holds):
            if all_holds or until <= self.clock:
                self.pools[shard].release(pages)
                self._pressure_holds.remove((until, shard, pages))

    def _inject_pressure(self):
        """Fire ``pressure`` faults: pin free pool pages for a few ticks
        so admissions see a dry pool without any real load behind it."""
        if self.inject is None or not self.paged:
            return
        for shard in range(self.microbatches):
            spec = self.inject.draw("pool")
            if spec is None:
                continue
            take = min(spec.pages, self.pools[shard].free_pages)
            pages = self.pools[shard].alloc(take) if take > 0 else None
            if pages:
                self._pressure_holds.append(
                    (self.clock + spec.ticks, shard, pages))
                self._tick_faults += 1

    def _readmit_recoveries(self):
        for req in list(self.requeue):
            if req.done:             # expired while queued
                self.requeue.remove(req)
                continue
            if self.admit(req):
                self.requeue.remove(req)

    def _deadline_sweep(self):
        now = time.monotonic()
        for i, req in enumerate(self.slots):
            if req is None or req.done:
                continue
            deadline = self._effective_deadline(req)
            if deadline is not None and req.t_seen is not None \
                    and now - req.t_seen > deadline:
                self._retire(req, i, "deadline")
                self.deadline_retired += 1
        for req in list(self.requeue):
            deadline = self._effective_deadline(req)
            if deadline is not None and req.t_seen is not None \
                    and now - req.t_seen > deadline:
                req.done = True
                req.finish_reason = "deadline"
                self.deadline_retired += 1
                self.requeue.remove(req)

    def quiesce(self):
        """Release injected pressure holds (end of a drive loop) so the
        pool's end state reflects only real holders — drain() calls this
        before ``verify()``."""
        self._expire_pressure(all_holds=True)

    # -------------------------------------------------------------- tick
    def tick(self) -> bool:
        """One lockstep decode step for every active shard.

        All active shards are dispatched before any result is read — the
        dependency-only barrier is the argmax read at the end.  Idle slots
        inside an active shard advance nothing (``seq_lens=0``).

        Fault-tolerance work rides the same clock: expired pressure
        holds are released, recovered requests re-enter admission,
        deadlines are enforced, a shard whose dispatch fails after
        retries routes its active requests into recovery, poisoned
        (non-finite) logits retire only their own slot, and the health
        state machine is advanced from the tick's fault/deferral counts.

        While spans record (:mod:`repro.obs`), the tick's span carries the
        page counts of :meth:`_page_counts`.
        """
        attrs = self._page_counts() if obs.recording() else {}
        with obs.span("server.tick", **attrs):
            return self._tick()

    def _page_counts(self) -> dict:
        """``pages_reserved``: pages allocated from the pools (slot tables,
        tree and holds); ``pages_unwritten``: pages of live slots' tables
        past the last one holding a cached token; ``pages_read``: pages the
        tick's decode step reads, ``ceil((cached + 1) / page_size)`` for
        each slot it feeds (what the paged-decode kernel reads of a
        layer's pool)."""
        if not self.paged:
            return {}
        unwritten = read = 0
        for req, table in zip(self.slots, self.slot_pages):
            if req is None or table is None:
                continue
            cached = len(req.prompt) + len(req.out) - 1 if req.out else 0
            unwritten += len(table) - -(-cached // self.page_size)
            if req.out and not req.done:
                read += -(-(cached + 1) // self.page_size)
        return {"pages_reserved": self.pages_in_use,
                "pages_unwritten": unwritten, "pages_read": read}

    def _tick(self) -> bool:
        t0 = time.perf_counter()
        self._tick_begin()
        inflight = self._decode_dispatch()
        if inflight:
            self._decode_collect(inflight)
            self.ticks += 1
            dt = time.perf_counter() - t0
            self.tick_wall_s.push(dt)
            self.straggler.observe(self.clock, dt)
        self._update_health()
        return bool(inflight)

    def _tick_begin(self):
        """Advance the serving clock and run the per-tick control work:
        expired pressure holds, pressure injection, recovery
        re-admission, deadline enforcement."""
        with obs.span("server.tick_control"):
            self.clock += 1
            self._expire_pressure()
            self._inject_pressure()
            self._readmit_recoveries()
            self._deadline_sweep()

    def _decode_dispatch(self) -> list[tuple[int, jax.Array, np.ndarray]]:
        """Fire-and-forget one decode step per active shard; returns
        ``(shard, logits, active_rows)`` futures for ``_decode_collect``.
        ``active_rows`` pins which rows were actually fed this dispatch,
        so requests that enter a slot *between* dispatch and collect
        (the disaggregated server completes prefills in that window)
        are not credited a token from a step they never rode."""
        inflight: list[tuple[int, jax.Array, np.ndarray]] = []
        for shard in range(self.microbatches):
            toks = np.zeros((self.mb, 1), np.int32)
            sl = np.zeros((self.mb,), np.int32)
            for j in range(self.mb):
                req = self.slots[shard * self.mb + j]
                if req is None or req.done or not req.out:
                    continue                 # empty out: prefill pending
                toks[j] = req.out[-1]       # prefill seeded out[0]
                sl[j] = 1
            if not sl.any():
                continue                     # idle shard: no dispatch
            out = self._submit("decode", self._decode, self.params,
                               jnp.asarray(toks), self.caches[shard],
                               jnp.asarray(sl))
            if out is None:
                # the whole shard's dispatch failed after retries: every
                # active request in it goes through recovery (the cache
                # was never touched — faults fire before dispatch)
                for j in range(self.mb):
                    i = shard * self.mb + j
                    req = self.slots[i]
                    if req is not None and not req.done and req.out:
                        self._recover(req, i, "decode_failed")
                continue
            logits, self.caches[shard] = out
            inflight.append((shard, logits, sl > 0))
        return inflight

    def _decode_collect(self, inflight) -> None:
        """Token readback — the tick's only sync point.  Poisoned
        (non-finite) rows retire only their own slot."""
        for shard, logits, active in inflight:
            with obs.span("server.decode_readback"):
                lg = logits[:, 0]
                finite = np.asarray(jnp.isfinite(lg).all(axis=-1))
                nxt = np.asarray(jnp.argmax(lg, axis=-1))
            for j in range(self.mb):
                if not active[j]:
                    continue
                i = shard * self.mb + j
                req = self.slots[i]
                if req is None or req.done:
                    continue
                if not finite[j]:
                    # poisoned row: retire ONLY this slot — the
                    # neighbours' logits and cache rows are intact
                    self._recover(req, i, "nan_logits")
                    continue
                self._append(req, i, int(nxt[j]))

    # ------------------------------------------------------------ verify
    def verify(self):
        """Run the serving-invariant checker over every shard's recorded
        pool trace: refcount leaks, double releases, eviction of pages an
        active slot still references, and model-vs-implementation
        refcount divergence.  Raises ``AnalysisError`` on any error;
        returns the aggregated :class:`repro.analysis.Report`."""
        from repro.analysis import Report, verify_pool
        if not (self.paged and self.verify_enabled):
            return Report(subject="serving (verification disabled)")
        out = Report(subject=f"serving {self.cfg.name} "
                             f"({self.microbatches} shard(s))")
        for shard, (pool, tree) in enumerate(zip(self.pools, self.trees)):
            live = [self.slot_pages[i]
                    for i in range(shard * self.mb, (shard + 1) * self.mb)
                    if self.slot_pages[i] is not None]
            live += [pages for _, sh, pages in self._pressure_holds
                     if sh == shard]
            out.extend(verify_pool(pool, tree, live_slot_pages=live),
                       passname="serving")
        return out.raise_on_error()

    # ------------------------------------------------------------- stats
    @property
    def pages_in_use(self) -> int:
        return sum(p.used_pages for p in self.pools) if self.paged else 0

    def oldest_requeue_age_s(self, now: float | None = None) -> float:
        """Age of the oldest request awaiting re-admission (0.0 when the
        requeue is empty) — the stuck-request signal for recovered work
        that has not made it back into a slot."""
        seen = [r.t_seen for r in self.requeue if r.t_seen is not None]
        if not seen:
            return 0.0
        return (time.monotonic() if now is None else now) - min(seen)

    def stats(self) -> dict:
        """Serving counters for benchmarks/tests: prefix-cache hit rate,
        prefill work skipped, pool occupancy, windowed tick-latency
        percentiles (over the last ``tick_window`` ticks), queue-level
        state (requeue depth and oldest queued age), and the
        fault/recovery/shed ledger."""
        ticks = (self.tick_wall_s.array() if len(self.tick_wall_s)
                 else np.asarray([0.0]))
        out = {
            "admitted": self.admitted,
            "ticks": self.ticks,
            "tick_p50_ms": round(float(np.percentile(ticks, 50)) * 1e3, 3),
            "tick_p99_ms": round(float(np.percentile(ticks, 99)) * 1e3, 3),
            "prefill_tokens": self.prefill_tokens,
            "prefill_tokens_skipped": self.prefill_tokens_skipped,
            "paged": self.paged,
            # fault tolerance ledger
            "health": self.health,
            "faults_injected": dict(self.inject.injected)
            if self.inject is not None else {},
            "faults_detected": self.faults_detected,
            "retries": self.retries,
            "recoveries": self.recoveries,
            "recovered_requests": self.recovered,
            "failed_requests": self.failed,
            "shed": self.shed,
            "rejected": self.rejected,
            "cancelled": self.cancelled,
            "deadline_retired": self.deadline_retired,
            "slots_quarantined": self.slots_quarantined,
            "straggler_ticks": len(self.straggler.flagged),
            # queue-level state: requests that are the server's promise
            # but currently hold no slot (recovery re-admission queue)
            "requeue_depth": len(self.requeue),
            "oldest_requeue_age_s": round(self.oldest_requeue_age_s(), 4),
        }
        if self.paged:
            out.update({
                "page_size": self.page_size,
                "pool_pages": self.pool_pages * self.microbatches,
                "pages_in_use": self.pages_in_use,
                "peak_pages_in_use": self.peak_pages_in_use,
                "prefix_hits": self.prefix_hits,
                "hit_rate": round(self.prefix_hits
                                  / max(self.admitted, 1), 3),
                "deferred_admissions": self.deferred_admissions,
                "tree_nodes": sum(t.nodes for t in self.trees),
            })
        return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--stagger", type=int, default=0,
                    help="ticks between request arrivals (0 = all at once)")
    ap.add_argument("--vary-prompts", action="store_true",
                    help="draw prompt lengths uniformly in [1, prompt-len]")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="give every request the same first N prompt "
                         "tokens (the shared-system-prompt workload; "
                         "prompt lengths stay >= N+1)")
    ap.add_argument("--eos-id", type=int, default=None,
                    help="retire a request early when it samples this token")
    ap.add_argument("--dense", action="store_true",
                    help="use the dense per-slot KV layout instead of the "
                         "paged pool (no prefix reuse)")
    ap.add_argument("--disagg", action="store_true",
                    help="disaggregated prefill/decode: prompts prefill on "
                         "a dedicated worker (own pool + prefix tree) and "
                         "their KV pages are handed off to the decode "
                         "shards (repro.launch.disagg)")
    ap.add_argument("--prefill-slots", type=int, default=2,
                    help="concurrent in-flight prefills (--disagg only)")
    ap.add_argument("--prefill-pool-pages", type=int, default=0,
                    help="prefill-pool capacity (--disagg only; 0 = sized "
                         "from --prefill-slots)")
    ap.add_argument("--page-size", type=int, default=0,
                    help="tokens per KV page (0 = config default or 8)")
    ap.add_argument("--pool-pages", type=int, default=0,
                    help="page-pool capacity per shard (0 = 2x the dense-"
                         "equivalent slot footprint)")
    ap.add_argument("--check", action="store_true",
                    help="assert every surviving request's greedy tokens "
                         "are bit-identical to its single-request "
                         "reference (decoded through the DENSE layout: a "
                         "cross-layout oracle)")
    ap.add_argument("--verify", action="store_true",
                    help="record page-pool operation traces and run the "
                         "serving-invariant checker (repro.analysis) "
                         "over them when the server drains")
    ap.add_argument("--inject", type=str, default=None,
                    help="arm a seeded fault plan, e.g. "
                         "'seed=3,raise:0.05,drop:0.05,nan:0.05,"
                         "stall:0.05:delay_s=0.002,pressure:0.1:pages=2'"
                         " (see repro.runtime.faults)")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="default per-request wall-clock deadline")
    ap.add_argument("--defer-cap", type=int, default=None,
                    help="pool-dry deferrals before a request is rejected")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = configs.get(args.arch)
    if args.reduced:
        cfg = reduce_cfg(cfg)
    params, _ = lm.init_params(cfg, jax.random.PRNGKey(0))
    # per-slot positions: the cache is sized by ONE sequence (prompt +
    # generation), no matter how many admission waves reuse the slot.
    max_len = args.prompt_len + args.gen + 8
    policy = ServePolicy()
    if args.deadline_s is not None:
        policy.deadline_s = args.deadline_s
    if args.defer_cap is not None:
        policy.defer_cap = args.defer_cap
    if args.disagg:
        if args.dense:
            ap.error("--disagg requires the paged KV cache (drop --dense)")
        from repro.launch.disagg import DisaggServer
        server = DisaggServer(
            cfg, params, batch=args.batch, max_len=max_len,
            microbatches=args.microbatches, eos_id=args.eos_id,
            page_size=args.page_size, pool_pages=args.pool_pages,
            prefill_slots=args.prefill_slots,
            prefill_pool_pages=args.prefill_pool_pages,
            verify=args.verify, policy=policy, inject=args.inject)
    else:
        server = Server(cfg, params, batch=args.batch, max_len=max_len,
                        microbatches=args.microbatches, eos_id=args.eos_id,
                        paged=False if args.dense else None,
                        page_size=args.page_size, pool_pages=args.pool_pages,
                        verify=args.verify, policy=policy, inject=args.inject)

    rng = np.random.default_rng(0)
    shared = rng.integers(0, cfg.vocab_size,
                          args.shared_prefix).astype(np.int32)
    pending = []
    for i in range(args.requests):
        lo = args.shared_prefix + 1
        plen = int(rng.integers(lo, args.prompt_len + 1)) \
            if args.vary_prompts else max(args.prompt_len, lo)
        tail = rng.integers(0, cfg.vocab_size,
                            plen - args.shared_prefix).astype(np.int32)
        pending.append(Request(
            i, np.concatenate([shared, tail]), args.gen,
            arrival=i * args.stagger))
    t0 = time.perf_counter()
    done = drain(server, pending)
    dt = time.perf_counter() - t0
    total_tokens = sum(len(r.out) for r in done)
    survivors = [r for r in done if r.finish_reason in SURVIVOR_REASONS]
    print(f"served {len(done)} requests, {total_tokens} tokens in "
          f"{dt:.2f}s ({total_tokens/dt:.1f} tok/s, "
          f"{server.ticks} decode ticks, "
          f"{server.queue.dispatched} queue dispatches incl. prefill)")
    print(f"stats: {server.stats()}")
    if args.inject:
        casualties = [(r.rid, r.finish_reason) for r in done
                      if r.finish_reason not in SURVIVOR_REASONS]
        print(f"chaos: plan {server.inject!r} injected "
              f"{server.inject.injected}; {len(survivors)} survivors, "
              f"{server.recovered} recovered after faults, "
              f"retired with reasons: {casualties or 'none'}")
        assert sum(server.inject.injected.values()) > 0, (
            "fault plan armed but nothing fired — raise the "
            "probabilities or the workload size")
        for r in done:      # every retirement carries an explicit reason
            assert r.finish_reason, f"request {r.rid} retired silently"
    if args.verify and server.paged:
        pools = list(server.pools)
        if args.disagg:
            pools.append(server.prefill.pool)
        n_ops = sum(len(p.trace or ()) for p in pools)
        extra = (f" + DSG handoff totality over "
                 f"{len(server.ledger.events)} ledger event(s)"
                 if args.disagg else "")
        print(f"verify: serving-invariant checker passed over {n_ops} "
              f"traced pool operation(s){extra}")
    if args.eos_id is None and not args.inject and args.deadline_s is None:
        assert all(len(r.out) == r.max_new for r in done)
    if args.check:
        for r in survivors:
            ref = solo_reference(cfg, params, r.prompt, r.max_new, max_len,
                                 eos_id=args.eos_id)
            assert r.out == ref, (
                f"request {r.rid}: served tokens diverge from the "
                f"single-request reference\n  got {r.out}\n  ref {ref}")
        print(f"check: all {len(survivors)} surviving requests "
              f"bit-identical to their solo references")
        if args.shared_prefix and not args.dense:
            skipped = server.prefill_tokens_skipped
            assert skipped > 0, (
                "shared-prefix workload admitted without any prefix reuse")
            print(f"check: prefix cache skipped {skipped} prefill tokens "
                  f"across {server.prefix_hits} hits")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
