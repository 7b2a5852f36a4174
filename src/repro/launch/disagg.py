"""Disaggregated prefill/decode serving: a two-pool runtime.

The colocated :class:`~repro.launch.serve.Server` runs compute-bound
batched prefill and bandwidth-bound single-token decode on the same
shard — the exact phase mismatch the paper's placement machinery exists
to kill (``core/placement.py`` ranks datapaths per phase via arithmetic
intensity vs machine balance; ``phase="prefill"``/``"decode"``).  This
module splits the runtime accordingly:

  * :class:`PrefillWorker` — the compute-side half.  Owns its own paged
    cache (a few wide prefill rows), its own ``PagePool`` **and the
    prefix tree** (so prompt reuse — including quarantine re-prefill
    after a fault — always lands on the prefill pool), and its own
    ``DeviceQueue("prefill")``.  Admission dispatches the prompt tail
    into a free prefill row fire-and-forget and returns immediately.
  * :class:`DecodeWorker` — the bandwidth-side half.  Owns the decode
    ``DeviceQueue`` and lands finished prefills into the decode shards:
    a jitted page migration (``lm.migrate_pages``) copies the prompt's
    KV pages from the prefill pool arrays into decode-pool pages that
    were *reserved at admission* (a finished prefill can never strand on
    a dry decode pool), then the refcounted custody move
    (``repro.serving.handoff.transfer``) and the page-table install make
    the slot decodable.
  * :class:`DisaggServer` — the scheduler over both.  One ``tick()``
    dispatches every active decode shard fire-and-forget, *then*
    completes pending prefills (reading prefill logits while the decode
    steps are still in flight — that window is the prefill/decode
    overlap, reported in ``stats()``), then collects decode tokens.

Every page's journey is journaled in a
:class:`~repro.serving.handoff.HandoffLedger` and verified by the DSG
rule family (``repro.analysis.handoff``): handoff totality, no
cross-pool double-ownership.  The gateway drives this server through the
same narrow submit/poll/cancel API, and ``--check`` still holds every
survivor bit-identical to the dense ``solo_reference`` — the oracle now
spans two pools, a device-to-device page copy, and the ownership
transfer on top of the paged/dense layout split.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import lm
from repro.runtime.executor import DeviceQueue
from repro.serving import HandoffLedger, PagePool, PrefixTree, transfer
from repro.launch.serve import Request, Server, _bucket

__all__ = ["DecodeWorker", "DisaggServer", "PrefillWorker"]


def _pad_pages(src, dst, floor: int = 4):
    """Bucket page-id vectors to a power-of-two length (bounds migrate
    recompiles) by repeating the first real (src, dst) pair — the
    duplicate writes carry identical content, so the copy stays
    deterministic."""
    n = len(src)
    b = _bucket(n, floor)
    s = np.asarray(list(src) + [src[0]] * (b - n), np.int32)
    d = np.asarray(list(dst) + [dst[0]] * (b - n), np.int32)
    return jnp.asarray(s), jnp.asarray(d)


@dataclasses.dataclass
class _PendingPrefill:
    """A request whose prompt is in flight on the prefill worker: its
    decode slot is held, its decode-pool pages are reserved, and
    ``logits`` is the un-read (still possibly executing) prefill
    output."""
    req: Request
    slot: int            # decode slot index (shard * mb + row)
    shard: int           # decode shard
    row: int             # prefill cache row
    pf_table: list       # prefill-pool pages holding the prompt
    shared_len: int
    plen: int
    dst_pages: list      # decode-pool pages reserved at admission
    logits: jax.Array


class PrefillWorker:
    """Compute-side worker: paged prefill cache + pool + prefix tree +
    its own device queue.  Rows are taken at admission and returned when
    the prefill completes (or is dropped), so ``free_rows`` is the
    worker's admission capacity."""

    def __init__(self, cfg, *, slots: int, max_len: int, page_size: int,
                 pool_pages: int, verify: bool, inject):
        self.slots = slots
        self.page_size = page_size
        self.n_slot_pages = -(-max_len // page_size)
        self.pool = PagePool(pool_pages, page_size, record=verify)
        self.tree = PrefixTree(self.pool)
        self.caches = lm.init_caches(cfg, slots, max_len, paged=True,
                                     page_size=page_size,
                                     n_pages=pool_pages)
        self.queue = DeviceQueue("prefill", injector=inject)
        self._free_rows = list(range(slots - 1, -1, -1))

    @property
    def free_rows(self) -> int:
        return len(self._free_rows)

    def take_row(self) -> int:
        return self._free_rows.pop()

    def free_row(self, row: int) -> None:
        self._free_rows.append(row)


class DecodeWorker:
    """Bandwidth-side worker: owns the decode queue and lands handoffs.

    The decode shard caches, pools, and slot tables stay on the server
    (the gateway reads them), but every device dispatch that touches
    them — decode steps (via the server's tick), the page migration, the
    page-table install — rides this worker's queue, so the decode side
    is a single ordered stream per shard."""

    def __init__(self, server: "DisaggServer"):
        self.server = server
        self.queue = server.queue

    def reserve(self, shard: int, n: int):
        """All-or-nothing decode-pool reservation (None when dry)."""
        return self.server.pools[shard].alloc(n)

    def land(self, p: _PendingPrefill) -> None:
        """Make a finished prefill decodable on its shard: device page
        copy, refcounted custody transfer, page-table install.

        Dispatch order matters: the migrate reads the prefill cache
        (data dependency on the prefill's writes) and donates only the
        decode cache; the install lands the table afterwards, so a
        partially-migrated slot is never addressable by a decode step.
        """
        srv = self.server
        n_x = len(p.pf_table)
        src_ids, dst_ids = _pad_pages(p.pf_table, p.dst_pages[:n_x])
        srv.caches[p.shard] = self.queue.submit(
            srv._migrate, srv.prefill.caches, srv.caches[p.shard],
            src_ids, dst_ids)
        transfer(srv.prefill.pool, srv.pools[p.shard], p.pf_table,
                 rid=p.req.rid, shard=p.shard,
                 dst_pages=p.dst_pages[:n_x], ledger=srv.ledger)
        row_table = np.full((srv.n_slot_pages,), -1, np.int32)
        row_table[:len(p.dst_pages)] = p.dst_pages
        srv.caches[p.shard] = self.queue.submit(
            srv._install, srv.caches[p.shard],
            jnp.int32(p.slot % srv.mb), jnp.asarray(row_table),
            jnp.int32(p.plen))
        srv.ledger.installed(p.req.rid, p.shard, p.dst_pages)
        srv.slot_pages[p.slot] = list(p.dst_pages)
        srv.transfers += 1
        srv.pages_transferred += n_x


class DisaggServer(Server):
    """Two-pool serving runtime: prefill and decode disaggregated.

    Inherits the whole colocated contract — the narrow submit/poll/
    cancel API, fault tolerance (retry/quarantine/re-admission/health
    machine), deadlines, the ``--check`` oracle — and changes *where*
    work runs: prompts prefill on a dedicated :class:`PrefillWorker`
    (own cache/pool/tree/queue), decode shards only ever see already-
    migrated pages.  ``admit()`` reserves the decode slot and its pool
    pages up front and dispatches the prefill fire-and-forget; the
    request becomes *pending* until the next ``tick()`` completes the
    handoff, overlapping its prefill against every other request's
    decode step.

    Extra knobs: ``prefill_slots`` (concurrent in-flight prefills) and
    ``prefill_pool_pages`` (the prefill pool, which also backs the
    prefix tree's retained prompts).
    """

    def __init__(self, cfg, params, *, batch: int, max_len: int,
                 microbatches: int = 1, prefill_slots: int = 2,
                 prefill_pool_pages: int = 0, **kw):
        if kw.pop("paged", True) is False:
            raise ValueError("disaggregated serving requires the paged "
                             "KV cache (page handoff is the mechanism)")
        super().__init__(cfg, params, batch=batch, max_len=max_len,
                         microbatches=microbatches, paged=True, **kw)
        if prefill_slots < 1:
            raise ValueError(f"prefill_slots must be >= 1, "
                             f"got {prefill_slots}")
        # decode pools keep no prefix trees: prompt reuse lives on the
        # prefill side, where the prompts are computed
        self.trees = []
        self.prefill = PrefillWorker(
            cfg, slots=prefill_slots, max_len=max_len,
            page_size=self.page_size,
            pool_pages=(prefill_pool_pages
                        or 2 * max(prefill_slots, 2) * self.n_slot_pages),
            verify=self.verify_enabled, inject=self.inject)
        self.decoder = DecodeWorker(self)
        self.ledger = HandoffLedger()
        self.pending: list[_PendingPrefill] = []

        def migrate_pages(s, d, si, di):
            return lm.migrate_pages(s, d, si, di, cfg)

        self._migrate = jax.jit(migrate_pages, donate_argnums=(1,))
        self.transfers = 0
        self.pages_transferred = 0
        self.overlap_ticks = 0

    # ------------------------------------------------------------- admit
    def admit(self, req: Request) -> bool:
        """One admission attempt.  Succeeding means the request holds a
        decode slot, its decode pages are reserved, and its prompt tail
        is in flight on the prefill worker; it produces its first token
        at the next tick's handoff completion."""
        if self._admission_gate(req):
            return True
        if not self.prefill.free_rows:
            return False                 # all prefill rows in flight
        for i, s in enumerate(self.slots):
            if s is not None or self._is_quarantined(i):
                continue
            got = self._begin_prefill(req, i, i // self.mb)
            if got == "pf_dry":
                # one prefill pool serves every shard: scanning further
                # slots cannot help — defer to a later retirement/evict
                return False
            if got != "dst_dry":
                return True              # admitted or consumed
            # dst_dry: this shard's decode pool is dry; other shards'
            # free slots may still hold the reservation
        return False

    def _defer(self, req: Request) -> None:
        self.deferred_admissions += 1
        self._tick_defers += 1
        req.deferrals += 1

    def _begin_prefill(self, req: Request, slot: int, shard: int) -> str:
        """Reserve decode capacity and launch the prompt's prefill.

        Returns ``"admitted"`` (pending handoff), ``"consumed"`` (the
        dispatch failed after retries and the request was routed into
        recovery), ``"pf_dry"``/``"dst_dry"`` (deferred: prefill pool /
        this shard's decode pool cannot hold it right now)."""
        pf = self.prefill
        plen = len(req.prompt)
        need = plen + req.max_new - 1
        n_dst = -(-need // self.page_size)
        n_src = -(-plen // self.page_size)
        if n_dst > self.pool_pages or n_src > pf.pool.n_pages:
            raise ValueError(
                f"request {req.rid} needs {n_src} prefill + {n_dst} "
                f"decode pages > pool capacities "
                f"({pf.pool.n_pages}/{self.pool_pages}) — it could "
                f"never be admitted")
        shared, shared_len = pf.tree.match(req.prompt)
        n_priv = n_src - len(shared)
        if pf.pool.free_pages < n_priv:
            pf.tree.evict(n_priv - pf.pool.free_pages)
        priv = pf.pool.alloc(n_priv)
        if priv is None:
            pf.pool.release(shared)
            self._defer(req)
            return "pf_dry"
        dst = self.decoder.reserve(shard, n_dst)
        if dst is None:
            pf.pool.release(shared + priv)
            self._defer(req)
            return "dst_dry"
        pf_table = shared + priv
        row = pf.take_row()
        row_table = np.full((pf.n_slot_pages,), -1, np.int32)
        row_table[:len(pf_table)] = pf_table
        pf.caches = pf.queue.submit(
            self._install, pf.caches, jnp.int32(row),
            jnp.asarray(row_table), jnp.int32(shared_len))
        tail = req.prompt[shared_len:]
        toks = np.zeros((pf.slots, _bucket(len(tail))), np.int32)
        toks[row, :len(tail)] = tail
        sl = np.zeros((pf.slots,), np.int32)
        sl[row] = len(tail)
        self.ledger.prefilled(req.rid, pf_table)
        out = self._submit("prefill", self._prefill, self.params,
                           jnp.asarray(toks), pf.caches,
                           jnp.asarray(sl), queue=pf.queue)
        if out is None:              # retries exhausted
            self.ledger.abandoned(req.rid, pf_table, "prefill_failed")
            pf.pool.release(pf_table)
            pf.free_row(row)
            self.pools[shard].release(dst)
            self._recover(req, slot, "prefill_failed")
            return "consumed"
        logits, pf.caches = out
        # NOT read here: the logits stay a device future until the next
        # tick's completion pass — that's the prefill/decode overlap
        self.slots[slot] = req
        req.prefill_len, req.shared_len = len(tail), shared_len
        self.pending.append(_PendingPrefill(
            req, slot, shard, row, pf_table, shared_len, plen, dst,
            logits))
        self.admitted += 1
        self.prefix_hits += shared_len > 0
        self.prefill_tokens += len(tail)
        self.prefill_tokens_skipped += shared_len
        self.peak_pages_in_use = max(self.peak_pages_in_use,
                                     self.pages_in_use)
        return "admitted"

    # ------------------------------------------------------ tick machine
    def _tick(self) -> bool:
        """Decode dispatch -> prefill completion -> decode collect.

        The completion pass sits *between* dispatch and collect on
        purpose: while every active decode shard's step is in flight,
        the host syncs on finished prefill logits, caches the prompt in
        the prefix tree, and lands the handoff (migrate + transfer +
        install) on the decode queue — so a tick that does both overlaps
        one request's prefill against the others' decode compute.  A
        request completed here starts decoding next tick (its slot was
        not in this tick's dispatch mask)."""
        t0 = time.perf_counter()
        self._tick_begin()
        inflight = self._decode_dispatch()
        completed = self._complete_prefills()
        if inflight:
            self._decode_collect(inflight)
            self.ticks += 1
            if completed:
                self.overlap_ticks += 1
            dt = time.perf_counter() - t0
            self.tick_wall_s.push(dt)
            self.straggler.observe(self.clock, dt)
        self._update_health()
        return bool(inflight) or bool(completed)

    def _complete_prefills(self) -> int:
        """Finish every pending prefill: read its logits (sync), insert
        the prompt into the prefix tree, hand the pages off to the
        decode shard, seed the first generated token."""
        done = 0
        for p in list(self.pending):
            self.pending.remove(p)
            req = p.req
            if req.done:
                # cancelled while pending is cleaned eagerly by cancel();
                # this handles deadline/retire-while-pending
                self._drop_pending(p, req.finish_reason or "dropped")
                continue
            row_logits = p.logits[p.row]
            if not bool(jnp.isfinite(row_logits).all()):
                # poisoned prefill: the request is damaged, the pages
                # were never certified — never insert them into the tree
                self._drop_pending(p, "nan_logits")
                self._recover(req, p.slot, "nan_logits")
                continue
            # the prompt's pages now hold certified KV: cache them for
            # future matches (and for this request's own re-prefill
            # should it ever be quarantined), then land the handoff
            self.prefill.tree.insert(req.prompt, p.pf_table)
            self.decoder.land(p)
            self.prefill.free_row(p.row)
            self._append(req, p.slot, int(jnp.argmax(row_logits)))
            done += 1
        return done

    def _drop_pending(self, p: _PendingPrefill, reason: str) -> None:
        """Release everything a pending prefill holds: prefill-side
        custody (journaled as abandoned), the reserved decode pages,
        and the prefill row.  The decode slot is the caller's problem
        (cancel/retire/recover already handled it)."""
        self.ledger.abandoned(p.req.rid, p.pf_table, reason)
        self.prefill.pool.release(p.pf_table)
        self.prefill.free_row(p.row)
        self.pools[p.shard].release(p.dst_pages)

    # ------------------------------------------------- retire and cancel
    def _release_slot(self, slot: int):
        pages = self.slot_pages[slot]
        if pages is not None:
            req = self.slots[slot]
            self.ledger.retired(req.rid if req is not None else None,
                                slot // self.mb, pages)
        super()._release_slot(slot)

    def cancel(self, req: Request):
        """Mid-flight cancel, including the pending-prefill window: the
        reserved decode pages are released against a ``cancel`` trace
        marker (the GWY004 cross-check), prefill-side custody is
        journaled as abandoned, and the decode slot frees immediately."""
        for p in self.pending:
            if p.req is req:
                self.pending.remove(p)
                pool = self.pools[p.shard]
                if pool.trace is not None:
                    pool.note("cancel", rid=req.rid, slot=p.slot)
                self.ledger.abandoned(req.rid, p.pf_table, "cancelled")
                self.prefill.pool.release(p.pf_table)
                self.prefill.free_row(p.row)
                pool.release(p.dst_pages)
                self.slots[p.slot] = None
                req.done, req.finish_reason = True, "cancelled"
                self.cancelled += 1
                return list(p.dst_pages)
        return super().cancel(req)

    # ------------------------------------------------------------ verify
    def verify(self):
        """SRV refcount discipline over the prefill pool (tree-aware)
        and every decode pool (reservation-aware), plus the DSG handoff
        totality rules over the ledger.  Raises ``AnalysisError`` on any
        violation."""
        from repro.analysis import (Report, check_handoff_trace,
                                    verify_pool)
        if not self.verify_enabled:
            return Report(subject="serving (verification disabled)")
        out = Report(subject=f"disagg serving {self.cfg.name} "
                             f"({self.microbatches} decode shard(s))")
        live_pf = [p.pf_table for p in self.pending]
        out.extend(verify_pool(self.prefill.pool, self.prefill.tree,
                               live_slot_pages=live_pf),
                   passname="serving")
        for shard, pool in enumerate(self.pools):
            live = [self.slot_pages[i]
                    for i in range(shard * self.mb, (shard + 1) * self.mb)
                    if self.slot_pages[i] is not None]
            live += [pages for _, sh, pages in self._pressure_holds
                     if sh == shard]
            live += [p.dst_pages for p in self.pending
                     if p.shard == shard]
            out.extend(verify_pool(pool, None, live_slot_pages=live),
                       passname="serving")
        out.extend(check_handoff_trace(
            self.ledger.events,
            live_rids=[p.req.rid for p in self.pending]),
            passname="handoff")
        return out.raise_on_error()

    # ------------------------------------------------------------- stats
    def stats(self) -> dict:
        out = super().stats()
        pf = self.prefill
        out.update({
            "disaggregated": True,
            "prefill_slots": pf.slots,
            "prefill_pool_pages": pf.pool.n_pages,
            "prefill_pages_in_use": pf.pool.used_pages,
            "tree_nodes": pf.tree.nodes,
            "pending_prefills": len(self.pending),
            "transfers": self.transfers,
            "pages_transferred": self.pages_transferred,
            "prefill_dispatches": pf.queue.dispatched,
            "overlap_ticks": self.overlap_ticks,
            # fraction of decode ticks that also completed a prefill:
            # the disaggregation win — prefill compute hidden behind
            # other requests' decode steps
            "prefill_decode_overlap": round(
                self.overlap_ticks / max(self.ticks, 1), 3),
        })
        return out
