"""The gateway: admission scheduling + token streaming + observability
in front of ``repro.launch.serve.Server``.

One :class:`Gateway` owns the three layers the tentpole names:

  * an :class:`~repro.gateway.admission.AdmissionScheduler` holding
    per-priority-class queues (WDRR fairness, queue-depth-aware batch
    sizing, 429-style backpressure — including surfacing the server's
    ``healthy -> degraded -> shedding`` health machine as explicit
    rejections at the front door);
  * the **streaming pump**: each :meth:`step` dispatches due admissions,
    ticks the server once, and polls every in-flight request for its
    token delta through the server's narrow ``submit/poll/cancel``
    interface — recording TTFT on the first token and per-token latency
    after that, and emitting :class:`~repro.gateway.api.StreamChunk`
    deltas for ``stream=True`` requests (with a ``restart`` marker when
    fault recovery rewinds a stream);
  * a :class:`~repro.gateway.metrics.GatewayMetrics` ledger exporting
    rolling p50/p99s, throughput, queue depth, and utilization as JSON
    snapshots or Prometheus text.

**Accounting is total**: every submitted request terminates in exactly
one of ``responses`` (it occupied a slot; ``finish_reason`` says how it
left) or ``rejections`` (it never did; ``status`` says why) —
:meth:`unaccounted` returns the ids violating that, and the loadgen/CI
smoke asserts it empty.  The gateway also records a lifecycle trace
(``submit``/``admit``/``retire``/``reject``/``cancel`` events) that the
``GWY00x`` rules in :mod:`repro.analysis.gateway` verify statically:
every admitted request eventually retires with a reason, and every
cancellation released exactly the page refs it held.
"""
from __future__ import annotations

import dataclasses
import itertools
import time

import numpy as np

from repro import obs
from repro.gateway.admission import AdmissionScheduler
from repro.gateway.api import (
    CompletionRequest, CompletionResponse, Rejection, StreamChunk, Usage,
    validate,
)
from repro.gateway.metrics import GatewayMetrics
from repro.launch.serve import SURVIVOR_REASONS, Request, Server

__all__ = ["Gateway"]


@dataclasses.dataclass
class _Live:
    """Gateway-side state for one non-terminal request."""

    creq: CompletionRequest
    t_submit: float
    sreq: Request | None = None      # set once dispatched into the server
    t_dispatch: float | None = None
    t_first_token: float | None = None
    t_last_token: float | None = None
    n_polled: int = 0                # stream cursor mirror (restart detect)
    chunks: list[StreamChunk] = dataclasses.field(default_factory=list)


class Gateway:
    """Network front-end over one :class:`Server` (see module docs)."""

    def __init__(self, server: Server, *,
                 scheduler: AdmissionScheduler | None = None,
                 metrics: GatewayMetrics | None = None,
                 record: bool = True, clock=time.monotonic):
        self.server = server
        self.clock = clock
        self.sched = scheduler or AdmissionScheduler(clock=clock)
        self.metrics = metrics or GatewayMetrics(clock=clock)
        # lifecycle trace for the GWY00x static rules
        self.trace: list[tuple] | None = [] if record else None
        self.responses: dict[str, CompletionResponse] = {}
        self.rejections: dict[str, Rejection] = {}
        self._live: dict[str, _Live] = {}
        self._done_chunks: dict[str, list[StreamChunk]] = {}
        self._ids: list[str] = []            # every rid ever submitted
        self._next_rid = itertools.count()
        self.steps = 0

    # ------------------------------------------------------------ helpers
    def _note(self, *event) -> None:
        if self.trace is not None:
            self.trace.append(event)

    def _finalize_reject(self, rej: Rejection) -> None:
        self.rejections[rej.rid] = rej
        self._live.pop(rej.rid, None)
        self._note("reject", rej.rid, rej.reason)
        if rej.reason == "cancelled":
            self.metrics.observe_cancel()
        else:
            self.metrics.observe_rejection(rej.reason)

    def _finalize_response(self, live: _Live, *,
                           terminal: str = "retire") -> CompletionResponse:
        sreq, creq = live.sreq, live.creq
        assert sreq is not None
        now = self.clock()
        finish = sreq.finish_reason or "length"
        resp = CompletionResponse(
            rid=creq.rid, tokens=list(sreq.out), finish_reason=finish,
            usage=Usage(prompt_tokens=int(np.asarray(creq.prompt).size),
                        cached_tokens=max(sreq.shared_len, 0),
                        generated_tokens=len(sreq.out)),
            priority=creq.priority,
            ttft_s=(None if live.t_first_token is None
                    else live.t_first_token - live.t_submit),
            latency_s=now - live.t_submit,
            queue_delay_s=((live.t_dispatch or live.t_submit)
                           - live.t_submit))
        self.responses[creq.rid] = resp
        if creq.stream:
            live.chunks.append(StreamChunk(creq.rid, [], done=True,
                                           finish_reason=finish))
            # keep undrained chunks past retirement for late collectors
            self._done_chunks[creq.rid] = live.chunks
        if terminal == "retire":
            self._note("retire", creq.rid, finish)
        if finish in SURVIVOR_REASONS:
            self.metrics.observe_completion(len(sreq.out), now)
        elif finish == "cancelled":
            self.metrics.observe_cancel()
        else:                               # deadline / failed:* / shed:*
            self.metrics.observe_rejection(finish)
        del self._live[creq.rid]
        return resp

    def _free_slots(self) -> int:
        """Slots an admission could take this step: empty, out of
        quarantine, and not already promised to a recovery re-admission
        (the server's requeue readmits inside ``tick`` and must not be
        starved by new arrivals)."""
        free = sum(1 for i, s in enumerate(self.server.slots)
                   if s is None and not self.server._is_quarantined(i))
        return max(0, free - len(self.server.requeue))

    # ------------------------------------------------------------- submit
    def submit(self, creq: CompletionRequest) -> str | Rejection:
        """Take one request at the front door.

        Returns its id when accepted into an admission queue, or a
        :class:`Rejection` (already recorded) when validation, queue
        bounds, or load shedding refuse it — the 429-style explicit
        backpressure path."""
        if not creq.rid:
            creq.rid = f"req-{next(self._next_rid)}"
        if creq.rid in self._live or creq.rid in self.responses \
                or creq.rid in self.rejections:
            raise ValueError(f"duplicate request id {creq.rid!r}")
        self._ids.append(creq.rid)
        self.metrics.observe_submit()
        self._note("submit", creq.rid, creq.priority)
        rej = validate(creq, vocab_size=self.server.cfg.vocab_size,
                       max_len=self.server.max_len)
        if rej is None:
            rej = self.sched.enqueue(creq, health=self.server.health,
                                     shed_reason=self.server._shed_reason)
        if rej is not None:
            self._finalize_reject(rej)
            return rej
        self._live[creq.rid] = _Live(creq, t_submit=self.clock())
        return creq.rid

    # ------------------------------------------------------------- cancel
    def cancel(self, rid: str) -> bool:
        """Cancel a queued or in-flight request.  Queued requests are
        rejected with reason ``cancelled`` (they never held a slot);
        in-flight requests retire with ``finish_reason="cancelled"``,
        keeping partial output, and their slot's page references are
        released immediately (verified by GWY004 against the pool
        trace).  Returns False when ``rid`` is unknown or already
        terminal."""
        live = self._live.get(rid)
        if live is None:
            return False
        if live.sreq is None:                    # still in the queue
            if self.sched.cancel(rid) is None:
                return False
            self._finalize_reject(Rejection(rid, "cancelled",
                                            "cancelled while queued"))
            return True
        pages = self.server.cancel(live.sreq)
        if pages is None:                        # retired this very step
            return False
        self._note("cancel", rid, tuple(int(p) for p in pages))
        self._finalize_response(live, terminal="cancel")
        return True

    # --------------------------------------------------------------- step
    def step(self) -> bool:
        """One gateway step: dispatch due admissions, tick the server,
        poll streams, sample gauges.  Returns whether the server's tick
        dispatched any decode work."""
        with obs.span("gateway.step"):
            self.steps += 1
            with obs.span("gateway.admit"):
                self._admit()
            ticked = self.server.tick()      # one lockstep decode tick
            with obs.span("gateway.poll"):
                self._poll()
            return ticked

    def _admit(self) -> None:
        """Admissions: the scheduler picks who and how many."""
        ready, expired = self.sched.dispatch(self._free_slots(),
                                             health=self.server.health)
        for rej in expired:
            self._finalize_reject(rej)
        now = self.clock()
        for creq, t_enq in ready:
            live = self._live[creq.rid]
            deadline = creq.deadline_s
            if deadline is not None:
                # the queue wait already spent part of the budget; the
                # server's own deadline clock starts at admission
                deadline = max(deadline - (now - live.t_submit), 1e-9)
            sreq = Request(creq.rid, np.asarray(creq.prompt, np.int32),
                           creq.max_tokens, deadline_s=deadline)
            if not self.server.submit(sreq):
                # slot/pool momentarily unavailable: back to the head of
                # its class queue with the original enqueue time
                self.sched.requeue_front(creq, t_enq)
                continue
            live.sreq, live.t_dispatch = sreq, now
            self.metrics.observe_queue_delay(creq.priority,
                                             now - live.t_submit)
            if sreq.done and sreq.finish_reason and (
                    sreq.finish_reason.startswith("shed:")
                    or sreq.finish_reason.startswith("rejected:")):
                # consumed at admission without ever occupying a slot
                reason = sreq.finish_reason
                reason = reason[len("rejected:"):] \
                    if reason.startswith("rejected:") else reason
                self._finalize_reject(Rejection(
                    creq.rid, reason, "refused at server admission"))
                continue
            self._note("admit", creq.rid)

    def _poll(self) -> None:
        """Poll every in-flight stream for its delta, finalise retired
        requests, and sample the gauges."""
        now = self.clock()
        for rid, live in list(self._live.items()):
            sreq = live.sreq
            if sreq is None:
                continue                        # still queued
            if sreq.streamed < live.n_polled:
                # fault recovery rewound the stream: previously emitted
                # tokens are void, generation restarts deterministically
                live.n_polled = 0
                if live.creq.stream:
                    live.chunks.append(StreamChunk(rid, [], restart=True))
            new = self.server.poll(sreq)
            if new:
                if live.t_first_token is None:
                    live.t_first_token = now
                    self.metrics.observe_ttft(now - live.t_submit)
                else:
                    dt = now - (live.t_last_token or live.t_first_token)
                    self.metrics.observe_token_latency(
                        dt / len(new), len(new))
                live.t_last_token = now
                live.n_polled += len(new)
                if live.creq.stream:
                    live.chunks.append(StreamChunk(rid, new))
            if sreq.done:
                self._finalize_response(live)
        busy = sum(s is not None for s in self.server.slots)
        pool_util = 0.0
        if self.server.paged:
            pool_util = (self.server.pages_in_use
                         / (self.server.pool_pages
                            * self.server.microbatches))
        self.metrics.sample(queue_depth=self.sched.depth,
                            slot_utilization=busy / self.server.batch,
                            pool_utilization=pool_util)

    # ------------------------------------------------------------- stream
    def chunks(self, rid: str) -> list[StreamChunk]:
        """Drain the stream chunks accumulated for ``rid`` (the poll-
        based stand-in for an SSE connection).  Chunks survive
        retirement until collected once."""
        live = self._live.get(rid)
        if live is not None:
            out, live.chunks = live.chunks, []
            return out
        return self._done_chunks.pop(rid, [])

    # -------------------------------------------------------------- drain
    def drain(self, *, max_steps: int = 10_000) -> None:
        """Step until every submitted request is terminal.  Raises with
        queue-level diagnostics (queued-by-class depths, oldest queued
        age — covering requests that never reached a slot) when the
        system does not converge."""
        while self._live or self.sched.depth:
            if self.steps >= max_steps:
                raise RuntimeError(self._stuck_report(max_steps))
            self.step()
        self.server.quiesce()
        if getattr(self.server, "verify_enabled", False) \
                or self.trace is not None:
            self.verify()

    def _stuck_report(self, max_steps: int) -> str:
        queued = [rid for rid, lv in self._live.items() if lv.sreq is None]
        inflight = [f"{rid} ({lv.n_polled}/{lv.creq.max_tokens} tokens)"
                    for rid, lv in self._live.items()
                    if lv.sreq is not None]
        st = self.sched.stats()
        return (f"gateway did not converge in {max_steps} steps\n"
                f"  queued (never reached a slot): {queued or 'none'}\n"
                f"  queued by class: {st['queued_by_class']}, oldest "
                f"queued {st['oldest_queued_age_s']}s\n"
                f"  in flight: {inflight or 'none'}\n"
                f"  server stats: {self.server.stats()}")

    # -------------------------------------------------------------- stats
    def unaccounted(self) -> list[str]:
        """Submitted ids with no terminal record — must be empty after
        :meth:`drain` (the CI gateway-smoke gate)."""
        return [rid for rid in self._ids
                if rid not in self.responses and rid not in self.rejections]

    def stats(self) -> dict:
        survivors = sum(r.finish_reason in SURVIVOR_REASONS
                        for r in self.responses.values())
        return {
            "submitted": len(self._ids),
            "responses": len(self.responses),
            "rejections": len(self.rejections),
            "survivors": survivors,
            "in_flight": len(self._live),
            "unaccounted": len(self.unaccounted()),
            "admission": self.sched.stats(),
            "metrics": self.metrics.snapshot(),
            "server": self.server.stats(),
        }

    # ------------------------------------------------------------- verify
    def verify(self):
        """Run the GWY00x gateway-invariant rules over the lifecycle
        trace (cross-checked against the server's pool traces when
        recorded), plus the server's own SRV refcount verification.
        Raises ``AnalysisError`` on any violation."""
        from repro.analysis import Report
        from repro.analysis.gateway import check_gateway_trace
        out = Report(subject=f"gateway over {self.server.cfg.name}")
        if self.trace is not None:
            pool_traces = []
            if self.server.paged:
                pool_traces = [p.trace for p in self.server.pools
                               if p.trace is not None]
            out.extend(check_gateway_trace(self.trace,
                                           pool_traces=pool_traces),
                       passname="gateway")
        if getattr(self.server, "verify_enabled", False):
            out.merge(self.server.verify())
        return out.raise_on_error()
