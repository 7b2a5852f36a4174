"""Closed-loop load generator for the gateway.

Simulates a population of virtual clients per priority class, each in a
closed loop: *think* for a sampled number of gateway steps, *submit* one
completion call, *wait* for its terminal record, repeat.  Two arrival
processes:

  * ``poisson`` — geometric per-client think times (the memoryless
    discretization of Poisson arrivals: submissions trickle in);
  * ``bursty``  — with probability ``burst_p`` a client's think time is
    zero, so think-time expiries clump into admission bursts that slam
    the queues (the workload the WDRR scheduler and queue-depth-aware
    batch sizing exist for).

Prompts come from a fixed shared-prefix pool (``--pool`` unique prompts,
all opening with the same system-prompt tokens), so the prefix tree gets
real reuse *and* ``--check`` stays affordable over thousands of
requests: the solo-reference oracle memoizes per (prompt, gen) pair.
Interactive clients stream; the generator reassembles their chunks
(restart-aware) and asserts the stream equals the final response.

A run's datapoint — throughput, rolling TTFT / per-token latency
p50/p99, per-class queueing delay, outcome counts — is appended under
the ``"gateway"`` key of ``benchmarks/BENCH_serve.json``; ``--snapshot``
additionally writes the full metrics snapshot (the CI artifact).

CLI::

    PYTHONPATH=src python -m repro.gateway.loadgen --arch smollm_135m \
        --reduced --requests 1000 --batch 8 --arrival bursty --check
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time

import numpy as np

from repro.gateway.api import CompletionRequest, Rejection
from repro.gateway.gateway import Gateway
from repro.launch.serve import SURVIVOR_REASONS, Server, solo_reference

__all__ = ["ClientClass", "DEFAULT_MIX", "run_loadgen"]

_BENCH_JSON = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))),
    "benchmarks", "BENCH_serve.json")


@dataclasses.dataclass(frozen=True)
class ClientClass:
    """One closed-loop client population."""

    priority: str
    clients: int          # concurrent virtual users
    mean_think: float     # mean think time, in gateway steps
    gen: int              # max_tokens per request
    stream: bool = False


DEFAULT_MIX = (
    ClientClass("interactive", clients=6, mean_think=2.0, gen=8,
                stream=True),
    ClientClass("standard", clients=4, mean_think=4.0, gen=12),
    ClientClass("batch", clients=4, mean_think=8.0, gen=16),
)


@dataclasses.dataclass
class _Client:
    spec: ClientClass
    think: int = 0
    rid: str | None = None
    pidx: int = -1
    cancel_at: int | None = None
    stream_toks: list[int] = dataclasses.field(default_factory=list)


def _prompt_pool(vocab_size, n, prompt_len, shared_prefix, rng):
    """``n`` unique prompts sharing their first ``shared_prefix`` tokens
    with random tails of varying length (<= ``prompt_len`` total)."""
    shared = rng.integers(0, vocab_size, shared_prefix).astype(np.int32)
    max_tail = max(prompt_len - shared_prefix, 1)
    return [np.concatenate([shared,
                            rng.integers(0, vocab_size,
                                         int(rng.integers(1, max_tail + 1))
                                         ).astype(np.int32)])
            for _ in range(n)]


def _think(spec: ClientClass, arrival: str, rng, burst_p: float) -> int:
    if arrival == "bursty" and rng.random() < burst_p:
        return 0                      # clump with everyone else's expiry
    mean = spec.mean_think * (2.0 if arrival == "bursty" else 1.0)
    return int(rng.geometric(min(1.0, 1.0 / max(mean, 1e-9))))


def run_loadgen(server: Server, *, requests: int = 1000,
                mix: tuple[ClientClass, ...] = DEFAULT_MIX,
                arrival: str = "bursty", burst_p: float = 0.5,
                pool: int = 64, prompt_len: int = 16,
                shared_prefix: int = 9, cancel_rate: float = 0.0,
                deadline_s: float | None = None,
                deadline_rate: float = 0.0, seed: int = 0,
                check: bool = False, max_steps: int | None = None,
                verbose: bool = True) -> tuple[Gateway, dict]:
    """Drive ``requests`` completions through a :class:`Gateway` over
    ``server`` and return ``(gateway, datapoint)``.  With ``check=True``
    every surviving response is asserted bit-identical to its memoized
    solo reference, streamed chunks must reassemble into the final
    tokens, and the summed ``cached_tokens`` usage must equal the
    server's ``prefill_tokens_skipped`` counter."""
    if arrival not in ("poisson", "bursty"):
        raise ValueError(f"unknown arrival process {arrival!r}")
    rng = np.random.default_rng(seed)
    cfg = server.cfg
    prompts = _prompt_pool(cfg.vocab_size, pool, prompt_len,
                           shared_prefix, rng)
    gw = Gateway(server)
    clients = [ _Client(spec, think=_think(spec, arrival, rng, burst_p))
                for spec in mix for _ in range(spec.clients) ]
    rid_to_pidx: dict[str, int] = {}
    rid_gen: dict[str, int] = {}
    submitted = 0
    cancels_sent = 0
    t0 = time.perf_counter()
    cap = max_steps if max_steps is not None else 500 * requests

    while True:
        live = [c for c in clients if c.rid is not None]
        if submitted >= requests and not live \
                and not gw._live and not gw.sched.depth:
            break
        if gw.steps >= cap:
            raise RuntimeError(gw._stuck_report(cap))
        # 1. expire think timers -> submissions (closed loop: a client
        # with an outstanding request never submits another)
        for c in clients:
            if c.rid is not None or submitted >= requests:
                continue
            if c.think > 0:
                c.think -= 1
                continue
            c.pidx = int(rng.integers(0, len(prompts)))
            dl = None
            if deadline_s is not None and rng.random() < deadline_rate:
                dl = deadline_s
            creq = CompletionRequest(
                prompts[c.pidx], c.spec.gen, priority=c.spec.priority,
                deadline_s=dl, stream=c.spec.stream)
            out = gw.submit(creq)
            submitted += 1
            if isinstance(out, Rejection):
                c.think = _think(c.spec, arrival, rng, burst_p)
                continue
            c.rid, c.stream_toks = out, []
            rid_to_pidx[out], rid_gen[out] = c.pidx, c.spec.gen
            c.cancel_at = None
            if cancel_rate > 0 and rng.random() < cancel_rate:
                c.cancel_at = gw.steps + int(rng.integers(1, 6))
        # 2. one gateway step (admissions + decode tick + stream polls)
        gw.step()
        # 3. collect streams / terminal records, fire due cancellations
        for c in clients:
            if c.rid is None:
                continue
            if c.spec.stream:
                for ch in gw.chunks(c.rid):
                    if ch.restart:
                        c.stream_toks = []   # recovery voided the stream
                    c.stream_toks.extend(ch.tokens)
            if c.rid in gw.responses or c.rid in gw.rejections:
                resp = gw.responses.get(c.rid)
                if check and resp is not None and c.spec.stream \
                        and resp.finish_reason in SURVIVOR_REASONS:
                    assert c.stream_toks == resp.tokens, (
                        f"{c.rid}: stream reassembly "
                        f"{c.stream_toks} != response {resp.tokens}")
                c.rid = None
                c.think = _think(c.spec, arrival, rng, burst_p)
            elif c.cancel_at is not None and gw.steps >= c.cancel_at:
                if gw.cancel(c.rid):
                    cancels_sent += 1
                c.cancel_at = None
    wall = time.perf_counter() - t0

    # ---- total accounting: the contract the CI smoke gates on
    assert not gw.unaccounted(), (
        f"unaccounted requests after drain: {gw.unaccounted()}")
    assert len(gw.responses) + len(gw.rejections) == submitted

    survivors = [r for r in gw.responses.values()
                 if r.finish_reason in SURVIVOR_REASONS]
    if check:
        memo: dict[tuple[int, int], list[int]] = {}
        for r in survivors:
            key = (rid_to_pidx[r.rid], rid_gen[r.rid])
            if key not in memo:
                memo[key] = solo_reference(
                    cfg, server.params, prompts[key[0]], key[1],
                    server.max_len)
            assert r.tokens == memo[key], (
                f"{r.rid}: served tokens diverge from the solo "
                f"reference\n  got {r.tokens}\n  ref {memo[key]}")
        cached = sum(r.usage.cached_tokens for r in gw.responses.values())
        assert cached == server.prefill_tokens_skipped, (
            f"usage cached_tokens {cached} != server "
            f"prefill_tokens_skipped {server.prefill_tokens_skipped}")
        if verbose:
            print(f"check: {len(survivors)} survivors bit-identical "
                  f"({len(memo)} unique references), usage accounts for "
                  f"{cached} cached prompt tokens")

    snap = gw.metrics.snapshot()
    tokens = sum(len(r.tokens) for r in gw.responses.values())
    by_outcome: dict[str, int] = {}
    for r in gw.responses.values():
        by_outcome[r.finish_reason] = by_outcome.get(r.finish_reason, 0) + 1
    for rej in gw.rejections.values():
        by_outcome[rej.reason] = by_outcome.get(rej.reason, 0) + 1
    point = {
        "date": time.strftime("%Y-%m-%d"),
        "arch": cfg.name,
        "requests": submitted,
        "arrival": arrival,
        "checked": check,
        "wall_s": round(wall, 3),
        "steps": gw.steps,
        "tokens": tokens,
        "tok_per_s": round(tokens / wall, 1) if wall else 0.0,
        "throughput_tok_s": snap["throughput_tok_s"],
        "ttft_ms": snap["ttft_ms"],
        "token_latency_ms": snap["token_latency_ms"],
        "queue_delay_ms": snap["queue_delay_ms"],
        "queue_depth": snap["queue_depth"],
        "outcomes": dict(sorted(by_outcome.items())),
        "survivors": len(survivors),
        "cancelled_sent": cancels_sent,
        "rejections": len(gw.rejections),
        "prefill_tokens_skipped": server.prefill_tokens_skipped,
        "by_class": {
            spec.priority: {
                "clients": spec.clients,
                "submitted": gw.sched.enqueued.get(spec.priority, 0),
                "dispatched": gw.sched.dispatched.get(spec.priority, 0),
            } for spec in mix},
    }
    if verbose:
        print(f"loadgen: {submitted} requests ({arrival}) -> "
              f"{len(gw.responses)} responses / {len(gw.rejections)} "
              f"rejections in {point['wall_s']}s "
              f"({point['tok_per_s']} tok/s, "
              f"ttft p50 {snap['ttft_ms']['p50']}ms "
              f"p99 {snap['ttft_ms']['p99']}ms, "
              f"token p50 {snap['token_latency_ms']['p50']}ms)")
        print(f"outcomes: {point['outcomes']}")
    return gw, point


def append_datapoint(point: dict, path: str = _BENCH_JSON) -> None:
    """Append a loadgen datapoint under the ``"gateway"`` key of the
    serve benchmark JSON (preserving the serve rows)."""
    payload: dict = {}
    if os.path.exists(path):
        with open(path) as f:
            payload = json.load(f)
    payload.setdefault("gateway", []).append(point)
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")


def main(argv=None):
    import jax

    import repro.configs as configs
    from repro.configs.base import reduce as reduce_cfg
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import lm

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm_135m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=1000)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--microbatches", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--shared-prefix", type=int, default=9)
    ap.add_argument("--pool", type=int, default=64,
                    help="unique prompts in the shared-prefix pool")
    ap.add_argument("--arrival", choices=("poisson", "bursty"),
                    default="bursty")
    ap.add_argument("--cancel-rate", type=float, default=0.0,
                    help="per-request probability of a mid-flight cancel")
    ap.add_argument("--deadline-s", type=float, default=None)
    ap.add_argument("--deadline-rate", type=float, default=0.0,
                    help="fraction of requests carrying --deadline-s")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--check", action="store_true",
                    help="bit-equivalence oracle over every survivor, "
                         "stream reassembly, and usage accounting")
    ap.add_argument("--verify", action="store_true",
                    help="record traces; run GWY + SRV checkers at drain")
    ap.add_argument("--disagg", action="store_true",
                    help="serve through the disaggregated prefill/decode "
                         "runtime (repro.launch.disagg) — adds the DSG "
                         "handoff checker under --verify")
    ap.add_argument("--prefill-slots", type=int, default=2,
                    help="concurrent in-flight prefills (--disagg only)")
    ap.add_argument("--out-json", type=str, default=None,
                    help="append the datapoint under this JSON's "
                         "'gateway' key (e.g. benchmarks/BENCH_serve.json)")
    ap.add_argument("--snapshot", type=str, default=None,
                    help="write the full metrics snapshot JSON here")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = configs.get(args.arch)
    if args.reduced:
        cfg = reduce_cfg(cfg)
    params, _ = lm.init_params(cfg, jax.random.PRNGKey(0))
    gen_max = max(c.gen for c in DEFAULT_MIX)
    server_cls = Server
    server_kw = {}
    if args.disagg:
        from repro.launch.disagg import DisaggServer
        server_cls = DisaggServer
        server_kw["prefill_slots"] = args.prefill_slots
    server = server_cls(cfg, params, batch=args.batch,
                        max_len=args.prompt_len + gen_max + 8,
                        microbatches=args.microbatches, verify=args.verify,
                        **server_kw)
    gw, point = run_loadgen(
        server, requests=args.requests, arrival=args.arrival,
        pool=args.pool, prompt_len=args.prompt_len,
        shared_prefix=args.shared_prefix, cancel_rate=args.cancel_rate,
        deadline_s=args.deadline_s, deadline_rate=args.deadline_rate,
        seed=args.seed, check=args.check)
    if args.verify:
        gw.verify()
        extra = " + DSG handoff" if args.disagg else ""
        print(f"verify: GWY gateway-lifecycle + SRV serving-invariant"
              f"{extra} checkers passed")
    if args.snapshot:
        with open(args.snapshot, "w") as f:
            json.dump(gw.metrics.snapshot(), f, indent=2)
            f.write("\n")
        print(f"wrote metrics snapshot to {args.snapshot}")
    if args.out_json:
        append_datapoint(point, args.out_json)
        print(f"appended gateway datapoint to {args.out_json}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
