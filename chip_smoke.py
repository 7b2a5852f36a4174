"""Smoke run of the main path on a TPU, through the entry points a user calls.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # four chips: the sharded trainer only

One chip runs two phases:

  * serve: smollm-135m at its published widths (random weights from a
    seed) behind the paged, prefix-sharing ``Server``, drained by the same
    ``drain`` loop ``repro.launch.serve`` uses; a few served requests are
    replayed through the server's own prefill and decode executables and
    their logits are held to the float32 reference
    (``repro.models.reference``);
  * pipeline: the paper's Fig. 6a tinyml graph on the Fig. 6d cluster
    through place -> allocate -> schedule -> emit and the ``AsyncExecutor``
    on the Pallas gemm and maxpool kernels, equal to the host-core program
    exactly, with the kernels compiled for the chip (no interpret mode).

``--four-chips`` runs a few steps of the sharded trainer
(``repro.launch.train``) at full width on a data 2 x model 2 mesh and on one
device, and holds the two loss curves to each other.

The last line of stdout is one JSON object, ``{"ok": true, "device":
{...}}``.  Without a TPU, or when any phase fails, the script exits
non-zero and prints no such line.  Numbers it prints are host-clock smoke
figures, not benchmarks.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import repro.configs as configs  # noqa: E402
from repro.core import allocate, build_schedule, emit, place  # noqa: E402
from repro.core.presets import cluster_6d, host_fns, tinyml_graph  # noqa: E402
from repro.kernels import use_interpret  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.serve import Request, Server, _bucket, drain  # noqa: E402
from repro.models import lm  # noqa: E402
from repro.models.reference import (  # noqa: E402
    LOGIT_TOL, logit_error, reference_logits,
)

ARCH = "smollm_135m"
SEED = 0
# the SNAX accelerators whose compute runs as Pallas kernels
KERNEL_ACCELS = ("gemm-accel", "maxpool-accel")
# two losses of one step agree to this fraction of the loss.  The meshes
# add the same bfloat16 products in different orders (partial sums of the
# sharded matmuls and of the gradient all-reduce are rounded to bfloat16
# before they meet).  At full widths on a v5e 2x2 host the worst of four
# steps differed by 4.1e-5 relative; the bound is a hundred times that
# and under a thirtieth of the 16% the loss fell over the same steps
TRAIN_LOSS_RTOL = 5e-3


@dataclasses.dataclass(frozen=True)
class Traffic:
    """The served workload: ``requests`` prompts of ``prompt_min`` to
    ``prompt_max`` tokens, every other one opening with the same
    ``shared_prefix`` tokens, each generating ``gen`` tokens, arriving
    ``stagger`` decode ticks apart, on a ``batch``-slot paged server."""
    requests: int = 16
    prompt_min: int = 128
    prompt_max: int = 512
    shared_prefix: int = 256
    gen: int = 32
    stagger: int = 4
    batch: int = 8
    page_size: int = 16
    checked: int = 3          # requests replayed against the reference

    @property
    def max_len(self) -> int:
        need = self.prompt_max + self.gen - 1
        return -(-need // self.page_size) * self.page_size


class CompileClock:
    """Seconds JAX spends producing executables (compiling, or reading
    them from the persistent cache), and persistent-cache hits, per
    phase."""

    def __init__(self):
        self.seconds: dict[str, float] = {}
        self.hits: dict[str, int] = {}
        self.phase: str | None = None
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_):
        if self.phase and event == "/jax/core/compile/backend_compile_duration":
            self.seconds[self.phase] += duration

    def _on_event(self, event: str, **_):
        if self.phase and event == "/jax/compilation_cache/cache_hits":
            self.hits[self.phase] += 1

    @contextlib.contextmanager
    def __call__(self, phase: str):
        self.phase = phase
        self.seconds.setdefault(phase, 0.0)
        self.hits.setdefault(phase, 0)
        try:
            yield
        finally:
            self.phase = None
            print(f"compile seconds [{phase}]: {self.seconds[phase]:.2f} "
                  f"({self.hits[phase]} persistent-cache hits)")


# ---------------------------------------------------------------- serve --
def make_requests(cfg, t: Traffic, seed: int = SEED) -> list[Request]:
    rng = np.random.default_rng(seed)
    shared = rng.integers(0, cfg.vocab_size, t.shared_prefix)
    out = []
    for i in range(t.requests):
        if i % 2 == 0:
            tail = rng.integers(t.prompt_min, t.prompt_max - t.shared_prefix
                                + 1)
            prompt = np.concatenate(
                [shared, rng.integers(0, cfg.vocab_size, tail)])
        else:
            n = rng.integers(t.prompt_min, t.prompt_max + 1)
            prompt = rng.integers(0, cfg.vocab_size, n)
        out.append(Request(i, prompt.astype(np.int32), t.gen,
                           arrival=i * t.stagger))
    return out


def replay_logits(server: Server, req: Request) -> np.ndarray:
    """(gen, V) logits of ``req`` replayed alone, teacher-forced on its
    served tokens, through the server's own prefill and decode
    executables on a fresh cache of the server's shapes."""
    cfg, mb, p = server.cfg, server.mb, len(req.prompt)
    caches = lm.init_caches(cfg, mb, server.max_len, paged=True,
                            page_size=server.page_size,
                            n_pages=server.pool_pages)
    n = -(-(p + len(req.out) - 1) // server.page_size)
    table = np.full((server.n_slot_pages,), -1, np.int32)
    table[:n] = np.arange(n)
    caches = server._install(caches, jnp.int32(0), jnp.asarray(table),
                             jnp.int32(0))
    toks = np.zeros((mb, _bucket(p)), np.int32)
    toks[0, :p] = req.prompt
    sl = np.zeros((mb,), np.int32)
    sl[0] = p
    logits, caches = server._prefill(server.params, jnp.asarray(toks),
                                     caches, jnp.asarray(sl))
    rows = [np.asarray(logits[0], np.float32)]
    sl[0] = 1
    for tok in req.out[:-1]:
        step = np.zeros((mb, 1), np.int32)
        step[0, 0] = tok
        logits, caches = server._decode(server.params, jnp.asarray(step),
                                        caches, jnp.asarray(sl))
        rows.append(np.asarray(logits[0, 0], np.float32))
    return np.stack(rows)


def check_against_reference(server: Server, req: Request) -> float:
    """logit_error of ``req``'s replayed logits against the reference
    over the same tokens."""
    got = replay_logits(server, req)
    if not np.isfinite(got).all():
        raise AssertionError(f"request {req.rid}: non-finite logits")
    p = len(req.prompt)
    toks = np.zeros((1, server.max_len), np.int32)   # one compile for all
    seq = np.concatenate([req.prompt, np.asarray(req.out[:-1], np.int32)])
    toks[0, :len(seq)] = seq
    ref = reference_logits(server.cfg, server.params, toks)
    return logit_error(got, np.asarray(ref[0, p - 1:len(seq)]))


def serve_phase(cfg, t: Traffic, *, seed: int = SEED) -> dict:
    """Serve ``t`` on ``cfg`` with random weights from ``seed``, check
    every request and the logits of ``t.checked`` of them; returns the
    figures the smoke prints."""
    params, _ = lm.init_params(cfg, jax.random.PRNGKey(seed))
    server = Server(cfg, params, batch=t.batch, max_len=t.max_len,
                    page_size=t.page_size)
    pending = make_requests(cfg, t, seed)
    t0 = time.perf_counter()
    done = drain(server, pending, max_iters=100 * (t.requests * t.stagger
                                                   + t.gen))
    wall = time.perf_counter() - t0
    if len(done) != t.requests:
        raise AssertionError(f"{len(done)}/{t.requests} requests retired")
    bad = [(r.rid, r.finish_reason, len(r.out)) for r in done
           if r.finish_reason != "length" or len(r.out) != t.gen]
    if bad:
        raise AssertionError(f"requests not served to length: {bad}")
    # the server checks every prefill and decode row for non-finite
    # logits and sends such a request into recovery
    if server.faults_detected or server.recoveries:
        raise AssertionError(f"faults while serving: {server.stats()}")
    if t.shared_prefix and not server.prefix_hits:
        raise AssertionError("no request reused the shared prefix")
    done.sort(key=lambda r: r.rid)
    step = max(1, len(done) // t.checked)
    errs = {r.rid: check_against_reference(server, r)
            for r in done[::step][:t.checked]}
    tol = LOGIT_TOL[cfg.compute_dtype]
    worst = max(errs.values())
    if not worst <= tol:
        raise AssertionError(
            f"logit error {worst:.3e} > tolerance {tol:.1e} ({errs})")
    tokens = sum(len(r.out) for r in done)
    return {"requests": len(done), "tokens": tokens,
            "prefill_tokens": server.prefill_tokens,
            "prefill_tokens_skipped": server.prefill_tokens_skipped,
            "wall_s": wall, "tok_s": tokens / wall,
            "logit_err": errs, "logit_tol": tol}


# ------------------------------------------------------------- pipeline --
def _stage_avals(graph, st, n_tiles: int):
    out = []
    for name in st.inputs:
        spec = graph.value_spec(name)
        shape = tuple(spec.shape)
        if name in st.tiled_inputs:
            shape = (shape[0] // n_tiles,) + shape[1:]
        out.append(jax.ShapeDtypeStruct(shape, jnp.dtype(spec.dtype)))
    return out


def pipeline_phase(*, n_tiles: int = 8, seed: int = SEED) -> dict:
    """Fig. 6a on Fig. 6d through the four passes and the AsyncExecutor;
    the output must equal the host-core program exactly, and every
    accelerator stage must lower to a compiled Pallas kernel (on the CPU,
    where tests run it, to an interpreted one)."""
    graph, cluster = tinyml_graph(), cluster_6d()
    placement = place(graph, cluster)
    if not set(KERNEL_ACCELS) <= set(placement.values()):
        raise AssertionError(f"placement skipped an accelerator: "
                             f"{placement}")
    plan = allocate(graph, cluster, n_tiles=n_tiles, streamed=("x",))
    build_schedule(graph, placement, cluster, plan=plan, n_tiles=n_tiles,
                   streamed=("x",), mode="pipelined")
    program = emit(graph, placement, cluster, streamed=("x",),
                   n_tiles=n_tiles)
    ks = jax.random.split(jax.random.PRNGKey(seed), len(graph.inputs))
    vals = {name: jax.random.randint(k, spec.shape, -8, 8, jnp.int8)
            for k, (name, spec) in zip(ks, graph.inputs.items())}
    got = np.asarray(program(vals)["fc"])
    env, fns = dict(vals), host_fns()
    for node in graph.topo():
        env[node.name] = fns[node.kernel](node.attrs,
                                          *[env[i] for i in node.inputs])
    want = np.asarray(env["fc"])
    if got.shape != want.shape or not np.array_equal(got, want):
        raise AssertionError(
            f"pipeline output differs from the host program: "
            f"{int(np.sum(got != want))} of {want.size} entries")
    kernels = {}
    for st in program.report.stages:
        if st.device in KERNEL_ACCELS:
            hlo = jax.jit(st.fn).lower(
                *_stage_avals(graph, st, n_tiles)).compile().as_text()
            kernels[st.stage] = "tpu_custom_call" in hlo
    compiled = not use_interpret()
    if any(k != compiled for k in kernels.values()):
        raise AssertionError(f"compiled kernel per stage: {kernels}, on "
                             f"{jax.default_backend()}")
    return {"shape": got.shape, "checksum": int(got.astype(np.int64).sum()),
            "kernels": kernels}


# ----------------------------------------------------------- four chips --
def train_losses(cfg, mesh, *, steps: int, batch: int, seq: int,
                 seed: int = SEED) -> list[float]:
    """Losses of ``steps`` sharded train steps on ``mesh`` (the trainer's
    own state builder and step, as ``repro.launch.train.main`` runs)."""
    from repro.data.synthetic import make_batch
    from repro.launch.train import build_train_step, make_sharded_state
    from repro.sharding.rules import batch_specs

    params, opt, p_sh, o_sh = make_sharded_state(cfg, mesh, seed=seed)
    b_sh = batch_specs(jax.eval_shape(lambda: make_batch(cfg, batch, seq)),
                       mesh)
    step = jax.jit(build_train_step(cfg, peak_lr=1e-3, warmup=1,
                                    total=steps),
                   in_shardings=(p_sh, o_sh, b_sh),
                   out_shardings=(p_sh, o_sh, None), donate_argnums=(0, 1))
    losses = []
    with mesh:
        for i in range(steps):
            data = jax.device_put(make_batch(cfg, batch, seq, i), b_sh)
            params, opt, m = step(params, opt, data)
            losses.append(float(m["loss"]))
    return losses


def four_chip_phase(cfg, *, steps: int = 4, batch: int = 8,
                    seq: int = 256) -> dict:
    from repro.launch.mesh import make_local_mesh

    sharded = make_local_mesh(model=2)
    one = make_local_mesh(data=1, model=1)
    got = train_losses(cfg, sharded, steps=steps, batch=batch, seq=seq)
    ref = train_losses(cfg, one, steps=steps, batch=batch, seq=seq)
    print(f"losses on mesh {dict(sharded.shape)}: {got}")
    print(f"losses on one device:            {ref}")
    rel = max(abs(a - b) / abs(b) for a, b in zip(got, ref))
    print(f"largest relative loss difference {rel:.3e} "
          f"(tolerance {TRAIN_LOSS_RTOL:.0e})")
    if not (np.isfinite(got).all() and rel <= TRAIN_LOSS_RTOL):
        raise AssertionError("sharded and one-device losses disagree")
    if not got[-1] < got[0]:
        raise AssertionError(f"loss did not fall: {got}")
    return {"sharded": got, "one_device": ref, "rel": rel}


# ----------------------------------------------------------------- main --
def require_tpu(count: int) -> list:
    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found "
                         f"{len(devices)} {d.platform} device(s) "
                         f"({d.device_kind})")
    if len(devices) < count:
        raise SystemExit(f"chip_smoke: needs {count} TPU chips, found "
                         f"{len(devices)}")
    return devices


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded trainer, on four chips")
    args = ap.parse_args(argv)
    devices = require_tpu(4 if args.four_chips else 1)
    print(f"device: {devices[0].device_kind} x{len(devices)}")
    print(f"compile cache: {enable_compile_cache()}")
    clock = CompileClock()
    cfg = configs.get(ARCH)
    print(f"model: {cfg.name} {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"heads {cfg.n_heads}/{cfg.n_kv_heads}, vocab {cfg.vocab_size}, "
          f"{cfg.compute_dtype}")
    if args.four_chips:
        with clock("train"):
            four_chip_phase(cfg)
    else:
        traffic = Traffic()
        with clock("serve"):
            s = serve_phase(cfg, traffic)
        print(f"served {s['requests']} requests, {s['tokens']} tokens "
              f"({s['prefill_tokens']} prompt tokens prefilled, "
              f"{s['prefill_tokens_skipped']} reused from the prefix "
              f"cache), every one to length")
        per = ", ".join(f"rid {k}: {v:.3e}"
                        for k, v in s["logit_err"].items())
        print(f"logit error vs float32 reference: "
              f"{max(s['logit_err'].values()):.3e} "
              f"(tolerance {s['logit_tol']:.0e}; {per})")
        print(f"smoke figure, not a benchmark: {s['tok_s']:.1f} generated "
              f"tok/s on the host clock over {s['wall_s']:.2f}s of drain, "
              f"compilation included")
        with clock("pipeline"):
            p = pipeline_phase()
        print(f"pipeline: fig6a on cluster 6d, output {p['shape']} equal to "
              f"the host program (checksum {p['checksum']}), compiled "
              f"kernels {p['kernels']}")
    stats = devices[0].memory_stats() or {}
    print(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use', 'not reported')}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
