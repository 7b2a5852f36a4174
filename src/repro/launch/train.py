"""Training launcher: mesh + sharded step + fault-tolerant supervisor.

On a real fleet this process runs per host (jax.distributed.initialize);
here it drives the same code on the local devices.  XLA flags for real-TPU
runs (latency-hiding scheduler = the compute/collective overlap knob) are
documented below and exported by ``tpu_xla_flags()``.

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch smollm_135m \
      --batch 8 --seq 128 --steps 100 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import functools

import jax

import repro.configs as configs
from repro.configs.base import reduce as reduce_cfg
from repro.data.pipeline import SyntheticSource, TokenFileSource
from repro.data.synthetic import make_batch
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh, make_production_mesh
from repro.models import lm
from repro.optim.adamw import adamw_init, adamw_update
from repro.optim.schedule import cosine_warmup
from repro.runtime.supervisor import Supervisor, TrainLoop
from repro.sharding.rules import (
    batch_specs, param_shardings, zero1_sharding,
)

__all__ = ["build_train_step", "make_sharded_state", "tpu_xla_flags",
           "main"]


def tpu_xla_flags() -> str:
    """XLA flags for real-TPU launches: async collectives + latency-hiding
    scheduler so gradient all-reduces overlap the backward pass."""
    return " ".join([
        "--xla_tpu_enable_async_collective_permute=true",
        "--xla_tpu_enable_async_all_gather=true",
        "--xla_tpu_data_parallel_opt_different_sized_ops=true",
        "--xla_tpu_overlap_compute_collective_tc=true",
        "--xla_enable_async_all_reduce=true",
    ])


def build_train_step(cfg, *, peak_lr=3e-4, warmup=100, total=10_000,
                     impl="auto"):
    def train_step(params, opt_state, batch):
        (loss, metrics), grads = jax.value_and_grad(
            functools.partial(lm.loss_fn, cfg=cfg, impl=impl),
            has_aux=True)(params, batch)
        lr = cosine_warmup(opt_state["step"], peak_lr=peak_lr,
                           warmup=warmup, total=total)
        new_p, new_o, om = adamw_update(grads, opt_state, params, lr=lr)
        return new_p, new_o, {"loss": loss, "lr": lr, **metrics, **om}

    return train_step


def make_sharded_state(cfg, mesh, *, seed=0, zero1=True):
    """Init params + optimizer state directly into their shardings."""
    params_s, specs = lm.abstract_params(cfg)
    p_shard = param_shardings(specs, params_s, mesh)
    init_jit = jax.jit(lambda k: lm.init_params(cfg, k)[0],
                       out_shardings=p_shard)
    with mesh:
        params = init_jit(jax.random.PRNGKey(seed))
    opt_s = jax.eval_shape(adamw_init, params_s)

    def like(name):
        return jax.tree_util.tree_map(
            lambda ps, xs: jax.NamedSharding(
                mesh, zero1_sharding(ps.spec, xs.shape, mesh) if zero1
                else ps.spec),
            p_shard, opt_s[name])

    o_shard = {"step": jax.NamedSharding(mesh,
                                         jax.sharding.PartitionSpec()),
               "master": like("master"), "mu": like("mu"),
               "nu": like("nu")}
    with mesh:
        opt_state = jax.jit(adamw_init, out_shardings=o_shard)(params)
    return params, opt_state, p_shard, o_shard


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-test-sized config")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--data", default=None,
                    help="token .npy file (default: synthetic)")
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--peak-lr", type=float, default=3e-4)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = configs.get(args.arch)
    if args.reduced:
        cfg = reduce_cfg(cfg)
    mesh = (make_production_mesh() if args.production_mesh
            else make_local_mesh(model=args.model_parallel))
    print(f"mesh: {dict(mesh.shape)}  arch: {cfg.name}  "
          f"params: {lm.count_params(cfg)/1e6:.1f}M (non-embedding)")

    def build_loop():
        params, opt_state, p_shard, o_shard = make_sharded_state(cfg, mesh)
        batch_shape = jax.eval_shape(
            lambda: make_batch(cfg, args.batch, args.seq, 0))
        b_shard = batch_specs(batch_shape, mesh)
        step = jax.jit(
            build_train_step(cfg, peak_lr=args.peak_lr,
                             total=args.steps),
            in_shardings=(p_shard, o_shard, b_shard),
            out_shardings=(p_shard, o_shard, None),
            donate_argnums=(0, 1))
        if args.data:
            src = TokenFileSource(args.data, cfg, args.batch, args.seq)
        else:
            src = SyntheticSource(cfg, args.batch, args.seq)

        def sharded_step(params, opt, batch):
            batch = jax.device_put(batch, b_shard)
            with mesh:
                return step(params, opt, batch)

        return TrainLoop(sharded_step, params, opt_state, src,
                         args.ckpt_dir, ckpt_every=args.ckpt_every,
                         shardings=(p_shard, o_shard))

    sup = Supervisor(build_loop)
    hist = sup.run(args.steps)
    print(f"final loss: {hist[-1]['loss']:.4f} "
          f"(first: {hist[0]['loss']:.4f})")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
