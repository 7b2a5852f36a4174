"""Pipeline parallelism demo — SNAX's asynchronous producer-consumer
schedule (Fig. 5) at pod scale: 8 emulated devices as 4 pipeline stages,
microbatches handed off with ``ppermute`` double buffering.

Run:  PYTHONPATH=src python examples/pipeline_pods.py
(sets the host-device count itself; run as a script, not under pytest)
"""
import os

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import jax                                               # noqa: E402
import jax.numpy as jnp                                  # noqa: E402
import numpy as np                                       # noqa: E402

from repro.distributed.pipeline import (                 # noqa: E402
    pipeline_forward, split_stages,
)
from repro.launch.mesh import make_mesh                  # noqa: E402


def main():
    mesh = make_mesh((4,), ("stage",))
    n_layers, d, t_micro, mb = 16, 64, 8, 4
    keys = jax.random.split(jax.random.PRNGKey(0), n_layers)
    w = jnp.stack([jax.random.normal(k, (d, d)) * 0.2 for k in keys])

    def block_fn(wl, x):
        return jnp.tanh(x @ wl)

    xs = jax.random.normal(jax.random.PRNGKey(1), (t_micro, mb, d))
    got = pipeline_forward(split_stages(w, 4), xs, block_fn, mesh)

    def seq(x):
        for i in range(n_layers):
            x = block_fn(w[i], x)
        return x

    want = jax.vmap(seq)(xs)
    err = float(jnp.abs(got - want).max())
    bubble = (4 - 1) / (t_micro + 4 - 1)
    print(f"pipeline over {mesh.shape} mesh: {t_micro} microbatches, "
          f"max|err| vs sequential = {err:.2e}, "
          f"GPipe bubble fraction = {bubble:.0%}")
    assert err < 1e-5
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5)
    print("OK")


if __name__ == "__main__":
    main()
