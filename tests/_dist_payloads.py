"""Multi-device test payloads, run in subprocesses with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (see test_distributed).
Each function prints 'PASS <name>' on success."""
import sys

from repro.launch.mesh import make_mesh


def payload_sharding_rules():
    from jax.sharding import PartitionSpec as P

    from repro.sharding.rules import resolve_leaf, zero1_sharding
    mesh = make_mesh((2, 4), ("data", "model"))
    # mlp dim divisible by 4 -> sharded
    assert resolve_leaf(("embed", "mlp"), (64, 128), mesh) == P(None,
                                                                "model")
    # heads=9 not divisible -> replicated
    assert resolve_leaf(("embed", "heads", "head"), (64, 9, 16),
                        mesh) == P(None, None, None)
    # experts preferred over expert_mlp, one axis use max
    assert resolve_leaf(("experts", "embed", "expert_mlp"), (8, 64, 128),
                        mesh) == P("model", None, None)
    # experts indivisible -> fall back to expert_mlp
    assert resolve_leaf(("experts", "embed", "expert_mlp"), (6, 64, 128),
                        mesh) == P(None, None, "model")
    # zero1: largest free dim gets data axis
    z = zero1_sharding(P(None, "model"), (64, 128), mesh)
    assert z == P("data", "model"), z
    print("PASS sharding_rules")


def payload_e2e_sharded_train():
    """Real sharded training: loss decreases on an 8-device (2,4) mesh."""
    import jax
    import numpy as np

    import repro.configs as configs
    from repro.configs.base import reduce
    from repro.data.pipeline import DataState, SyntheticSource
    from repro.launch.train import build_train_step, make_sharded_state
    from repro.sharding.rules import batch_specs

    cfg = reduce(configs.get("smollm_135m"))
    mesh = make_mesh((2, 4), ("data", "model"))
    params, opt, p_sh, o_sh = make_sharded_state(cfg, mesh)
    src = SyntheticSource(cfg, batch=4, seq=32)
    batch0, _ = src.get(DataState())
    b_sh = batch_specs(batch0, mesh)
    step = jax.jit(build_train_step(cfg, peak_lr=1e-3, warmup=2,
                                    total=30),
                   in_shardings=(p_sh, o_sh, b_sh),
                   out_shardings=(p_sh, o_sh, None),
                   donate_argnums=(0, 1))
    state = DataState()
    losses = []
    with mesh:
        for _ in range(30):
            batch, state = src.get(state)
            batch = jax.device_put(batch, b_sh)
            params, opt, m = step(params, opt, batch)
            losses.append(float(m["loss"]))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] - 0.1, (losses[0], losses[-1])
    # params actually sharded over model axis
    leaf = params["layers"]["ffn"]["gate"]["w"]
    assert len(leaf.sharding.device_set) >= 4
    print("PASS e2e_sharded_train")


def payload_pipeline_forward():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.distributed.pipeline import pipeline_forward, split_stages
    mesh = make_mesh((4,), ("stage",))
    n_layers, d = 8, 16
    keys = jax.random.split(jax.random.PRNGKey(0), n_layers)
    w = jnp.stack([
        jax.random.normal(k, (d, d)) * 0.2 for k in keys])  # (L, d, d)

    def block_fn(wl, x):
        return jnp.tanh(x @ wl)

    xs = jax.random.normal(jax.random.PRNGKey(1), (6, 3, d))  # (T,mb,d)

    # sequential reference
    def seq_apply(x):
        for i in range(n_layers):
            x = block_fn(w[i], x)
        return x

    want = jax.vmap(seq_apply)(xs)
    got = pipeline_forward(split_stages(w, 4), xs, block_fn, mesh)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    print("PASS pipeline_forward")


def payload_flash_decode_sp():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.distributed.flash_decode import sp_attention_shardmap
    from repro.kernels.flash_attention.ref import attention_ref
    mesh = make_mesh((8,), ("model",))
    b, h, kv, s, d = 2, 8, 4, 64, 16
    kq, kk, kv_ = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (b, h, d))
    k = jax.random.normal(kk, (b, s, kv, d))
    v = jax.random.normal(kv_, (b, s, kv, d))
    valid = jnp.arange(s)[None, :] < 50      # partial fill
    valid = jnp.broadcast_to(valid, (b, s))
    fn = sp_attention_shardmap(mesh, "model")
    with mesh:
        got = fn(q, k, v, valid, jnp.array([d ** -0.5]))
    # reference: masked attention with q len 1
    km = jnp.where(valid[:, :, None, None], k, 0)
    ref = attention_ref(
        q[:, :, None, :],                      # (b,h,1,d)
        jnp.moveaxis(jnp.where(valid[:, :, None, None], k, -1e9), 1, 2)[
            :, :, :50],
        jnp.moveaxis(v, 1, 2)[:, :, :50],
        causal=False)[:, :, 0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    print("PASS flash_decode_sp")


def payload_compressed_psum():
    import functools
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from repro.distributed.compression import compressed_psum_mean
    mesh = make_mesh((8,), ("pod",))

    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=(P("pod"), P("pod")), out_specs=P("pod"))
    def run(x, err):
        m, e = compressed_psum_mean(x[0], "pod", err[0])
        return m[None]

    x = jax.random.normal(jax.random.PRNGKey(0), (8, 64))
    err = jnp.zeros((8, 64))
    with mesh:
        got = run(x, err)
    want = jnp.mean(x, axis=0)
    for i in range(8):
        np.testing.assert_allclose(np.asarray(got[i]), np.asarray(want),
                                   atol=0.03)
    # HLO carries an int8 all-gather (the wire saving)
    txt = jax.jit(run).lower(x, err).compile().as_text()
    assert "s8[" in txt, "int8 collective missing from HLO"
    print("PASS compressed_psum")


def payload_elastic_restore():
    """Checkpoint from a (2,4) mesh restores onto a (4,2) mesh."""
    import jax
    import numpy as np

    import repro.configs as configs
    from repro.checkpoint.ckpt import load_checkpoint, save_checkpoint
    from repro.configs.base import reduce
    from repro.models import lm
    from repro.sharding.rules import param_shardings
    import tempfile

    cfg = reduce(configs.get("smollm_135m"))
    mesh1 = make_mesh((2, 4), ("data", "model"))
    params_s, specs = lm.abstract_params(cfg)
    sh1 = param_shardings(specs, params_s, mesh1)
    with mesh1:
        params = jax.jit(lambda k: lm.init_params(cfg, k)[0],
                         out_shardings=sh1)(jax.random.PRNGKey(0))
    with tempfile.TemporaryDirectory() as d:
        save_checkpoint(d, 1, params)
        mesh2 = make_mesh((4, 2), ("data", "model"))
        sh2 = param_shardings(specs, params_s, mesh2)
        restored, _ = load_checkpoint(d, 1, params_s, shardings=sh2)
        a = np.asarray(jax.device_get(
            params["layers"]["ffn"]["gate"]["w"]), np.float32)
        b = np.asarray(jax.device_get(
            restored["layers"]["ffn"]["gate"]["w"]), np.float32)
        np.testing.assert_array_equal(a, b)
    print("PASS elastic_restore")


def payload_pipeline_grad():
    """Gradients flow correctly through the ppermute pipeline (PP is
    trainable, not just a forward schedule)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.distributed.pipeline import pipeline_forward, split_stages
    mesh = make_mesh((4,), ("stage",))
    n_layers, d = 8, 8
    keys = jax.random.split(jax.random.PRNGKey(0), n_layers)
    w = jnp.stack([jax.random.normal(k, (d, d)) * 0.3 for k in keys])

    def block_fn(wl, x):
        return jnp.tanh(x @ wl)

    xs = jax.random.normal(jax.random.PRNGKey(1), (6, 2, d))

    def loss_pp(w):
        y = pipeline_forward(split_stages(w, 4), xs, block_fn, mesh)
        return jnp.sum(y ** 2)

    def loss_seq(w):
        def apply(x):
            for i in range(n_layers):
                x = block_fn(w[i], x)
            return x
        return jnp.sum(jax.vmap(apply)(xs) ** 2)

    g_pp = jax.grad(loss_pp)(w)
    g_seq = jax.grad(loss_seq)(w)
    np.testing.assert_allclose(np.asarray(g_pp), np.asarray(g_seq),
                               rtol=1e-4, atol=1e-4)
    print("PASS pipeline_grad")


if __name__ == "__main__":
    globals()[f"payload_{sys.argv[1]}"]()
