"""Compile the main path for a described TPU v5e chip, without the chip.

The TPU compiler refuses what interpret mode accepts: tilings it cannot
lay out, reductions it does not implement, programs that do not fit the
device.  Each test compiles one kernel or one program at real widths for
one chip of a ``v5e:2x2`` topology described here, and checks that every
kernel lowered to a compiled Pallas call (``tpu_custom_call``).

The topology is described in a fixture, never at import: only one process
at a time may load the TPU library, and every test worker imports this
file.
"""
import os
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro.configs as configs
from repro.core import emit, place
from repro.core.presets import cluster_6d, tinyml_graph
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.gemm import ops as gemm_ops
from repro.kernels.paged_decode import ops as paged_decode_ops
from repro.kernels.paged_decode.ops import paged_decode
from repro.kernels.maxpool import ops as maxpool_ops
from repro.kernels.rmsnorm.ops import rmsnorm
from repro.kernels.ssd.ops import ssd_chunked
from repro.launch.serve import _bucket, serving_fns
from repro.models import lm

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
import chip_smoke  # noqa: E402
from test_paging import pool_copies  # noqa: E402

V5E_HBM_BYTES = 16 * 1024 ** 3


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)


def _on(sharding, tree):
    return jax.tree_util.tree_map(
        lambda x: _spec(sharding, x.shape, x.dtype), tree)


def _kernel_hlo(fn, *args) -> str:
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo
    return hlo


@pytest.mark.parametrize("dtype", ["bfloat16", "int8"])
def test_gemm_compiles(one_chip, dtype):
    _kernel_hlo(lambda a, b: gemm_ops.matmul(a, b, interpret=False),
                _spec(one_chip, (512, 576), dtype),
                _spec(one_chip, (576, 1536), dtype))


def test_flash_attention_gqa_compiles(one_chip):
    q = _spec(one_chip, (1, 9, 512, 64), "bfloat16")
    kv = _spec(one_chip, (1, 3, 512, 64), "bfloat16")
    _kernel_hlo(lambda q, k, v: flash_attention(q, k, v, interpret=False),
                q, kv, kv)


@pytest.mark.parametrize("h,n_kv,hd,layers,n_pages,n_slot_pages", [
    (9, 3, 64, 30, 2048, 112),      # smollm-135m, the chat cell's pool
    (32, 32, 80, 0, 768, 96),       # stablelm-3b, one layer's pool
], ids=["smollm", "stablelm"])
def test_paged_decode_compiles(one_chip, h, n_kv, hd, layers, n_pages,
                               n_slot_pages):
    width = -(-n_kv * hd // 128) * 128
    pool = _spec(one_chip, ((layers,) if layers else ()) + (
        n_pages, 16, width), "bfloat16")
    b = 16 if layers else 8
    layer = _spec(one_chip, (), "int32") if layers else None
    _kernel_hlo(lambda q, k, v, t, n, i: paged_decode(
        q, k, v, t, n, i, n_kv=n_kv, interpret=False),
        _spec(one_chip, (b, h, hd), "bfloat16"), pool, pool,
        _spec(one_chip, (b, n_slot_pages), "int32"),
        _spec(one_chip, (b,), "int32"), layer)


def test_rmsnorm_compiles(one_chip):
    _kernel_hlo(lambda x, w: rmsnorm(x, w, interpret=False),
                _spec(one_chip, (512, 576), "bfloat16"),
                _spec(one_chip, (576,), "float32"))


def test_maxpool_int8_compiles(one_chip):
    from repro.kernels.maxpool.kernel import maxpool
    # the tinyml graph's pool input: (batch 8, 16 x 16, 32 channels) int8
    _kernel_hlo(lambda x: maxpool(x, k=2, bc=32, interpret=False),
                _spec(one_chip, (8, 16, 16, 32), "int8"))


def test_ssd_compiles(one_chip):
    b, h, nc, q, n, p = 1, 4, 4, 128, 64, 64
    _kernel_hlo(lambda *a: ssd_chunked(*a, interpret=False),
                _spec(one_chip, (b, h, nc, q, p), "float32"),
                _spec(one_chip, (b, nc, q, n), "float32"),
                _spec(one_chip, (b, nc, q, n), "float32"),
                _spec(one_chip, (b, h, nc, q), "float32"))


def test_tinyml_program_compiles(one_chip, monkeypatch):
    """The whole Fig. 6a program on the Fig. 6d cluster, one jitted
    program with both accelerators' kernels compiled in."""
    # the ops ask the (CPU) default backend; steer them to the chip's path
    monkeypatch.setattr(gemm_ops, "use_interpret", lambda: False)
    monkeypatch.setattr(maxpool_ops, "use_interpret", lambda: False)
    graph, cluster = tinyml_graph(), cluster_6d()
    program = emit(graph, place(graph, cluster), cluster)
    args = {name: _spec(one_chip, spec.shape, spec.dtype)
            for name, spec in graph.inputs.items()}
    # an earlier test in this process may hold the ops' interpret-mode
    # traces under the same key: trace them again for the chip
    jax.clear_caches()
    try:
        hlo = program.lower(args).compile().as_text()
    finally:
        # the ops' jitted traces now hold the chip's choice under the key
        # of the default one; drop them so later CPU calls trace again
        jax.clear_caches()
    # conv + fc on the gemm kernel, pool on the maxpool kernel
    assert hlo.count('custom_call_target="tpu_custom_call"') >= 3


@pytest.mark.parametrize("step", ["prefill", "decode"])
def test_smollm_serving_step_compiles(one_chip, step, monkeypatch):
    """smollm-135m's serving steps at its published widths, on the paged
    cache and at the shapes the chip smoke serves (prefill at its widest
    prompt bucket), fit one chip.  The chip's default layout of the pool's
    lane-dense rows is the one its page writes and reads use, so the
    donated pool is updated where it lies, with no copy of it; and the
    token writes are native scatters, not a loop over the tokens (a second
    ``while`` beside the layer loop's).  The decode step reads the pages
    with the compiled paged-decode kernel and holds no temporary buffer,
    as before the kernel."""
    if step == "decode":
        # the model asks the (CPU) default backend; steer it to the chip's
        monkeypatch.setattr(paged_decode_ops, "on_tpu", lambda: True)
        monkeypatch.setattr(paged_decode_ops, "use_interpret", lambda: False)
    cfg = configs.get("smollm_135m")
    t = chip_smoke.Traffic()
    width = _bucket(t.prompt_max) if step == "prefill" else 1
    n_slot_pages = -(-t.max_len // t.page_size)
    params = jax.eval_shape(lambda k: lm.init_params(cfg, k)[0],
                            jax.random.PRNGKey(0))
    caches = jax.eval_shape(lambda: lm.init_caches(
        cfg, t.batch, t.max_len, paged=True, page_size=t.page_size,
        n_pages=2 * t.batch * n_slot_pages))
    fns = dict(zip(("prefill", "decode"), serving_fns(cfg, donate=True)))
    compiled = fns[step].lower(
        _on(one_chip, params), _spec(one_chip, (t.batch, width), "int32"),
        _on(one_chip, caches), _spec(one_chip, (t.batch,), "int32"),
    ).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used < V5E_HBM_BYTES, used
    hlo = compiled.as_text()
    assert not pool_copies(hlo, caches["self"]["k_pages"].shape)
    assert hlo.count(" while(") == 1
    if step == "decode":
        assert "tpu_custom_call" in hlo
        assert mem.temp_size_in_bytes == 0
