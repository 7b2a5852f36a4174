"""``repro.obs``: spans off and on, the ring, the profiler's host plane, and
the span tree of a served request."""
import glob
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as configs
from repro import obs
from repro.configs.base import reduce
from repro.gateway import CompletionRequest, Gateway
from repro.launch.serve import Server, serving_fns
from repro.models import lm


@pytest.fixture(autouse=True)
def fresh():
    obs.disable()
    obs.clear()
    yield
    obs.disable()
    obs.clear()


def _children(recs, parent):
    return [r for r in recs if r[3] == parent]


def test_off_records_nothing_and_returns_the_shared_null_context():
    assert not obs.recording()
    a, b = obs.span("server.tick"), obs.span("gateway.step", rid="x")
    assert a is b
    with a, b:
        pass
    assert obs.records() == []


def test_enable_records_nesting_attrs_and_ordered_times():
    obs.enable()
    assert obs.recording()
    with obs.span("gateway.step"):
        with obs.span("server.submit", rid="req-1"):
            with obs.span("server.prefill", rid="req-1", tail=7, shared=16):
                time.sleep(0.001)
        with obs.span("server.tick"):
            pass
    obs.disable()
    with obs.span("gateway.step"):
        pass                                 # off again: not recorded
    recs = obs.records()
    assert [r[0] for r in recs] == ["gateway.step", "server.submit",
                                    "server.prefill", "server.tick"]
    assert [r[3] for r in recs] == [None, 0, 1, 0]
    assert recs[1][4] == {"rid": "req-1"}
    assert recs[2][4] == {"rid": "req-1", "tail": 7, "shared": 16}
    for name, t0, t1, parent, _ in recs:
        assert t0 <= t1
        if parent is not None:               # inside its parent
            assert recs[parent][1] <= t0 and t1 <= recs[parent][2]
    assert recs[2][2] - recs[2][1] >= 0.001
    assert recs[1][2] <= recs[3][1]          # siblings in order
    obs.clear()
    assert obs.records() == []


def test_the_ring_stays_bounded():
    obs.enable()
    with obs.span("gateway.step"):
        for _ in range(obs.RING + 10):
            with obs.span("server.tick"):
                pass
        with obs.span("gateway.poll"):
            with obs.span("server.decode_readback"):
                pass
    recs = obs.records()
    assert len(recs) == obs.RING
    # the outer span was dropped: its children lose their parent, and the
    # indices of those kept point inside the ring
    assert recs[0][0] == "server.tick" and recs[0][3] is None
    assert recs[-1][0] == "server.decode_readback"
    assert recs[-1][3] == len(recs) - 2 and recs[-2][3] is None
    assert recs[-2][2] is not None


def test_a_profiler_session_records_spans_onto_its_host_plane(tmp_path):
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        assert obs.recording()
        with jax.profiler.TraceAnnotation("bench.window"):
            with obs.span("gateway.step"):
                with obs.span("server.submit", rid="req-3"):
                    pass
    finally:
        jax.profiler.stop_trace()
    assert not obs.recording()
    assert [(r[0], r[3], r[4]) for r in obs.records()] == [
        ("gateway.step", None, {}), ("server.submit", 0, {"rid": "req-3"})]
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    events[e.name] = (e.start_ns, e.start_ns + e.duration_ns)
    outer = events["bench.window"]
    for name in ("gateway.step", "server.submit"):
        a, b = events[name]
        assert outer[0] <= a <= b <= outer[1], name
    a, b = events["server.submit"]
    assert events["gateway.step"][0] <= a and b <= events["gateway.step"][1]


@pytest.fixture(scope="module")
def smollm():
    cfg = reduce(configs.get("smollm_135m"))
    params, _ = lm.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def test_a_served_run_gives_each_admission_and_tick_its_tree(smollm):
    cfg, params = smollm
    server = Server(cfg, params, batch=2, max_len=32, page_size=4)
    gw = Gateway(server)
    rng = np.random.default_rng(0)
    rids = [gw.submit(CompletionRequest(
        rng.integers(0, 100, 5 + 3 * i).astype(np.int32), 6))
        for i in range(3)]
    obs.enable()
    gw.drain()
    obs.disable()
    recs = obs.records()
    assert all(r[2] is not None for r in recs)

    submits = [k for k, r in enumerate(recs) if r[0] == "server.submit"]
    assert sorted(recs[k][4]["rid"] for k in submits) == sorted(rids)
    for k in submits:
        assert recs[recs[k][3]][0] == "gateway.admit"
        rid = recs[k][4]["rid"]
        pre, = [j for j in range(len(recs)) if recs[j][3] == k
                and recs[j][0] == "server.prefill"]
        assert recs[pre][4]["rid"] == rid
        assert recs[pre][4]["tail"] + recs[pre][4]["shared"] > 0
        assert [r[0] for r in _children(recs, pre)] == [
            "model.prefill_dispatch", "server.prefill_readback"]
        assert {r[0] for r in _children(recs, k)} >= {
            "server.prefix_match", "server.page_install", "server.prefill"}

    ticks = [k for k, r in enumerate(recs) if r[0] == "server.tick"]
    decoded = 0
    for k in ticks:
        assert recs[recs[k][3]][0] == "gateway.step"
        kids = [r[0] for r in _children(recs, k)]
        assert kids[0] == "server.tick_control"
        attrs = recs[k][4]
        assert 0 <= attrs["pages_unwritten"] <= attrs["pages_reserved"]
        assert 0 <= attrs["pages_read"] <= 2 * server.n_slot_pages
        if "model.decode_dispatch" in kids:
            assert kids.count("server.decode_readback") == 1
            decoded += 1
    assert decoded == server.ticks > 0
    steps = [k for k, r in enumerate(recs) if r[0] == "gateway.step"]
    assert len(steps) == gw.steps
    for k in steps:
        assert [r[0] for r in _children(recs, k)] == [
            "gateway.admit", "server.tick", "gateway.poll"]


def test_a_live_slot_counts_its_reserved_pages_unwritten(smollm):
    cfg, params = smollm
    server = Server(cfg, params, batch=2, max_len=32, page_size=4)
    prompt = np.arange(1, 10, dtype=np.int32)          # 9 tokens
    gw = Gateway(server)
    gw.submit(CompletionRequest(prompt, 12))           # 20 cached: 5 pages
    gw.step()                 # admitted, prefilled, decoded once: 10 cached
    req = server.slots[0]
    assert len(req.out) == 2 and len(server.slot_pages[0]) == 5
    counts = server._page_counts()
    assert counts == {"pages_reserved": server.pages_in_use,
                      "pages_unwritten": 5 - 3, "pages_read": 3}


def test_pages_read_counts_the_pages_each_fed_slot_reads(smollm):
    """A tick's ``pages_read`` is what its decode step reads: for each slot
    it feeds, the pages of the cached tokens and of the one it writes;
    nothing for an empty slot or a finished request."""
    cfg, params = smollm
    server = Server(cfg, params, batch=3, max_len=32, page_size=4)
    gw = Gateway(server)
    gw.submit(CompletionRequest(np.arange(1, 10, dtype=np.int32), 12))
    gw.submit(CompletionRequest(np.arange(1, 5, dtype=np.int32), 12))
    gw.step()
    assert [len(r.out) for r in server.slots[:2]] == [2, 2]
    assert server.slots[2] is None
    # 9 + 1 and 4 + 1 tokens cached, each writing one more: 11 and 6
    assert server._page_counts()["pages_read"] == 3 + 2
    server.slots[1].done = True
    assert server._page_counts()["pages_read"] == 3


def test_serving_executables_lower_under_their_names(smollm):
    cfg, params = smollm
    prefill, decode = serving_fns(cfg)
    caches = lm.init_caches(cfg, 1, 16)
    toks, sl = jnp.zeros((1, 8), jnp.int32), jnp.asarray([8], jnp.int32)
    assert "serve_prefill" in prefill.lower(params, toks, caches, sl).as_text()
    assert "serve_decode" in decode.lower(
        params, toks[:, :1], caches, jnp.asarray([1], jnp.int32)).as_text()
