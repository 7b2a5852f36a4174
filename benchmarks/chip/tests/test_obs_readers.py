"""The readers of the program's spans: hand-made records against hand
counts, nothing to read, a program without ``repro.obs``, and a traced run
of the tiny chat cell."""
import dataclasses
import math
import sys

import pytest

import harness
from conftest import run_tiny, tiny_cell
from repro import obs

NAMES = ("model.prefill_ms.chat", "server.tick_host_ms.chat",
         "server.kv_written_share.chat")


@dataclasses.dataclass
class Window:
    w0: float = 10.0
    w1: float = 20.0


def read(name, recs, monkeypatch):
    monkeypatch.setattr(obs, "records", lambda: recs)
    return harness.load_module(harness.HERE, "metrics", name).read(Window())


def tick(t0, t1, parent, reserved, unwritten):
    return ("server.tick", t0, t1, parent,
            {"pages_reserved": reserved, "pages_unwritten": unwritten})


# two steps inside the window, one before it; times in seconds
RECS = [
    ("gateway.step", 9.0, 9.5, None, {}),                         # 0
    tick(9.1, 9.4, 0, 10, 10),                                    # 1
    ("server.decode_readback", 9.2, 9.3, 1, {}),                  # 2
    ("gateway.step", 11.0, 11.2, None, {}),                       # 3
    ("gateway.admit", 11.0, 11.08, 3, {}),                        # 4
    ("server.submit", 11.0, 11.08, 4, {"rid": "a"}),              # 5
    ("server.prefill", 11.01, 11.07, 5,
     {"rid": "a", "tail": 40, "shared": 512}),                    # 6
    tick(11.08, 11.11, 3, 40, 10),                                # 7
    ("server.tick_control", 11.08, 11.081, 7, {}),                # 8
    ("server.decode_readback", 11.09, 11.105, 7, {}),             # 9
    ("gateway.step", 12.0, 12.2, None, {}),                       # 10
    ("server.prefill", 12.0, 12.02, 10,
     {"rid": "b", "tail": 8, "shared": 0}),                       # 11
    ("server.prefill", 12.05, 12.15, 10,
     {"rid": "c", "tail": 90, "shared": 16}),                     # 12
    tick(12.15, 12.18, 10, 50, 0),                                # 13
    tick(12.18, 12.185, 10, 0, 0),                                # 14: idle
    ("server.tick", 12.19, None, 10, {}),                         # 15: open
]


def test_values_against_hand_counts(monkeypatch):
    # prefills in the window: 60, 20 and 100 ms
    assert read("model.prefill_ms.chat", RECS, monkeypatch) == \
        pytest.approx(60.0)
    # decode ticks in the window: 30 - 15 ms (tick 7); tick 13 has no
    # readback and the first tick lies before the window
    assert read("server.tick_host_ms.chat", RECS, monkeypatch) == \
        pytest.approx(15.0)
    # ticks in the window with pages: 1 - 10/40, 1 - 0/50
    assert read("server.kv_written_share.chat", RECS, monkeypatch) == \
        pytest.approx(100.0 * (0.75 + 1.0) / 2)


@pytest.mark.parametrize("name", NAMES)
def test_nothing_to_read_is_none(name, monkeypatch):
    assert read(name, [], monkeypatch) is None
    assert read(name, RECS[:3], monkeypatch) is None    # before the window


@pytest.mark.parametrize("name", NAMES)
def test_a_program_without_obs_reads_none(name, monkeypatch):
    monkeypatch.setitem(sys.modules, "repro.obs", None)  # import fails
    mod = harness.load_module(harness.HERE, "metrics", name)
    assert mod.read(Window()) is None


def test_a_traced_tiny_chat_run_reports_all_three():
    cell = tiny_cell("chat")
    cell.per_layer = [m for m in cell.per_layer if m["name"] in NAMES]
    assert len(cell.per_layer) == len(NAMES)
    obs.clear()
    res = run_tiny(cell, seed=2**31 + 11, trace=True, check=False)
    got = {n: m["value"] for n, m in res["metrics"].items()}
    assert set(got) == set(NAMES)
    assert all(math.isfinite(v) and v > 0 for v in got.values()), got
    assert got["server.kv_written_share.chat"] <= 100.0
