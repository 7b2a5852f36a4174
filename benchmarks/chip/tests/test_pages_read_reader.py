"""The reader of ``kernels.decode_pages_read_share.chat``: hand-made tick
records against a hand count, nothing to read, a program whose ticks carry
no ``pages_read`` or that has no ``repro.obs``, and a traced run of the
tiny chat cell."""
import dataclasses
import math
import sys

import pytest

import harness
from conftest import run_tiny, tiny_cell
from repro import obs

NAME = "kernels.decode_pages_read_share.chat"


@dataclasses.dataclass
class Window:
    w0: float = 10.0
    w1: float = 20.0
    batch: int = 4
    # 4 slots of ceil(100 / 16) = 7 pages: a table of 28 pages
    conf: dict = dataclasses.field(default_factory=lambda: {
        "serving": {"batch": 4, "max_len": 100, "page_size": 16}})


def tick(t0, t1, read=None):
    attrs = {"pages_reserved": 40, "pages_unwritten": 3}
    if read is not None:
        attrs["pages_read"] = read
    return ("server.tick", t0, t1, None, attrs)


RECS = [tick(9.0, 9.1, 28),             # before the window
        tick(11.0, 11.1, 7),
        ("server.decode_readback", 11.05, 11.08, 1, {}),
        tick(12.0, 12.1, 14),
        tick(13.0, 13.1, 0),             # nothing fed
        ("server.tick", 14.0, None, None, {"pages_read": 28})]   # open


def read(recs, monkeypatch):
    monkeypatch.setattr(obs, "records", lambda: recs)
    return harness.load_module(harness.HERE, "metrics", NAME).read(Window())


def test_value_against_a_hand_count(monkeypatch):
    # ticks in the window: 7, 14 and 0 of 28 pages
    assert read(RECS, monkeypatch) == pytest.approx(
        100.0 * (7 + 14 + 0) / 28 / 3)


@pytest.mark.parametrize("recs", [[], RECS[:1],
                                  [tick(11.0, 11.1), tick(12.0, 12.1)]],
                         ids=["empty", "before_window", "no_pages_read"])
def test_nothing_to_read_is_none(recs, monkeypatch):
    assert read(recs, monkeypatch) is None


def test_a_program_without_obs_reads_none(monkeypatch):
    monkeypatch.setitem(sys.modules, "repro.obs", None)  # import fails
    mod = harness.load_module(harness.HERE, "metrics", NAME)
    assert mod.read(Window()) is None


def test_a_traced_tiny_chat_run_reports_it():
    cell = tiny_cell("chat")
    cell.per_layer = [m for m in cell.per_layer if m["name"] == NAME]
    assert len(cell.per_layer) == 1
    obs.clear()
    res = run_tiny(cell, seed=2**31 + 13, trace=True, check=False)
    got = res["metrics"][NAME]["value"]
    assert math.isfinite(got) and 0 < got <= 100.0
