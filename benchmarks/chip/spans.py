"""The program's own spans (``repro.obs``) that a run's window holds, for
the readers of the per-layer metrics that time them.

A program without ``repro.obs`` records no span: every reader then finds
nothing and returns None.
"""
from __future__ import annotations


def in_window(run) -> tuple[list, list[int]]:
    """``(records, picked)``: every span ``repro.obs`` holds, as
    ``(name, t0, t1, parent, attrs)``, and the indices of those that opened
    and closed inside the window of ``run``."""
    try:
        from repro import obs
    except ImportError:
        return [], []
    recs = obs.records()
    picked = [k for k, (_, t0, t1, _, _) in enumerate(recs)
              if t1 is not None and run.w0 <= t0 and t1 <= run.w1]
    return recs, picked
