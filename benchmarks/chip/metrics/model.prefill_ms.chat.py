"""model.prefill_ms.chat: median over the traced admissions of the
``server.prefill`` span (``Server._dispatch_prefill``): the tail's dispatch
to its first token on the host, readback included (ms)."""
import numpy as np

import spans


def read(run):
    recs, picked = spans.in_window(run)
    ms = [1e3 * (recs[k][2] - recs[k][1]) for k in picked
          if recs[k][0] == "server.prefill"]
    return float(np.median(ms)) if ms else None
