"""server.tick_host_ms.chat: median over the traced decode ticks of the
``server.tick`` span less the time its ``server.decode_readback`` child
covers: the host's own work in a tick, without the wait for the step (ms).
Ticks that dispatched no decode step have no readback and are left out."""
import numpy as np

import spans


def read(run):
    recs, picked = spans.in_window(run)
    readback: dict = {}
    for name, t0, t1, parent, _ in recs:
        if name == "server.decode_readback" and parent is not None \
                and t1 is not None:
            readback[parent] = readback.get(parent, 0.0) + (t1 - t0)
    ms = [1e3 * (recs[k][2] - recs[k][1] - readback[k]) for k in picked
          if recs[k][0] == "server.tick" and k in readback]
    return float(np.median(ms)) if ms else None
