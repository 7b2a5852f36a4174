"""server.kv_written_share.chat: mean over the traced ticks of the share of
the pool's allocated pages that hold a cached token, from the attributes of
each ``server.tick`` span (``Server._page_counts``):
``100 * (1 - pages_unwritten / pages_reserved)`` (%)."""
import numpy as np

import spans


def read(run):
    recs, picked = spans.in_window(run)
    shares = []
    for k in picked:
        name, _, _, _, attrs = recs[k]
        if name == "server.tick" and attrs.get("pages_reserved"):
            shares.append(100.0 * (1.0 - attrs["pages_unwritten"]
                                   / attrs["pages_reserved"]))
    return float(np.mean(shares)) if shares else None
