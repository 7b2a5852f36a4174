"""kernels.decode_pages_read_share.chat: mean over the traced ticks of the
share of the slots' page tables that the tick's decode step reads, from the
``pages_read`` attribute of each ``server.tick`` span
(``Server._page_counts``): ``100 * pages_read / (batch * n_slot_pages)``
(%), ``n_slot_pages`` being ``ceil(max_len / page_size)``.  A program
whose ticks carry no ``pages_read`` reads None."""
import numpy as np

import spans


def read(run):
    serving = run.conf["serving"]
    table = run.batch * -(-int(serving["max_len"])
                          // int(serving["page_size"]))
    recs, picked = spans.in_window(run)
    shares = [100.0 * attrs["pages_read"] / table
              for name, _, _, _, attrs in (recs[k] for k in picked)
              if name == "server.tick" and "pages_read" in attrs]
    return float(np.mean(shares)) if shares else None
