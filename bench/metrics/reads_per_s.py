"""reads_per_s: requests answered inside the window, over the time from
the window's start to the last of those answers (host clock).  One
request is one read-candidate pair; an answer that reads ``ok: false``
counts, a shed, expired, failed or unanswered request does not.

The rate ends at the last answer, not at the window's close: long reads
come back in lumps of a whole dispatch (128 reads every ~0.6 s), and over
the full window the count would move in steps of a lump, 3% of a 20 s
window.  Up to the last answer the work and the time it took are both
whole."""
import numpy as np


def read(run):
    got = run.answered_in(run.t0, run.t1)
    if not got.any():
        return None
    t_last = float(np.max(run.req["t_done"][got]))
    return int(got.sum()) / (t_last - run.t0)
