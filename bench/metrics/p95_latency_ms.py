"""p95_latency_ms: the 95th percentile (nearest rank) over every request
due in the window of the time from when it was due to its answer (host
clock).  A request shed, failed or never answered counts as answered when
the run stopped waiting for it."""
import math

import numpy as np

from bench.harness import ANSWERED


def read(run):
    due = run.due_in_window()
    if not due.any():
        return None
    req = run.req
    done = np.where(req["state"] == ANSWERED, req["t_done"], run.t_giveup)
    lat = np.sort(done[due] - req["t_due"][due])
    return 1e3 * float(lat[math.ceil(0.95 * len(lat)) - 1])
