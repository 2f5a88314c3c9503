"""session.host_us_per_read: time inside the session's ``session.dispatch``
and ``retire.decode`` spans during the window, per request answered in it
(microseconds, host clock).  ``retire.decode`` includes the wait for the
dispatch's results to come back from the device."""

SPANS = ("session.dispatch", "retire.decode")


def read(run):
    n = int(run.answered_in(run.t0, run.t1).sum())
    if n == 0:
        return None
    busy = sum(max(0.0, min(s["t1"], run.t1) - max(s["t0"], run.t0))
               for s in run.spans if s["name"] in SPANS)
    return 1e6 * busy / n
