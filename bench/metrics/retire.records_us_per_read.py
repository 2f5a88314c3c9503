"""retire.records_us_per_read: time inside the session's
``retire.records`` spans during the window, per request answered in it
(microseconds, host clock), clipped to the window as
``session.host_us_per_read`` is.  The span is the host decode of a
dispatch's outputs into per-request records (``decode_batch``,
``records_from_state``), opened on each side of a bucket-rescue rung.  A
program without the span reports nothing."""

SPAN = "retire.records"


def read(run):
    spans = [s for s in run.spans if s["name"] == SPAN]
    n = int(run.answered_in(run.t0, run.t1).sum())
    if not spans or n == 0:
        return None
    busy = sum(max(0.0, min(s["t1"], run.t1) - max(s["t0"], run.t0))
               for s in spans)
    return 1e6 * busy / n
