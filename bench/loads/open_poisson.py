"""Open loop: Poisson arrivals at a fixed ``rate`` (requests per second),
sent whether or not earlier ones were answered.  Each request is timed
from when it was *due*, so a stall in the generator or the gateway shows
in the latency of every request behind it; how late the generator ran is
reported on its own line.  The gateway's sweeper runs, as in production,
so partial batches flush after ``linger_s``.

Traffic parameters: ``rate``, ``linger_s``, ``sweep_interval_s`` and
``capacity`` (the gateway's admission ceiling in pairs)."""
from __future__ import annotations

import time

import numpy as np


def lane_classes(full: int, ladder) -> list[int]:
    """Linger flushes dispatch partial batches: every class of the ladder."""
    return list(ladder)


def policy(traffic: dict, full: int) -> dict:
    return {"capacity": int(traffic["capacity"]),
            "linger_s": float(traffic["linger_s"])}


def arrivals(rate: float, seconds: float, rng) -> np.ndarray:
    """Offsets from the window's start of every arrival in it."""
    n = int(rate * seconds + 10 * np.sqrt(rate * seconds) + 16)
    t = np.cumsum(rng.exponential(1.0 / rate, n))
    while t[-1] < seconds:
        t = np.concatenate([t, t[-1] + np.cumsum(
            rng.exponential(1.0 / rate, n))])
    return t[t < seconds]


def run(ctx, t0: float) -> list[str]:
    from bench.harness import SHED
    ctx.gateway.start_sweeper(float(ctx.traffic["sweep_interval_s"]))
    due = t0 + arrivals(float(ctx.traffic["rate"]), ctx.seconds, ctx.rng)
    reqs = ctx.requests
    late = np.zeros(len(due))
    for n, t_due in enumerate(due.tolist()):
        reqs.harvest()
        wait = t_due - ctx.clock()
        if wait > 0:
            with ctx.span("load.sleep"):
                time.sleep(wait)
        i = next(ctx.order)
        late[n] = ctx.clock() - t_due
        reqs.submit(ctx.tenant, ctx.pool[i], i, t_due)
    wait = t0 + ctx.seconds - ctx.clock()
    if wait > 0:
        time.sleep(wait)
    q = np.percentile(late, [50, 99, 100]) * 1e3 if len(late) else [0] * 3
    shed = int((np.frombuffer(reqs.state, np.int8) == SHED).sum())
    return [f"load open_poisson: {len(due)} due at "
            f"{ctx.traffic['rate']}/s; generator lateness p50 "
            f"{q[0]:.3f} ms, p99 {q[1]:.3f} ms, max {q[2]:.3f} ms; "
            f"{shed} shed"]
