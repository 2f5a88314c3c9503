"""Closed loop with a fixed backlog: ``depth_batches`` whole lane classes
of requests are outstanding at all times.  The client submits one full
lane class at a time (which the gateway dispatches as one full batch),
waits for the oldest outstanding class to be answered, and submits the
next, so the gateway's queue never runs dry and every dispatch is full.

Traffic parameters: ``depth_batches`` (classes outstanding) and
``linger_s`` (the gateway's partial-batch flush, set long: the client
only ever submits whole classes)."""
from __future__ import annotations


def lane_classes(full: int, ladder) -> list[int]:
    """Only full batches dispatch."""
    return [full]


def policy(traffic: dict, full: int) -> dict:
    """Admission never sheds: room for one class more than is outstanding."""
    return {"capacity": (int(traffic["depth_batches"]) + 1) * full,
            "linger_s": float(traffic["linger_s"])}


def run(ctx, t0: float) -> list[str]:
    end = t0 + ctx.seconds
    depth = int(ctx.traffic["depth_batches"])
    reqs = ctx.requests

    def submit_group():
        with ctx.span("load.submit"):
            for _ in range(ctx.lanes):
                i = next(ctx.order)
                reqs.submit(ctx.tenant, ctx.pool[i], i, ctx.clock())

    for _ in range(depth):
        submit_group()
    while ctx.clock() < end:
        with ctx.span("load.wait"):
            reqs.wait(ctx.lanes, end + ctx.drain_s)
        if ctx.clock() < end:
            submit_group()
    return [f"load closed_backlog: {depth} x {ctx.lanes} outstanding, "
            f"{len(reqs)} submitted"]
