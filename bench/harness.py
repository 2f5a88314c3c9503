"""One run of one cell: set-up, the measured window, the check, the result.

``run_cell.py`` is the command; this module does the work so that the
tests can drive a run in-process.  The steps:

1. Find the cell's parts by name (``spec.py``) and check the devices: a
   run whose JAX sees no TPU, or fewer chips than the cell asks for,
   exits non-zero and prints no result (``--rehearse`` runs on the CPU at
   tiny sizes, interpret-mode kernels, and is never a measurement).
2. Set-up, all counted in ``setup_s``: the pair pool from ``--seed``, the
   session the configuration file describes (``plan``), a compile (or
   compile-cache load) of every lane class the load kind can dispatch for
   every length bucket of the pool, one real dispatch of each, and the
   ``Gateway`` over the session.
3. The window: the load kind drives ``Tenant.submit`` for ``--seconds``.
   With ``--trace 1`` the session carries the benchmark's annotated
   tracer and the profiler records ``TRACE_S`` seconds mid-window.
4. After the window: every answer is awaited (60 s past the close at
   most), the device memory peak read, the session closed, and the
   answers of a seeded sample of pool entries compared with the plain
   reference (``check.py``).
5. One JSON line: ``correct``, ``attempted``, ``failed``, ``metrics``,
   ``device``, (``breakdown``), ``checks``.
"""
from __future__ import annotations

import argparse
import array
import dataclasses
import json
import math
import os
import sys
import time
from collections import deque

import numpy as np

from . import check, simulate, spec

#: answers are awaited at most this long past the window's close
DRAIN_S = 60.0
#: a --trace 1 run profiles this many seconds in the middle of its window
#: (the profiler keeps about 6.3 M device events; the long-read cell makes
#: 1.6 M a second)
TRACE_S = 1.0
#: span names the idle-gap attribution looks for on the host
HOST_SPANS = ("gateway.admit", "session.dispatch", "device.execute",
              "retire.decode", "rescue.rung", "load.submit", "load.wait",
              "load.sleep")


class NoDevice(SystemExit):
    """No accelerator, or fewer chips than the cell asks for."""


#: the state of a request in `Requests.state`
PENDING, ANSWERED, SHED, ERROR, UNANSWERED = range(5)


class Requests:
    """Every request of the window, kept compact: per request its pool
    entry, due time, answer time, gateway queue time and state, in typed
    arrays; the answers themselves only for the pool entries the check
    compares (`keep`).  A future is held only while it is in flight: the
    load kind calls `harvest` (or `wait`) and an answered one is written
    down and let go, as a server lets go of what it has answered."""

    def __init__(self, keep, clock):
        self.keep, self.clock = frozenset(int(i) for i in keep), clock
        self.idx = array.array("q")
        self.t_due = array.array("d")
        self.t_done = array.array("d")
        self.queue_s = array.array("d")
        self.state = array.array("b")
        self.pending = deque()          # (position, future), oldest first
        self.kept = []                  # (pool entry, record) to compare

    def __len__(self) -> int:
        return len(self.idx)

    def submit(self, tenant, pair, idx: int, t_due: float) -> None:
        from repro.api.gateway import ShedError
        pos = len(self.idx)
        self.idx.append(idx)
        self.t_due.append(t_due)
        self.t_done.append(math.nan)
        self.queue_s.append(math.nan)
        try:
            fut = tenant.submit(*pair)
        except ShedError:
            self.state.append(SHED)
            return
        self.state.append(PENDING)
        self.pending.append((pos, fut))

    def harvest(self) -> None:
        """Write down the answered futures at the head of the queue."""
        while self.pending and self.pending[0][1].done():
            self._settle(*self.pending.popleft(), 0.0)

    def wait(self, n: int, until: float) -> None:
        """Wait (until `until` at most) for the `n` oldest in flight."""
        for _ in range(min(n, len(self.pending))):
            self._settle(*self.pending.popleft(),
                         max(0.0, until - self.clock()))

    def settle_all(self, until: float) -> None:
        self.wait(len(self.pending), until)

    def _settle(self, pos: int, fut, timeout: float) -> None:
        try:
            rec = fut.result(timeout=timeout)
        except TimeoutError:
            self.state[pos] = UNANSWERED
            return
        except Exception:               # noqa: BLE001 — counted, reported
            self.state[pos] = ERROR
            return
        self.state[pos] = ANSWERED
        self.t_done[pos] = fut.t_done
        if fut.t_dispatch is not None:
            self.queue_s[pos] = fut.t_dispatch - fut.t_submit
        if self.idx[pos] in self.keep:
            self.kept.append((self.idx[pos], rec))

    def arrays(self) -> dict:
        return {"idx": np.frombuffer(self.idx, np.int64),
                "t_due": np.frombuffer(self.t_due),
                "t_done": np.frombuffer(self.t_done),
                "queue_s": np.frombuffer(self.queue_s),
                "state": np.frombuffer(self.state, np.int8)}


@dataclasses.dataclass
class RunData:
    """What the metric readers read (``metrics/<name>.py``).  ``req``
    holds one array per field of `Requests` (numpy, one entry a request)."""
    t0: float
    t1: float
    t_giveup: float
    setup_s: float
    req: dict
    stats0: dict             # session counters at the window's start
    stats1: dict             # ... and at its close
    spans: list              # repro.obs span records (traced run)
    trace: object            # trace_reduce.Summary (traced run) or None

    def due_in_window(self) -> np.ndarray:
        t = self.req["t_due"]
        return (t >= self.t0) & (t < self.t1)

    def answered_in(self, lo: float, hi: float) -> np.ndarray:
        t = self.req["t_done"]
        return ((self.req["state"] == ANSWERED) & (t >= lo) & (t <= hi))


@dataclasses.dataclass
class LoadContext:
    """What a load kind (``loads/<kind>.py``) is given."""
    traffic: dict
    gateway: object
    tenant: object
    pool: list
    order: object            # endless iterator of pool indices
    lanes: int               # the session's full lane class
    seconds: float
    rng: object
    clock: object
    span: object             # span(name) context manager (load.* spans)
    drain_s: float           # how long past the close an answer is awaited
    requests: Requests       # where the load submits and writes down


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description="one run of one cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU, interpret-mode kernels; "
                         "never a measurement")
    return ap.parse_args(argv)


def rehearsal(cell):
    """The cell cut to CPU size (interpret-mode kernels)."""
    cfg = json.loads(json.dumps(cell.config))
    cfg["genome_bp"] = min(cfg["genome_bp"], 200_000)
    cfg["reads"]["read_len"] = min(cfg["reads"]["read_len"], 400)
    cfg["session"]["batch_lanes"] = min(cfg["session"]["batch_lanes"], 4)
    cfg["bench"]["pool_pairs"] = min(cfg["bench"]["pool_pairs"], 32)
    cfg["bench"]["check_pairs"] = min(cfg["bench"]["check_pairs"], 32)
    traffic = dict(cell.traffic)
    if "rate" in traffic:
        traffic["rate"] = min(traffic["rate"], 20.0)
    return dataclasses.replace(cell, config=cfg, traffic=traffic)


def devices_for(cell, rehearse: bool):
    import jax
    devs = jax.devices()
    if not rehearse and devs[0].platform != "tpu":
        raise NoDevice(f"JAX sees no TPU (platform {devs[0].platform!r})")
    if len(devs) < cell.chips:
        raise NoDevice(f"the cell asks for {cell.chips} chips; JAX sees "
                       f"{len(devs)}")
    return devs[:cell.chips]


def cycle_order(n: int, rng):
    """Pool indices in a seeded order, cycled (a fresh shuffle per lap)."""
    while True:
        yield from rng.permutation(n).tolist()


def build_session(cell, devs, obs):
    from repro.api import plan
    from repro.core.config import AlignerConfig
    mesh = None
    if cell.chips > 1:
        from repro.launch.mesh import make_test_mesh
        mesh = make_test_mesh((cell.chips,), ("data",))
    return plan(AlignerConfig(**cell.config["aligner"]), mesh=mesh, obs=obs,
                **cell.config["session"])


def warm(session, pool, classes) -> None:
    """Compile (or load from the compile cache) every lane class the load
    can dispatch for every length bucket of the pool, then run each once
    with real pairs, so nothing compiles or warms inside the window."""
    by_bucket = {}
    for i, (r, f) in enumerate(pool):
        by_bucket.setdefault(session.bucket_for(len(r), len(f)), []).append(i)
    for lanes in classes:
        session.warmup(sorted(by_bucket), lanes=lanes)
    for idxs in by_bucket.values():
        for lanes in classes:
            pick = [idxs[j % len(idxs)] for j in range(lanes)]
            futs = [session.submit(*pool[i]) for i in pick]
            session.flush()
            for f in futs:
                f.result()


def memory_peak(devs) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return int(max(peaks))


def run(argv=None, t_start: float | None = None) -> dict:
    """One run; returns the result dict (run_cell.py prints it)."""
    t_start = time.monotonic() if t_start is None else t_start
    args = parse(argv)
    cell = spec.load_cell(args.workload)
    if args.rehearse:
        cell = rehearsal(cell)
    devs = devices_for(cell, args.rehearse)
    import jax
    from repro.api import Gateway, GatewayPolicy
    from repro.distributed.sharding import lane_classes
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    cfg, traffic = cell.config, cell.traffic
    load = spec.load_kind(traffic["kind"])
    _, pool = simulate.pool(cfg, args.seed, int(cfg["bench"]["pool_pairs"]))

    obs = "off"
    if args.trace:
        from .tracing import traced_obs
        obs = traced_obs()
    session = build_session(cell, devs, obs)
    full = session.spec.batch_lanes
    classes = load.lane_classes(
        full, lane_classes(full, session.cfg, session.mesh))
    warm(session, pool, classes)
    lowerings = session.cache.store  # process-wide; counts under obs="off"
    warm_lowerings = lowerings.lowerings
    gateway = Gateway(session, GatewayPolicy(**load.policy(traffic, full)))
    tenant = gateway.tenant("bench", priority=0)
    log(f"setup: {len(pool)} pairs of {cfg['reads']['read_len']} bp, "
        f"lane classes {classes}, {warm_lowerings} executables lowered in "
        f"this process, compile cache {cache_dir}")

    # the pool entries whose answers are compared: a seeded sample, drawn
    # before the window so that only their answers are kept
    keep = check.sample(range(len(pool)), int(cfg["bench"]["check_pairs"]),
                        simulate.rng_for(args.seed, 4))
    requests = Requests(keep, time.monotonic)
    tracer = session.obs.tracer
    ctx = LoadContext(traffic=traffic, gateway=gateway, tenant=tenant,
                      pool=pool, lanes=full, seconds=args.seconds,
                      order=cycle_order(len(pool), simulate.rng_for(
                          args.seed, 2)),
                      rng=simulate.rng_for(args.seed, 3),
                      clock=time.monotonic, span=tracer.span,
                      drain_s=DRAIN_S, requests=requests)
    profiler = None
    if args.trace:
        profiler = Profiler(str(spec.ROOT / ".bench_trace" / args.workload),
                            min(TRACE_S, args.seconds), args.seconds)
    stats0 = dict(session.stats)
    t0 = time.monotonic()
    setup_s = t0 - t_start
    if profiler:
        profiler.window.start()
    notes = load.run(ctx, t0)
    t1 = t0 + args.seconds
    stats1 = dict(session.stats)
    if profiler:
        profiler.window.join()
    t_giveup = max(time.monotonic(), t1) + DRAIN_S
    requests.settle_all(t_giveup)
    window_lowerings = lowerings.lowerings - warm_lowerings
    for line in notes:
        log(line)
    log(f"window: lowerings inside the window and drain: {window_lowerings}")
    spans = session.obs.tracer.records()
    gateway.close()
    session.close()
    peak = memory_peak(devs)
    del gateway, session

    trace = None
    if profiler:
        from . import trace_reduce
        names = HOST_SPANS + (Profiler.MARK,)
        trace = trace_reduce.summarize(
            trace_reduce.load_events(
                trace_reduce.find_xplane(profiler.trace_dir), names),
            window=Profiler.MARK, host_names=HOST_SPANS)
    req = requests.arrays()
    data = RunData(t0=t0, t1=t1, t_giveup=t_giveup, setup_s=setup_s,
                   req=req, stats0=stats0, stats1=stats1, spans=spans,
                   trace=trace)

    # the check: every answer of the sampled entries the window requested
    t_ref = time.monotonic()
    picked = sorted(set(keep) & set(req["idx"].tolist()))
    want = check.reference_records(pool, picked, cfg)
    checks = check.compare(
        requests.kept, int(np.isin(req["state"], (ERROR, UNANSWERED)).sum()),
        picked, want, min_compared=len(picked))
    log(f"check: reference over {len(picked)} pool entries in "
        f"{time.monotonic() - t_ref:.2f} s")

    metrics = {}
    entries = cell.per_layer if args.trace else cell.end_to_end
    for m in entries:
        value = spec.metric_reader(m["name"])(data)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    attempted = req["t_due"] < t1
    failed = int((attempted & (req["state"] != ANSWERED)).sum())
    d0 = devs[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    out = {"correct": check.passed(checks), "attempted": int(attempted.sum()),
           "failed": failed, "metrics": metrics, "device": device}
    if trace is not None:
        device["busy_s"] = trace.busy_s
        device["window_s"] = trace.window_s
        out["breakdown"] = trace.breakdown()
    out["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']} (limit {c['rule']} {c['limit']})")
    return out


class Profiler:
    """The JAX profiler over `seconds` in the middle of a window of
    `window_s`: a thread of its own starts it, marks those seconds with a
    ``bench.trace`` annotation, and stops it."""

    MARK = "bench.trace"

    def __init__(self, trace_dir: str, seconds: float, window_s: float):
        import shutil
        import threading
        self.trace_dir, self.seconds = trace_dir, seconds
        self.offset = max(0.0, (window_s - seconds) / 2)
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir, exist_ok=True)
        self.lo = self.hi = 0.0
        self.window = threading.Thread(target=self._mark, name="bench-trace")

    def _mark(self) -> None:
        import jax
        time.sleep(self.offset)
        jax.profiler.start_trace(self.trace_dir)
        with jax.profiler.TraceAnnotation(self.MARK):
            self.lo = time.monotonic()
            time.sleep(self.seconds)
            self.hi = time.monotonic()
        jax.profiler.stop_trace()


def main(argv=None, t_start: float | None = None) -> int:
    try:
        out = run(argv, t_start)
    except NoDevice as e:
        log(f"no result: {e}")
        return 3
    print(json.dumps(out), flush=True)
    return 0

