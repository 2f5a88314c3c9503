"""Find the knee of an open-loop cell: the highest Poisson rate whose
answers keep up with its arrivals.

    python3 bench/sweep.py --config illumina150 --traffic open80 \
        --seed 7 --seconds 8 --rates 2000,4000,8000

One process, one set-up (the configuration and its lane classes), then
for each rate a fresh gateway over the same session, driven by the cell's
load kind at that rate.  Per rate it prints the answered rate, the
latency percentiles from due time, the generator's lateness and how many
requests were still unanswered when the window closed (a queue that
grows shows there).  Writes the table as JSON to ``--out`` too.  A
measurement aid run once when a cell is defined; the cell's traffic file
then carries the rate as a number.
"""
import os
import sys
import time

T_START = time.monotonic()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    import argparse
    import json

    import numpy as np

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--out", default=None)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    from bench import harness, simulate, spec
    cell = spec.load_parts(args.config, args.traffic)
    if args.rehearse:
        cell = harness.rehearsal(cell)
    devs = harness.devices_for(cell, args.rehearse)
    import jax
    from repro.api import Gateway, GatewayPolicy
    from repro.distributed.sharding import lane_classes
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    load = spec.load_kind(cell.traffic["kind"])
    _, pool = simulate.pool(cell.config, args.seed,
                            int(cell.config["bench"]["pool_pairs"]))
    session = harness.build_session(cell, devs, "off")
    full = session.spec.batch_lanes
    harness.warm(session, pool, load.lane_classes(
        full, lane_classes(full, session.cfg, session.mesh)))
    harness.log(f"sweep set-up {time.monotonic() - T_START:.1f} s")
    order = harness.cycle_order(len(pool), simulate.rng_for(args.seed, 2))
    rows = []
    for n, rate in enumerate(float(r) for r in args.rates.split(",")):
        traffic = dict(cell.traffic, rate=rate)
        gw = Gateway(session, GatewayPolicy(**load.policy(traffic, full)))
        reqs = harness.Requests((), time.monotonic)
        ctx = harness.LoadContext(
            traffic=traffic, gateway=gw, tenant=gw.tenant("bench", 0),
            pool=pool, order=order, lanes=full, seconds=args.seconds,
            rng=simulate.rng_for(args.seed, 10 + n), clock=time.monotonic,
            span=session.obs.tracer.span, drain_s=harness.DRAIN_S,
            requests=reqs)
        t0 = time.monotonic()
        notes = load.run(ctx, t0)
        t1 = t0 + args.seconds
        backlog = sum(1 for _, f in reqs.pending if not f.done())
        reqs.settle_all(time.monotonic() + harness.DRAIN_S)
        gw.close()
        data = harness.RunData(t0=t0, t1=t1, t_giveup=t1 + harness.DRAIN_S,
                               setup_s=0.0, req=reqs.arrays(), stats0={},
                               stats1={}, spans=[], trace=None)
        due = data.due_in_window()
        ok = due & (data.req["state"] == harness.ANSWERED)
        lat = np.sort(data.req["t_done"][ok] - data.req["t_due"][ok])
        q = [1e3 * float(lat[min(len(lat) - 1, int(p * len(lat)))])
             for p in (0.5, 0.95, 0.99)] if len(lat) else [None] * 3
        row = {"rate": rate, "due": int(due.sum()),
               "answered_per_s": int(data.answered_in(t0, t1).sum())
               / args.seconds,
               "p50_ms": q[0], "p95_ms": q[1], "p99_ms": q[2],
               "unanswered_at_close": backlog,
               "failed": int((data.req["state"] != harness.ANSWERED).sum()),
               "notes": notes}
        rows.append(row)
        harness.log(json.dumps(row))
    session.close()
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"config": args.config, "traffic": args.traffic,
                       "seed": args.seed,
                       "seconds": args.seconds, "device": devs[0].device_kind,
                       "rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    # libtpu writes its logs under /tmp unless told otherwise; a run reads
    # and writes only inside its checkout and its own HOME and TMPDIR
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if "--rehearse" in sys.argv:
        os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    sys.exit(main(sys.argv[1:]))
