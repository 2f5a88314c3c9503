"""Alignment-as-a-service through the ONE front door (repro.api): plan an
AlignSession with the background retire executor, AOT warm-up its length
buckets before traffic, stream ragged requests as futures while host CIGAR
decode overlaps dispatch on the retire thread, and read the
compile-stability counters — the paper's GPU batch processing mapped to a
production-shaped serving layer.

    PYTHONPATH=src python examples/serve_alignment.py [--requests 32]
        [--len 800] [--fast]
"""
import argparse
import time

import numpy as np

from repro.api import plan
from repro.core.config import AlignerConfig
from repro.data.genome import ReadSimConfig, simulate_reads, synth_genome
from repro.launch.compile_cache import enable_compile_cache

ap = argparse.ArgumentParser()
ap.add_argument("--requests", type=int, default=32)
ap.add_argument("--len", type=int, default=800, dest="rlen")
ap.add_argument("--fast", action="store_true",
                help="small geometry for CI smoke runs")
ap.add_argument("--backend", choices=("jnp", "pallas", "pallas_fused",
                                      "pallas_gpu"), default="jnp",
                help="aligner execution path (docs/backends.md); Pallas "
                     "backends print whether they run interpreted or "
                     "compiled on this host")
ap.add_argument("--executor", choices=("thread", "sync"), default="thread",
                help="'thread' (default) retires dispatches on the "
                     "background executor so CIGAR decode overlaps "
                     "dispatch; 'sync' is the single-threaded reference")
ap.add_argument("--gateway", action="store_true",
                help="additionally demo the multi-tenant gateway: two "
                     "tenants (latency lane with deadlines vs bulk) on "
                     "concurrent client threads, with the SLO readout")
ap.add_argument("--metrics-dump", action="store_true",
                help="after each demo, dump its obs registry as "
                     "Prometheus exposition text (every printed number "
                     "above is derivable from this dump — see "
                     "docs/observability.md)")
args = ap.parse_args()
enable_compile_cache()

cfg = AlignerConfig(W=32, O=12, k=8, backend=args.backend) if args.fast \
    else AlignerConfig(W=64, O=24, k=12, backend=args.backend)
if args.backend != "jnp":
    # say which execution mode is actually in effect on this host — the
    # backend names a lowering, default_interpret decides where it runs
    # (docs/backends.md, "Three-way execution modes")
    from repro.kernels.ops import default_interpret
    mode = "interpret" if default_interpret(args.backend) else "compiled"
    print(f"backend {args.backend}: {mode} mode on this host "
          f"(jax default_backend={__import__('jax').default_backend()})")
genome = synth_genome(200_000 if args.fast else 500_000, seed=3)
# a RAGGED stream: three read-length classes hitting different buckets
lens = [max(64, args.rlen // 4), max(96, args.rlen // 2), args.rlen]
streams = [simulate_reads(genome, -(-args.requests // len(lens)),
                          ReadSimConfig(read_len=L, error_rate=0.08,
                                        seed=9 + i))
           for i, L in enumerate(lens)]

# the session is a context manager: __exit__ drains and stops the
# background retire thread (clean shutdown is part of the executor API)
with plan(cfg, rescue_rounds=1, batch_lanes=8,
          executor=args.executor) as session:
    # warm-up is a METHOD: from a traffic sample, compile every length
    # bucket before the first request arrives (one AOT executable per
    # bucket) — including the smaller lane class the ragged stream tails
    # land in
    buckets = sorted({session.bucket_for(len(r), len(s))
                      for rs in streams
                      for r, s in zip(rs.reads, rs.ref_segments)})
    session.warmup(buckets)
    tail = -(-args.requests // len(lens)) % session.spec.batch_lanes
    warm = session.warmup(buckets, lanes=tail) if tail \
        else session.cache.stats()
    print(f"warmed {warm['executables']} executables "
          f"(lowerings={warm['lowerings']})")

    # req/s is END-TO-END wall clock around the whole stream (submit ->
    # last result collected): the session's own wall_s/retire_wall_s split
    # per-thread time, which under the threaded executor overlaps and
    # would overstate throughput if divided into either alone
    t0 = time.time()
    futures = {}
    for rs in streams:
        for read, seg in zip(rs.reads, rs.ref_segments):
            fut = session.submit(read, seg)   # routed to its length bucket;
            futures[fut.rid] = fut            # retire overlaps dispatch
    session.flush()
    results = {rid: fut.result() for rid, fut in futures.items()}
    elapsed = max(time.time() - t0, 1e-9)

    st = session.session_stats()
    cc = st["compile_cache"]
    ok = sum(1 for r in results.values() if r["ok"])
    stalls = cc["lowerings"] - warm["lowerings"]
    print(f"served {len(results)} requests in {st['dispatches']} dispatches "
          f"({st['pad_lanes']} pad lanes), {ok} aligned, "
          f"{len(results) - ok} failed, "
          f"{len(results) / elapsed:.1f} req/s end-to-end"
          + (f" (incl. {stalls} mid-stream rescue-rung lowering(s) — the "
             f"residual warmup stall documented in docs/api.md)"
             if stalls else ""))
    if args.executor == "thread":
        # decode/rescue wall-clock that ran on the retire thread instead
        # of serialising after each dispatch (the overlap the executor buys)
        print(f"retire thread absorbed {st['retire_wall_s']:.3f}s of host "
              f"decode + rescue alongside {st['wall_s']:.3f}s of dispatch")
    print(f"compile cache: {cc['lowerings']} lowerings "
          f"({cc['lowerings'] - warm['lowerings']} after warm-up, rescue-rung "
          f"lane classes) for {st['dispatches'] + st['rescue_dispatches']} "
          f"dispatches, {cc['hits']} hits ({cc['shared_hits']} from other "
          f"sessions of this spec) — steady state never re-traces")
    r0 = results[0]
    print(f"request 0: dist={r0['dist']} k_used={r0['k_used']} "
          f"cigar[:60]={r0['cigar'][:60]}")
    assert ok > 0

if args.metrics_dump:
    # every stat printed above is a view over this registry — the dump IS
    # the session's whole story (docs/observability.md)
    print("\n# ---- session metrics (Prometheus exposition text) ----")
    print(session.obs.prometheus(), end="")

if args.gateway:
    # ---- the multi-tenant gateway: SLOs on top of the same session ----
    # two tenants on their own client threads: a latency lane (priority
    # 0, short reads, per-request deadline) and a bulk lane (priority 1,
    # long reads) — the gateway preempts bulk at bucket granularity,
    # sweeps deadlines on the background pump, and sheds reject-fast at
    # the occupancy-derived capacity (docs/api.md, "The multi-tenant
    # gateway").
    import threading

    from repro.api import Gateway, GatewayPolicy, ShedError

    short_rs, long_rs = streams[0], streams[-1]
    with plan(cfg, rescue_rounds=1, batch_lanes=8,
              executor=args.executor) as session:
        session.warmup(buckets)
        gw = Gateway(session, GatewayPolicy(linger_s=0.002))
        gw.start_sweeper(0.001)
        # deadline is a stall canary, not a latency target: interpret-mode
        # compiles on a 1-core CI runner stall several seconds mid-stream,
        # and a queued request expiring would trip the expired==0 assert
        # below (same 30s convention as benchmarks gateway_multitenant)
        latency = gw.tenant("latency", priority=0, deadline_s=30.0)
        bulk = gw.tenant("bulk", priority=1)
        shed = 0

        def client(ten, rs, pace):
            global shed
            for read, seg in zip(rs.reads, rs.ref_segments):
                try:
                    ten.submit(read, seg)
                except ShedError:
                    shed += 1
                time.sleep(pace)

        threads = [
            threading.Thread(target=client, args=(latency, short_rs, 0.002)),
            threading.Thread(target=client, args=(bulk, long_rs, 0.006)),
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        gw.close()                      # drains: every future resolves
        st = gw.gateway_stats()
        print(f"gateway: {st['completed']} completed over 2 tenants "
              f"(capacity {st['capacity']} from the session's inflight "
              f"signal), {st['deadline_hits']} deadline hits / "
              f"{st['deadline_misses']} misses, {st['expired']} expired, "
              f"{st['shed']} shed, {st['partial_dispatches']} partial "
              f"(linger/deadline-urgent) dispatches")
        for name, ts in st["tenants"].items():
            print(f"  tenant {name}: submitted={ts['submitted']} "
                  f"completed={ts['completed']} hits={ts['deadline_hits']}")
        assert st["completed"] > 0 and st["expired"] == 0
        if args.metrics_dump:
            # the gateway shares the session's obs domain: one dump
            # carries admission (gateway_*) AND serving (session_*)
            # counters, tenants as labels (docs/observability.md)
            print("\n# ---- gateway metrics (Prometheus exposition "
                  "text) ----")
            print(gw.obs.prometheus(), end="")
