"""Benchmark harness — one function per paper table.
Prints ``name,us_per_call,derived`` CSV (assignment format).

  PYTHONPATH=src python -m benchmarks.run [--fast]
"""
from __future__ import annotations

import argparse
import datetime
import json
import os
import platform
import subprocess
import sys


def _meta() -> dict:
    """Provenance for one bench run — committed beside the numbers so a
    trajectory regression is diagnosable at a glance (PR 8's -28%
    container-noise confusion: same numbers, different machine)."""
    import jax

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    try:
        sha = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], cwd=root,
            capture_output=True, text=True, timeout=10).stdout.strip()
    except Exception:
        sha = ""
    return {
        "jax_version": jax.__version__,
        "cpu_count": os.cpu_count(),
        "platform": platform.platform(),
        "git_sha": sha or "unknown",
        "timestamp_utc": datetime.datetime.now(
            datetime.timezone.utc).isoformat(timespec="seconds"),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="smaller problem sizes (CI)")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also dump rows + derived metrics as JSON "
                         "(uploaded as a CI artifact to track the perf "
                         "trajectory)")
    ap.add_argument("--devices", type=int, default=0, metavar="N",
                    help="also benchmark the mesh-sharded aligner on N "
                         "forced host devices (re-execs a fresh "
                         "interpreter; reports per-device pairs/s and "
                         "transfer bytes)")
    ap.add_argument("--obs-dir", metavar="DIR", default=None,
                    help="dump the session-stream observability bundle "
                         "(Prometheus text, JSONL + perfetto traces) "
                         "into DIR — uploaded as nightly CI artifacts")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    meta = _meta()
    print(f"# meta: jax={meta['jax_version']} cpus={meta['cpu_count']} "
          f"sha={meta['git_sha']} at={meta['timestamp_utc']}")
    print(f"# meta: {meta['platform']}")
    print("name,us_per_call,derived")
    all_rows = []
    all_derived = {}

    def emit(rows):
        for n, us, d in rows:
            print(f"{n},{us:.1f},{d}")
            all_rows.append({"name": n, "us_per_call": float(us),
                             "derived": str(d)})

    from benchmarks import bench_aligners
    rows, derived = bench_aligners.table(
        n_reads=8 if args.fast else 24, read_len=500 if args.fast else 1000)
    emit(rows)
    all_derived["aligners"] = derived
    print(f"aligners/speedup_improved_vs_unimproved,0.0,"
          f"{derived['improved_vs_unimproved']:.2f}x_paper_cpu1.9x")
    print(f"aligners/speedup_improved_vs_edlib_like,0.0,"
          f"{derived['improved_vs_edlib_like']:.2f}x_paper_cpu1.7x")
    print(f"aligners/speedup_improved_vs_edlib_banded_model,0.0,"
          f"{derived['improved_vs_edlib_banded_model']:.2f}x")
    print(f"aligners/speedup_improved_vs_ksw2_like,0.0,"
          f"{derived['improved_vs_ksw2_like']:.2f}x_paper_cpu15.2x")
    print(f"aligners/speedup_dc_engine_vs_edlib_like,0.0,"
          f"{derived['dc_engine_vs_edlib_like']:.2f}x_paper_cpu1.7x")
    # The pallas_gpu paper-headline family (4.1x / 62x / 7.2x) rides in
    # the table() rows above (bench_aligners.gpu_rows): pending-hardware
    # zeros on CPU-only runners, measured — and gated via the
    # gpu_pairs_per_s derived key — on runners with a CUDA/ROCm device.

    # the session front door: ragged-stream pairs/s + bucket-hit stats
    # (the compile-stability numbers the PR-over-PR trajectory tracks).
    # One obs bundle spans the backend legs (labelled session=<backend>)
    # so --obs-dir can export the whole run's metrics + trace.
    from repro.obs import Obs, write_artifacts
    bench_obs = Obs.private()
    rows, derived = bench_aligners.session_stream(
        n_reads=9 if args.fast else 24,
        max_len=160 if args.fast else 400, obs=bench_obs)
    emit(rows)
    all_derived["session"] = derived
    if args.obs_dir:
        paths = write_artifacts(bench_obs, args.obs_dir, prefix="obs")
        for kind, p in paths.items():
            print(f"# wrote {kind} artifact: {p}", file=sys.stderr)

    # the serving executor: sync vs background-retire on a rescue-heavy
    # ragged stream (decode-overlap gain) + cross-session cache sharing.
    # Not shrunk under --fast: below ~24 pairs the stream is too short for
    # overlap to beat thread-handoff overhead, and this row carries the
    # decode-overlap claim in the committed BENCH_* trajectory.
    rows, derived = bench_aligners.session_concurrent()
    emit(rows)
    print(f"aligners/session_concurrent_overlap_gain,0.0,"
          f"{derived['concurrent_overlap_gain_jnp']:.2f}x_thread_vs_sync")
    all_derived["session_concurrent"] = derived

    # the multi-tenant gateway: SLO rows from a skewed 2-tenant open-loop
    # load (latency p50/p99, deadline-hit-rate) plus the deterministic
    # burst-shed rate.  compare.py gates p99/shed GROWTH and hit-rate
    # DROPS.  Not shrunk under --fast: the latency percentiles need the
    # full request count to mean anything.
    rows, derived = bench_aligners.gateway_multitenant()
    emit(rows)
    all_derived["gateway"] = derived

    # the mapping front half: seed/chain/pre-filter funnel feeding the
    # session — mapped-reads/s is gated by compare.py like pairs/s
    rows, derived = bench_aligners.mapper_stream(
        n_reads=12 if args.fast else 24,
        read_len=300 if args.fast else 400,
        genome_len=100_000 if args.fast else 200_000)
    emit(rows)
    all_derived["mapper"] = derived

    from benchmarks import bench_memory
    rows, derived = bench_memory.table()
    emit(rows)
    all_derived["memory"] = {k: {kk: float(vv) for kk, vv in v.items()}
                             for k, v in derived.items()}

    from benchmarks import bench_kernel
    rows, derived = bench_kernel.table(B=1024 if args.fast else 4096)
    emit(rows)
    all_derived["kernel"] = derived

    if args.devices > 0:
        rows, derived = bench_aligners.multidevice(n_devices=args.devices)
        emit(rows)
        all_derived["multidevice"] = derived

    print("# derived summary (JSON):")
    print(json.dumps(all_derived, indent=1, default=float))
    if args.json:
        with open(args.json, "w") as fh:
            json.dump({"meta": meta, "rows": all_rows,
                       "derived": all_derived}, fh, indent=1, default=float)
        print(f"# wrote {args.json}", file=sys.stderr)


if __name__ == "__main__":
    main()
