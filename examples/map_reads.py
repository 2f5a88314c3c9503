"""End-to-end READ MAPPING driver: the front half the other examples skip.

`align_longreads.py` fabricates candidate chains from ground truth; this
driver discovers them the way a real mapper does — minimizer index over
the genome, seed + colinear chain to candidate loci, banded X-drop
pre-filter, then the survivors stream through the AlignSession front
door.  Decoys are PLANTED IN THE GENOME (partial repeats of each read's
interior, `data.genome.plant_decoys`), so the pipeline has to find and
reject them itself; the driver asserts the acceptance floor (>= 95% of
reads at their true locus under the default 10% error profile with 4
decoys/read) — docs/mapper.md records the measured numbers.

    PYTHONPATH=src python examples/map_reads.py [--reads 200] [--len 1000]
    PYTHONPATH=src python examples/map_reads.py --fast     # CI smoke size
"""
import argparse
import time

import numpy as np

from repro.data.genome import (ReadSimConfig, plant_decoys, simulate_reads,
                               synth_genome)
from repro.launch.compile_cache import enable_compile_cache
from repro.mapper import MapperConfig, ReadMapper

ap = argparse.ArgumentParser()
ap.add_argument("--reads", type=int, default=200)
ap.add_argument("--len", type=int, default=1000, dest="rlen")
ap.add_argument("--genome", type=int, default=1_000_000)
ap.add_argument("--decoys", type=int, default=4)
ap.add_argument("--error-rate", type=float, default=0.10)
ap.add_argument("--W", type=int, default=64)
ap.add_argument("--fast", action="store_true",
                help="CI smoke size: small geometry, fewer/shorter reads")
args = ap.parse_args()
enable_compile_cache()
if args.fast:
    args.reads, args.rlen, args.genome, args.W = 24, 400, 120_000, 32

genome = synth_genome(args.genome, seed=11)
rs = simulate_reads(genome, args.reads,
                    ReadSimConfig(read_len=args.rlen,
                                  error_rate=args.error_rate, seed=5))
genome, decoy_pos = plant_decoys(genome, rs, decoys_per_read=args.decoys,
                                 chunk=max(160, args.rlen // 4), seed=13)
print(f"{args.reads} reads x {args.rlen}bp @ {args.error_rate:.0%} error, "
      f"{args.decoys} planted decoys/read, genome {len(genome):,}bp")

t0 = time.time()
mapper = ReadMapper(genome, MapperConfig(),
                    W=args.W, O=args.W * 3 // 8, k=args.W * 3 // 16,
                    rescue_rounds=2, batch_lanes=64)
t_index = time.time() - t0
print(f"index: {mapper.index.stats()} ({t_index:.2f}s)")

with mapper:
    t0 = time.time()
    out = mapper.map_batch(rs.reads)      # first batch AOT-compiles buckets
    t_first = time.time() - t0
    t0 = time.time()
    out = mapper.map_batch(rs.reads)
    t_steady = time.time() - t0

st = out.stats
hits = sum(1 for mr, tp in zip(out.mapped, rs.true_pos)
           if mr.ok and abs(mr.ref_start - tp) <= 20)
decoy_hits = sum(1 for mr in out.mapped if mr.ok and
                 any(abs(mr.ref_start - dp) <= 50
                     for dp in decoy_pos[mr.read_id]))
recall = hits / st["n_reads"]
reads_per_s = st["n_reads"] / t_steady

print(f"funnel: {st['n_candidates']} candidates from {st['n_reads']} reads "
      f"-> {st['n_killed']} killed by X-drop ({st['kill_rate']:.0%}) "
      f"-> {st['n_aligned']} aligned -> {st['n_mapped']} mapped")
print(f"true locus: {hits}/{st['n_reads']} ({recall:.1%}); "
      f"mapped at a decoy: {decoy_hits}")
print(f"first batch {t_first:.2f}s (compiles), steady {t_steady:.2f}s = "
      f"{reads_per_s:.1f} mapped reads/s")

assert recall >= 0.95, f"recall {recall:.1%} below the 95% floor"
assert decoy_hits == 0, f"{decoy_hits} reads mapped at planted decoys"
assert st["kill_rate"] > 0.2, "pre-filter killed nothing"

# the batch stats above are registry-counter DELTAS; the cumulative story
# (both batches, plus the session's own serving counters) lives on the
# shared obs registry — docs/observability.md maps every name
snap = mapper.obs.snapshot()
print(f"registry: mapper_reads_total={snap['mapper_reads_total']} "
      f"mapper_candidates_total={snap['mapper_candidates_total']} "
      f"session_dispatches_total={snap['session_dispatches_total']} "
      f"({len(snap)} metrics — see `serve_alignment.py --metrics-dump` "
      f"for the full Prometheus dump)")
print("OK")
