"""End-to-end driver mirroring the paper's evaluation (§II): simulate
PacBio-like reads from a genome, generate candidate chains (true locus +
decoys), align every candidate with the improved GenASM, report throughput
and accuracy.  This is the paper-native e2e pipeline (the aligner is the
"model"; the pipeline is sim -> chain -> align -> report).

    PYTHONPATH=src python examples/align_longreads.py [--reads 16] [--len 2000]
"""
import argparse
import time

import jax
import numpy as np

from repro.api import plan
from repro.core.config import AlignerConfig
from repro.core.oracle import validate_cigar
from repro.data.genome import (ReadSimConfig, candidate_chains, simulate_reads,
                               synth_genome)
from repro.launch.compile_cache import enable_compile_cache

ap = argparse.ArgumentParser()
ap.add_argument("--reads", type=int, default=16)
ap.add_argument("--len", type=int, default=2000, dest="rlen")
ap.add_argument("--decoys", type=int, default=1)
ap.add_argument("--error-rate", type=float, default=0.10)
ap.add_argument("--genome", type=int, default=1_000_000)
ap.add_argument("--W", type=int, default=64)
ap.add_argument("--backend", choices=("jnp", "pallas", "pallas_fused",
                                      "pallas_gpu"), default="jnp",
                help="aligner execution path (docs/backends.md)")
args = ap.parse_args()
enable_compile_cache()

if args.backend != "jnp":
    # the backend names a lowering; default_interpret decides where it
    # actually runs on this host (docs/backends.md)
    from repro.kernels.ops import default_interpret
    mode = "interpret" if default_interpret(args.backend) else "compiled"
    print(f"backend {args.backend}: {mode} mode on this host "
          f"(jax default_backend={jax.default_backend()})")

genome = synth_genome(args.genome, seed=11)
rs = simulate_reads(genome, args.reads,
                    ReadSimConfig(read_len=args.rlen,
                                  error_rate=args.error_rate, seed=5))
chains = candidate_chains(genome, rs, decoys_per_read=args.decoys)
print(f"{args.reads} reads x {args.rlen}bp @ {args.error_rate:.0%} error, "
      f"{len(chains)} candidate locations")

# the session front door: plan once, warm the one bucket this pipeline
# hits, and the steady-state pass is pure cache hits (no re-tracing)
session = plan(AlignerConfig(W=args.W, O=args.W * 3 // 8, k=args.W * 3 // 16,
                             backend=args.backend),
               rescue_rounds=1, batch_lanes=len(chains))
reads = [rs.reads[i] for i, _ in chains]
refs = [seg for _, seg in chains]

t0 = time.time()
res = session.align(reads, refs)          # first call AOT-compiles buckets
t_first = time.time() - t0
lowered = session.cache.lowerings
t0 = time.time()
res = session.align(reads, refs)
t_steady = time.time() - t0
assert session.cache.lowerings == lowered, "steady state re-traced!"

ok = ~res.failed
true_mask = np.array([j == 0 for i, (ri, _) in enumerate(chains)
                      for j in [i % (1 + args.decoys)]])
n_true = args.reads
aligned_true = sum(1 for i in range(len(chains))
                   if i % (1 + args.decoys) == 0 and ok[i])
rejected_decoys = sum(1 for i in range(len(chains))
                      if i % (1 + args.decoys) != 0 and not ok[i])
for i in range(0, len(chains), max(1, len(chains) // 4)):
    if ok[i]:
        validate_cigar(reads[i], refs[i], res.ops[i], res.dist[i])

bp = sum(len(r) for r in reads)
print(f"aligned true loci: {aligned_true}/{n_true}; "
      f"rejected decoys: {rejected_decoys}/{len(chains)-n_true}")
print(f"summary: {res.summary(base_k=session.cfg.k)}")
print(f"steady-state: {t_steady:.2f}s = {len(chains)/t_steady:.1f} pairs/s = "
      f"{bp/t_steady/1e6:.2f} Mbp/s ({args.backend} backend on "
      f"{jax.default_backend()})")
print(f"mean edit distance of true alignments: "
      f"{np.mean([res.dist[i] for i in range(len(chains)) if i % (1+args.decoys)==0 and ok[i]]):.1f} "
      f"(expected ~{args.error_rate*args.rlen*0.95:.0f})")
