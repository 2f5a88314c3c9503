"""Quickstart: align a handful of simulated long reads with the improved
GenASM aligner and show the paper's three ideas in action.

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.core.aligner import GenASMAligner
from repro.core.config import AlignerConfig
from repro.core.counting import reduction_report
from repro.data.genome import ReadSimConfig, simulate_reads, synth_genome
from repro.launch.compile_cache import enable_compile_cache

enable_compile_cache()
genome = synth_genome(100_000, seed=1)
rs = simulate_reads(genome, 4, ReadSimConfig(read_len=600, error_rate=0.08,
                                             seed=2))

cfg = AlignerConfig(W=64, O=24, k=12, store="band", early_term=True)
aligner = GenASMAligner(cfg)
res = aligner.align(rs.reads, rs.ref_segments)

for i, cig in enumerate(res.cigars):
    print(f"read {i}: dist={res.dist[i]}  failed={res.failed[i]}")
    print(f"  cigar[:70] = {cig[:70]}...")

rep = reduction_report(cfg, avg_levels=7.0)
print("\npaper's improvements for this config (per window):")
print(f"  footprint: {rep['baseline_footprint_words']}w -> "
      f"{rep['improved_touched_words']:.0f}w "
      f"({rep['footprint_reduction_touched']:.1f}x, paper: 24x)")
print(f"  accesses : {rep['baseline_accesses']}w -> {rep['improved_accesses']}w "
      f"({rep['access_reduction']:.1f}x, paper: 12x)")
print(f"  on-chip bytes/problem: {rep['vmem_bytes_per_problem']}")
