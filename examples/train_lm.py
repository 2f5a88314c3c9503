"""LM-framework example: train a reduced model for a few hundred steps with
checkpointing + fault-tolerant supervision (CPU-scale; the same driver
lowers unchanged onto the production mesh — see launch/dryrun.py).

    PYTHONPATH=src python examples/train_lm.py [--arch xlstm-125m]
"""
import argparse
import sys

from repro.launch.compile_cache import enable_compile_cache
from repro.launch.train import main as train_main

ap = argparse.ArgumentParser()
ap.add_argument("--arch", default="llama3.2-1b")
ap.add_argument("--steps", type=int, default=200)
args = ap.parse_args()
enable_compile_cache()

train_main([
    "--arch", args.arch, "--tiny", "--layers", "4",
    "--steps", str(args.steps), "--batch", "8", "--seq", "256",
    "--ckpt-dir", "/tmp/repro_example_ckpt", "--ckpt-every", "50",
])
