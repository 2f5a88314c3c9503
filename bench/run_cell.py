"""Run one cell of the benchmark once and print its result line.

    python3 bench/run_cell.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1> [--rehearse]

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``).  The numbers compared for
``correct`` are also the last lines of standard error.  A host whose JAX
sees no TPU (or fewer chips than the cell asks for) exits 3 and prints no
result; ``--rehearse`` runs tiny sizes on the CPU instead and is never a
measurement.  See bench/harness.py for the steps of a run.
"""
import time

T_START = time.monotonic()

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

if __name__ == "__main__":
    # libtpu writes its logs under /tmp unless told otherwise; a run reads
    # and writes only inside its checkout and its own HOME and TMPDIR
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if "--rehearse" in sys.argv:
        os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench.harness import main
    sys.exit(main(sys.argv[1:], t_start=T_START))
