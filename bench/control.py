"""The controls of the comparison that decides ``correct``.

A control is the plain reference put in the program's place with one of
the configuration's guarantees broken, compared with the reference by
the same comparison a run makes (``check.compare``).  It has to come out
not correct on every seed; its ``mismatched`` count is the upper reading
of that number.

* ``gaps_first``: the walk prefers deletion, then match, substitution,
  insertion, instead of match, substitution, deletion, insertion (the
  guarantee: "the walk takes match, then substitution, then deletion, then
  insertion").  Every indel in a repeat moves to the other end of it.
* ``no_rescue``: no k = 24 rung (the guarantee: "a pair that fails at
  k = 12 is tried at k = 24"): the step that would halve a long read's
  device time.

    python3 bench/control.py --workload long10k.backlog --seeds 1,2,3

One line of JSON per seed and control on standard output: the numbers
compared for the program's answers and for the control's, on the cell's
own pool and sample size.
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def controls(geo):
    """{name: reference geometry with one guarantee broken}."""
    import dataclasses

    from bench import reference
    out = {"gaps_first": dataclasses.replace(geo, order=(
        reference.OP_DEL, reference.OP_MATCH, reference.OP_SUBST,
        reference.OP_INS))}
    if geo.rescue_rounds > 0:
        out["no_rescue"] = dataclasses.replace(geo, rescue_rounds=0)
    return out


def readings(config: dict, seed: int, n_pool: int | None = None,
             n_check: int | None = None) -> dict:
    """{control: mismatched} on the cell's pool for one seed."""
    from bench import check, reference, simulate
    n_pool = n_pool or int(config["bench"]["pool_pairs"])
    n_check = n_check or int(config["bench"]["check_pairs"])
    _, pool = simulate.pool(config, seed, n_pool)
    picked = check.sample(range(len(pool)), n_check,
                          simulate.rng_for(seed, 4))
    geo = check.geometry(config)
    want = check.reference_records(pool, picked, config, geo)
    out = {}
    for name, g in controls(geo).items():
        got = reference.align([pool[i][0] for i in picked],
                              [pool[i][1] for i in picked], g)
        checks = check.compare(list(zip(picked, got)), 0, picked, want,
                               min_compared=len(picked))
        out[name] = {"mismatched": checks["mismatched"]["value"],
                     "compared": checks["compared"]["value"],
                     "correct": check.passed(checks)}
    return out


def main(argv=None) -> int:
    import argparse
    import json

    from bench import spec
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "controls": readings(cell.config, seed)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    sys.exit(main(sys.argv[1:]))
