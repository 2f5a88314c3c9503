"""Seeded inputs for the benchmark: a random genome and simulated reads.

The read model is the one the program's own simulator mirrors from PBSIM2:
walk the reference and, at each emitted position, draw a deletion (skip a
reference character), an insertion (emit a random base), a substitution
(emit a different base) or a match, with the configured error rate split
by the configured fractions.  This copy draws every event of a block of
reads in one numpy call instead of one Python step per base, so making a
pool of 10 kbp reads costs about a millisecond a read.

A pair is (read, candidate reference segment): the segment is exactly the
reference span the read was drawn from.
"""
from __future__ import annotations

import numpy as np

DEL, INS, SUB, MATCH = 0, 1, 2, 3


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per (seed, stream); any non-negative
    seed, however large."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


def genome(length: int, rng: np.random.Generator) -> np.ndarray:
    return rng.integers(0, 4, length, dtype=np.uint8)


def _event_thresholds(error_rate, sub_frac, ins_frac, del_frac):
    tot = sub_frac + ins_frac + del_frac
    p_del = error_rate * del_frac / tot
    p_ins = error_rate * ins_frac / tot
    p_sub = error_rate * sub_frac / tot
    return p_del, p_del + p_ins, p_del + p_ins + p_sub


def reads(gen: np.ndarray, n_reads: int, read_len: int, error_rate: float,
          sub_frac: float, ins_frac: float, del_frac: float,
          rng: np.random.Generator, block: int = 64):
    """n_reads (read, segment) pairs, each read exactly read_len long."""
    t_del, t_ins, t_sub = _event_thresholds(error_rate, sub_frac, ins_frac,
                                            del_frac)
    p_del = t_del
    need = read_len / (1.0 - p_del)
    n_draw = int(need + 8.0 * np.sqrt(need) + 64)
    max_span = n_draw + 1
    out = []
    while len(out) < n_reads:
        b = min(block, n_reads - len(out))
        u = rng.random((b, n_draw))
        ev = np.where(u < t_del, DEL, np.where(u < t_ins, INS, np.where(
            u < t_sub, SUB, MATCH))).astype(np.int8)
        emit = ev != DEL
        consume = ev != INS
        n_emit = np.cumsum(emit, axis=1)
        short = n_emit[:, -1] < read_len
        starts = rng.integers(0, len(gen) - max_span, b)
        # the reference offset each event reads (before it consumes)
        off = np.cumsum(consume, axis=1) - consume
        base = gen[starts[:, None] + off]
        rand_base = rng.integers(0, 4, (b, n_draw), dtype=np.uint8)
        shift = rng.integers(1, 4, (b, n_draw), dtype=np.uint8)
        char = np.where(ev == INS, rand_base,
                        np.where(ev == SUB, (base + shift) % 4, base))
        for r in range(b):
            if short[r]:
                continue            # too few emitting draws: redraw later
            last = int(np.searchsorted(n_emit[r], read_len))  # index of Lth
            sel = emit[r, :last + 1]
            read = char[r, :last + 1][sel].astype(np.uint8)
            span = int(off[r, last] + consume[r, last])
            seg = gen[starts[r]:starts[r] + span].copy()
            out.append((read, seg))
    return out


def pool(cfg: dict, seed: int, n_pairs: int):
    """The pair pool of a configuration file's `reads` block, from
    `seed`: (genome, [(read, segment), ...])."""
    rc = cfg["reads"]
    g = genome(int(cfg["genome_bp"]), rng_for(seed, 0))
    pairs = reads(g, n_pairs, int(rc["read_len"]), float(rc["error_rate"]),
                  float(rc["sub_frac"]), float(rc["ins_frac"]),
                  float(rc["del_frac"]), rng_for(seed, 1))
    return g, pairs
