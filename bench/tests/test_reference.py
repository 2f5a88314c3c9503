"""The plain reference against a textbook DP and hand-made cases."""
import numpy as np
import pytest

from bench import reference, simulate


def dp(p, t):
    D = np.zeros((len(p) + 1, len(t) + 1), int)
    D[:, 0] = range(len(p) + 1)
    D[0, :] = range(len(t) + 1)
    for a in range(1, len(p) + 1):
        for c in range(1, len(t) + 1):
            D[a, c] = min(D[a - 1, c - 1] + (p[a - 1] != t[c - 1]),
                          D[a - 1, c] + 1, D[a, c - 1] + 1)
    return D


@pytest.mark.parametrize("seed", range(4))
def test_edit_table_is_the_textbook_table(seed):
    rng = np.random.default_rng(seed)
    p = rng.integers(0, 4, (3, 17), dtype=np.uint8)
    t = rng.integers(0, 4, (3, 23), dtype=np.uint8)
    D = reference.edit_table(p, t)
    for b in range(3):
        np.testing.assert_array_equal(D[b], dp(p[b], t[b]))


def cost_of(ops, read, ref):
    """(read consumed, ref consumed, edits) of a valid op list; raises on
    an op that does not fit."""
    i = j = cost = 0
    for op in ops:
        if op == reference.OP_MATCH:
            assert read[i] == ref[j]
            i, j = i + 1, j + 1
        elif op == reference.OP_SUBST:
            assert read[i] != ref[j]
            i, j, cost = i + 1, j + 1, cost + 1
        elif op == reference.OP_INS:
            i, cost = i + 1, cost + 1
        else:
            j, cost = j + 1, cost + 1
    return i, j, cost


GEO = reference.Geometry(W=64, O=24, k=12, rescue_rounds=1)


def test_identical_pair_is_all_matches():
    read = np.random.default_rng(1).integers(0, 4, 300, dtype=np.uint8)
    (rec,) = reference.align([read], [read.copy()], GEO)
    assert rec["ok"] and rec["dist"] == 0 and rec["k_used"] == 12
    assert rec["cigar"] == "300="


def test_one_substitution():
    read = np.random.default_rng(2).integers(0, 4, 150, dtype=np.uint8)
    ref = read.copy()
    ref[70] = (ref[70] + 1) % 4
    (rec,) = reference.align([read], [ref], GEO)
    assert rec["dist"] == 1 and rec["cigar"] == "70=1X79="


@pytest.mark.parametrize("err,n", [(0.01, 150), (0.10, 600), (0.15, 900)])
def test_records_are_valid_alignments(err, n):
    g = simulate.genome(100_000, simulate.rng_for(3, 0))
    pairs = simulate.reads(g, 24, n, err, 0.4, 0.35, 0.25,
                           simulate.rng_for(3, 1))
    recs = reference.align([r for r, _ in pairs], [f for _, f in pairs], GEO)
    assert sum(r["ok"] for r in recs) >= 20
    for (read, ref), rec in zip(pairs, recs):
        if not rec["ok"]:
            continue
        i, j, cost = cost_of(rec["ops"], read, ref)
        assert (i, j) == (len(read), len(ref)) == (rec["read_consumed"],
                                                   rec["ref_consumed"])
        assert cost == rec["dist"] >= dp(read, ref)[-1, -1]
        assert rec["cigar"] == reference.cigar_string(rec["ops"])


def test_rescue_rung_and_failure():
    g = simulate.genome(100_000, simulate.rng_for(4, 0))
    noisy = simulate.reads(g, 8, 600, 0.15, 0.4, 0.35, 0.25,
                           simulate.rng_for(4, 1))
    recs = reference.align([r for r, _ in noisy], [f for _, f in noisy], GEO)
    assert any(r["k_used"] == 24 for r in recs)
    junk = simulate.genome(300, simulate.rng_for(4, 2))
    (bad,) = reference.align([junk], [junk[::-1].copy()], GEO)
    assert not bad["ok"] and bad["cigar"] == "" and bad["dist"] == 0


def test_simulated_reads_have_the_asked_length_and_span():
    g = simulate.genome(50_000, simulate.rng_for(5, 0))
    for read, seg in simulate.reads(g, 16, 500, 0.1, 0.4, 0.35, 0.25,
                                    simulate.rng_for(5, 1)):
        assert len(read) == 500 and 400 < len(seg) < 600
        assert read.max() < 4 and seg.max() < 4


def test_simulator_is_seeded():
    cfg = {"genome_bp": 20_000, "reads": {
        "read_len": 150, "error_rate": 0.01, "sub_frac": 0.8,
        "ins_frac": 0.1, "del_frac": 0.1}}
    a = simulate.pool(cfg, 2**33 + 5, 8)[1]
    b = simulate.pool(cfg, 2**33 + 5, 8)[1]
    c = simulate.pool(cfg, 2**33 + 6, 8)[1]
    assert all(np.array_equal(x[0], y[0]) for x, y in zip(a, b))
    assert not all(np.array_equal(x[0], y[0]) for x, y in zip(a, c))
