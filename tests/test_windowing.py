"""Long-read windowed alignment: validity, accuracy vs full DP, variants.

The simulated read set and the per-variant alignment results are session-
scoped fixtures (tests/conftest.py): each aligner config is jitted and run
once, shared by every test below."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.aligner import GenASMAligner
from repro.core.config import AlignerConfig
from repro.core.oracle import levenshtein, validate_cigar
from repro.core.windowing import (SENTINEL_READ, SENTINEL_REF, align_pairs,
                                  align_pairs_rescued, n_main_windows,
                                  pad_geometry, rescue_schedule)
from repro.data.genome import ReadSimConfig, simulate_reads, synth_genome

CFG_BAND = AlignerConfig(W=64, O=24, k=12, store="band", early_term=True)
CFG_EDGES = AlignerConfig(W=64, O=24, k=12, store="edges4", early_term=False)
CFG_AND = AlignerConfig(W=64, O=24, k=12, store="and", early_term=True)


@pytest.mark.parametrize("cfg", [
    pytest.param(CFG_BAND, id="band"),
    # edges4/and ride nightly: tier-1 covers the store-mode equivalence at
    # window scale via test_kernel_fused/test_genasm_tb (W=32), and the
    # W=64 edges4 fill is the slowest single compile in the suite
    pytest.param(CFG_EDGES, id="edges4", marks=pytest.mark.slow),
    pytest.param(CFG_AND, id="and", marks=pytest.mark.slow),
])
def test_windowed_alignment_valid_all_variants(readset, aligned, cfg):
    res = aligned(cfg)
    assert not res.failed.any()
    for i in range(len(readset.reads)):
        validate_cigar(readset.reads[i], readset.ref_segments[i],
                       res.ops[i], expected_dist=res.dist[i])


@pytest.mark.slow
def test_improved_equals_unimproved_distances(aligned):
    """The paper's improvements change memory traffic, not results.
    (@slow with the edges4 variant above — it triggers the same compile.)"""
    assert list(aligned(CFG_BAND).dist) == list(aligned(CFG_EDGES).dist)


def test_windowed_distance_near_optimal(readset, aligned):
    """Windowed alignment is a heuristic >= true edit distance; with W=64
    O=24 on 8% error reads it should be within a few percent."""
    res = aligned(CFG_BAND)
    for i in range(3):
        ed = levenshtein(readset.reads[i], readset.ref_segments[i])
        assert res.dist[i] >= ed
        assert res.dist[i] <= ed * 1.08 + 3


@pytest.mark.slow
def test_rescue_on_high_error_pair(rng):
    """A pair exceeding k in some window gets rescued with doubled k.
    (@slow: a W=64 ladder compile; tier-1 rescue semantics live in
    tests/test_rescue.py at W=16.)"""
    g = synth_genome(20_000, seed=21)
    rs = simulate_reads(g, 2, ReadSimConfig(read_len=200, error_rate=0.20,
                                            seed=22))
    al = GenASMAligner(AlignerConfig(W=64, O=24, k=8), rescue_rounds=1)
    res = al.align(rs.reads, rs.ref_segments)
    assert (res.k_used[~res.failed] >= 8).all()
    for i in range(len(rs.reads)):
        if not res.failed[i]:
            validate_cigar(rs.reads[i], rs.ref_segments[i], res.ops[i],
                           expected_dist=res.dist[i])
    assert res.failed.sum() <= 1  # most should rescue at k=16


def test_decoy_pairs_fail(readset):
    """The reads against unrelated reference segments must fail (same
    window geometry as the shared readset -> reuses its compile)."""
    g = synth_genome(50_000, seed=31)
    reads = readset.reads[:2]
    decoys = [g[40_000:40_000 + len(s)] for s in readset.ref_segments[:2]]
    al = GenASMAligner(CFG_BAND, rescue_rounds=0)
    res = al.align(reads, decoys)
    assert res.failed.all()


# ---- the window loop ends at the last active lane -----------------------
#
# align_pairs bounds its main-window loop by the length class's window
# count but stops as soon as no lane is active (every read has <= W
# characters left or has failed).  At W=16, O=6 (stride 10) a batch of
# reads under 50 bp in a 128 bucket would otherwise run 12 main steps.

CFG_LOOP = AlignerConfig(W=16, O=6, k=2)
BUCKET = 128


def _loop_cfg(backend):
    return dataclasses.replace(CFG_LOOP, backend=backend)


def _pad_to(reads, refs, cfg, max_read_len, rescue_rounds=0):
    """Sentinel-pad a batch for align_pairs at the length class
    `max_read_len` (>= the longest read)."""
    Lr, Lf = pad_geometry(cfg, max_read_len, max(len(f) for f in refs),
                          rescue_rounds)
    rpad = np.full((len(reads), Lr), SENTINEL_READ, np.uint8)
    fpad = np.full((len(refs), Lf), SENTINEL_REF, np.uint8)
    for i, (r, f) in enumerate(zip(reads, refs)):
        rpad[i, :len(r)] = r
        fpad[i, :len(f)] = f
    lens = lambda xs: np.array([len(x) for x in xs], np.int32)  # noqa: E731
    return rpad, lens(reads), fpad, lens(refs)


def _subs(seq, at):
    out = seq.copy()
    out[at] = (out[at] + 1) % 4
    return out


def _main(read_len):
    return n_main_windows(read_len, CFG_LOOP)


@pytest.mark.parametrize("backend", ["jnp", "pallas_fused"])
def test_loop_stops_at_longest_read_below_bucket(backend):
    """Reads far shorter than their length class: the loop runs the
    longest read's windows, not the bucket's, and every output equals the
    exact-shape host loop's and is a valid alignment of the whole pair."""
    cfg = _loop_cfg(backend)
    rng = np.random.default_rng(41)
    refs = [rng.integers(0, 4, n).astype(np.uint8) for n in (21, 28, 35, 44)]
    # at most one substitution in any 16 bp window: k = 2 solves them all
    reads = [_subs(f, np.arange(5, len(f), 17)) for f in refs]
    out = align_pairs(*map(jnp.asarray, _pad_to(reads, refs, cfg, BUCKET)),
                      cfg=cfg, max_read_len=BUCKET)
    assert not np.asarray(out["failed"]).any()
    assert int(out["window_steps"]) == max(map(_main, (21, 28, 35, 44))) + 1
    assert int(out["window_steps"]) < _main(BUCKET) + 1
    host = GenASMAligner(cfg, rescue_rounds=0,
                         rescue_mode="host").align(reads, refs)
    np.testing.assert_array_equal(np.asarray(out["dist"]), host.dist)
    np.testing.assert_array_equal(np.asarray(out["read_consumed"]),
                                  host.read_consumed)
    np.testing.assert_array_equal(np.asarray(out["ref_consumed"]),
                                  host.ref_consumed)
    for i, (r, f) in enumerate(zip(reads, refs)):
        ops = np.asarray(out["ops"])[i, :int(out["n_ops"][i])]
        np.testing.assert_array_equal(ops, host.ops[i])
        validate_cigar(r, f, ops, expected_dist=int(out["dist"][i]))
        assert int(out["dist"][i]) >= levenshtein(r, f)


@pytest.mark.parametrize("backend", ["jnp", "pallas_fused"])
def test_loop_stops_after_last_failure(backend):
    """Every lane fails at k before its read ends: the loop stops after the
    step in which the last lane failed, and each lane's merged partial
    progress (committed ops, dist, consumption) equals what the exact-shape
    align_pairs call of the host rescue loop reports."""
    cfg = _loop_cfg(backend)
    rng = np.random.default_rng(43)
    reads, refs = [], []
    for n, good in ((80, 12), (60, 31), (45, 0), (70, 22)):
        # the read follows its ref for `good` bases, then is unrelated
        f = rng.integers(0, 4, n + 10).astype(np.uint8)
        reads.append(np.concatenate(
            [f[:good], rng.integers(0, 4, n - good).astype(np.uint8)]))
        refs.append(f)
    args = tuple(map(jnp.asarray, _pad_to(reads, refs, cfg, BUCKET)))
    out = align_pairs_rescued(*args, cfg=cfg, max_read_len=BUCKET,
                              rescue_rounds=0)
    failed = np.asarray(out["failed"])
    assert failed.all()
    # a lane that failed in window f committed f windows of `stride` reads
    rcon = np.asarray(out["read_consumed"])
    assert (rcon % cfg.stride == 0).all()
    last_failure = int(rcon.max()) // cfg.stride
    assert int(out["window_steps"]) == last_failure + 1 + 1
    assert int(out["window_steps"]) < _main(80) + 1
    exact = align_pairs(*map(jnp.asarray, _pad_to(reads, refs, cfg, 80)),
                        cfg=cfg, max_read_len=80)
    for key in ("n_ops", "dist", "failed", "read_consumed", "ref_consumed"):
        np.testing.assert_array_equal(np.asarray(out[key]),
                                      np.asarray(exact[key]), err_msg=key)
    host = GenASMAligner(cfg, rescue_rounds=0,
                         rescue_mode="host").align(reads, refs)
    np.testing.assert_array_equal(failed, host.failed)
    fcon = np.asarray(out["ref_consumed"])
    for i, (r, f) in enumerate(zip(reads, refs)):
        n = int(out["n_ops"][i])
        ops = np.asarray(out["ops"])[i, :n]
        np.testing.assert_array_equal(ops, np.asarray(exact["ops"])[i, :n])
        validate_cigar(r[:rcon[i]], f[:fcon[i]], ops,
                       expected_dist=int(out["dist"][i]))


@pytest.mark.parametrize("backend", ["jnp", "pallas_fused"])
def test_rescued_window_steps_sum_each_rungs_own(backend):
    """Under the on-device ladder the base rung runs every window step and
    its tail, and a higher rung only the steps in which some lane's window
    failed below it: rung_window_steps holds each rung's count and
    window_steps their sum.  Lanes: a clean 20 bp read, a 44 bp read with
    three substitutions in its first window (k = 2 fails it there, k = 4
    solves it) and an unrelated decoy (fails its first window at both)."""
    cfg = _loop_cfg(backend)
    rng = np.random.default_rng(47)
    refs = [rng.integers(0, 4, n).astype(np.uint8) for n in (20, 44, 30)]
    reads = [refs[0].copy(), _subs(refs[1], [2, 6, 10]),
             rng.integers(0, 4, 30).astype(np.uint8)]
    args = tuple(map(jnp.asarray,
                     _pad_to(reads, refs, cfg, BUCKET, rescue_rounds=1)))
    out = align_pairs_rescued(*args, cfg=cfg, max_read_len=BUCKET,
                              rescue_rounds=1)
    assert np.asarray(out["failed"]).tolist() == [False, False, True]
    assert np.asarray(out["k_used"]).tolist() == [2, 4, 0]
    # k = 2: every step to the 44 bp read's last window, and the tail;
    # k = 4: the first step only, where the 44 bp read and the decoy
    # failed at k = 2; no tail needed it
    rung_steps = [max(_main(20), _main(44), 1) + 1, 1]
    assert np.asarray(out["rung_window_steps"]).tolist() == rung_steps
    assert int(out["window_steps"]) == sum(rung_steps)
    assert int(out["window_steps"]) < _main(BUCKET) + 1
    # the 44 bp read commits its first window at k = 4, the rest at k = 2
    assert np.asarray(out["rung_commits"])[:2].tolist() == [
        [_main(20) + 1, 0], [_main(44), 1]]
    host = GenASMAligner(cfg, rescue_rounds=1,
                         rescue_mode="host").align(reads, refs)
    np.testing.assert_array_equal(np.asarray(out["dist"]), host.dist)
    np.testing.assert_array_equal(np.asarray(out["k_used"]), host.k_used)
    for i in range(2):
        np.testing.assert_array_equal(
            np.asarray(out["ops"])[i, :int(out["n_ops"][i])], host.ops[i])


@pytest.mark.parametrize("backend", ["jnp", "pallas_fused"])
def test_top_rung_runs_only_the_steps_that_need_it(backend):
    """Closed form of rung_window_steps on the ladder 2, 4, 8: one window
    of one read needs k = 8, so the k = 8 rung runs one step (the k = 4
    rung that step too, where it fails), plus one more at k = 4 when a
    second read's tail alone needs k = 4.  Window j covers read
    [10j, 10j + 16): five substitutions at 26..30 put d = 5 in window 2
    and one substitution in window 3."""
    cfg = _loop_cfg(backend)
    rng = np.random.default_rng(53)
    refs = [rng.integers(0, 4, n).astype(np.uint8) for n in (60, 60, 36)]
    burst = _subs(refs[1], np.arange(26, 31))
    base_steps = max(_main(60), _main(36)) + 1
    # the third read clean, then with three substitutions past its last
    # main window (it ends at 26): same shapes, so one compile
    for tail_read, k3, rung_steps in (
            (refs[2].copy(), 2, [base_steps, 1, 1]),
            (_subs(refs[2], [28, 31, 34]), 4, [base_steps, 1 + 1, 1])):
        reads = [refs[0].copy(), burst, tail_read]
        args = tuple(map(jnp.asarray,
                         _pad_to(reads, refs, cfg, BUCKET, rescue_rounds=2)))
        out = align_pairs_rescued(*args, cfg=cfg, max_read_len=BUCKET,
                                  rescue_rounds=2)
        assert not np.asarray(out["failed"]).any()
        assert np.asarray(out["k_used"]).tolist() == [2, 8, k3]
        assert np.asarray(out["rung_window_steps"]).tolist() == rung_steps
        assert int(out["window_steps"]) == sum(rung_steps)
        host = GenASMAligner(cfg, rescue_rounds=2,
                             rescue_mode="host").align(reads, refs)
        np.testing.assert_array_equal(np.asarray(out["dist"]), host.dist)
        np.testing.assert_array_equal(np.asarray(out["k_used"]), host.k_used)
        for i in range(3):
            np.testing.assert_array_equal(
                np.asarray(out["ops"])[i, :int(out["n_ops"][i])], host.ops[i])
