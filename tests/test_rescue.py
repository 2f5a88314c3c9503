"""Rescue semantics: the on-device k-doubling ladder vs the host loop.

Properties enforced:
  * rescue_rounds=0 is exactly plain align_pairs (plus k_used bookkeeping),
  * k_used is minimal on the k-doubling ladder (the previous rung fails),
  * failed / k_used / ops agree between host-loop and on-device rescue,
    which escalates per window, wherever a lane's windows escalate (one
    window, two far apart, the tail alone, failure at the top rung) and
    with padding lanes,
  * lanes are independent: permuting the batch permutes the results
    (escalating one lane's window never leaks state across lanes),
  * the on-device path performs exactly one upload and one download per
    batch, independent of how many rescue rounds run (the zero
    per-round-round-trip claim); the host loop pays per executed round;
  * the served ladder of the default geometry reaches k = 48 and agrees
    with the benchmark's plain reference (``bench/reference.py``) there.
"""
import os
import sys

import numpy as np
import pytest
import jax.numpy as jnp

from repro.core import transfer
from repro.core.aligner import GenASMAligner
from repro.core.config import AlignerConfig
from repro.core.oracle import validate_cigar
from repro.core.windowing import (SENTINEL_READ, SENTINEL_REF, align_pairs,
                                  align_pairs_rescued, n_main_windows,
                                  rescue_schedule, self_tail_width)

CFG = AlignerConfig(W=16, O=6, k=2)
ROUNDS = 2                                     # ladder [2, 4, 8]


def _mk_corpus(seed=5, n=8, read_len=36):
    """Error gradient (clean ... heavy-indel) + one decoy: spans the whole
    k-doubling ladder, including never-solved lanes."""
    from tests.test_differential import _walk_read

    rng = np.random.default_rng(seed)
    reads, refs = [], []
    for i in range(n):
        ref = rng.integers(0, 4, int(read_len * 1.3) + 8).astype(np.uint8)
        err = (0.0, 0.05, 0.1, 0.18, 0.28, 0.4)[i % 6]
        read, span = _walk_read(ref, rng, err, (30, 35, 35), read_len)
        reads.append(read)
        refs.append(ref[:span].copy())
    # decoy: unrelated ref of plausible length -> fails the whole ladder
    reads.append(reads[0].copy())
    refs.append(rng.integers(0, 4, len(refs[0])).astype(np.uint8))
    return reads, refs


def _pad_batch(reads, refs, cfg, rescue_rounds):
    wt = self_tail_width(rescue_schedule(cfg, rescue_rounds)[-1])
    max_r = max(len(r) for r in reads)
    B = len(reads)
    rpad = np.full((B, max_r + cfg.W + 1), SENTINEL_READ, np.uint8)
    fpad = np.full((B, max(len(f) for f in refs) + cfg.W + wt + 1),
                   SENTINEL_REF, np.uint8)
    rlen = np.zeros(B, np.int32)
    flen = np.zeros(B, np.int32)
    for i, (r, f) in enumerate(zip(reads, refs)):
        rpad[i, :len(r)] = r
        rlen[i] = len(r)
        fpad[i, :len(f)] = f
        flen[i] = len(f)
    return (jnp.asarray(rpad), jnp.asarray(rlen), jnp.asarray(fpad),
            jnp.asarray(flen)), max_r


@pytest.fixture(scope="module")
def corpus():
    return _mk_corpus()


@pytest.fixture(scope="module")
def dev_res(corpus):
    return GenASMAligner(CFG, rescue_rounds=ROUNDS).align(*corpus)


@pytest.fixture(scope="module")
def host_res(corpus):
    return GenASMAligner(CFG, rescue_rounds=ROUNDS,
                         rescue_mode="host").align(*corpus)


def test_rescue_schedule_doubles_and_caps():
    ks = [c.k for c in rescue_schedule(CFG, 5)]
    assert ks == [2, 4, 8, 15]                 # doubled, capped at W-1, deduped
    assert [c.k for c in rescue_schedule(CFG, 0)] == [2]
    capped = AlignerConfig(W=16, O=6, k=15)
    assert [c.k for c in rescue_schedule(capped, 3)] == [15]


def test_rescue_rounds_zero_equals_plain_align_pairs(corpus):
    reads, refs = corpus
    args, max_r = _pad_batch(reads, refs, CFG, 0)
    plain = align_pairs(*args, cfg=CFG, max_read_len=max_r)
    resc = align_pairs_rescued(*args, cfg=CFG, max_read_len=max_r,
                               rescue_rounds=0)
    for key in ("n_ops", "dist", "failed", "read_consumed", "ref_consumed"):
        np.testing.assert_array_equal(np.asarray(resc[key]),
                                      np.asarray(plain[key]), err_msg=key)
    np.testing.assert_array_equal(np.asarray(resc["ops"]),
                                  np.asarray(plain["ops"]))
    failed = np.asarray(plain["failed"])
    np.testing.assert_array_equal(np.asarray(resc["k_used"]),
                                  np.where(failed, 0, CFG.k))
    assert int(resc["n_rounds"]) == 1


def test_k_used_minimal_on_ladder(corpus, dev_res):
    """Solving at k_used implies failing at the previous ladder rung.
    Lanes are grouped by rung so each distinct prev-k compiles one batched
    align instead of one per lane."""
    reads, refs = corpus
    ks = [c.k for c in rescue_schedule(CFG, ROUNDS)]
    rescued = [i for i in range(len(reads))
               if not dev_res.failed[i] and dev_res.k_used[i] > CFG.k]
    assert rescued, "corpus must exercise the ladder"
    by_rung = {}
    for i in rescued:
        prev_k = ks[ks.index(int(dev_res.k_used[i])) - 1]
        by_rung.setdefault(prev_k, []).append(i)
        validate_cigar(reads[i], refs[i], dev_res.ops[i],
                       expected_dist=dev_res.dist[i])
    for prev_k, lanes in by_rung.items():
        again = GenASMAligner(
            AlignerConfig(W=CFG.W, O=CFG.O, k=prev_k),
            rescue_rounds=0).align([reads[i] for i in lanes],
                                   [refs[i] for i in lanes])
        assert again.failed.all(), \
            f"lanes {lanes}: k_used minimal claim broken at k={prev_k}"


def test_failed_flag_agrees_host_vs_device(corpus, dev_res, host_res):
    np.testing.assert_array_equal(dev_res.failed, host_res.failed)
    np.testing.assert_array_equal(dev_res.k_used, host_res.k_used)
    np.testing.assert_array_equal(dev_res.dist, host_res.dist)
    for a, b in zip(dev_res.ops, host_res.ops):
        np.testing.assert_array_equal(a, b)
    assert dev_res.failed[-1]                  # the decoy never aligns
    assert not dev_res.failed[0]               # the clean lane always does


def test_gpu_backend_rescue_ladder_bit_identical(corpus, dev_res):
    """The full k-doubling ladder under backend='pallas_gpu' (Triton
    lowering, interpret mode here) == the jnp on-device ladder, bit for
    bit — including the decoy lane that fails every rung."""
    gpu = GenASMAligner(CFG, rescue_rounds=ROUNDS,
                        backend="pallas_gpu").align(*corpus)
    np.testing.assert_array_equal(gpu.failed, dev_res.failed)
    np.testing.assert_array_equal(gpu.k_used, dev_res.k_used)
    np.testing.assert_array_equal(gpu.dist, dev_res.dist)
    assert gpu.cigars == dev_res.cigars
    for a, b in zip(gpu.ops, dev_res.ops):
        np.testing.assert_array_equal(a, b)


def test_lane_independence_under_permutation(corpus, dev_res):
    """Permuting the batch permutes the results: the rescue mask freezes
    solved lanes without leaking state across lanes.  Same shapes/config as
    dev_res, so the permuted align reuses its compile."""
    reads, refs = corpus
    perm = np.random.default_rng(9).permutation(len(reads))
    shuf = GenASMAligner(CFG, rescue_rounds=ROUNDS).align(
        [reads[i] for i in perm], [refs[i] for i in perm])
    for loc, glob in enumerate(perm):
        assert shuf.dist[loc] == dev_res.dist[glob]
        assert shuf.failed[loc] == dev_res.failed[glob]
        assert shuf.k_used[loc] == dev_res.k_used[glob]
        np.testing.assert_array_equal(shuf.ops[loc], dev_res.ops[glob])


def test_session_bucket_rescue_bit_identical_to_host_loop(corpus, host_res):
    """The rescue-efficiency item (ROADMAP): repro.api.AlignSession's
    'bucket' rescue gathers still-failed lanes and compacts them into the
    next-smaller length/lane bucket per k-doubling rung — solved lanes'
    windows are never recomputed and shapes stay bucket-stable
    (unlike the host loop, which re-traces ragged subsets).  Must be
    bit-identical per lane to rescue_mode='host'."""
    from repro.api import plan
    reads, refs = corpus
    # cache='private': the lowerings count below must not see executables
    # other suites put in the process-shared store
    s = plan(CFG, rescue_rounds=ROUNDS, rescue_mode="bucket",
             batch_lanes=len(reads), cache="private")
    res = s.align(reads, refs)
    np.testing.assert_array_equal(res.failed, host_res.failed)
    np.testing.assert_array_equal(res.dist, host_res.dist)
    np.testing.assert_array_equal(res.k_used, host_res.k_used)
    np.testing.assert_array_equal(res.read_consumed, host_res.read_consumed)
    np.testing.assert_array_equal(res.ref_consumed, host_res.ref_consumed)
    assert res.cigars == host_res.cigars
    for a, b in zip(res.ops, host_res.ops):
        np.testing.assert_array_equal(a, b)
    # compaction really happened: the decoy keeps every ladder rung alive,
    # and each rescue dispatch ran on a SMALLER lane class than round 0
    st = s.stats
    assert st["rescue_dispatches"] == ROUNDS
    assert st["rescue_lanes"] < st["rescue_dispatches"] * st["lanes"]
    # each rung's executable is its own cached bucket (round 0 + 2 rungs)
    assert s.cache.stats()["lowerings"] == 1 + ROUNDS


@pytest.mark.slow
def test_device_rescue_zero_per_round_roundtrips_fused_backend(corpus):
    """The transfer-counting acceptance check: with the fused backend the
    whole multi-round rescue costs exactly one host->device upload and one
    device->host download — zero per-round round-trips — while the host
    loop pays one of each per executed round.  (@slow: two fresh fused
    ladder compiles; tier-1 keeps the 1x/1x assertion in
    tests/test_multidevice.py where it rides the sharded parity run.)"""
    reads, refs = corpus
    reads, refs = reads[:4] + [reads[-1]], refs[:4] + [refs[-1]]
    transfer.reset()
    GenASMAligner(CFG, rescue_rounds=1,
                  backend="pallas_fused").align(reads, refs)
    s = transfer.stats()
    assert (s.h2d_calls, s.d2h_calls) == (1, 1)

    transfer.reset()
    GenASMAligner(CFG, rescue_rounds=1, rescue_mode="host",
                  backend="pallas_fused").align(reads, refs)
    s_host = transfer.stats()
    # the decoy fails k=2 and k=4, so both ladder rounds execute
    assert s_host.d2h_calls == 2
    assert s_host.h2d_calls == 2


def _ont15_pairs(n=6, read_len=200):
    """Seeded 15%-error pairs with the ONT error split (23:31:46), and one
    read with a burst of 30 substitutions in its second window: more than
    k = 24 allows there, so only the k = 48 rung aligns it."""
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from bench import simulate
    gen = simulate.genome(20_000, simulate.rng_for(16, 0))
    pairs = simulate.reads(gen, n, read_len, 0.15, 23, 31, 46,
                           simulate.rng_for(16, 1))
    read, ref = pairs[0]
    rng = simulate.rng_for(16, 2)
    burst = rng.choice(np.arange(44, 100), 30, replace=False)
    read = read.copy()
    read[burst] = (read[burst] + rng.integers(1, 4, 30)) % 4
    pairs[0] = (read, ref)
    return pairs


@pytest.mark.parametrize("backend", ["jnp", "pallas_fused"])
def test_served_ladder_reaches_k48_and_matches_the_reference(backend):
    """plan(rescue_mode='device', rescue_rounds=2) at W=64 O=24 k=12 runs
    the ladder 12, 24, 48 (interpret-mode kernels for 'pallas_fused'):
    the burst read is solved at k = 48, and every record equals the plain
    reference's (dist, k_used, CIGAR ops, consumed lengths)."""
    from bench import reference
    from repro.api import plan
    pairs = _ont15_pairs()
    want = reference.align([r for r, _ in pairs], [f for _, f in pairs],
                           reference.Geometry(W=64, O=24, k=12,
                                              rescue_rounds=2))
    cfg = AlignerConfig(W=64, O=24, k=12, backend=backend, lane_tile=8)
    with plan(cfg, rescue_rounds=2, rescue_mode="device",
              batch_lanes=8) as s:
        futs = [s.submit(r, f) for r, f in pairs]
        s.flush()
        got = [fut.result() for fut in futs]
    assert got[0]["k_used"] == want[0]["k_used"] == 48
    assert {w["k_used"] for w in want[1:]} <= {12, 24}
    for g, w in zip(got, want):
        for field in ("ok", "dist", "k_used", "cigar", "read_consumed",
                      "ref_consumed"):
            assert g[field] == w[field], field
        np.testing.assert_array_equal(np.asarray(g["ops"]), w["ops"])


def _subs(seq, at):
    out = seq.copy()
    out[at] = (out[at] + 1) % 4
    return out


def _escalation_pairs():
    """Pairs for the ladder 2, 4, 8 at W=16 O=6, each escalating in a known
    place.  Window j covers read [10j, 10j + 16), so a substitution in
    [10j + 6, 10j + 10) lies in window j alone, and one past the last
    main window's end lies in the tail alone.  Returns (reads, refs, the
    rung_commits row each lane should report; None for a failed lane)."""
    rng = np.random.default_rng(17)
    mk = lambda n: rng.integers(0, 4, n).astype(np.uint8)  # noqa: E731
    cases = []
    f = mk(60)                                  # clean
    cases.append((f.copy(), f, [6, 0, 0]))
    f = mk(60)                                  # window 2 needs k = 4
    cases.append((_subs(f, [26, 27, 29]), f, [5, 1, 0]))
    f = mk(90)                                  # windows 2 and 6 need k = 4
    cases.append((_subs(f, [26, 27, 29, 66, 67, 69]), f, [7, 2, 0]))
    f = mk(60)                                  # window 2 needs k = 8
    cases.append((_subs(f, [26, 27, 28, 29, 30]), f, [5, 0, 1]))
    f = mk(36)                                  # the tail alone needs k = 4
    cases.append((_subs(f, [28, 31, 34]), f, [2, 1, 0]))
    # five bases inserted past the last main window: the tail is shorter
    # than m - 2k allows at k = 2, holds 5 edits at k = 4, solves at k = 8
    f = mk(50)
    cases.append((np.concatenate([f[:46], mk(5), f[46:]]), f, [4, 0, 1]))
    # fails at the top rung mid-read: follows its ref for 25 bases
    f = mk(70)
    cases.append((np.concatenate([f[:25], mk(45)]), f, None))
    # the reference runs on 60 bases past the read: the tail's text is
    # wider than W + 4k at every rung, so the lane fails after its windows
    f = mk(100)
    cases.append((f[:40].copy(), f, None))
    reads, refs, commits = zip(*cases)
    return list(reads), list(refs), list(commits)


@pytest.mark.parametrize("backend", ["jnp", "pallas_fused"])
def test_per_window_escalation_bit_identical_to_host_loop(backend):
    """The on-device ladder escalates the failing window, not the read:
    each lane commits every window at the first rung that solves it and
    ends up bit-identical to the host loop, which reruns the whole read at
    each k.  Lanes need a higher rung in one window, in two windows far
    apart, in the tail alone (through the tail's m - 2k bound too), or fail
    at the top rung mid-read or in a tail wider than W + 4k; a failed
    lane keeps the partial progress the top rung alone would report."""
    cfg = CFG.replace(backend=backend, lane_tile=8)
    reads, refs, commits = _escalation_pairs()
    args, max_r = _pad_batch(reads, refs, cfg, ROUNDS)
    out = align_pairs_rescued(*args, cfg=cfg, max_read_len=max_r,
                              rescue_rounds=ROUNDS)
    host = GenASMAligner(cfg, rescue_rounds=ROUNDS,
                         rescue_mode="host").align(reads, refs)
    failed = np.asarray(out["failed"])
    np.testing.assert_array_equal(failed, host.failed)
    assert failed.tolist() == [c is None for c in commits]
    np.testing.assert_array_equal(np.asarray(out["k_used"]), host.k_used)
    assert np.asarray(out["k_used"]).tolist() == [2, 4, 4, 8, 4, 8, 0, 0]
    rc = np.asarray(out["rung_commits"])
    ops = np.asarray(out["ops"])
    for i in np.flatnonzero(~failed):
        assert rc[i].tolist() == commits[i]
        assert rc[i].sum() == n_main_windows(len(reads[i]), cfg) + 1
        assert int(out["dist"][i]) == host.dist[i]
        assert int(out["read_consumed"][i]) == host.read_consumed[i]
        assert int(out["ref_consumed"][i]) == host.ref_consumed[i]
        np.testing.assert_array_equal(ops[i, :int(out["n_ops"][i])],
                                      host.ops[i])
    top = align_pairs(*args, cfg=rescue_schedule(cfg, ROUNDS)[-1],
                      max_read_len=max_r)
    for i in np.flatnonzero(failed):
        for key in ("n_ops", "dist", "read_consumed", "ref_consumed"):
            assert int(out[key][i]) == int(top[key][i]), key
        n = int(out["n_ops"][i])
        assert n > 0                           # windows were committed
        np.testing.assert_array_equal(ops[i], np.asarray(top["ops"])[i])


@pytest.mark.parametrize("backend", ["jnp", "pallas_fused"])
def test_served_ladder_with_padding_lanes_matches_host_loop(backend):
    """The served on-device ladder over lane classes with padding lanes
    (the pairs fall into several length buckets, none filling its class):
    every record equals the host loop's."""
    from repro.api import plan
    cfg = CFG.replace(backend=backend, lane_tile=8)
    reads, refs, _ = _escalation_pairs()
    host = GenASMAligner(cfg, rescue_rounds=ROUNDS,
                         rescue_mode="host").align(reads, refs)
    with plan(cfg, rescue_rounds=ROUNDS, rescue_mode="device",
              batch_lanes=16, cache="private") as s:
        res = s.align(reads, refs)
        assert s.stats["pad_lanes"] > 0
    np.testing.assert_array_equal(res.failed, host.failed)
    np.testing.assert_array_equal(res.k_used, host.k_used)
    np.testing.assert_array_equal(res.dist, host.dist)
    np.testing.assert_array_equal(res.read_consumed, host.read_consumed)
    np.testing.assert_array_equal(res.ref_consumed, host.ref_consumed)
    assert res.cigars == host.cigars
    for a, b in zip(res.ops, host.ops):
        np.testing.assert_array_equal(a, b)
