"""Fused GenASM-DC+TB Pallas kernel: bit-identical to the jnp 'band' path,
CIGAR-valid vs the classic DP oracle, consistent with all three jnp store
modes on the committed prefix, and correct through windowing + rescue."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.config import AlignerConfig
from repro.core.genasm import dc_dmajor, dc_jmajor
from repro.core.oracle import levenshtein, validate_cigar
from repro.core.cigar import ops_to_string
from repro.core.traceback import OP_NONE, traceback
from repro.kernels.ops import GPU_PLATFORMS, genasm_tb_fused_op
from tests.conftest import mutate_seq


def batch(rng, W, k, B):
    pats, txts, eds = [], [], []
    for _ in range(B):
        p = rng.integers(0, 4, W).astype(np.uint8)
        t = mutate_seq(p, int(rng.integers(0, k + 2)), rng, extend_to=W)
        pats.append(p); txts.append(t); eds.append(levenshtein(p, t))
    return np.stack(pats), np.stack(txts), eds


def jnp_band_tb(pat, txt, cfg, commit_limit, max_ops, max_steps):
    B = pat.shape[0]
    wl = jnp.full((B,), cfg.W, jnp.int32)
    res = dc_dmajor(pat, txt, cfg=cfg)
    tb = traceback(res.store, pat, txt, wl, wl, res.dist,
                   jnp.int32(commit_limit), cfg=cfg, mode="band",
                   max_ops=max_ops, max_steps=max_steps)
    return res, tb


@pytest.mark.parametrize("W,k,tile,B", [
    (16, 3, 4, 4),
    (32, 15, 8, 8),    # nwb = 2: two-word band windows
    (32, 11, 4, 5),    # B not a multiple of tile
])
def test_fused_bit_identical_to_jnp_band(W, k, tile, B, rng):
    """The acceptance sweep: fused ops/dist == jnp band path, bit for bit."""
    cfg = AlignerConfig(W=W, O=max(1, W // 3), k=k)
    stride = cfg.stride
    max_ops, max_steps = cfg.tb_max_ops, cfg.tb_max_steps
    pats, txts, _ = batch(rng, W, k, B)
    pat, txt = jnp.array(pats), jnp.array(txts)
    res, tb = jnp_band_tb(pat, txt, cfg, stride, max_ops, max_steps)
    fz = genasm_tb_fused_op(pat, txt, cfg=cfg, commit_limit=stride,
                            max_ops=max_ops, max_steps=max_steps, tile=tile,
                            interpret=True)
    assert (np.array(fz["dist"]) == np.array(res.dist)).all()
    assert int(fz["levels"]) == int(res.levels_run)
    assert bool(np.array(fz["ok"]).all())
    for key in ("ops", "n_ops", "read_adv", "ref_adv", "cost", "d_final"):
        np.testing.assert_array_equal(np.array(fz[key]), np.array(tb[key]),
                                      err_msg=key)


def test_fused_cigars_optimal_vs_oracle(rng):
    """With a full-coverage band (ncb == W+1) and no commit limit the fused
    walk is a complete traceback; its CIGARs must be valid and optimal."""
    W, k, B = 16, 5, 8
    cfg = AlignerConfig(W=W, O=2, k=k)       # stride+k+margin > W+1
    assert cfg.ncols_band == W + 1
    max_ops, max_steps = 2 * W + k, 2 * W + k + 4
    pats, txts, eds = batch(rng, W, k, B)
    fz = genasm_tb_fused_op(jnp.array(pats), jnp.array(txts), cfg=cfg,
                            commit_limit=10**6, max_ops=max_ops,
                            max_steps=max_steps, tile=4, interpret=True)
    assert bool(np.array(fz["ok"]).all())
    n_solved = 0
    for b in range(B):
        if eds[b] <= k:
            assert int(fz["dist"][b]) == eds[b]
            ops = np.array(fz["ops"])[b][:int(fz["n_ops"][b])]
            assert not (ops == OP_NONE).any()
            # ops are front-first over REVERSED windows
            validate_cigar(pats[b][::-1], txts[b][::-1], ops,
                           expected_dist=eds[b])
            n_solved += 1
    assert n_solved > 0


def test_fused_matches_all_jnp_store_modes_committed(rng):
    """Committed-prefix ops agree across edges4/and/band jnp modes and the
    fused kernel (the paper's equivalence claim, extended on-chip)."""
    W, k, B = 32, 9, 8
    stride = W - W // 3
    max_ops, max_steps = stride + k + 2, stride + k + 4
    pats, txts, eds = batch(rng, W, k, B)
    pat, txt = jnp.array(pats), jnp.array(txts)
    wl = jnp.full((B,), W, jnp.int32)
    committed = {}
    for mode in ("edges4", "and", "band"):
        cfg = AlignerConfig(W=W, O=W // 3, k=k, store=mode)
        if mode == "band":
            res = dc_dmajor(pat, txt, cfg=cfg)
        else:
            res = dc_jmajor(pat, txt, wl, wl, k=k, n=W, nw=cfg.nw, store=mode)
        tb = traceback(res.store, pat, txt, wl, wl, res.dist,
                       jnp.int32(stride), cfg=cfg, mode=mode,
                       max_ops=max_ops, max_steps=max_steps)
        committed[mode] = [
            ops_to_string(np.array(tb["ops"])[b][:int(tb["n_ops"][b])])
            if eds[b] <= k else None for b in range(B)]
    cfg = AlignerConfig(W=W, O=W // 3, k=k)
    fz = genasm_tb_fused_op(pat, txt, cfg=cfg, commit_limit=stride,
                            max_ops=max_ops, max_steps=max_steps, tile=8,
                            interpret=True)
    committed["fused"] = [
        ops_to_string(np.array(fz["ops"])[b][:int(fz["n_ops"][b])])
        if eds[b] <= k else None for b in range(B)]
    assert (committed["edges4"] == committed["and"] == committed["band"]
            == committed["fused"])


def test_fused_windowed_alignment_matches_jnp(rng):
    """pallas_fused through GenASMAligner + serve engine: equal to the jnp
    backend on clean reads."""
    from repro.core.aligner import GenASMAligner
    from repro.data.genome import ReadSimConfig, simulate_reads, synth_genome

    g = synth_genome(15_000, seed=77)
    rs = simulate_reads(g, 3, ReadSimConfig(read_len=120, error_rate=0.06,
                                            seed=78))
    cfg = AlignerConfig(W=32, O=12, k=8)
    # rescue_rounds=0: nothing here fails (asserted below), and skipping the
    # extra k-doubling round compiles keeps tier-1 fast; rescue through the
    # fused backend is covered by test_fused_rescue_doubles_k (slow) and
    # tests/test_rescue.py
    res_j = GenASMAligner(cfg, rescue_rounds=0).align(rs.reads,
                                                      rs.ref_segments)
    res_f = GenASMAligner(cfg, rescue_rounds=0, backend="pallas_fused").align(
        rs.reads, rs.ref_segments)
    assert not res_f.failed.any()
    assert list(res_j.dist) == list(res_f.dist)
    assert res_j.cigars == res_f.cigars


def _kernel_call(pats, txts, cfg, interpret=True, **kw):
    return genasm_tb_fused_op(jnp.array(pats), jnp.array(txts), cfg=cfg,
                              commit_limit=cfg.stride, max_ops=cfg.tb_max_ops,
                              max_steps=cfg.tb_max_steps, interpret=interpret,
                              **kw)


def test_fused_tile_grouping_invariance(rng):
    """Per-lane results must not depend on which problem tile a lane lands
    in (whole-tile early termination only changes how many levels run, and
    the walk never visits levels above a lane's own dist).  This is the
    property that makes sharded dispatch bit-identical: the mesh regroups
    lanes into per-device tiles (kernels.ops)."""
    cfg = AlignerConfig(W=16, O=6, k=4)
    pats, txts, _ = batch(rng, 16, 4, 16)
    a = _kernel_call(pats, txts, cfg, tile=4)
    b = _kernel_call(pats, txts, cfg, tile=16)
    for key in ("ops", "n_ops", "dist", "read_adv", "ref_adv", "cost"):
        np.testing.assert_array_equal(np.array(a[key]), np.array(b[key]),
                                      err_msg=key)


def _tail_batch(rng, cfg, B, wt):
    """Ragged reversed tails (sentinel-padded), incl. the edge lanes the
    band proof's clips must survive: empty pattern, empty text, both."""
    from repro.core.bitops import SENTINEL_PAT, SENTINEL_TEXT
    W, k = cfg.W, cfg.k
    pat = np.full((B, W), SENTINEL_PAT, np.uint8)
    txt = np.full((B, wt), SENTINEL_TEXT, np.uint8)
    ml = np.zeros(B, np.int32)
    nl = np.zeros(B, np.int32)
    edge = [(0, 3), (3, 0), (0, 0)]
    for b in range(B):
        if b < len(edge):
            m, n = edge[b]
        else:
            m = int(rng.integers(1, W + 1))
            n = int(np.clip(m + rng.integers(-k, k + 1), 1, wt))
        if m:
            p = rng.integers(0, 4, m).astype(np.uint8)
            pat[b, :m] = p[::-1]
        if n:
            t = mutate_seq(pat[b, :m][::-1].copy() if m else
                           rng.integers(0, 4, n).astype(np.uint8),
                           int(rng.integers(0, k + 1)), rng)[:n]
            if len(t) < n:
                t = np.concatenate(
                    [t, rng.integers(0, 4, n - len(t)).astype(np.uint8)])
            txt[b, :n] = t[::-1]
        ml[b], nl[b] = m, n
    return pat, txt, ml, nl


@pytest.mark.parametrize("W,O,k", [
    (64, 24, 12),   # headline geometry: band is a strict win (nwb < nw)
    (32, 10, 15),   # nwb = 2: two-word band windows
    (16, 6, 4),     # boundary: nwb == nw — band forced, no strict win
])
def test_tail_banded_bit_identical_to_full_store(W, O, k, rng):
    """The tentpole's bit-exactness bar at kernel level: the Scrooge-style
    banded tail store (per-lane diagonal DENT window, analytic column 0)
    produces the same traceback dict as the full-SENE-table fallback on
    ragged differential tails — every key, every lane, including empty
    pattern/text edge lanes and a ragged last tile."""
    import dataclasses
    from repro.kernels.ops import genasm_tail_fused_op
    full = AlignerConfig(W=W, O=O, k=k, tail_store="full")
    band = dataclasses.replace(full, tail_store="band")
    assert band.tail_banded and not full.tail_banded
    wt = W + 4 * k
    pat, txt, ml, nl = _tail_batch(rng, full, 6, wt)   # 6 lanes, tile=4
    args = (jnp.asarray(pat), jnp.asarray(txt), jnp.asarray(ml),
            jnp.asarray(nl))
    kw = dict(n_text=wt, commit_limit=2 * (W + wt), max_ops=W + wt,
              max_steps=W + wt + 4, tile=4, interpret=True)
    a = genasm_tail_fused_op(*args, cfg=full, **kw)
    b = genasm_tail_fused_op(*args, cfg=band, **kw)
    assert set(a) == set(b)
    for key in a:
        np.testing.assert_array_equal(np.array(a[key]), np.array(b[key]),
                                      err_msg=key)
    assert bool(np.array(a["ok"]).all())
    assert bool(np.array(a["solved"]).any())           # corpus nontrivial


def test_gpu_band_as_output_bit_identical_to_scratch(rng):
    """The Triton lowering's structural trick: backend='pallas_gpu' declares
    the DENT band as an extra GMEM-backed *output* block (jax's Triton
    backend has no scratch memory) while the kernel body is byte-for-byte
    the same function — output refs precede scratch refs, so band_ref lands
    in the identical positional slot.  Both square and tail kernels must be
    bit-identical to the pallas_fused scratch path, every key, in interpret
    mode (this always runs; the compiled-CUDA twin below is skip-guarded)."""
    import dataclasses
    from repro.kernels.ops import genasm_tail_fused_op
    cfg = AlignerConfig(W=32, O=10, k=9)
    gpu = dataclasses.replace(cfg, backend="pallas_gpu")
    pats, txts, _ = batch(rng, 32, 9, 8)
    a = _kernel_call(pats, txts, cfg, tile=4)
    b = _kernel_call(pats, txts, gpu, tile=4)
    assert set(a) == set(b)
    for key in a:
        np.testing.assert_array_equal(np.array(a[key]), np.array(b[key]),
                                      err_msg=key)
    wt = cfg.W + 4 * cfg.k
    pat, txt, ml, nl = _tail_batch(rng, cfg, 6, wt)
    args = (jnp.asarray(pat), jnp.asarray(txt), jnp.asarray(ml),
            jnp.asarray(nl))
    kw = dict(n_text=wt, commit_limit=2 * (cfg.W + wt), max_ops=cfg.W + wt,
              max_steps=cfg.W + wt + 4, tile=4, interpret=True)
    for store in ("band", "full"):   # both tail stores have a GPU lowering
        ct = dataclasses.replace(cfg, tail_store=store)
        gt = dataclasses.replace(gpu, tail_store=store)
        at = genasm_tail_fused_op(*args, cfg=ct, **kw)
        bt = genasm_tail_fused_op(*args, cfg=gt, **kw)
        assert set(at) == set(bt)
        for key in at:
            np.testing.assert_array_equal(np.array(at[key]),
                                          np.array(bt[key]),
                                          err_msg=f"{store}:{key}")


@pytest.mark.skipif(
    jax.default_backend() not in GPU_PLATFORMS,
    reason="no CUDA/ROCm device — compiled Triton parity needs a real GPU; "
           "interpret-mode parity above covers the lowering structure "
           "(see docs/backends.md)")
def test_gpu_compiled_parity_real_device(rng):
    """On a real GPU runner: the actually-compiled Triton kernels (this is
    what default_interpret flips to) must be bit-identical to interpret
    mode.  CI's gpu-parity step inverse-guards this: it fails the build if
    this test silently skips on a runner that reports a GPU backend."""
    import dataclasses
    cfg = dataclasses.replace(AlignerConfig(W=32, O=10, k=9),
                              backend="pallas_gpu")
    pats, txts, _ = batch(rng, 32, 9, 8)
    interp = _kernel_call(pats, txts, cfg, tile=4, interpret=True)
    compiled = _kernel_call(pats, txts, cfg, tile=4, interpret=False)
    for key in ("ops", "n_ops", "dist", "read_adv", "ref_adv", "cost"):
        np.testing.assert_array_equal(np.array(interp[key]),
                                      np.array(compiled[key]), err_msg=key)


def test_fused_kernels_tpu_interpret_parity(rng):
    """The fused kernel under pltpu.force_tpu_interpret_mode (the TPU
    lowering semantics, emulated) must be bit-identical to plain interpret
    mode — the CPU-side check behind compiling the kernels on a TPU."""
    from jax.experimental.pallas import tpu as pltpu
    cfg = AlignerConfig(W=16, O=6, k=4)
    pats, txts, _ = batch(rng, 16, 4, 8)
    plain = _kernel_call(pats, txts, cfg, tile=4, interpret=True)
    with pltpu.force_tpu_interpret_mode():
        tpu_interp = _kernel_call(pats, txts, cfg, tile=4, interpret=False)
    for key in ("ops", "n_ops", "dist", "read_adv", "ref_adv", "cost"):
        np.testing.assert_array_equal(np.array(plain[key]),
                                      np.array(tpu_interp[key]), err_msg=key)


@pytest.mark.slow
def test_fused_rescue_doubles_k(rng):
    """rescue-round k doubling recompiles the fused kernel with the doubled
    threshold."""
    from repro.core.aligner import GenASMAligner
    from repro.data.genome import ReadSimConfig, simulate_reads, synth_genome

    g = synth_genome(15_000, seed=77)
    # high-error pair: some window exceeds k=4 -> rescued with doubled k
    rs2 = simulate_reads(g, 2, ReadSimConfig(read_len=100, error_rate=0.25,
                                             seed=79))
    al = GenASMAligner(AlignerConfig(W=32, O=12, k=4),
                       rescue_rounds=2, backend="pallas_fused")
    res = al.align(rs2.reads, rs2.ref_segments)
    for i in range(len(rs2.reads)):
        if not res.failed[i]:
            validate_cigar(rs2.reads[i], rs2.ref_segments[i], res.ops[i],
                           expected_dist=res.dist[i])
    assert (res.k_used[~res.failed] >= 4).all()
    assert (res.k_used[~res.failed] > 4).any()   # at least one needed rescue


@pytest.mark.parametrize("store", ["band", "full"])
def test_tail_kernels_tpu_interpret_parity(store, rng):
    """Both rectangular-tail kernels under pltpu.force_tpu_interpret_mode
    are bit-identical to plain interpret mode on ragged tails."""
    import dataclasses
    from jax.experimental.pallas import tpu as pltpu
    from repro.kernels.ops import genasm_tail_fused_op
    cfg = dataclasses.replace(AlignerConfig(W=32, O=10, k=9),
                              tail_store=store)
    wt = cfg.W + 4 * cfg.k
    pat, txt, ml, nl = _tail_batch(rng, cfg, 6, wt)
    args = tuple(jnp.asarray(x) for x in (pat, txt, ml, nl))
    kw = dict(cfg=cfg, n_text=wt, commit_limit=2 * (cfg.W + wt),
              max_ops=cfg.W + wt, max_steps=cfg.W + wt + 4, tile=4)
    plain = genasm_tail_fused_op(*args, interpret=True, **kw)
    with pltpu.force_tpu_interpret_mode():
        tpu_interp = genasm_tail_fused_op(*args, interpret=False, **kw)
    for key in plain:
        np.testing.assert_array_equal(np.array(plain[key]),
                                      np.array(tpu_interp[key]), err_msg=key)
    assert bool(np.array(plain["solved"]).any())
