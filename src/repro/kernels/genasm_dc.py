"""Pallas TPU kernels: improved GenASM-DC (SENE + DENT + ET) and the fused
GenASM-DC+TB pipeline that never ships the DP state off-chip.

TPU mapping (see DESIGN.md §2): one VPU *lane* per alignment problem — the
innermost axis of every array is the problem tile (TB, a multiple of 128).
Bitvector words live in small leading axes and are unrolled; all DP state
is VMEM scratch, which is the paper's point: after the three improvements
the entire traceback table fits on-chip (`vmem_bytes` below).

Grid: one program per problem tile.  Per tile, the DC fill runs
*column-major*: a fori_loop over the W text columns carries the two live
DP columns — all k+1 levels of R_{j-1} ride in the loop state
("registers"), never in scratch — and per column the DENT band window
(funnel-shift extracted, sub-word) is stored for the traceback-reachable
columns only.  That is Scrooge's store-elimination idiom (arxiv
2208.09985): anything the shared traceback walk can re-derive from its two
live columns is never materialised, so the declared VMEM scratch *is* the
counting model's footprint (core.counting.kernel_scratch_words).

Three kernels share helpers:

  * `genasm_dc_pallas` (split) — writes the DENT band to an HBM output so
    the host-side jnp traceback (core.traceback, mode='band') can walk it.
    Band traffic per tile: (k+1) * ncols_band * nwb * TB * 4 bytes each way.
  * `genasm_tb_fused_pallas` (fused) — keeps the band in VMEM scratch and
    walks GenASM-TB *inside* the kernel: the same funnel-shift band-window
    reads as `store_band`, inverted, now per-lane dynamic (each problem is
    at its own (i, j, d) DP cell, so window/column/PM lookups become
    one-hot gathers over the small static axes, vectorized across lanes).
    Only the per-problem op array (<= max_ops int32) and a meta row leave
    the chip — the band never round-trips through HBM, which is the
    bandwidth win the paper's 24x working-set compression pays for.
  * `genasm_tail_fused_pallas` — the ragged rectangular tail.  Stores a
    per-lane *dynamic* DENT band (`_kernel_tail_banded`, the tentpole of
    the Scrooge port: ~2x less tail scratch at W=64 k=12) whenever
    `cfg.tail_banded`, falling back to the full SENE store
    (`_kernel_tail_fused`) when the band is not a strict win.

The traceback walk is bit-identical to core.traceback mode='band' (same
=,X,D,I preference, same commit-limit semantics); tests assert ops/dist
equality against the jnp path.

GPU lowering (``cfg.backend == 'pallas_gpu'``): the same three kernel
bodies compile through Pallas's *Triton* backend for CUDA GPUs.  One
Triton program per problem tile (lane-per-thread: the innermost problem
axis vectorises across the program's threads, ``gpu_num_warps`` warps of
32), with two mapping differences from the TPU path, both decided here at
trace time:

  * **No scratch memory.**  Pallas's Triton lowering rejects
    ``scratch_shapes`` outright, so the DENT band / SENE store that the
    TPU path keeps in VMEM scratch rides a GMEM-backed *output block*
    instead.  Kernel bodies are reused unchanged — Pallas passes output
    refs before scratch refs, so ``band_ref`` sits in the same positional
    slot either way; the wrapper simply discards the extra output.  The
    live DP columns stay loop-carried (registers), which is why the
    per-backend planner budget is a register model
    (``core.counting.gpu_lane_state_words``), not a 16 MiB VMEM budget.
  * **GPU-shaped tiles.**  The lane tile quantum is a warp (32) and the
    ceiling a CTA (1024 threads), planned by
    ``core.windowing.plan_lane_tile`` from the register model.

Outputs are bit-identical to the TPU/interpret path — asserted per grid
point by tests/test_kernel_fused.py and on the full differential corpus
by tests/test_differential.py.

The pure-jnp oracle is kernels/ref.py (which defers to core.genasm); the
jit'd wrapper with layout marshalling is kernels/ops.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.config import AlignerConfig
from ..core.counting import kernel_scratch_words, tail_scratch_words
from ..core.oracle import OP_DEL, OP_INS, OP_MATCH, OP_SUBST
from ..core.traceback import OP_NONE

WORD = 32

# meta_ref row layout of the fused kernel (8 rows for sublane alignment)
META_DIST, META_LVL, META_NOPS, META_RD, META_RF, META_DFIN, META_OK = range(7)
META_ROWS = 8


def _band_base(j, k, m_pad, nwb):
    lo = j - 2 - k
    hi = m_pad - WORD * nwb
    return jnp.clip(lo, 0, hi)


def default_max_ops(cfg: AlignerConfig) -> int:
    """Op budget of one committed window walk (= core.windowing's)."""
    return cfg.tb_max_ops


def default_max_steps(cfg: AlignerConfig) -> int:
    return cfg.tb_max_steps


def gpu_num_warps(tile: int) -> int:
    """Warps per Triton program for a `tile`-lane block: one thread per
    lane up to the CTA ceiling (warp = 32 threads, <= 8 warps so two CTAs
    can co-reside per SM at the default tile)."""
    return max(1, min(8, tile // 32))


#: Scoped-VMEM limit handed to Mosaic for every TPU kernel: twice the
#: lane-tile planner's 16 MiB scratch budget (core.windowing.plan_lane_tile).
#: The other half holds the double-buffered in/out blocks and the traceback
#: walk's one-hot temporaries, which grow with the tile like the scratch
#: does; Mosaic's default 16 MiB scope refuses the planned tile.
#: tests/test_tpu_compile.py compiles every kernel at the planned tile
#: against a described v5e (128 MiB of VMEM per core) under this limit.
VMEM_LIMIT_BYTES = 32 * 2**20


def _compiler_params(cfg: AlignerConfig, tile: int, interpret: bool):
    """Lowering parameters for a compiled launch (None in interpret mode).

    TPU: the scoped-VMEM limit above.  Triton: num_stages stays 1 — the DC
    fill is a serial column recurrence, so software-pipelining its loads
    buys nothing and costs registers, the binding resource of the
    lane-per-thread mapping."""
    if interpret:
        return None
    if cfg.backend == "pallas_gpu":
        from jax.experimental.pallas import triton as plgpu
        return plgpu.CompilerParams(num_warps=gpu_num_warps(tile),
                                    num_stages=1)
    return pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)


def fused_scratch_shapes(cfg: AlignerConfig, tile: int):
    """The declared VMEM scratch of the square fused kernel: the DENT band,
    nothing else — the DC fill's live columns are loop-carried values.
    Single source for `genasm_tb_fused_pallas` and the accounting tests."""
    return [pltpu.VMEM((cfg.k + 1, cfg.ncols_band, cfg.nwb, tile),
                       jnp.uint32)]


def gpu_fused_store_shapes(cfg: AlignerConfig, tile: int):
    """Declared per-program DP store of the square fused kernel on the
    Triton path: the identical DENT band, as a GMEM-backed output block
    (Triton has no scratch memory), one `jax.ShapeDtypeStruct` per store.
    Same words as `fused_scratch_shapes` — only the memory space differs —
    which tests/test_scratch_accounting.py asserts against the
    `core.counting.gpu_store_words` model."""
    return [jax.ShapeDtypeStruct((cfg.k + 1, cfg.ncols_band, cfg.nwb, tile),
                                 jnp.uint32)]


def gpu_tail_store_shapes(cfg: AlignerConfig, tile: int, n_text: int,
                          banded: bool | None = None):
    """Declared per-program DP store of the rectangular-tail kernel on the
    Triton path (GMEM output block, same words as `tail_scratch_shapes`)."""
    banded = cfg.tail_banded if banded is None else banded
    if banded:
        return [jax.ShapeDtypeStruct((cfg.k + 1, n_text, cfg.nwb, tile),
                                     jnp.uint32)]
    return [jax.ShapeDtypeStruct((cfg.k + 1, n_text + 1, cfg.nw, tile),
                                 jnp.uint32)]


def tail_scratch_shapes(cfg: AlignerConfig, tile: int, n_text: int,
                        banded: bool | None = None):
    """Declared VMEM scratch of the rectangular-tail kernel: the per-lane
    dynamic band (columns 1..n_text x nwb words; column 0 is analytic), or
    the full SENE table on the no-band-win fallback."""
    banded = cfg.tail_banded if banded is None else banded
    if banded:
        return [pltpu.VMEM((cfg.k + 1, n_text, cfg.nwb, tile), jnp.uint32)]
    return [pltpu.VMEM((cfg.k + 1, n_text + 1, cfg.nw, tile), jnp.uint32)]


def vmem_bytes(cfg: AlignerConfig, tile: int) -> int:
    """On-chip DP-store bytes per problem tile (the paper's 'fits in
    on-chip memory' claim, checked against ~16MB VMEM in tests).

    Exactly the declared scratch of the fused kernel — which, post
    store-elimination, is the band and only the band, so this equals
    `core.counting.kernel_scratch_words * 4` (one source of truth; the
    equality is asserted per grid point in tests/test_scratch_accounting).
    For the split kernel the identical band is an output block instead of
    scratch: same bytes resident while the tile is in flight."""
    return kernel_scratch_words(cfg, tile) * 4


def vmem_bytes_tail(cfg: AlignerConfig, tile: int, n_text: int | None = None,
                    banded: bool | None = None) -> int:
    """On-chip DP-store bytes of the rectangular-tail fused kernel per
    problem tile: the declared scratch of `tail_scratch_shapes`, via the
    counting model (banded defaults to cfg.tail_banded)."""
    return tail_scratch_words(cfg, tile, n_text, banded) * 4


def _pm_lookup(pm_ref, cj, nw, n_sym=4):
    """cj: (TB,) int32 -> list of nw (TB,) mask words (sentinel -> all ones)."""
    out = []
    for w in range(nw):
        acc = jnp.full(cj.shape, 0xFFFFFFFF, jnp.uint32)
        for c in range(n_sym):
            acc = jnp.where(cj == c, pm_ref[c, w, :], acc)
        out.append(acc)
    return out


def _text_row(text_ref, row):
    """Text chars of `row` (a scalar, or one row per lane), in range: a
    one-hot sum over the static row axis of the whole (rows, TB) block.

    Every ref access in these kernels has a static address, and every
    loop starts at 0.  On a v5e a kernel whose loop ran from 1 and loaded
    row `j - 1` of its loop index never returned, while the same load in
    a loop from 0 did; the kernels as run on the chip avoid both (see
    `_set_row`)."""
    t = text_ref[:, :]
    rows = jax.lax.broadcasted_iota(jnp.int32, t.shape, 0)
    return jnp.sum(jnp.where(rows == row, t, 0), axis=0).astype(jnp.int32)


def _set_row(ref, d, w, row, value):
    """ref[d, row, w, :] = value for a traced scalar `row`, written as a
    select over the whole static (rows, TB) slab ref[d, :, w, :], so the
    store's address is static (see `_text_row`).  A row out of range
    leaves the slab unchanged."""
    slab = ref[d, :, w, :]
    rows = jax.lax.broadcasted_iota(jnp.int32, slab.shape, 0)
    ref[d, :, w, :] = jnp.where(rows == row, value[None, :], slab)


def _bool_select(c, a, b):
    """jnp.where over boolean vectors, as logic: Mosaic cannot select
    between i1 vectors."""
    return (c & a) | (~c & b)


def _gather_cell(ref, w, dd, col):
    """Per-lane read of ref[dd, col, w, :] (uint32) for per-lane level dd
    and column col, both already clipped into range.

    Two stages keep every temporary at one (columns, TB) slab instead of
    the whole (levels, columns, TB) store: the level slab is selected per
    lane across the small static level axis, then the column is picked by
    a one-hot sum.  The sum runs in int32 because Mosaic has no unsigned
    reductions; it is exact, since at most one addend is non-zero."""
    slab = ref[0, :, w, :]
    for d in range(1, ref.shape[0]):
        slab = jnp.where((dd == d)[None, :], ref[d, :, w, :], slab)
    c_ids = jax.lax.broadcasted_iota(jnp.int32, slab.shape, 0)
    v = jax.lax.bitcast_convert_type(slab, jnp.int32)
    s = jnp.sum(jnp.where(c_ids == col[None, :], v, 0), axis=0)
    return jax.lax.bitcast_convert_type(s, jnp.uint32)


def _shift1_words(words, carry_in, nw):
    """Left-shift a word-list bitvector (LSW first) by one; carry_in at bit 0.
    words: list of nw (TB,) uint32."""
    out, carry = [], carry_in
    for w in range(nw):
        out.append((words[w] << jnp.uint32(1)) | carry)
        carry = words[w] >> jnp.uint32(WORD - 1)
    return out


def _ones_below_words(d, nw, lane_shape):
    """(nw-word, lanes) GenASM level-d init vector ~0 << d for traced d."""
    out = []
    for w in range(nw):
        lo = jnp.clip(d - w * WORD, 0, WORD)
        val = jnp.where(lo >= WORD, jnp.uint32(0),
                        jnp.uint32(0xFFFFFFFF) << lo.astype(jnp.uint32))
        out.append(jnp.broadcast_to(val, lane_shape))
    return out


def _word_select(words, w0):
    """Per-lane dynamic word pick from a word list; w0: (TB,) int32."""
    word = words[0]
    for w in range(1, len(words)):
        word = jnp.where(w0 == w, words[w], word)
    return word


def _next_column(prev, cur_below, pm_j, t, d, nw):
    """One SENE cell: R_j[d] from the three stored neighbours + PM mask.
    prev = [R_{j-1}[d], R_{j-1}[d-1]] (or [R_{j-1}[0]] at level 0),
    cur_below = R_j[d-1] (already frozen/final for this column)."""
    if d == 0:
        bM = (t > 0).astype(jnp.uint32)
        return [a | b for a, b in zip(_shift1_words(prev[0], bM, nw), pm_j)]
    r_prev, p_jm1 = prev
    bM = (t > d).astype(jnp.uint32)
    bS = (t >= d).astype(jnp.uint32)
    bI = (t >= d - 1).astype(jnp.uint32)
    M = [a | b for a, b in zip(_shift1_words(r_prev, bM, nw), pm_j)]
    S = _shift1_words(p_jm1, bS, nw)
    I = _shift1_words(cur_below, bI, nw)
    return [M[w] & S[w] & p_jm1[w] & I[w] for w in range(nw)]


def _ids_dist_dend(last_cols, bit_w, bit_o, guard, cfg):
    """dist = min level whose final column clears the target bit (monotone
    in d, so the fold below and the level-major first-hit agree), and the
    analytic d_end that reproduces the retired whole-tile-ET while loop's
    exit level exactly: with ET the loop ran levels 1..max(dist) (capped at
    k) and exited at the next level; without ET it always reached k+1."""
    k = cfg.k
    u1 = jnp.uint32(1)
    dist = None
    for d in range(k, -1, -1):
        bit = (_word_select(list(last_cols[d]), bit_w) >> bit_o) & u1
        hit = (bit == 0) & guard
        full = jnp.full(hit.shape, k + 1, jnp.int32)
        dist = jnp.where(hit, d, full if dist is None else dist)
    return dist, _levels_run(dist, cfg)


def _levels_run(dist, cfg):
    """The whole-tile-ET level count as a (1,) lane vector: levels
    1..max(dist) (capped at k) ran, exit at the next; k+1 without ET.
    Kept in vector registers (keepdims) — no vector-to-scalar move."""
    if cfg.early_term:
        return jnp.minimum(jnp.max(dist, keepdims=True), cfg.k) + 1
    return jnp.full((1,), cfg.k + 1, jnp.int32)


def _dc_phase(pm_ref, text_ref, band_ref, *, cfg: AlignerConfig):
    """Column-major improved GenASM-DC fill: all k+1 levels of the two live
    DP columns ride in the fori_loop carry; only the DENT band windows are
    materialised (into band_ref — output block or VMEM scratch).  Returns
    (dist, d_end).

    Level values stored at levels above a lane's dist can differ from the
    retired level-major ET fill (which left them zero) — but no consumer
    reads them: the traceback starts at d = dist and only descends, and the
    band parity tests compare levels [:d_end] only."""
    W, k, nw, nwb = cfg.W, cfg.k, cfg.nw, cfg.nwb
    m_pad = cfg.m_pad
    ncb = cfg.ncols_band
    col0 = W + 1 - ncb
    tgt_w, tgt_o = (W - 1) // WORD, jnp.uint32((W - 1) % WORD)

    def store_band(d, j, words):
        """Funnel-shift extract the band window of column j and store it."""
        base = _band_base(j, k, m_pad, nwb)
        w0 = base // WORD
        s = (base % WORD).astype(jnp.uint32)
        for b in range(nwb):
            lo = words[0]
            hi = words[0]
            for w in range(nw):          # dynamic word select, unrolled
                lo = jnp.where(w0 + b == w, words[w], lo)
                hi = jnp.where(w0 + b + 1 == w, words[w],
                               jnp.where(w0 + b + 1 >= nw, jnp.uint32(0xFFFFFFFF),
                                         hi))
            win = jnp.where(s == 0, lo, (lo >> s) | (hi << (jnp.uint32(WORD) - s)))
            @pl.when(j >= col0)
            def _():
                _set_row(band_ref, d, b, j - col0, win)

    lane_shape = text_ref.shape[1:]
    cols0 = [_ones_below_words(jnp.int32(d), nw, lane_shape)
             for d in range(k + 1)]
    if col0 == 0:                         # column 0 only stored if in band
        for d in range(k + 1):
            store_band(d, jnp.int32(0), cols0[d])

    def col_body(t, carry):               # t = j - 1: text index of column j
        j = t + 1
        prev = [list(c) for c in carry]
        pm_j = _pm_lookup(pm_ref, _text_row(text_ref, t), nw)
        cur = [_next_column([prev[0]], None, pm_j, t, 0, nw)]
        for d in range(1, k + 1):
            cur.append(_next_column([prev[d], prev[d - 1]], cur[d - 1],
                                    pm_j, t, d, nw))
        for d in range(k + 1):
            store_band(d, j, cur[d])
        return tuple(tuple(c) for c in cur)

    last = jax.lax.fori_loop(0, W, col_body, tuple(tuple(c) for c in cols0))
    guard = jnp.ones(lane_shape, bool)
    return _ids_dist_dend(last, tgt_w, tgt_o, guard, cfg)


def _kernel(pm_ref, text_ref, band_ref, dist_ref, lvl_ref, *,
            cfg: AlignerConfig):
    dist, d_end = _dc_phase(pm_ref, text_ref, band_ref, cfg=cfg)
    dist_ref[0, :] = dist
    lvl_ref[0, :] = jnp.broadcast_to(d_end, lvl_ref.shape[1:]).astype(jnp.int32)


def _tb_walk(*, TB, dist, k, init_i, init_j, commit_limit, max_ops, max_steps,
             avail_words, zbit, peq_at, text_at):
    """Shared in-kernel GenASM-TB walk, bit-identical to core.traceback:
    per-lane (i, j, d) cursors advanced with the =,X,D,I preference order, a
    tail drain (pattern exhausted -> remaining text as deletions), and the
    commit-limit stop.  ``avail_words(dd, jj)`` gathers the stored bitvector
    words of (level dd, column jj); ``zbit(words, dd, jj, ii)`` tests bit ii.

    Returns the final (i, j, d, nops, ops, rd, rf, done, ok) state, with
    done and ok as int32 0/1 flags."""
    slot_ids = jax.lax.broadcasted_iota(jnp.int32, (max_ops, TB), 0)

    def body(state):
        i, j, d, nops, ops, rd, rf, done, ok = state
        done = done != 0
        tail = i < 0
        stopped = rd >= commit_limit
        active = ~done & ~stopped

        w_d_jm1 = avail_words(d, j - 1)
        w_dm1_jm1 = avail_words(d - 1, j - 1)
        w_dm1_j = avail_words(d - 1, j)
        peq = peq_at(text_at(j), i)
        mA = (j > 0) & peq & zbit(w_d_jm1, d, j - 1, i - 1)
        sA = (j > 0) & (d > 0) & zbit(w_dm1_jm1, d - 1, j - 1, i - 1)
        dA = (j > 0) & (d > 0) & zbit(w_dm1_jm1, d - 1, j - 1, i)
        iA = (d > 0) & zbit(w_dm1_j, d - 1, j, i - 1)

        # tail: pattern exhausted, drain remaining text as deletions
        tail_emit = tail & (j > 0)
        mA &= ~tail; sA &= ~tail; dA &= ~tail; iA &= ~tail

        any_edge = mA | sA | dA | iA | tail_emit
        # exclusive choice with GenASM's =,X,D,I preference
        cM = mA
        cS = ~mA & sA
        cD = ~mA & ~sA & dA
        cI = ~mA & ~sA & ~dA & iA
        op = jnp.where(cM, OP_MATCH,
             jnp.where(cS, OP_SUBST,
             jnp.where(cD, OP_DEL,
             jnp.where(cI, OP_INS, OP_DEL)))).astype(jnp.int32)

        takes_read = active & (cM | cS | cI)
        takes_ref = active & (cM | cS | cD | tail_emit)
        costs = active & (cS | cD | cI | tail_emit)

        new_i = jnp.where(takes_read, i - 1, i)
        new_j = jnp.where(takes_ref, j - 1, j)
        new_d = jnp.where(costs, d - 1, d)
        new_rd = rd + takes_read
        new_rf = rf + takes_ref

        emit = active & any_edge
        slot = jnp.where(emit, nops, max_ops)   # max_ops -> no iota row: drop
        ops = jnp.where(slot_ids == slot[None, :], op[None, :], ops)
        nops = nops + emit

        finished = (new_i < 0) & (new_j <= 0)
        new_done = done | (active & finished) | stopped
        # invariant: an active, unfinished cell always has an available edge
        ok &= (~(active & ~finished) | any_edge
               | ((i < 0) & (j <= 0))).astype(jnp.int32)
        return (new_i, new_j, new_d, nops, ops, new_rd, new_rf,
                new_done.astype(jnp.int32), ok)

    # done/ok ride the loop carry as int32 0/1: Mosaic cannot carry boolean
    # vectors through a loop (i8 -> i1 truncation).  Every step runs the
    # body (finished lanes are masked by `active`): skipping a step for the
    # whole tile would need a vector-to-scalar move per step.

    zeros = jnp.zeros((TB,), jnp.int32)
    skip = dist > k
    init = (
        init_i,                                     # i (m_len - 1)
        init_j,                                     # j (n_len)
        dist,                                       # d
        zeros,                                      # nops
        jnp.full((max_ops, TB), OP_NONE, jnp.int32),
        zeros,                                      # read_adv
        zeros,                                      # ref_adv
        skip.astype(jnp.int32),                     # done
        jnp.ones((TB,), jnp.int32),                 # ok
    )
    return jax.lax.fori_loop(0, max_steps, lambda _, s: body(s), init)


def _kernel_fused(pm_ref, text_ref, ops_ref, meta_ref, band_ref, *,
                  cfg: AlignerConfig, commit_limit: int, max_ops: int,
                  max_steps: int):
    """DC phase into VMEM scratch, then GenASM-TB walked in-kernel.

    The walk mirrors core.traceback (mode='band') bit for bit: SENE edge
    availability is recomputed from neighbouring stored band windows + the
    PM masks, with the =,X,D,I preference order, a per-lane tail drain, and
    the commit-limit stop.  Per-lane dynamic (d, j) band reads use one-hot
    sums over the small static (k+1, ncols_band) axes — the inverted form
    of store_band's funnel-shift stores.  The column-major fill writes
    every band entry, so no zero-init pass is needed (and the walk never
    visits levels above its lane's dist anyway).
    """
    W, k, nw, nwb = cfg.W, cfg.k, cfg.nw, cfg.nwb
    m_pad = cfg.m_pad
    ncb = cfg.ncols_band
    col0 = W + 1 - ncb
    TB = text_ref.shape[1]
    u1 = jnp.uint32(1)

    dist, d_end = _dc_phase(pm_ref, text_ref, band_ref, cfg=cfg)

    # ---------------- traceback phase ----------------
    def band_words(dd, jj):
        """Per-lane gather of the stored band window of (level dd, col jj),
        clipped like core.traceback._zbit_band."""
        ddc = jnp.clip(dd, 0, k)
        col = jnp.clip(jj - col0, 0, ncb - 1)
        return [_gather_cell(band_ref, b, ddc, col) for b in range(nwb)]

    def zbit(words, dd, jj, ii):
        """bit ii of the band window == 0; ii == -1 encodes the DP's first
        column: ED(0, jj) <= dd  ⟺  jj <= dd."""
        base = _band_base(jj, k, m_pad, nwb)
        off = ii - base
        inband = (off >= 0) & (off < nwb * WORD)
        offc = jnp.clip(off, 0, nwb * WORD - 1)
        o = (offc % WORD).astype(jnp.uint32)
        bit = (_word_select(words, offc // WORD) >> o) & u1
        return _bool_select(ii < 0, jj <= dd, (bit == 0) & inband)

    def text_at(jj):
        """text char of column jj (= text index jj-1, clipped)."""
        return _text_row(text_ref, jnp.clip(jj - 1, 0, W - 1))

    def peq_at(cj, ii):
        """P[ii] == text char cj, via the PM masks (sentinels never match)."""
        words = _pm_lookup(pm_ref, cj, nw)
        iic = jnp.clip(ii, 0, m_pad - 1)
        o = (iic % WORD).astype(jnp.uint32)
        return ((_word_select(words, iic // WORD) >> o) & u1) == 0

    i, j, d, nops, ops, rd, rf, done, ok = _tb_walk(
        TB=TB, dist=dist, k=k,
        init_i=jnp.full((TB,), W - 1, jnp.int32),
        init_j=jnp.full((TB,), W, jnp.int32),
        commit_limit=commit_limit, max_ops=max_ops, max_steps=max_steps,
        avail_words=band_words, zbit=zbit, peq_at=peq_at, text_at=text_at)

    ops_ref[:, :] = ops
    meta_ref[META_DIST, :] = dist
    meta_ref[META_LVL, :] = jnp.broadcast_to(d_end, (TB,)).astype(jnp.int32)
    meta_ref[META_NOPS, :] = nops
    meta_ref[META_RD, :] = rd
    meta_ref[META_RF, :] = rf
    meta_ref[META_DFIN, :] = d
    meta_ref[META_OK, :] = ok
    meta_ref[META_ROWS - 1, :] = jnp.zeros((TB,), jnp.int32)


def genasm_dc_pallas(pm, text, *, cfg: AlignerConfig, tile: int = 128,
                     interpret: bool):
    """pm: (5, NW, B) uint32; text: (W, B) int32 (kernel layout, problems
    innermost).  Returns (dist (B,), band (k+1, ncb, nwb, B), levels (B,)).
    No VMEM scratch at all: the DC state is loop-carried, the band is the
    output block — which is why this kernel lowers through the Triton
    backend (cfg.backend == 'pallas_gpu') completely unchanged."""
    _, nw, B = pm.shape
    W = text.shape[0]
    assert W == cfg.W and nw == cfg.nw and B % tile == 0
    ncb, nwb, k = cfg.ncols_band, cfg.nwb, cfg.k
    grid = (B // tile,)
    kern = functools.partial(_kernel, cfg=cfg)
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((5, nw, tile), lambda i: (0, 0, i)),
            pl.BlockSpec((W, tile), lambda i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((k + 1, ncb, nwb, tile), lambda i: (0, 0, 0, i)),
            pl.BlockSpec((1, tile), lambda i: (0, i)),
            pl.BlockSpec((1, tile), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k + 1, ncb, nwb, B), jnp.uint32),
            jax.ShapeDtypeStruct((1, B), jnp.int32),
            jax.ShapeDtypeStruct((1, B), jnp.int32),
        ],
        compiler_params=_compiler_params(cfg, tile, interpret),
        interpret=interpret,
    )(pm, text)
    band, dist, lvl = out
    return dist[0], band, lvl[0]


def genasm_tb_fused_pallas(pm, text, *, cfg: AlignerConfig, commit_limit: int,
                           max_ops: int | None = None,
                           max_steps: int | None = None, tile: int = 128,
                           interpret: bool):
    """Fused DC+TB.  pm: (5, NW, B) uint32; text: (W, B) int32 (kernel
    layout).  Returns (ops (max_ops, B) int32 front-first with OP_NONE
    padding, meta (META_ROWS, B) int32 — see META_* row constants).  The
    DENT band lives and dies on-chip: VMEM scratch on the TPU path
    (`fused_scratch_shapes`), a discarded GMEM output block on the Triton
    path (`gpu_fused_store_shapes` — cfg.backend == 'pallas_gpu', whose
    lowering has no scratch memory).  The kernel body is identical either
    way: output refs precede scratch refs, so band_ref occupies the same
    positional slot as 3rd output or 1st scratch."""
    _, nw, B = pm.shape
    W = text.shape[0]
    assert W == cfg.W and nw == cfg.nw and B % tile == 0
    if max_ops is None:
        max_ops = default_max_ops(cfg)
    if max_steps is None:
        max_steps = default_max_steps(cfg)
    grid = (B // tile,)
    gpu = cfg.backend == "pallas_gpu"
    kern = functools.partial(_kernel_fused, cfg=cfg, commit_limit=commit_limit,
                             max_ops=max_ops, max_steps=max_steps)
    ncb, nwb, k = cfg.ncols_band, cfg.nwb, cfg.k
    out_specs = [
        pl.BlockSpec((max_ops, tile), lambda i: (0, i)),
        pl.BlockSpec((META_ROWS, tile), lambda i: (0, i)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((max_ops, B), jnp.int32),
        jax.ShapeDtypeStruct((META_ROWS, B), jnp.int32),
    ]
    if gpu:
        out_specs.append(pl.BlockSpec((k + 1, ncb, nwb, tile),
                                      lambda i: (0, 0, 0, i)))
        (blk,) = gpu_fused_store_shapes(cfg, tile)
        out_shape.append(jax.ShapeDtypeStruct(blk.shape[:-1] + (B,),
                                              blk.dtype))
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((5, nw, tile), lambda i: (0, 0, i)),
            pl.BlockSpec((W, tile), lambda i: (0, i)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=() if gpu else fused_scratch_shapes(cfg, tile),
        compiler_params=_compiler_params(cfg, tile, interpret),
        interpret=interpret,
    )(pm, text)
    ops, meta = out[0], out[1]       # gpu: out[2] is the discarded band
    return ops, meta


def _tail_fill(pm_ref, text_ref, m_len, n_len, store_col, *,
               cfg: AlignerConfig, n_text: int):
    """Column-major DC fill of the ragged rectangular tail, shared by both
    tail kernels: all k+1 levels of the live column ride in the loop carry
    (no full-table reads), and ``store_col(d, j, words)`` stores column
    j >= 1 of level d.  Keeps dc_jmajor's ragged semantics: columns past a
    lane's n_len freeze their left neighbour, and dist reads the per-lane
    bit (m_len - 1) of the final carried column (== frozen column n_len);
    empty lanes (m_len == 0) never hit.  Every level is filled and d_end
    reproduces the whole-tile-ET level count analytically
    (`_ids_dist_dend`); the walk never visits a level above its lane's
    dist, so the extra levels cannot change results.  Returns
    (dist, d_end)."""
    k, nw, m_pad = cfg.k, cfg.nw, cfg.m_pad
    TB = text_ref.shape[1]
    cols0 = [_ones_below_words(jnp.int32(d), nw, (TB,)) for d in range(k + 1)]

    def col_body(t, carry):               # t = j - 1: text index of column j
        j = t + 1
        prev = [list(c) for c in carry]
        pm_j = _pm_lookup(pm_ref, _text_row(text_ref, t), nw)
        live = j <= n_len
        cur = []
        for d in range(k + 1):
            below = cur[d - 1] if d else None
            r = _next_column([prev[d]] if d == 0 else [prev[d], prev[d - 1]],
                             below, pm_j, t, d, nw)
            cur.append([jnp.where(live, rw, pw)
                        for rw, pw in zip(r, prev[d])])
        for d in range(k + 1):
            store_col(d, j, cur[d])
        return tuple(tuple(c) for c in cur)

    last = jax.lax.fori_loop(0, n_text, col_body,
                             tuple(tuple(c) for c in cols0))
    tm = jnp.clip(m_len - 1, 0, m_pad - 1)
    return _ids_dist_dend(last, tm // WORD, (tm % WORD).astype(jnp.uint32),
                          m_len >= 1, cfg)


def _kernel_tail_fused(pm_ref, text_ref, mlen_ref, nlen_ref, ops_ref, meta_ref,
                       rfull_ref, *, cfg: AlignerConfig, n_text: int,
                       commit_limit: int, max_ops: int, max_steps: int):
    """Rectangular-tail fused DC+TB, full-store fallback.

    Unlike the square main-window kernel the tail is rectangular and ragged:
    per-lane m_len <= W pattern chars against n_len <= n_text text chars.
    This variant stores the full SENE ('and') vectors for every (level,
    column) in VMEM scratch and the traceback walks them in-kernel — the
    exact analogue of core.windowing's jnp 'and'-store tail path, bit for
    bit, with neither the store nor the walk ever leaving the chip.  It is
    dispatched only when the banded store (`_kernel_tail_banded`) is not a
    strict win (cfg.tail_banded False, i.e. nwb == nw or forced 'full').

    The fill is `_tail_fill`, shared with the banded kernel; this variant
    stores every column whole, column 0 (R_0[d] = ones_below(d)) included.
    """
    k, nw = cfg.k, cfg.nw
    m_pad = cfg.m_pad
    TB = text_ref.shape[1]
    u1 = jnp.uint32(1)
    m_len = mlen_ref[0, :]
    n_len = nlen_ref[0, :]

    for d in range(k + 1):
        for w, word in enumerate(_ones_below_words(jnp.int32(d), nw, (TB,))):
            rfull_ref[d, 0, w, :] = word

    def store_col(d, j, words):
        for w in range(nw):
            _set_row(rfull_ref, d, w, j, words[w])

    dist, d_end = _tail_fill(pm_ref, text_ref, m_len, n_len, store_col,
                             cfg=cfg, n_text=n_text)

    # ------- traceback phase: full-vector zbit, like core.traceback 'and' ---
    def r_words(dd, jj):
        """Per-lane gather of stored R_jj[dd], clipped like _zbit_full."""
        ddc = jnp.clip(dd, 0, k)
        col = jnp.clip(jj, 0, n_text)
        return [_gather_cell(rfull_ref, w, ddc, col) for w in range(nw)]

    def zbit(words, dd, jj, ii):
        iic = jnp.clip(ii, 0, m_pad - 1)
        o = (iic % WORD).astype(jnp.uint32)
        bit = (_word_select(words, iic // WORD) >> o) & u1
        return _bool_select(ii < 0, jj <= dd, bit == 0)

    def text_at(jj):
        return _text_row(text_ref, jnp.clip(jj - 1, 0, n_text - 1))

    def peq_at(cj, ii):
        words = _pm_lookup(pm_ref, cj, nw)
        iic = jnp.clip(ii, 0, m_pad - 1)
        o = (iic % WORD).astype(jnp.uint32)
        return ((_word_select(words, iic // WORD) >> o) & u1) == 0

    i, j, d, nops, ops, rd, rf, done, ok = _tb_walk(
        TB=TB, dist=dist, k=k, init_i=m_len - 1, init_j=n_len,
        commit_limit=commit_limit, max_ops=max_ops, max_steps=max_steps,
        avail_words=r_words, zbit=zbit, peq_at=peq_at, text_at=text_at)

    ops_ref[:, :] = ops
    meta_ref[META_DIST, :] = dist
    meta_ref[META_LVL, :] = jnp.broadcast_to(d_end, (TB,)).astype(jnp.int32)
    meta_ref[META_NOPS, :] = nops
    meta_ref[META_RD, :] = rd
    meta_ref[META_RF, :] = rf
    meta_ref[META_DFIN, :] = d
    meta_ref[META_OK, :] = ok
    meta_ref[META_ROWS - 1, :] = jnp.zeros((TB,), jnp.int32)


def _kernel_tail_banded(pm_ref, text_ref, mlen_ref, nlen_ref, ops_ref,
                        meta_ref, band_ref, *, cfg: AlignerConfig, n_text: int,
                        commit_limit: int, max_ops: int, max_steps: int):
    """Rectangular-tail fused DC+TB with the Scrooge-style banded store.

    The band proof (the tentpole): the traceback walk starts at the
    per-lane cell (i = m_len-1, j = n_len) and every step moves i and/or j
    down by one, spending at most dist <= k unit costs on indels — so at
    any visited cell, i - j differs from the starting diagonal
    (m_len - 1 - n_len) by at most k, and the walk's bit reads (at offsets
    -1..+1 around the cursor) stay within [c(j)-k-1, c(j)+k+1] of the
    per-lane column center c(j) = j + m_len - 1 - n_len.  That window is
    2k+3 bits = nwb words: the kernel stores only those words per (level,
    column), funnel-shifted from the live column exactly like the square
    kernel's store_band — but with a per-lane *dynamic* base, since every
    lane sits on its own diagonal.  Column 0 (R_0[d] = ones_below(d)) and
    the i < 0 drain are analytic in zbit, so they need no store at all.

    The fill is `_tail_fill`, shared with the full-store kernel.
    """
    k, nw, nwb = cfg.k, cfg.nw, cfg.nwb
    m_pad = cfg.m_pad
    TB = text_ref.shape[1]
    u1 = jnp.uint32(1)
    m_len = mlen_ref[0, :]
    n_len = nlen_ref[0, :]
    diag = m_len - 1 - n_len              # per-lane starting diagonal

    def tail_base(jj):
        """Lowest stored bit of column jj's window: k+1 below the per-lane
        center, clipped into the padded pattern like _band_base."""
        return jnp.clip(jj + diag - (k + 1), 0, m_pad - WORD * nwb)

    def store_band(d, j, words):
        base = tail_base(j)
        w0 = base // WORD
        s = (base % WORD).astype(jnp.uint32)
        for b in range(nwb):
            lo = words[0]
            hi = words[0]
            for w in range(nw):          # per-lane dynamic select, unrolled
                lo = jnp.where(w0 + b == w, words[w], lo)
                hi = jnp.where(w0 + b + 1 == w, words[w],
                               jnp.where(w0 + b + 1 >= nw, jnp.uint32(0xFFFFFFFF),
                                         hi))
            win = jnp.where(s == 0, lo, (lo >> s) | (hi << (jnp.uint32(WORD) - s)))
            _set_row(band_ref, d, b, j - 1, win)

    dist, d_end = _tail_fill(pm_ref, text_ref, m_len, n_len, store_band,
                             cfg=cfg, n_text=n_text)

    # ---------------- traceback phase: banded zbit ----------------
    def band_words(dd, jj):
        """Per-lane gather of the window of (level dd, col jj); column 0 has
        no store (analytic in zbit), so jj clips into 1..n_text."""
        ddc = jnp.clip(dd, 0, k)
        col = jnp.clip(jj, 1, n_text) - 1
        return [_gather_cell(band_ref, b, ddc, col) for b in range(nwb)]

    def zbit(words, dd, jj, ii):
        """bit ii of R_jj[dd] == 0 from the banded store; analytic for the
        unstored boundaries: ii < 0 is the DP's first row (ED(0, jj) = jj),
        jj <= 0 the first column (R_0[d] = ones_below(d): ED(ii+1, 0))."""
        base = tail_base(jj)
        off = ii - base
        inband = (off >= 0) & (off < nwb * WORD)
        offc = jnp.clip(off, 0, nwb * WORD - 1)
        o = (offc % WORD).astype(jnp.uint32)
        bit = (_word_select(words, offc // WORD) >> o) & u1
        z = _bool_select(jj <= 0, ii < dd, (bit == 0) & inband)
        return _bool_select(ii < 0, jj <= dd, z)

    def text_at(jj):
        return _text_row(text_ref, jnp.clip(jj - 1, 0, n_text - 1))

    def peq_at(cj, ii):
        words = _pm_lookup(pm_ref, cj, nw)
        iic = jnp.clip(ii, 0, m_pad - 1)
        o = (iic % WORD).astype(jnp.uint32)
        return ((_word_select(words, iic // WORD) >> o) & u1) == 0

    i, j, d, nops, ops, rd, rf, done, ok = _tb_walk(
        TB=TB, dist=dist, k=k, init_i=m_len - 1, init_j=n_len,
        commit_limit=commit_limit, max_ops=max_ops, max_steps=max_steps,
        avail_words=band_words, zbit=zbit, peq_at=peq_at, text_at=text_at)

    ops_ref[:, :] = ops
    meta_ref[META_DIST, :] = dist
    meta_ref[META_LVL, :] = jnp.broadcast_to(d_end, (TB,)).astype(jnp.int32)
    meta_ref[META_NOPS, :] = nops
    meta_ref[META_RD, :] = rd
    meta_ref[META_RF, :] = rf
    meta_ref[META_DFIN, :] = d
    meta_ref[META_OK, :] = ok
    meta_ref[META_ROWS - 1, :] = jnp.zeros((TB,), jnp.int32)


def genasm_tail_fused_pallas(pm, text, m_len, n_len, *, cfg: AlignerConfig,
                             n_text: int, commit_limit: int, max_ops: int,
                             max_steps: int, tile: int = 128,
                             interpret: bool):
    """Fused rectangular-tail DC+TB.  pm: (5, NW, B) uint32; text:
    (n_text, B) int32; m_len/n_len: (1, B) int32 (kernel layout, problems
    innermost).  Returns (ops (max_ops, B) int32, meta (META_ROWS, B) int32)
    like genasm_tb_fused_pallas; the SENE store lives and dies in VMEM
    scratch — banded (`cfg.tail_banded`, ~2x less scratch at the default
    geometry) or full on the fallback — and the tail window never touches
    HBM either.  On the Triton path (cfg.backend == 'pallas_gpu', no
    scratch memory in that lowering) the same store is a discarded GMEM
    output block (`gpu_tail_store_shapes`); kernel bodies unchanged.  All
    variants are bit-identical on every output
    (tests/test_kernel_fused.py, tests/test_differential.py)."""
    _, nw, B = pm.shape
    assert text.shape[0] == n_text and nw == cfg.nw and B % tile == 0
    grid = (B // tile,)
    gpu = cfg.backend == "pallas_gpu"
    body = _kernel_tail_banded if cfg.tail_banded else _kernel_tail_fused
    kern = functools.partial(body, cfg=cfg, n_text=n_text,
                             commit_limit=commit_limit, max_ops=max_ops,
                             max_steps=max_steps)
    out_specs = [
        pl.BlockSpec((max_ops, tile), lambda i: (0, i)),
        pl.BlockSpec((META_ROWS, tile), lambda i: (0, i)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((max_ops, B), jnp.int32),
        jax.ShapeDtypeStruct((META_ROWS, B), jnp.int32),
    ]
    if gpu:
        (blk,) = gpu_tail_store_shapes(cfg, tile, n_text)
        nd = len(blk.shape)
        out_specs.append(pl.BlockSpec(
            blk.shape, lambda i, nd=nd: (0,) * (nd - 1) + (i,)))
        out_shape.append(jax.ShapeDtypeStruct(blk.shape[:-1] + (B,),
                                              blk.dtype))
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((5, nw, tile), lambda i: (0, 0, i)),
            pl.BlockSpec((n_text, tile), lambda i: (0, i)),
            pl.BlockSpec((1, tile), lambda i: (0, i)),
            pl.BlockSpec((1, tile), lambda i: (0, i)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=() if gpu else tail_scratch_shapes(cfg, tile, n_text),
        compiler_params=_compiler_params(cfg, tile, interpret),
        interpret=interpret,
    )(pm, text, m_len, n_len)
    ops, meta = out[0], out[1]       # gpu: out[2] is the discarded store
    return ops, meta
