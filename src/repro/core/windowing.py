"""Windowed long-read alignment (GenASM's W/O windowing, batched + jittable).

A (read, candidate-ref-segment) pair is aligned as a sequence of W x W
windows: DC+TB inside the window (on *reversed* window contents, so the
traceback emits front-first ops), commit the first W-O read characters'
worth of operations, advance read by exactly W-O and ref by the committed
ref consumption, repeat.  The final <= W read chars are aligned in a single
"tail" window against the remaining reference (end-to-end).

All problems advance in lockstep (read stride is uniform), and the window
loop ends at the step after which no problem is active: every read has at
most W characters left or has failed.  The length class's window count is
only the loop's upper bound.  Under the k-doubling rescue ladder a window
whose edit distance exceeds k escalates to the next rung within the same
step (``align_pairs_rescued``); problems whose window is unsolved at the
top rung are flagged `failed`.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from .bitops import SENTINEL_PAT, SENTINEL_TEXT
from .config import PALLAS_BACKENDS, AlignerConfig
from .genasm import dc, dc_jmajor
from .traceback import OP_NONE, traceback

SENTINEL_READ = SENTINEL_PAT    # never matches (out of PM alphabet)
SENTINEL_REF = SENTINEL_TEXT    # maps to the all-ones PM row


def n_main_windows(max_read_len: int, cfg: AlignerConfig) -> int:
    """Windows before every problem's remaining read length is <= W."""
    return max(0, -(-(max_read_len - cfg.W) // cfg.stride))


def total_op_budget(max_read_len: int, cfg: AlignerConfig) -> int:
    nm = n_main_windows(max_read_len, cfg)
    return nm * (cfg.stride + cfg.k) + cfg.W + self_tail_width(cfg)


def self_tail_width(cfg: AlignerConfig) -> int:
    return cfg.W + 4 * cfg.k


# ---- bucket-shaped geometry (the session front door's shape classes) ----
#
# `repro.api.AlignSession` never derives pad widths from a batch's ragged
# max_read_len: it quantises lengths to power-of-two BUCKETS and compiles
# one executable per bucket.  These helpers are the single source of truth
# for that geometry — the legacy aligner's exact-shape path uses the same
# pad_geometry so both doors stay bit-identical.

def pow2_bucket(n: int, floor: int = 1) -> int:
    """Smallest power of two >= max(n, floor): the static length class a
    ragged length is padded into."""
    assert n >= 0 and floor >= 1
    b = 1 << max(n - 1, floor - 1, 0).bit_length()
    return max(b, floor)


def pad_geometry(cfg: AlignerConfig, max_read_len: int, max_ref_len: int,
                 rescue_rounds: int = 0) -> tuple[int, int]:
    """(Lr, Lf) padded array widths for a (read, ref) length class: reads
    carry >= W sentinels past read_len, refs enough for the FINAL rescue
    round's tail width (the contract of align_pairs / align_pairs_rescued)."""
    wt = self_tail_width(rescue_schedule(cfg, rescue_rounds)[-1])
    return max_read_len + cfg.W + 1, max_ref_len + cfg.W + wt + 1


def bucket_avals(cfg: AlignerConfig, lanes: int, read_bucket: int,
                 ref_bucket: int, rescue_rounds: int = 0):
    """ShapeDtypeStructs of one bucket's batch — what the session AOT-lowers
    an executable against (see repro.api.CompileCache)."""
    Lr, Lf = pad_geometry(cfg, read_bucket, ref_bucket, rescue_rounds)
    sds = jax.ShapeDtypeStruct
    return (sds((lanes, Lr), jnp.uint8), sds((lanes,), jnp.int32),
            sds((lanes, Lf), jnp.uint8), sds((lanes,), jnp.int32))


#: GPU lane-tile planning constants: the quantum is a warp (32 threads,
#: one lane per thread), the ceiling a CTA (1024 threads), and the budget
#: one SM's 32-bit register file (64K registers) — the live DP columns are
#: the Triton mapping's binding resource, not scratch bytes (the band
#: store is GMEM-backed on that path; see core.counting.gpu_*).
GPU_LANE_QUANTUM = 32
GPU_LANE_CEILING = 1024
GPU_REG_BUDGET_WORDS = 64 * 1024


def plan_lane_tile(cfg: AlignerConfig, vmem_budget_bytes: int = 16 * 2**20,
                   quantum: int = 128, ceiling: int = 4096,
                   reg_budget_words: int = GPU_REG_BUDGET_WORDS) -> int:
    """Largest lane tile whose kernels fit the backend's on-chip budget.

    TPU backends (and jnp, which shares their geometry when a pallas
    backend is swapped in later): the largest multiple of `quantum` (the
    VPU lane width) whose square fused kernel AND tail kernel VMEM scratch
    both fit `vmem_budget_bytes`.  This is where the tentpole's reclaimed
    bytes get *spent*: the tail kernel's store was the binding constraint,
    and the Scrooge-style band (cfg.tail_banded) roughly halves it at the
    default geometry, so the planner's ceiling doubles — more lanes per
    kernel launch, fewer grid steps per batch.  The budget is half the
    scoped-VMEM limit the kernels ask Mosaic for
    (kernels.genasm_dc.VMEM_LIMIT_BYTES); the other half holds pipeline
    buffers and walk temporaries.  tests/test_tpu_compile.py compiles the
    planned tile for a described v5e.

    backend='pallas_gpu': a *register* model instead — the Triton lowering
    keeps the band store in GMEM (no scratch memory) and the live DP
    columns in registers, so the tile is the largest multiple of a warp
    (GPU_LANE_QUANTUM) whose per-lane live state
    (core.counting.gpu_lane_state_words) fits `reg_budget_words`, capped
    at a CTA (GPU_LANE_CEILING).

    Sessions opt in with plan(..., lane_tile='auto') (repro.api); the
    bucket pad unit (lane_tile * n_shards) follows automatically through
    kernels.ops._pad_unit.  Raises ValueError (naming the W/k geometry and
    bytes) when even one quantum of lanes over-commits the budget —
    flooring silently would launch kernels past the budget."""
    from .counting import (gpu_lane_state_words, kernel_scratch_words,
                           tail_scratch_words)
    if cfg.backend == "pallas_gpu":
        per_lane = gpu_lane_state_words(cfg)
        tile = (reg_budget_words // (per_lane * GPU_LANE_QUANTUM)) \
            * GPU_LANE_QUANTUM
        if tile == 0:
            raise ValueError(
                f"one warp of live DP state does not fit the register "
                f"budget: geometry W={cfg.W} k={cfg.k} needs "
                f"{per_lane * GPU_LANE_QUANTUM:,} words for "
                f"{GPU_LANE_QUANTUM} lanes but reg_budget_words="
                f"{reg_budget_words:,}")
        return int(min(tile, GPU_LANE_CEILING))
    assert quantum > 0 and ceiling >= quantum
    per_lane = 4 * max(kernel_scratch_words(cfg, 1),
                       tail_scratch_words(cfg, 1))
    tile = (vmem_budget_bytes // (per_lane * quantum)) * quantum
    if tile == 0:
        # flooring to one quantum here would SILENTLY over-commit VMEM:
        # the caller asked for a budget the geometry cannot meet, and the
        # kernel would launch with more scratch than the budget allows
        raise ValueError(
            f"one lane quantum of scratch does not fit the VMEM budget: "
            f"geometry W={cfg.W} k={cfg.k} needs {per_lane * quantum:,} "
            f"bytes for {quantum} lanes but vmem_budget_bytes="
            f"{vmem_budget_bytes:,}")
    return int(min(tile, ceiling))


def _slice_rev(seq, pos, width, length):
    """Per-problem: take seq[pos:pos+width], reversed, with the `length` real
    chars packed at the front (sentinel padding after).  seq must be padded
    with >= width sentinels at the end."""
    def one(s, p, ln):
        w = jax.lax.dynamic_slice(s, (p,), (width,))
        rev = w[::-1]
        idx = (jnp.arange(width) + (width - ln)) % width
        return rev[idx]
    return jax.vmap(one)(seq, pos, length)


def _append_ops(buf, off, ops, nops, active):
    """Scatter window ops into the per-problem op buffer at offset `off`
    (vmapped per row: keeps the scatter local to each batch shard)."""
    B, max_w = ops.shape
    pos = off[:, None] + jnp.arange(max_w, dtype=jnp.int32)[None, :]
    valid = (jnp.arange(max_w)[None, :] < nops[:, None]) & active[:, None]
    pos = jnp.where(valid, pos, buf.shape[1])  # OOB -> dropped
    return jax.vmap(lambda row, px, ox: row.at[px].set(ox, mode="drop"))(
        buf, pos, ops)


def _fused(cfg: AlignerConfig) -> bool:
    """Whether the windows run the fused DC + traceback Pallas kernels (the
    DENT band never leaves the chip; 'pallas_gpu' lowers the same kernel
    bodies through Triton) rather than the jnp DC and traceback."""
    return cfg.store == "band" and cfg.backend in ("pallas_fused",
                                                   "pallas_gpu")


#: the per-lane fields of a window that a lane commits from its rung
_COMMIT_KEYS = ("ops", "n_ops", "read_adv", "ref_adv", "cost")


def _window_out(tb, max_ops, **extra):
    """A rung's window as the ladder uses it: the traceback's
    `_COMMIT_KEYS` fields, its op rows padded to `max_ops` (the top rung's
    width) so that every rung's rows select into one buffer, and
    `extra`."""
    out = {key: tb[key] for key in _COMMIT_KEYS}
    pad = max_ops - out["ops"].shape[1]
    out["ops"] = jnp.pad(out["ops"], ((0, 0), (0, pad)),
                         constant_values=OP_NONE)
    return dict(out, **extra)


def _square_window(pat, txt, cfg: AlignerConfig, max_ops: int, mesh):
    """One rung's main window on every lane: the committed traceback of
    the first W - O read characters, plus `solved` and the DC levels run."""
    W, stride = cfg.W, cfg.stride
    if _fused(cfg):
        from ..kernels.ops import default_interpret, genasm_tb_fused_op
        tb = genasm_tb_fused_op(pat, txt, cfg=cfg, commit_limit=stride,
                                max_ops=cfg.tb_max_ops,
                                max_steps=cfg.tb_max_steps,
                                interpret=default_interpret(cfg.backend),
                                mesh=mesh)
        solved, levels = tb["solved"], tb["levels"]
    else:
        wfull = jnp.full((pat.shape[0],), W, jnp.int32)
        res = dc(pat, txt, wfull, wfull, cfg, mesh=mesh)
        tb = traceback(res.store, pat, txt, wfull, wfull, res.dist,
                       jnp.int32(stride), cfg=cfg, mode=cfg.store,
                       max_ops=cfg.tb_max_ops, max_steps=cfg.tb_max_steps)
        solved, levels = res.solved, res.levels_run
    return _window_out(tb, max_ops, solved=solved, levels=levels)


def _tail_window(refs, ref_pos, pat_t, m_tail, n_rem, cfg: AlignerConfig,
                 max_ops: int, mesh):
    """One rung's tail window on every lane: the remaining read (at most W
    characters) against the remaining reference, end to end, in a text
    window of W + 4k characters."""
    W = cfg.W
    wt = self_tail_width(cfg)
    n_tail = jnp.clip(n_rem, 0, wt)
    txt_t = _slice_rev(refs, ref_pos, wt, n_tail)
    if _fused(cfg):
        # rectangular-tail fused kernel: the tail's SENE store is walked
        # on-chip too (bit-identical to the jnp 'and'-store path below)
        from ..kernels.ops import default_interpret, genasm_tail_fused_op
        tb = genasm_tail_fused_op(
            pat_t, txt_t, m_tail, n_tail, cfg=cfg, n_text=wt,
            commit_limit=2 * (W + wt), max_ops=W + wt,
            max_steps=W + wt + 4,
            interpret=default_interpret(cfg.backend), mesh=mesh)
        solved = tb["solved"]
    else:
        res = dc_jmajor(pat_t, txt_t, m_tail, n_tail, k=cfg.k, n=wt,
                        nw=cfg.nw, store="and")
        tb = traceback(res.store, pat_t, txt_t, m_tail, n_tail, res.dist,
                       jnp.int32(2 * (W + wt)), cfg=cfg, mode="and",
                       max_ops=W + wt, max_steps=W + wt + 4)
        solved = res.solved
    return _window_out(tb, max_ops, solved=solved)


def _escalate(cfgs, need, run):
    """Try one window at each rung of the ladder in turn.  The base rung
    runs on every lane; rung r runs, under ``lax.cond``, only when some
    lane in `need` is still unsolved below it, and a skipped rung solves
    nothing.  Each rung's kernels run under the named scope ``rung_k<k>``.
    `run(r)` returns rung r's `_window_out`: with `solved` and, for a
    main window, the DC `levels` run.

    Returns (pick, won, ran): each lane's `_COMMIT_KEYS` fields from the
    first rung that solved it, plus `levels` summed over the rungs that
    ran; that rung's index per lane (-1: unsolved at the top rung); and
    per rung, 1 if its kernel ran."""
    pick, won, ran = None, None, []
    for r, cfg_r in enumerate(cfgs):
        with jax.named_scope(f"rung_k{cfg_r.k}"):
            if r == 0:
                out, did = run(r), jnp.bool_(True)
            else:
                did = jnp.any(need)
                spec = jax.eval_shape(partial(run, r))
                out = jax.lax.cond(
                    did, partial(run, r),
                    lambda spec=spec: jax.tree_util.tree_map(
                        lambda s: jnp.zeros(s.shape, s.dtype), spec))
        win = need & out["solved"]
        if r == 0:
            pick = {key: out[key] for key in _COMMIT_KEYS}
            pick["levels"] = out.get("levels", jnp.int32(0))
            won = jnp.where(win, 0, -1).astype(jnp.int32)
        else:
            for key in _COMMIT_KEYS:
                sel = win.reshape(win.shape + (1,) * (out[key].ndim - 1))
                pick[key] = jnp.where(sel, out[key], pick[key])
            pick["levels"] = pick["levels"] + out.get("levels", 0)
            won = jnp.where(win, r, won)
        need = need & ~win
        ran.append(did.astype(jnp.int32))
    return pick, won, jnp.stack(ran)


def _window_ladder(reads, read_len, refs, ref_len, *, cfgs, max_read_len: int,
                   mesh):
    """The window loop under the k-doubling ladder `cfgs` (one rung: plain
    windowed alignment).  Every window step slices the read and reference
    windows once and runs the base rung on every lane; a lane whose window
    the base rung cannot align tries the next rung, and so on, each higher
    rung's kernel running only in steps where some lane needs it
    (`_escalate`).  An active lane commits the ops, advance and cost of the
    first rung that solved its window; a window unsolved at the top rung
    fails its lane, which keeps its partial progress.  The tail escalates
    the same way, each rung under its own text width W + 4k and bounds.

    A window whose edit distance d* is at most k aligns the same at every
    rung >= k (the text window is W characters at every rung, the walk
    visits levels <= d* only, the DENT band holds every cell within d* of
    the diagonal and the op and step budgets are not reached), so each
    lane's result equals a per-read rerun of the whole read at its lowest
    sufficient rung: the host rescue loop (core.aligner)."""
    from ..distributed.sharding import constrain_pairs
    reads, read_len, refs, ref_len = constrain_pairs(
        mesh, reads, read_len, refs, ref_len)
    base, top = cfgs[0], cfgs[-1]
    B = reads.shape[0]
    W = base.W
    nm = n_main_windows(max_read_len, base)
    op_budget = total_op_budget(max_read_len, top)
    max_ops_w = top.tb_max_ops
    max_ops_t = W + self_tail_width(top)
    n_rungs = len(cfgs)

    read_len = jnp.asarray(read_len, jnp.int32)
    ref_len = jnp.asarray(ref_len, jnp.int32)

    def active_lanes(read_pos, failed):
        return (read_len - read_pos > W) & ~failed

    def any_active(carry):
        (step, read_pos, _, _, _, failed, *_), _ = carry
        return (step < nm) & jnp.any(active_lanes(read_pos, failed))

    def commit(won, ok, rung_hi, commits):
        """Per-lane bookkeeping of the rung each committed window used."""
        rung_hi = jnp.where(ok, jnp.maximum(rung_hi, won), rung_hi)
        commits = commits + jax.nn.one_hot(
            jnp.where(ok, won, -1), n_rungs, dtype=jnp.int32)
        return rung_hi, commits

    @jax.named_scope("window_step")
    def append_main(carry):
        (step, read_pos, ref_pos, off, dist, failed, rung_hi, commits,
         rung_steps, levels), buf = carry
        active = active_lanes(read_pos, failed)
        wfull = jnp.full((B,), W, jnp.int32)
        pat = _slice_rev(reads, read_pos, W, wfull)
        txt = _slice_rev(refs, ref_pos, W, wfull)
        tb, won, ran = _escalate(
            cfgs, active,
            lambda r: _square_window(pat, txt, cfgs[r], max_ops_w, mesh))
        ok = active & (won >= 0)
        buf = _append_ops(buf, off, tb["ops"], jnp.where(ok, tb["n_ops"], 0),
                          ok)
        rung_hi, commits = commit(won, ok, rung_hi, commits)
        st = (
            step + 1,
            jnp.where(ok, read_pos + tb["read_adv"], read_pos),
            jnp.where(ok, ref_pos + tb["ref_adv"], ref_pos),
            jnp.where(ok, off + tb["n_ops"], off),
            jnp.where(ok, dist + tb["cost"], dist),
            failed | (active & ~ok),
            rung_hi, commits, rung_steps + ran,
            levels + tb["levels"],
        )
        return st, buf

    zeros = jnp.zeros((B,), jnp.int32)
    buf = jnp.full((B, op_budget), OP_NONE, jnp.uint8)
    state = (jnp.int32(0), zeros, zeros, zeros, zeros,
             jnp.zeros((B,), bool), zeros,
             jnp.zeros((B, n_rungs), jnp.int32),
             jnp.zeros((n_rungs,), jnp.int32), jnp.int32(0))
    state, buf = jax.lax.while_loop(any_active, append_main, (state, buf))
    (_, read_pos, ref_pos, off, dist, failed, rung_hi, commits, rung_steps,
     levels) = state

    # ---- tail window: remaining read (in (O, W]) vs remaining ref, global ----
    with jax.named_scope("tail_window"):
        m_tail = jnp.clip(read_len - read_pos, 0, W)
        n_rem = ref_len - ref_pos
        pat_t = _slice_rev(reads, read_pos, W, m_tail)

        def run_tail(r):
            cfg_r = cfgs[r]
            out = _tail_window(refs, ref_pos, pat_t, m_tail, n_rem, cfg_r,
                               max_ops_t, mesh)
            tail_bad = ((n_rem > self_tail_width(cfg_r))
                        | (n_rem < jnp.maximum(m_tail - 2 * cfg_r.k, 0)))
            return dict(out, solved=out["solved"] & ~tail_bad)

        tb_t, won_t, ran_t = _escalate(cfgs, ~failed, run_tail)
        t_ok = ~failed & (won_t >= 0)
        buf = _append_ops(buf, off, tb_t["ops"],
                          jnp.where(t_ok, tb_t["n_ops"], 0), t_ok)
        rung_hi, commits = commit(won_t, t_ok, rung_hi, commits)
        n_ops = jnp.where(t_ok, off + tb_t["n_ops"], off)
        dist = jnp.where(t_ok, dist + tb_t["cost"], dist)
        failed = ~t_ok
        read_end = jnp.where(t_ok, read_pos + tb_t["read_adv"], read_pos)
        ref_end = jnp.where(t_ok, ref_pos + tb_t["ref_adv"], ref_pos)
    rung_steps = rung_steps + ran_t

    ks = jnp.asarray([c.k for c in cfgs], jnp.int32)
    return {"ops": buf, "n_ops": n_ops, "dist": dist, "failed": failed,
            "k_used": jnp.where(failed, 0, ks[rung_hi]),
            "read_consumed": read_end, "ref_consumed": ref_end,
            "levels_run_total": levels,
            "rounds_run": jnp.sum((rung_steps > 0).astype(jnp.int32)),
            "n_rounds": jnp.int32(n_rungs),
            "window_steps": jnp.sum(rung_steps),
            "rung_window_steps": rung_steps, "rung_commits": commits}


#: the outputs of plain windowed alignment (align_pairs)
_PLAIN_KEYS = ("ops", "n_ops", "dist", "failed", "read_consumed",
               "ref_consumed", "levels_run_total", "window_steps")


@partial(jax.jit, static_argnames=("cfg", "max_read_len", "mesh"))
def align_pairs(reads, read_len, refs, ref_len, *, cfg: AlignerConfig,
                max_read_len: int, mesh=None):
    """Batched windowed alignment at one k: the one-rung window ladder.

    reads: (B, Lr_pad) uint8 codes, sentinel-padded by >= W past read_len.
    refs:  (B, Lf_pad) uint8 codes, sentinel-padded by >= W+4k past ref_len.
    Returns dict with front-first op buffer, n_ops, dist, failed, read/ref
    consumption, window ET stats and ``window_steps`` (main-window steps
    the loop ran + 1 tail, every lane runs them).  The main-window loop
    ends at the last active lane: once every read has <= W characters left
    or has failed, a step would commit nothing, so none runs;
    ``n_main_windows(max_read_len)`` only bounds it.  The loop body runs
    under the named scope ``window_step``, the tail under ``tail_window``
    and each kernel under ``rung_k<k>``.

    `mesh`: shard the pair axis over the mesh's data axes — the Pallas
    dispatches run under shard_map (each device fills/walks its local
    lanes on-chip) and the jnp paths are GSPMD-constrained.  Bit-identical
    to the unsharded run on every output (tests/test_multidevice.py).
    """
    out = _window_ladder(reads, read_len, refs, ref_len, cfgs=(cfg,),
                         max_read_len=max_read_len, mesh=mesh)
    return {key: out[key] for key in _PLAIN_KEYS}


def rescue_schedule(cfg: AlignerConfig, rescue_rounds: int):
    """The k-doubling ladder: round r runs with k_r = min(k * 2**r, W - 1),
    deduplicated once the cap is hit.  Single source of truth for the
    host-loop and on-device rescue paths (and for padding geometry).

    A Pallas rung keeps the previous rung's lane tile unless its doubled-k
    stores no longer fit the planner's VMEM budget there; it then drops to
    its own planned tile (`plan_lane_tile`), so a tile planned for the base
    k never launches a rung kernel that the TPU compiler refuses.  Per-lane
    results do not depend on the tile."""
    cfgs = [cfg]
    for _ in range(rescue_rounds):
        new_k = min(cfgs[-1].k * 2, cfg.W - 1)
        if new_k == cfgs[-1].k:
            break
        rung = dataclasses.replace(cfgs[-1], k=new_k)
        if rung.backend in PALLAS_BACKENDS:
            rung = dataclasses.replace(
                rung, lane_tile=min(rung.lane_tile, plan_lane_tile(rung)))
        cfgs.append(rung)
    return tuple(cfgs)


@partial(jax.jit,
         static_argnames=("cfg", "max_read_len", "rescue_rounds", "mesh"))
def align_pairs_rescued(reads, read_len, refs, ref_len, *, cfg: AlignerConfig,
                        max_read_len: int, rescue_rounds: int = 2, mesh=None):
    """Multi-round k-doubling rescue, entirely on-device: one compile, zero
    host round-trips between rounds, and one window loop that escalates
    per window (`_window_ladder`): a rung's kernel runs only in the window
    steps where some lane's window failed every rung below it.  Per lane
    bit-identical to the host numpy rescue loop in core.aligner, which
    reruns each failed read whole at the next k.

    refs must be sentinel-padded for the FINAL round's tail width
    (``self_tail_width(rescue_schedule(cfg, rescue_rounds)[-1])``); reads
    need the usual >= W padding.  Returns the align_pairs dict plus k_used
    (the highest rung a solved lane committed a window at; 0 where never
    solved), rounds_run (rungs whose kernel ran at all) and n_rounds;
    ``rung_window_steps`` (one int32 per rung of the ladder) holds the
    window steps in which each rung's kernel ran, + 1 if its tail ran, and
    ``window_steps`` is their sum; ``rung_commits`` (B, rungs) counts the
    windows, tail included, each lane committed at each rung.

    `mesh` threads through to every rung's kernels: the whole ladder runs
    sharded over the pair axes, and each rung's `any` gate is a GLOBAL any
    (GSPMD reduces it across shards), so a rung runs on every device
    whenever any shard needs it — exactly the single-device schedule,
    hence bit-identical results.
    """
    return _window_ladder(reads, read_len, refs, ref_len,
                          cfgs=rescue_schedule(cfg, rescue_rounds),
                          max_read_len=max_read_len, mesh=mesh)
