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
only the loop's upper bound.  Problems whose window edit distance exceeds
k are flagged `failed` (callers may rescue by re-running those pairs with
a larger k, see core.aligner).
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


@partial(jax.jit, static_argnames=("cfg", "max_read_len", "mesh"))
def align_pairs(reads, read_len, refs, ref_len, *, cfg: AlignerConfig,
                max_read_len: int, mesh=None):
    """Batched windowed alignment.

    reads: (B, Lr_pad) uint8 codes, sentinel-padded by >= W past read_len.
    refs:  (B, Lf_pad) uint8 codes, sentinel-padded by >= W+4k past ref_len.
    Returns dict with front-first op buffer, n_ops, dist, failed, read/ref
    consumption, window ET stats and ``window_steps`` (main-window steps
    the loop ran + 1 tail, every lane runs them).  The main-window loop
    ends at the last active lane: once every read has <= W characters left
    or has failed, a step would commit nothing, so none runs;
    ``n_main_windows(max_read_len)`` only bounds it.  The loop body runs
    under the named scope ``window_step`` and the tail under
    ``tail_window``.

    `mesh`: shard the pair axis over the mesh's data axes — the Pallas
    dispatches run under shard_map (each device fills/walks its local
    lanes on-chip) and the jnp paths are GSPMD-constrained.  Bit-identical
    to the unsharded run on every output (tests/test_multidevice.py).
    """
    from ..distributed.sharding import constrain_pairs
    reads, read_len, refs, ref_len = constrain_pairs(
        mesh, reads, read_len, refs, ref_len)
    B = reads.shape[0]
    W, O, k, stride = cfg.W, cfg.O, cfg.k, cfg.stride
    nm = n_main_windows(max_read_len, cfg)
    wt = self_tail_width(cfg)
    op_budget = total_op_budget(max_read_len, cfg)
    max_ops_w = cfg.tb_max_ops
    max_steps_w = cfg.tb_max_steps
    max_ops_t = W + wt
    max_steps_t = W + wt + 4

    read_len = jnp.asarray(read_len, jnp.int32)
    ref_len = jnp.asarray(ref_len, jnp.int32)

    def active_lanes(read_pos, failed):
        return (read_len - read_pos > W) & ~failed

    def any_active(carry):
        (step, read_pos, _, _, _, failed, _), _ = carry
        return (step < nm) & jnp.any(active_lanes(read_pos, failed))

    @jax.named_scope("window_step")
    def append_main(carry):
        (step, read_pos, ref_pos, off, dist, failed, levels), buf = carry
        active = active_lanes(read_pos, failed)
        wfull = jnp.full((B,), W, jnp.int32)
        pat = _slice_rev(reads, read_pos, W, wfull)
        txt = _slice_rev(refs, ref_pos, W, wfull)
        if cfg.store == "band" and cfg.backend in ("pallas_fused",
                                                   "pallas_gpu"):
            # fused kernel: DC + committed traceback in one Pallas call, the
            # DENT band never leaves the chip — no host-side traceback walk
            # ('pallas_gpu' lowers the same kernel body through Triton)
            from ..kernels.ops import default_interpret, genasm_tb_fused_op
            tb = genasm_tb_fused_op(pat, txt, cfg=cfg, commit_limit=stride,
                                    max_ops=max_ops_w, max_steps=max_steps_w,
                                    interpret=default_interpret(cfg.backend),
                                    mesh=mesh)
            solved, levels_run = tb["solved"], tb["levels"]
        else:
            res = dc(pat, txt, wfull, wfull, cfg, mesh=mesh)
            tb = traceback(res.store, pat, txt, wfull, wfull,
                           res.dist, jnp.int32(stride), cfg=cfg,
                           mode=cfg.store, max_ops=max_ops_w,
                           max_steps=max_steps_w)
            solved, levels_run = res.solved, res.levels_run
        commit = active & solved
        buf = _append_ops(buf, off, tb["ops"], jnp.where(commit, tb["n_ops"], 0),
                          commit)
        st = (
            step + 1,
            jnp.where(commit, read_pos + tb["read_adv"], read_pos),
            jnp.where(commit, ref_pos + tb["ref_adv"], ref_pos),
            jnp.where(commit, off + tb["n_ops"], off),
            jnp.where(commit, dist + tb["cost"], dist),
            failed | (active & ~solved),
            levels + levels_run,
        )
        return st, buf

    buf = jnp.full((B, op_budget), OP_NONE, jnp.uint8)
    state = (jnp.int32(0), jnp.zeros((B,), jnp.int32),
             jnp.zeros((B,), jnp.int32), jnp.zeros((B,), jnp.int32),
             jnp.zeros((B,), jnp.int32), jnp.zeros((B,), bool), jnp.int32(0))
    state, buf = jax.lax.while_loop(any_active, append_main, (state, buf))
    steps, read_pos, ref_pos, off, dist, failed, levels = state

    # ---- tail window: remaining read (in (O, W]) vs remaining ref, global ----
    with jax.named_scope("tail_window"):
        m_tail = jnp.clip(read_len - read_pos, 0, W)
        n_rem = ref_len - ref_pos
        n_tail = jnp.clip(n_rem, 0, wt)
        tail_bad = (n_rem > wt) | (n_rem < jnp.maximum(m_tail - 2 * k, 0))
        pat_t = _slice_rev(reads, read_pos, W, m_tail)
        txt_t = _slice_rev(refs, ref_pos, wt, n_tail)
        if cfg.store == "band" and cfg.backend in ("pallas_fused",
                                                   "pallas_gpu"):
            # rectangular-tail fused kernel: the tail's SENE store is walked
            # on-chip too, so whole-read alignment never ships DP state to
            # HBM (bit-identical to the jnp 'and'-store path below)
            from ..kernels.ops import default_interpret, genasm_tail_fused_op
            tb_t = genasm_tail_fused_op(
                pat_t, txt_t, m_tail, n_tail, cfg=cfg, n_text=wt,
                commit_limit=2 * (W + wt), max_ops=max_ops_t,
                max_steps=max_steps_t,
                interpret=default_interpret(cfg.backend), mesh=mesh)
            solved_t = tb_t["solved"]
        else:
            res_t = dc_jmajor(pat_t, txt_t, m_tail, n_tail, k=k, n=wt,
                              nw=cfg.nw, store="and")
            tb_t = traceback(res_t.store, pat_t, txt_t, m_tail, n_tail,
                             res_t.dist, jnp.int32(2 * (W + wt)), cfg=cfg,
                             mode="and", max_ops=max_ops_t,
                             max_steps=max_steps_t)
            solved_t = res_t.solved
        t_ok = ~failed & ~tail_bad & solved_t
        buf = _append_ops(buf, off, tb_t["ops"],
                          jnp.where(t_ok, tb_t["n_ops"], 0), t_ok)
        n_ops = jnp.where(t_ok, off + tb_t["n_ops"], off)
        dist = jnp.where(t_ok, dist + tb_t["cost"], dist)
        failed = failed | tail_bad | ~solved_t
        read_end = jnp.where(t_ok, read_pos + tb_t["read_adv"], read_pos)
        ref_end = jnp.where(t_ok, ref_pos + tb_t["ref_adv"], ref_pos)

    return {"ops": buf, "n_ops": n_ops, "dist": dist, "failed": failed,
            "read_consumed": read_end, "ref_consumed": ref_end,
            "levels_run_total": levels, "window_steps": steps + 1}


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
    host round-trips between rounds.

    Round 0 is plain ``align_pairs``; each later round re-runs the whole
    batch with doubled k under a ``lax.cond`` gate (skipped outright when no
    lane is still failed), and a per-lane mask freezes already-solved lanes
    so their ops/dist/k_used never change — bit-identical per lane to the
    host numpy rescue loop in core.aligner.  Each round runs under the
    named scope ``rung_k<k>``, so its operators carry the rung in a device
    trace's ``op_name``.

    refs must be sentinel-padded for the FINAL round's tail width
    (``self_tail_width(rescue_schedule(cfg, rescue_rounds)[-1])``); reads
    need the usual >= W padding.  Returns the align_pairs dict plus k_used
    (0 where never solved), rounds_run and n_rounds; ``rung_window_steps``
    (one int32 per rung of the ladder) holds each rung's window steps (main
    windows + tail, its loop ending at its own last active lane; 0 for a
    rung that was skipped) and ``window_steps`` is their sum.

    `mesh` threads through to every round's align_pairs: the whole ladder
    runs sharded over the pair axes, and the `any(failed)` round gate is a
    GLOBAL any (GSPMD reduces it across shards), so a round runs on every
    device whenever any shard still has a failed lane — exactly the
    single-device schedule, hence bit-identical results.
    """
    cfgs = rescue_schedule(cfg, rescue_rounds)
    B = reads.shape[0]
    budget = total_op_budget(max_read_len, cfgs[-1])
    ops = jnp.full((B, budget), OP_NONE, jnp.uint8)
    n_ops = jnp.zeros((B,), jnp.int32)
    dist = jnp.zeros((B,), jnp.int32)
    rcon = jnp.zeros((B,), jnp.int32)
    fcon = jnp.zeros((B,), jnp.int32)
    k_used = jnp.zeros((B,), jnp.int32)
    failed = jnp.ones((B,), bool)
    levels = jnp.int32(0)
    rounds_run = jnp.int32(0)
    rung_steps = []

    for rnd, cfg_r in enumerate(cfgs):
        def run_round(cfg_r=cfg_r):
            with jax.named_scope(f"rung_k{cfg_r.k}"):
                return align_pairs(reads, read_len, refs, ref_len, cfg=cfg_r,
                                   max_read_len=max_read_len, mesh=mesh)
        if rnd == 0:
            out = run_round()
            ran = jnp.bool_(True)
        else:
            ran = jnp.any(failed)
            spec = jax.eval_shape(run_round)

            def skip_round(spec=spec):
                z = jax.tree_util.tree_map(
                    lambda s: jnp.zeros(s.shape, s.dtype), spec)
                z["failed"] = jnp.ones((B,), bool)  # nothing merges
                return z

            out = jax.lax.cond(ran, run_round, skip_round)
        newly = failed & ~out["failed"]
        # final round also merges the partial progress (committed main-window
        # ops/dist) of still-failed lanes, so rescue_rounds=0 is bit-equal to
        # plain align_pairs; a skipped final round has no failed lanes.
        upd = newly
        if rnd == len(cfgs) - 1:
            upd = newly | (failed & out["failed"])
        ops_r = jnp.pad(out["ops"], ((0, 0), (0, budget - out["ops"].shape[1])),
                        constant_values=OP_NONE)
        ops = jnp.where(upd[:, None], ops_r, ops)
        n_ops = jnp.where(upd, out["n_ops"], n_ops)
        dist = jnp.where(upd, out["dist"], dist)
        rcon = jnp.where(upd, out["read_consumed"], rcon)
        fcon = jnp.where(upd, out["ref_consumed"], fcon)
        k_used = jnp.where(newly, jnp.int32(cfg_r.k), k_used)
        failed = failed & out["failed"]
        levels = levels + out["levels_run_total"]
        rounds_run = rounds_run + ran.astype(jnp.int32)
        rung_steps.append(out["window_steps"])  # 0 when skipped

    rung_window_steps = jnp.stack(rung_steps)
    return {"ops": ops, "n_ops": n_ops, "dist": dist, "failed": failed,
            "k_used": k_used, "read_consumed": rcon, "ref_consumed": fcon,
            "levels_run_total": levels, "rounds_run": rounds_run,
            "n_rounds": jnp.int32(len(cfgs)),
            "window_steps": jnp.sum(rung_window_steps),
            "rung_window_steps": rung_window_steps}
