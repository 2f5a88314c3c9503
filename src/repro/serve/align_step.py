"""Distributed alignment step: the paper's batched aligner sharded over
the production mesh (embarrassingly data-parallel across pairs; stats are
psum'd by GSPMD when reduced).  Used by the alignment service and the
aligner dry-run/roofline cell.

One factory serves every variant: ``make_align_step(cfg, L, mesh)`` is the
plain windowed step, ``make_align_step(cfg, L, mesh, rescue_rounds=r)``
the on-device k-doubling ladder — both thread the mesh all the way into
``core.windowing`` so the Pallas hot path runs shard_map'd per device
(kernels.ops), not just the jnp fills.  The former trio of near-identical
factories (plain / rescued / per-call wrappers) collapsed into this one;
``make_align_step_rescued`` remains as a thin alias."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ..core.config import AlignerConfig
from ..core.windowing import (align_pairs, align_pairs_rescued,
                              bucket_avals)
from ..distributed.sharding import pair_shardings


def align_step(reads, read_len, refs, ref_len, *, cfg: AlignerConfig,
               max_read_len: int, rescue_rounds: int | None = None,
               mesh=None):
    """One batched alignment step + summary stats.  rescue_rounds=None runs
    plain ``align_pairs``; an int runs the on-device k-doubling ladder
    (every round inside this one jitted step — no host round-trips between
    rounds on any shard).  Summary stats reduce across the whole batch
    (collectives over the pair axes when sharded)."""
    if rescue_rounds is None:
        out = align_pairs(reads, read_len, refs, ref_len, cfg=cfg,
                          max_read_len=max_read_len, mesh=mesh)
    else:
        out = align_pairs_rescued(reads, read_len, refs, ref_len, cfg=cfg,
                                  max_read_len=max_read_len,
                                  rescue_rounds=rescue_rounds, mesh=mesh)
    summary = {
        "n_failed": jnp.sum(out["failed"].astype(jnp.int32)),
        "total_edits": jnp.sum(out["dist"]),
        "total_ops": jnp.sum(out["n_ops"]),
    }
    if rescue_rounds is not None:
        summary["n_rescued"] = jnp.sum(
            (~out["failed"] & (out["k_used"] > cfg.k)).astype(jnp.int32))
        summary["rounds_run"] = out["rounds_run"]
    return out, summary


def make_align_step(cfg: AlignerConfig, max_read_len: int, mesh,
                    rescue_rounds: int | None = None):
    """The align-step factory (plain or rescued, one code path) — also the
    executable builder behind ``repro.api.AlignSession``: the session
    AOT-lowers this jit per length bucket (``.lower(*bucket_avals)
    .compile()``) so steady-state serving never re-traces.

    With ``mesh=None`` it is a plain jit (single device, no shardings).
    Sharded, out_shardings are explicit: without them GSPMD replicates the
    CIGAR buffer to every device (a ~1.7 GB all-gather for 128k pairs —
    §Perf aligner iteration in EXPERIMENTS.md).  Per-lane outputs (k_used,
    the op buffer, consumption) shard with the batch; scalar stats and
    round counters replicate."""
    fn = partial(align_step, cfg=cfg, max_read_len=max_read_len,
                 rescue_rounds=rescue_rounds, mesh=mesh)
    if mesh is None:
        return jax.jit(fn)
    bsh, vsh, rep = pair_shardings(mesh)
    out_lanes = {"ops": bsh, "n_ops": vsh, "dist": vsh, "failed": vsh,
                 "read_consumed": vsh, "ref_consumed": vsh,
                 "levels_run_total": rep, "window_steps": rep}
    sum_sh = {"n_failed": rep, "total_edits": rep, "total_ops": rep}
    if rescue_rounds is not None:
        out_lanes = dict(out_lanes, k_used=vsh, rounds_run=rep, n_rounds=rep,
                         rung_window_steps=rep, rung_commits=bsh)
        sum_sh = dict(sum_sh, n_rescued=rep, rounds_run=rep)
    return jax.jit(fn, in_shardings=(bsh, vsh, bsh, vsh),
                   out_shardings=(out_lanes, sum_sh))


def make_align_step_rescued(cfg: AlignerConfig, max_read_len: int, mesh,
                            rescue_rounds: int = 2):
    """Alias for make_align_step(..., rescue_rounds=rescue_rounds)."""
    return make_align_step(cfg, max_read_len, mesh,
                           rescue_rounds=rescue_rounds)


def align_input_specs(batch: int, read_len: int, cfg: AlignerConfig,
                      rescue_rounds: int = 0):
    """ShapeDtypeStructs for the aligner dry-run cell — the bucket_avals
    geometry with the dry-run's 1.3x read->ref length model.  With
    rescue_rounds, the ref padding covers the FINAL round's tail width
    (the contract of align_pairs_rescued)."""
    return bucket_avals(cfg, batch, read_len, int(read_len * 1.3),
                        rescue_rounds)
