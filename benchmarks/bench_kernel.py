"""Kernel-level table: per-window DC cost for the improved vs unimproved
fills (jnp path timed on CPU; the Pallas kernel is validated in interpret
mode — its on-chip working set is reported against the 16MB VMEM budget,
which is the paper's 'entire DP table fits on-chip' claim), plus the fused
DC+TB kernel vs the split DC-kernel + host-traceback pipeline.

Interpret-mode wall times on CPU do not model TPU speed; the
architecturally meaningful fused-vs-split numbers are the HBM bytes per
window (the band round-trip the fusion deletes), reported alongside.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bitops import SENTINEL_PAT, SENTINEL_TEXT
from repro.core.config import AlignerConfig
from repro.core.genasm import dc_dmajor, dc_jmajor
from repro.core.traceback import traceback
from repro.kernels.genasm_dc import (default_max_ops, default_max_steps,
                                     vmem_bytes, vmem_bytes_tail)
from repro.kernels.ops import (default_interpret, genasm_dc_op,
                               genasm_tail_fused_op, genasm_tb_fused_op)


def _t(fn, reps=3):
    fn()
    ts = []
    for _ in range(reps):
        t0 = time.time(); fn(); ts.append(time.time() - t0)
    return sorted(ts)[len(ts) // 2]


def table(B=4096, W=64, k=12):
    rng = np.random.default_rng(0)
    pat = jnp.array(rng.integers(0, 4, (B, W)), jnp.int32)
    txt = jnp.array(rng.integers(0, 4, (B, W)), jnp.int32)
    wl = jnp.full((B,), W, jnp.int32)
    cfg = AlignerConfig(W=W, O=24, k=k)

    t_imp = _t(lambda: jax.block_until_ready(
        dc_dmajor(pat, txt, cfg=cfg).dist))
    t_base = _t(lambda: jax.block_until_ready(
        dc_jmajor(pat, txt, wl, wl, k=k, n=W, nw=cfg.nw,
                  store="edges4").dist))
    rows = [
        ("kernel/dc_improved_batch4096", t_imp * 1e6,
         f"us_per_window={t_imp/B*1e6:.2f}"),
        ("kernel/dc_unimproved_batch4096", t_base * 1e6,
         f"us_per_window={t_base/B*1e6:.2f}"),
        ("kernel/vmem_tile512_bytes", 0.0,
         f"{vmem_bytes(cfg, 512)}_of_16MiB="
         f"{vmem_bytes(cfg, 512)/(16*2**20):.2%}"),
    ]
    derived = {"dc_speedup_jnp_cpu": t_base / t_imp,
               "vmem_fraction": vmem_bytes(cfg, 512) / (16 * 2**20)}

    f_rows, f_derived = fused_vs_split(B=min(B, 256))
    rows += f_rows
    derived.update(f_derived)

    t_rows, t_derived = tail_fused_vs_split(B=min(B, 128))
    rows += t_rows
    derived.update(t_derived)

    m_rows, m_derived = footprint_rows()
    rows += m_rows
    derived.update(m_derived)
    return rows, derived


def footprint_rows(W=64, O=24, k=12, tile=256):
    """Declared-scratch footprint of the tail kernel, banded (Scrooge-style
    store elimination; the default wherever the band is a strict win) vs
    the full-store fallback, at the headline geometry — plus the lane-tile
    ceiling the bucket planner buys back from the savings.  Pure shape
    math (no compiles); the scratch-accounting suite proves these equal
    the kernels' declared ``pltpu.VMEM`` shapes.  The ``vmem_bytes_*``
    derived keys are gated by benchmarks.compare: they may only shrink."""
    from repro.core.windowing import plan_lane_tile
    cfg = AlignerConfig(W=W, O=O, k=k)             # tail_store='auto' → band
    cfg_full = AlignerConfig(W=W, O=O, k=k, tail_store="full")
    banded = vmem_bytes_tail(cfg, tile)
    full = vmem_bytes_tail(cfg_full, tile)
    square = vmem_bytes(cfg, tile)
    lt_band, lt_full = plan_lane_tile(cfg), plan_lane_tile(cfg_full)
    gname = f"w{W}k{k}_tile{tile}"
    rows = [
        (f"kernel/tail_scratch_banded_{gname}", 0.0,
         f"{banded}B_of_16MiB={banded/(16*2**20):.2%}"),
        (f"kernel/tail_scratch_full_{gname}", 0.0,
         f"{full}B_of_16MiB={full/(16*2**20):.2%}"),
        (f"kernel/tail_store_reduction_{gname}", 0.0,
         f"{full/banded:.2f}x_full_over_banded"),
        (f"kernel/planned_lane_tile_{gname}", 0.0,
         f"banded={lt_band}_full={lt_full}_at_16MiB_budget"),
    ]
    derived = {
        f"vmem_bytes_tail_{gname}_banded": banded,
        f"vmem_bytes_tail_{gname}_full": full,
        f"tail_store_reduction_{gname}": full / banded,
        f"vmem_bytes_square_{gname}": square,
        f"planned_lane_tile_{gname}_banded": lt_band,
        f"planned_lane_tile_{gname}_full": lt_full,
    }
    return rows, derived


def fused_vs_split(B=256, W=32, k=7, tile=128):
    """Fused DC+TB kernel vs split DC kernel + host jnp traceback, both in
    interpret mode (small geometry: interpret-mode walks are host loops).
    Also reports the per-window band HBM round-trip the fusion removes."""
    rng = np.random.default_rng(1)
    cfg = AlignerConfig(W=W, O=max(1, W // 3), k=k)
    pat = jnp.array(rng.integers(0, 4, (B, W)), jnp.int32)
    txt = jnp.array(rng.integers(0, 4, (B, W)), jnp.int32)
    wl = jnp.full((B,), W, jnp.int32)
    stride = cfg.stride
    max_ops, max_steps = default_max_ops(cfg), default_max_steps(cfg)
    interpret = default_interpret(cfg.backend)

    def split():
        dist, band, lvl = genasm_dc_op(pat, txt, cfg=cfg, tile=tile,
                                       interpret=interpret)
        tb = traceback({"Rb": band}, pat, txt, wl, wl, dist,
                       jnp.int32(stride), cfg=cfg, mode="band",
                       max_ops=max_ops, max_steps=max_steps)
        return tb["n_ops"]

    def fused():
        return genasm_tb_fused_op(pat, txt, cfg=cfg, commit_limit=stride,
                                  max_ops=max_ops, max_steps=max_steps,
                                  tile=tile, interpret=interpret)["n_ops"]

    t_split = _t(lambda: jax.block_until_ready(split()))
    t_fused = _t(lambda: jax.block_until_ready(fused()))
    # band round-trip bytes the fused kernel never moves (write + read back)
    band_bytes = 2 * (k + 1) * cfg.ncols_band * cfg.nwb * 4
    out_bytes = (max_ops + 8) * 4
    rows = [
        (f"kernel/split_dc_plus_host_tb_B{B}_W{W}", t_split * 1e6,
         f"us_per_window={t_split/B*1e6:.2f}_interpret"),
        (f"kernel/fused_dc_tb_B{B}_W{W}", t_fused * 1e6,
         f"us_per_window={t_fused/B*1e6:.2f}_interpret"),
        ("kernel/fused_hbm_bytes_saved_per_window", 0.0,
         f"band_roundtrip={band_bytes}B_vs_ops_out={out_bytes}B"),
    ]
    derived = {"fused_vs_split_wall": t_split / t_fused,
               "fused_hbm_traffic_ratio": out_bytes / (band_bytes + out_bytes)}
    return rows, derived


def tail_fused_vs_split(B=128, W=32, k=7, tile=64):
    """Rectangular-tail window: the fused tail kernel vs the jnp 'and'-store
    fill + host traceback it replaces, on ragged (m_len <= W, n_len <= wt)
    tails like core.windowing produces.  Also reports the store round-trip
    bytes the fusion removes and the tail kernel's VMEM footprint."""
    rng = np.random.default_rng(2)
    cfg = AlignerConfig(W=W, O=max(1, W // 3), k=k)
    wt = W + 4 * k
    max_ops_t, max_steps_t = W + wt, W + wt + 4
    interpret = default_interpret(cfg.backend)
    pat = np.full((B, W), SENTINEL_PAT, np.uint8)
    txt = np.full((B, wt), SENTINEL_TEXT, np.uint8)
    ml = np.zeros(B, np.int32)
    nl = np.zeros(B, np.int32)
    for b in range(B):
        m = int(rng.integers(W // 2, W + 1))
        n = int(np.clip(m + rng.integers(-k, k + 1), 1, wt))
        p = rng.integers(0, 4, m).astype(np.uint8)
        t = p.copy()
        for _ in range(int(rng.integers(0, k))):
            t[rng.integers(0, len(t))] = rng.integers(0, 4)
        t = t[:n] if len(t) >= n else np.concatenate(
            [t, rng.integers(0, 4, n - len(t)).astype(np.uint8)])
        pat[b, :m] = p[::-1]
        txt[b, :n] = t[::-1]
        ml[b], nl[b] = m, n
    patj, txtj = jnp.asarray(pat), jnp.asarray(txt)
    mlj, nlj = jnp.asarray(ml), jnp.asarray(nl)

    def split():
        res = dc_jmajor(patj, txtj, mlj, nlj, k=k, n=wt, nw=cfg.nw,
                        store="and")
        tb = traceback(res.store, patj, txtj, mlj, nlj, res.dist,
                       jnp.int32(2 * (W + wt)), cfg=cfg, mode="and",
                       max_ops=max_ops_t, max_steps=max_steps_t)
        return tb["n_ops"]

    def fused():
        return genasm_tail_fused_op(patj, txtj, mlj, nlj, cfg=cfg, n_text=wt,
                                    commit_limit=2 * (W + wt),
                                    max_ops=max_ops_t, max_steps=max_steps_t,
                                    tile=tile, interpret=interpret)["n_ops"]

    t_split = _t(lambda: jax.block_until_ready(split()))
    t_fused = _t(lambda: jax.block_until_ready(fused()))
    # the full SENE store the split path round-trips per problem per tail
    store_bytes = 2 * (k + 1) * (wt + 1) * cfg.nw * 4
    out_bytes = (max_ops_t + 8) * 4
    vmem = vmem_bytes_tail(cfg, 256, n_text=wt)
    rows = [
        (f"kernel/tail_split_and_store_B{B}_W{W}", t_split * 1e6,
         f"us_per_tail={t_split/B*1e6:.2f}_interpret"),
        (f"kernel/tail_fused_B{B}_W{W}", t_fused * 1e6,
         f"us_per_tail={t_fused/B*1e6:.2f}_interpret"),
        ("kernel/tail_fused_hbm_bytes_saved", 0.0,
         f"store_roundtrip={store_bytes}B_vs_ops_out={out_bytes}B"),
        ("kernel/tail_vmem_tile256_bytes", 0.0,
         f"{vmem}_of_16MiB={vmem/(16*2**20):.2%}"),
    ]
    derived = {"tail_fused_vs_split_wall": t_split / t_fused,
               "tail_hbm_traffic_ratio": out_bytes / (store_bytes + out_bytes),
               "tail_vmem_fraction": vmem / (16 * 2**20)}
    return rows, derived
