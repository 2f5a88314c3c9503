"""jit'd wrappers for the Pallas kernels (layout marshalling + dispatch).

Every op takes ``interpret`` from its caller; there is no default.
cfg.backend picks the lowering the kernel wrappers build
('pallas'/'pallas_fused' → Mosaic TPU with VMEM scratch, 'pallas_gpu' →
Triton with the store as a GMEM output block; see kernels.genasm_dc and
docs/backends.md).  ``default_interpret(cfg.backend)`` is the one place
that decides it from the platform: the Pallas interpreter on the CPU, the
compiled lowering on the platform it targets, and a ValueError anywhere
else — a TPU never runs a kernel interpreted.

Multi-device: every op takes an optional ``mesh``.  When given, the
pallas_call is wrapped in ``shard_map`` over the mesh's pair axes
(distributed.sharding.pair_axes), so each device runs the Pallas grid on
its local slice of the problem axis — the batch is padded to
``tile * n_pair_shards`` first so every shard holds whole kernel tiles.
Per-lane kernel results are independent of tile composition (padding
lanes solve at level 0, so only the per-tile ``levels`` statistic — the
analytic whole-tile-ET level count — sees them, and never as the max),
and the cross-lane ``levels`` reduction is taken OUTSIDE the shard_map on
the global array, so sharded dispatch is bit-identical to single-device
dispatch (asserted by tests/test_multidevice.py).

``cfg`` is a static jit argument, so knobs that pick a kernel body —
notably ``cfg.tail_store``, which selects the banded vs full-store tail
kernel — resolve at trace time and key separate executables.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core.config import AlignerConfig
from ..core.genasm import build_pm_ext
from ..distributed.sharding import n_pair_shards, pair_axes
from ..launch.mesh import shard_map
from .genasm_dc import (META_DFIN, META_DIST, META_LVL, META_NOPS, META_OK,
                        META_RD, META_RF, genasm_dc_pallas,
                        genasm_tail_fused_pallas, genasm_tb_fused_pallas)


#: jax.default_backend() values that carry a CUDA/ROCm device — the
#: platforms where the Triton lowering compiles for real
GPU_PLATFORMS = ("gpu", "cuda", "rocm")


def default_interpret(backend: str) -> bool:
    """Whether cfg.backend's kernels run in the Pallas interpreter here.

    True on the CPU only.  False on the platform the backend's lowering
    compiles for: a TPU for 'pallas'/'pallas_fused', a CUDA/ROCm device for
    'pallas_gpu'.  Any other pairing raises ValueError (e.g. 'pallas_gpu'
    on a TPU): interpreting there would hide the device behind a CPU-speed
    fallback.  Called with cfg.backend by every dispatch site
    (core.windowing, core.genasm)."""
    platform = jax.default_backend()
    if platform == "cpu":
        return True
    targets = GPU_PLATFORMS if backend == "pallas_gpu" else ("tpu",)
    if platform not in targets:
        raise ValueError(
            f"backend={backend!r} has no lowering for platform "
            f"{platform!r} (it compiles for {'/'.join(targets)}); choose "
            f"a backend that targets {platform!r} or 'jnp'")
    return False


def _pad_to_tile(pat_codes, text_codes, tile):
    """Pad the batch to a tile multiple with identical all-zero ('AAA...')
    lanes: they solve at level 0, so they never block the kernel's
    whole-tile early termination or inflate the levels stat (sentinel pads
    would sit at dist > k forever).  Padded lanes are trimmed after the
    kernel."""
    B = pat_codes.shape[0]
    pad = (-B) % tile
    if pad:
        pat_codes = jnp.pad(pat_codes, ((0, pad), (0, 0)))
        text_codes = jnp.pad(text_codes, ((0, pad), (0, 0)))
    return pat_codes, text_codes


def _to_kernel_layout(pat_codes, text_codes, cfg):
    pm = build_pm_ext(pat_codes, cfg.nw)                  # (B', 5, NW)
    pm_k = jnp.transpose(pm, (1, 2, 0))                   # (5, NW, B')
    text_k = jnp.transpose(text_codes.astype(jnp.int32), (1, 0))
    return pm_k, text_k


def _pad_unit(cfg, tile, mesh) -> tuple[int, int]:
    """(resolved lane tile, global batch pad unit): the batch pads to
    tile * n_shards so every mesh shard holds whole kernel tiles."""
    tile = tile or cfg.lane_tile
    return tile, tile * (n_pair_shards(mesh) if mesh is not None else 1)


def _shard_pairs(call, mesh, in_specs, out_specs):
    """Wrap a kernel-layout pallas dispatch in shard_map over the mesh's
    pair axes (problems are the INNERMOST axis of every kernel array, so
    the pair dim is the last entry of each spec).  Identity when there is
    nothing to shard over."""
    if mesh is None or n_pair_shards(mesh) == 1:
        return call
    return shard_map(call, mesh=mesh, in_specs=in_specs,
                     out_specs=out_specs, check=False)


def _pair_specs(mesh, ranks_in, ranks_out):
    """P specs placing the pair axes on the last dim of each operand."""
    ax = pair_axes(mesh) if mesh is not None else ()
    mk = lambda r: P(*([None] * (r - 1) + [ax]))
    return tuple(mk(r) for r in ranks_in), tuple(mk(r) for r in ranks_out)


@partial(jax.jit, static_argnames=("cfg", "tile", "interpret", "mesh"))
def genasm_dc_op(pat_codes, text_codes, *, cfg: AlignerConfig,
                 interpret: bool, tile: int | None = None, mesh=None):
    """Standard layout in, standard layout out.

    pat_codes/text_codes: (B, W).  Returns DCResult-like tuple
    (dist (B,), band (k+1, ncb, B, nwb), levels ()) — same as core.dc_dmajor
    store layout, so core.traceback consumes it unchanged.
    """
    B = pat_codes.shape[0]
    tile, unit = _pad_unit(cfg, tile, mesh)
    pat_codes, text_codes = _pad_to_tile(pat_codes, text_codes, unit)
    pm_k, text_k = _to_kernel_layout(pat_codes, text_codes, cfg)
    call = partial(genasm_dc_pallas, cfg=cfg, tile=tile, interpret=interpret)
    in_sp, out_sp = _pair_specs(mesh, (3, 2), (1, 4, 1))
    dist, band, lvl = _shard_pairs(call, mesh, in_sp, out_sp)(pm_k, text_k)
    band = jnp.transpose(band, (0, 1, 3, 2))              # (K1, ncb, B', nwb)
    return dist[:B], band[:, :, :B, :], jnp.max(lvl)


@partial(jax.jit, static_argnames=("cfg", "commit_limit", "max_ops",
                                   "max_steps", "tile", "interpret", "mesh"))
def genasm_tb_fused_op(pat_codes, text_codes, *, cfg: AlignerConfig,
                       commit_limit: int, max_ops: int, max_steps: int,
                       interpret: bool, tile: int | None = None,
                       mesh=None):
    """Fused GenASM-DC+TB: standard layout in, traceback dict out.

    pat_codes/text_codes: (B, W) reversed square windows (the windowed
    pipeline's main-window contract).  Returns the same dict as
    core.traceback (ops front-first uint8, n_ops, read_adv, ref_adv, cost,
    ok, d_final) plus dist and levels — the DENT band never leaves the
    kernel's VMEM scratch.
    """
    B = pat_codes.shape[0]
    tile, unit = _pad_unit(cfg, tile, mesh)
    pat_codes, text_codes = _pad_to_tile(pat_codes, text_codes, unit)
    pm_k, text_k = _to_kernel_layout(pat_codes, text_codes, cfg)
    call = partial(genasm_tb_fused_pallas, cfg=cfg, commit_limit=commit_limit,
                   max_ops=max_ops, max_steps=max_steps, tile=tile,
                   interpret=interpret)
    in_sp, out_sp = _pair_specs(mesh, (3, 2), (2, 2))
    ops_k, meta = _shard_pairs(call, mesh, in_sp, out_sp)(pm_k, text_k)
    ops = jnp.transpose(ops_k, (1, 0))[:B].astype(jnp.uint8)   # (B, max_ops)
    meta = meta[:, :B]
    return _unpack_meta(ops, meta, cfg)


def _unpack_meta(ops, meta, cfg):
    dist = meta[META_DIST]
    skip = dist > cfg.k
    return {
        "ops": ops,
        "n_ops": meta[META_NOPS],
        "read_adv": meta[META_RD],
        "ref_adv": meta[META_RF],
        "cost": jnp.where(skip, 0, dist - meta[META_DFIN]),
        "ok": meta[META_OK].astype(bool),
        "d_final": meta[META_DFIN],
        "dist": dist,
        "solved": ~skip,
        "levels": jnp.max(meta[META_LVL]),
    }


@partial(jax.jit, static_argnames=("cfg", "n_text", "commit_limit", "max_ops",
                                   "max_steps", "tile", "interpret", "mesh"))
def genasm_tail_fused_op(pat_codes, text_codes, m_len, n_len, *,
                         cfg: AlignerConfig, n_text: int, commit_limit: int,
                         max_ops: int, max_steps: int, interpret: bool,
                         tile: int | None = None, mesh=None):
    """Fused rectangular-tail GenASM-DC+TB: standard layout in, traceback
    dict out (same contract as the jnp dc_jmajor + traceback mode='and'
    tail path of core.windowing, bit for bit).

    pat_codes: (B, <= m_pad) reversed tail patterns (sentinel-padded past
    m_len); text_codes: (B, n_text) reversed tail texts (sentinel-padded
    past n_len).  Batch-padding lanes are trivial 'A' vs 'A' one-char
    problems (m_len = n_len = 1): they solve at level 0, so they never
    stall the kernel's (analytic or looped) whole-tile early termination,
    and are trimmed.

    The SENE store stays in VMEM scratch either way; cfg.tail_banded picks
    the Scrooge-style banded store vs the full-table fallback at trace
    time — bit-identical outputs, ~2x less scratch when banded."""
    B = pat_codes.shape[0]
    tile, unit = _pad_unit(cfg, tile, mesh)
    pat_codes, text_codes = _pad_to_tile(pat_codes, text_codes, unit)
    pad = (-B) % unit
    m_len = jnp.asarray(m_len, jnp.int32)
    n_len = jnp.asarray(n_len, jnp.int32)
    if pad:
        m_len = jnp.pad(m_len, ((0, pad),), constant_values=1)
        n_len = jnp.pad(n_len, ((0, pad),), constant_values=1)
    pm_k, text_k = _to_kernel_layout(pat_codes, text_codes, cfg)
    call = partial(genasm_tail_fused_pallas, cfg=cfg, n_text=n_text,
                   commit_limit=commit_limit, max_ops=max_ops,
                   max_steps=max_steps, tile=tile, interpret=interpret)
    in_sp, out_sp = _pair_specs(mesh, (3, 2, 2, 2), (2, 2))
    ops_k, meta = _shard_pairs(call, mesh, in_sp, out_sp)(
        pm_k, text_k, m_len[None, :], n_len[None, :])
    ops = jnp.transpose(ops_k, (1, 0))[:B].astype(jnp.uint8)   # (B, max_ops)
    return _unpack_meta(ops, meta[:, :B], cfg)
