"""The Pallas kernels of the served path compile for a TPU v5e.

Nothing here runs a kernel: each test lowers one kernel for a *described*
v5e (no chip attached) and compiles it with the chip's own compiler, which
refuses what the interpreter accepts — unsupported ops, unaligned slices,
more VMEM than the kernel may use.  One check the compiler does not make
is made on the traced kernels: no ref access at a traced index.  Geometry: the default W=64 O=24 k=12,
at the 128-lane tile and at the planner's tile (``plan_lane_tile``), plus
every rung of the rescue ladder at the tile the planner hands that rung.

The topology is described inside a fixture, never at import: only one
process may load the TPU compiler's library, and every test worker imports
this file.
"""
from functools import partial

import pytest

import jax
import jax.numpy as jnp

from repro.core.config import AlignerConfig, resolve_config
from repro.core.windowing import (plan_lane_tile, rescue_schedule,
                                  self_tail_width)
from repro.kernels.genasm_dc import (genasm_dc_pallas,
                                     genasm_tail_fused_pallas,
                                     genasm_tb_fused_pallas)

CFG = AlignerConfig(W=64, O=24, k=12, backend="pallas_fused")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, shapes, sharding):
    """Compile `fn` for the described chip; return the compiled text."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _square(cfg, tile, sharding, kernel):
    shapes = [((5, cfg.nw, tile), jnp.uint32), ((cfg.W, tile), jnp.int32)]
    if kernel == "split":
        fn = lambda pm, t: genasm_dc_pallas(pm, t, cfg=cfg, tile=tile,
                                            interpret=False)
    else:
        fn = lambda pm, t: genasm_tb_fused_pallas(
            pm, t, cfg=cfg, commit_limit=cfg.stride, tile=tile,
            interpret=False)
    return _compile(fn, shapes, sharding)


def _tail(cfg, tile, sharding):
    wt = self_tail_width(cfg)
    shapes = [((5, cfg.nw, tile), jnp.uint32), ((wt, tile), jnp.int32),
              ((1, tile), jnp.int32), ((1, tile), jnp.int32)]
    fn = lambda pm, t, m, n: genasm_tail_fused_pallas(
        pm, t, m, n, cfg=cfg, n_text=wt, commit_limit=2 * (cfg.W + wt),
        max_ops=cfg.W + wt, max_steps=cfg.W + wt + 4, tile=tile,
        interpret=False)
    return _compile(fn, shapes, sharding)


@pytest.mark.parametrize("planned", [False, True], ids=["tile128", "planned"])
@pytest.mark.parametrize("kernel", ["fused", "split"])
def test_square_kernel_compiles(kernel, planned, one_chip):
    tile = plan_lane_tile(CFG) if planned else 128
    assert "tpu_custom_call" in _square(CFG, tile, one_chip, kernel)


@pytest.mark.parametrize("planned", [False, True], ids=["tile128", "planned"])
@pytest.mark.parametrize("store", ["band", "full"])
def test_tail_kernel_compiles(store, planned, one_chip):
    cfg = CFG.replace(tail_store=store)
    assert cfg.tail_banded == (store == "band")
    tile = plan_lane_tile(cfg) if planned else 128
    assert "tpu_custom_call" in _tail(cfg, tile, one_chip)


def _ref_accesses(jaxpr):
    """(primitive, number of traced index operands) of every ref read and
    write in `jaxpr` and the jaxprs nested in it (loop bodies, branches)."""
    from jax.extend import core as jcore
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in ("get", "swap", "addupdate"):
            idx = eqn.invars[1 if eqn.primitive.name == "get" else 2:]
            out.append((eqn.primitive.name, sum(
                not isinstance(v, jcore.Literal) for v in idx)))
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (tuple, list)) else (p,)):
                if isinstance(sub, jcore.ClosedJaxpr):
                    out += _ref_accesses(sub.jaxpr)
                elif isinstance(sub, jcore.Jaxpr):
                    out += _ref_accesses(sub)
    return out


@pytest.mark.parametrize("kernel", ["split", "fused", "tail-band",
                                    "tail-full"])
def test_kernels_address_refs_at_static_indices(kernel):
    """No kernel reads or writes a ref at a traced index, the shape in
    which the kernels run on a v5e.  Their earlier column loops ran from 1
    and addressed text row j-1 and band column j; those kernels never
    returned on the chip (a loop from 1 loading a row from its index
    hangs there, one from 0 does not).  Traced rows go through one-hot
    selects over static slabs (genasm_dc._text_row / _set_row).  Traces
    only; needs no chip."""
    tile = 128
    pm = jnp.zeros((5, CFG.nw, tile), jnp.uint32)
    text = jnp.zeros((CFG.W, tile), jnp.int32)
    if kernel == "split":
        call = partial(genasm_dc_pallas, cfg=CFG, tile=tile, interpret=True)
        args = (pm, text)
    elif kernel == "fused":
        call = partial(genasm_tb_fused_pallas, cfg=CFG,
                       commit_limit=CFG.stride, tile=tile, interpret=True)
        args = (pm, text)
    else:
        cfg = CFG.replace(tail_store=kernel.split("-")[1])
        wt = self_tail_width(cfg)
        lens = jnp.zeros((1, tile), jnp.int32)
        call = partial(genasm_tail_fused_pallas, cfg=cfg, n_text=wt,
                       commit_limit=2 * (cfg.W + wt), max_ops=cfg.W + wt,
                       max_steps=cfg.W + wt + 4, tile=tile, interpret=True)
        args = (pm, jnp.zeros((wt, tile), jnp.int32), lens, lens)
    accesses = _ref_accesses(jax.make_jaxpr(call)(*args).jaxpr)
    assert any(p == "swap" for p, _ in accesses)
    assert [a for a in accesses if a[1]] == []


@pytest.mark.parametrize("rung", [1, 2])
def test_rescue_rung_kernels_compile_at_their_tile(rung, one_chip):
    """A session planned with lane_tile='auto' doubles k per rescue rung;
    each rung runs at the tile rescue_schedule hands it, which must be one
    the chip's compiler accepts."""
    base = resolve_config(CFG, lane_tile="auto")
    cfg = rescue_schedule(base, 2)[rung]
    assert cfg.k == CFG.k * 2 ** rung
    assert cfg.lane_tile <= plan_lane_tile(cfg) < base.lane_tile
    assert "tpu_custom_call" in _square(cfg, cfg.lane_tile, one_chip, "fused")
    assert "tpu_custom_call" in _tail(cfg, cfg.lane_tile, one_chip)
