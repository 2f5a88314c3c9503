"""Smoke run of the GenASM aligner's served path on a TPU.

One chip (the default): plan a W=64 O=24 k=12 ``pallas_fused`` session
over a 5 Mbp synthetic genome, warm its buckets, and answer short reads
(150 bp, 1% error) and noisy long reads (10 kbp, 10% error, which drive the
window scan and the rescue ladder) through a two-tenant ``Gateway``; then
map reads through ``ReadMapper``.  Every result is checked against the same
requests through a ``jnp`` session on the same chip, and every CIGAR is
validated against its read and reference (``core.oracle.validate_cigar``).

Four chips (``--chips 4``): only the data-parallel path — a ('data',) mesh
session against a single-device session on the same batch of long reads,
which must be bit-identical — plus each device's peak memory, so work
piled on one device would show.

    python chip_smoke.py [--seed 0]
    python chip_smoke.py --chips 4

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Any failure exits non-zero without printing it, and so does a host whose
JAX sees no TPU.  The earlier lines are smoke numbers (set-up and compile
seconds, counts), not benchmark metrics.  Compiles go to JAX's persistent
cache (``repro.launch.compile_cache``).
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.api import Gateway, GatewayPolicy, plan  # noqa: E402
from repro.core.config import AlignerConfig  # noqa: E402
from repro.core.oracle import OP_CHARS, validate_cigar  # noqa: E402
from repro.data.genome import (ReadSimConfig, plant_decoys,  # noqa: E402
                               simulate_reads, synth_genome)
from repro.kernels.ops import default_interpret  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_test_mesh  # noqa: E402
from repro.mapper import MapperConfig, ReadMapper  # noqa: E402

GEOMETRY = dict(W=64, O=24, k=12)
#: session knobs: the on-device rescue ladder keeps every rung inside the
#: bucket's one executable, so no rescue lane class compiles mid-stream
SESSION = dict(batch_lanes=64, rescue_rounds=1, rescue_mode="device")
#: the fields of a session result record that must agree across backends
RECORD_KEYS = ("ok", "dist", "cigar", "k_used")


def log(msg: str) -> None:
    print(msg, flush=True)


def require_tpu() -> list:
    """The devices, or SystemExit when JAX sees no TPU.  On a TPU no
    kernel may run interpreted, and the GPU lowering must refuse."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX sees no TPU (platform "
                         f"{devices[0].platform!r})")
    assert default_interpret("pallas_fused") is False
    try:
        default_interpret("pallas_gpu")
    except ValueError:
        pass
    else:
        raise AssertionError("pallas_gpu did not refuse a TPU")
    return devices


def cigar_ops(cigar: str) -> np.ndarray:
    """Expand a run-length CIGAR ('12=1X3I') back into op codes."""
    runs = re.findall(r"(\d+)([=XID])", cigar)
    return np.concatenate([np.full(int(n), OP_CHARS.index(c), np.uint8)
                           for n, c in runs] or [np.zeros(0, np.uint8)])


def answer(session, pairs) -> list:
    """Result records of `pairs` through a plain session (submit/flush)."""
    futs = [session.submit(r, f) for r, f in pairs]
    session.flush()
    return [f.result() for f in futs]


def check_records(name, got, want, pairs) -> tuple[int, int]:
    """(mismatches against the reference records, CIGARs validated)."""
    assert len(got) == len(want) == len(pairs), name
    mismatches = sum(1 for g, w in zip(got, want)
                     if any(g[k] != w[k] for k in RECORD_KEYS))
    aligned = 0
    for rec, (read, ref) in zip(got, pairs):
        if rec["ok"]:
            validate_cigar(read, ref, rec["ops"], rec["dist"])
            aligned += 1
    return mismatches, aligned


def serve_through_gateway(session, tenants: dict) -> tuple[dict, dict]:
    """Submit each tenant's pairs from its own client thread, drain, and
    return ({tenant: records}, gateway stats)."""
    # partial batches wait for close(): only warmed lane classes dispatch
    gw = Gateway(session, GatewayPolicy(
        capacity=4 * sum(len(p) for _, p in tenants.values()),
        linger_s=3600.0))
    futs, errors = {}, []

    def client(name, priority, pairs):
        try:
            ten = gw.tenant(name, priority=priority)
            futs[name] = [ten.submit(r, f) for r, f in pairs]
        except BaseException as e:      # noqa: BLE001 — re-raised below
            errors.append(e)

    threads = [threading.Thread(target=client, args=(n, pr, p))
               for n, (pr, p) in tenants.items()]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    gw.close()                          # drains: every future resolves
    if errors:
        raise errors[0]
    return ({n: [f.result() for f in fs] for n, fs in futs.items()},
            gw.gateway_stats())


def check_served_path(seed: int, genome_bp: int = 5_000_000,
                      n_short: int = 256, n_long: int = 32,
                      long_len: int = 10_000, n_map: int = 16,
                      map_len: int = 1000) -> None:
    """The one-chip smoke: gateway (short + long tenants) and mapper, every
    result checked against a jnp session and validated."""
    t0 = time.time()
    genome = synth_genome(genome_bp, seed=seed)
    map_rs = simulate_reads(genome, n_map, ReadSimConfig(
        read_len=map_len, error_rate=0.10, seed=seed + 1))
    genome, decoy_pos = plant_decoys(genome, map_rs, decoys_per_read=4,
                                     chunk=max(160, map_len // 4),
                                     seed=seed + 2)
    short_rs = simulate_reads(genome, n_short, ReadSimConfig(
        read_len=150, error_rate=0.01, seed=seed + 3))
    long_rs = simulate_reads(genome, n_long, ReadSimConfig(
        read_len=long_len, seed=seed + 4))
    short = list(zip(short_rs.reads, short_rs.ref_segments))
    long_ = list(zip(long_rs.reads, long_rs.ref_segments))
    log(f"setup: genome {len(genome):,} bp, {n_short} short x 150 bp @ 1%, "
        f"{n_long} long x {long_len:,} bp @ 10%, {n_map} mapper reads x "
        f"{map_len} bp: {time.time() - t0:.2f} s")

    fused = plan(AlignerConfig(**GEOMETRY, backend="pallas_fused"),
                 executor="thread", **SESSION)
    ref = plan(AlignerConfig(**GEOMETRY, backend="jnp"), **SESSION)
    with fused, ref:
        t0 = time.time()
        for pairs in (short, long_):
            buckets = sorted({fused.bucket_for(len(r), len(f))
                              for r, f in pairs})
            fused.warmup(buckets,
                         lanes=min(len(pairs), SESSION["batch_lanes"]))
        warm = fused.cache.lowerings
        log(f"warmup pallas_fused: {warm} executables compiled in "
            f"{time.time() - t0:.2f} s")

        t0 = time.time()
        got, gst = serve_through_gateway(
            fused, {"short": (0, short), "long": (1, long_)})
        log(f"gateway: {gst['completed']} completed ({gst['shed']} shed, "
            f"{gst['expired']} expired) in {time.time() - t0:.2f} s; "
            f"lowerings after warm-up: {fused.cache.lowerings - warm}")
        assert gst["completed"] == n_short + n_long, gst
        assert gst["shed"] == 0 and gst["expired"] == 0, gst

        for name, pairs in (("short", short), ("long", long_)):
            t0 = time.time()
            want = answer(ref, pairs)
            t_ref = time.time() - t0
            mism, aligned = check_records(name, got[name], want, pairs)
            rescued = sum(1 for r in got[name]
                          if r["ok"] and r["k_used"] > GEOMETRY["k"])
            log(f"{name}: {len(pairs)} served, {aligned} aligned "
                f"({rescued} rescued past k={GEOMETRY['k']}), CIGARs "
                f"valid, {mism} mismatches vs jnp (jnp session "
                f"{t_ref:.2f} s incl. compile)")
            assert mism == 0, f"{name}: {mism} results differ from jnp"
            assert aligned > 0, f"{name}: nothing aligned"

        t0 = time.time()
        mapper = ReadMapper(genome, MapperConfig(), session=fused)
        t_index = time.time() - t0
        t0 = time.time()
        out = mapper.map_batch(map_rs.reads)
        t_map = time.time() - t0
        st = out.stats
        hits = sum(1 for mr, tp in zip(out.mapped, map_rs.true_pos)
                   if mr.ok and abs(mr.ref_start - tp) <= 20)
        decoy_hits = sum(1 for mr in out.mapped if mr.ok and any(
            abs(mr.ref_start - dp) <= 50 for dp in decoy_pos[mr.read_id]))
        # every candidate the mapper aligned, again through the jnp session
        cand = [(mr, c) for mr in out.mapped for c in mr.candidates
                if not c.killed]
        want = answer(ref, [(map_rs.reads[mr.read_id],
                             genome[c.ref_start:c.ref_end])
                            for mr, c in cand])
        mism = sum(1 for (mr, c), w in zip(cand, want)
                   if c.ok != w["ok"] or (c.ok and c.dist != w["dist"]))
        for mr in out.mapped:
            if mr.ok:
                best = next((w for (m, c), w in zip(cand, want)
                             if m is mr and c.ref_start == mr.ref_start
                             and c.ok and c.dist == mr.dist), None)
                assert best is not None, f"read {mr.read_id}: no jnp twin"
                mism += (best["cigar"] != mr.cigar
                         or best["k_used"] != mr.k_used)
                validate_cigar(map_rs.reads[mr.read_id],
                               genome[mr.ref_start:mr.ref_end],
                               cigar_ops(mr.cigar), mr.dist)
        log(f"mapper: index {t_index:.2f} s, map {t_map:.2f} s; "
            f"{st['n_candidates']} candidates, {st['n_killed']} killed, "
            f"{st['n_aligned']} aligned, {st['n_mapped']}/{st['n_reads']} "
            f"mapped, {hits} at the true locus, {decoy_hits} at a decoy; "
            f"{mism} mismatches vs jnp")
        assert mism == 0, f"mapper: {mism} results differ from jnp"
        assert hits >= 0.9 * n_map and decoy_hits == 0
        log(f"compile cache: {fused.cache.lowerings} pallas_fused "
            f"lowerings ({fused.cache.lowerings - warm} after warm-up)")


def check_mesh(seed: int, n_devices: int, lanes_per_device: int = 128,
               read_len: int = 10_000, genome_bp: int = 5_000_000) -> None:
    """The four-chip smoke: a ('data',) mesh session against a
    single-device session on the same batch of noisy long reads, every
    record field equal, and how much memory each device used."""
    genome = synth_genome(genome_bp, seed=seed)
    n = lanes_per_device * n_devices     # one kernel tile of reads each
    rs = simulate_reads(genome, n, ReadSimConfig(read_len=read_len,
                                                 seed=seed + 4))
    pairs = list(zip(rs.reads, rs.ref_segments))
    cfg = AlignerConfig(**GEOMETRY, backend="pallas_fused")
    mesh = make_test_mesh((n_devices,), ("data",))
    knobs = dict(SESSION, batch_lanes=n)
    results = {}
    for name, m in (("mesh", mesh), ("single", None)):
        t0 = time.time()
        with plan(cfg, mesh=m, **knobs) as s:
            results[name] = answer(s, pairs)
            lanes = s.spec.batch_lanes
        log(f"{name}: {len(pairs)} x {read_len:,} bp in "
            f"{time.time() - t0:.2f} s incl. compile ({lanes} lanes per "
            f"dispatch)")
        if m is not None:               # where the shards ran
            peaks = {d.id: (d.memory_stats() or {}).get("peak_bytes_in_use")
                     for d in mesh.devices.flat}
            log(f"mesh {dict(mesh.shape)}: peak bytes in use by device id "
                f"{peaks}")
    mism = sum(1 for a, b in zip(results["mesh"], results["single"])
               if any(a[k] != b[k] for k in RECORD_KEYS)
               or not np.array_equal(a["ops"], b["ops"]))
    _, aligned = check_records("mesh", results["mesh"], results["single"],
                               pairs)
    log(f"mesh vs single device: {mism} mismatches over {len(pairs)} "
        f"pairs, {aligned} aligned, CIGARs valid")
    assert mism == 0, f"{mism} sharded results differ from single device"
    assert aligned > 0


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the served path on one chip; 4: only the "
                         "data-parallel mesh against one device")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    devices = require_tpu()
    if len(devices) < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} but JAX sees "
                         f"{len(devices)} device(s)")
    enable_compile_cache()
    log(f"devices: {len(devices)} x {devices[0].device_kind}; "
        f"jax {jax.__version__}")
    t0 = time.time()
    if args.chips == 4:
        check_mesh(args.seed, n_devices=4)
    else:
        check_served_path(args.seed)
    log(f"total: {time.time() - t0:.2f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
