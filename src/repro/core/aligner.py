"""Public aligner API: batch alignment of (read, candidate-ref) pairs with
failure rescue, host-side padding, and CIGAR decoding.

Rescue (pairs whose per-window edit distance exceeds cfg.k retried with
doubled k) runs in one of two modes:

* ``device`` (default) — a single jitted ``align_pairs_rescued`` call: one
  window loop escalates each failing window up the k-doubling ladder on
  device, so a batch is uploaded once and downloaded once no matter how
  many rungs run.
* ``host`` — the legacy numpy loop (re-pad and re-upload the failed subset
  every round).  Kept as the differential reference: both modes are
  bit-identical per lane (ops, dist, k_used, failed — see
  tests/test_rescue.py) and both are transfer-accounted via core.transfer.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from . import transfer
from .config import AlignerConfig, resolve_config
from .cigar import decode_batch, ops_to_string, records_from_state
from .windowing import (SENTINEL_READ, SENTINEL_REF, align_pairs,
                        align_pairs_rescued, pad_geometry)

DNA = "ACGT"


def encode(seq: str) -> np.ndarray:
    """Encode a READ: non-ACGT chars (N, IUPAC codes) -> SENTINEL_READ,
    which never matches any reference character."""
    lut = np.full(128, SENTINEL_READ, np.uint8)
    for i, c in enumerate(DNA):
        lut[ord(c)] = i
        lut[ord(c.lower())] = i
    return lut[np.frombuffer(seq.encode(), np.uint8)]


def encode_ref(seq: str) -> np.ndarray:
    """Encode a REFERENCE: non-ACGT chars -> SENTINEL_REF (the all-ones PM
    row), which never matches any read character — including a read 'N'.

    Refs must NOT be encoded with ``encode``: a ref 'N' mapped to
    SENTINEL_READ would raw-compare equal to a read 'N' in the jnp
    traceback while the DP's pattern masks say mismatch, diverging from
    the PM-based Pallas kernels.  ``encode_ref`` keeps all backends (and
    the DP itself) consistent: N never matches anything.
    """
    lut = np.full(128, SENTINEL_REF, np.uint8)
    for i, c in enumerate(DNA):
        lut[ord(c)] = i
        lut[ord(c.lower())] = i
    return lut[np.frombuffer(seq.encode(), np.uint8)]


@dataclasses.dataclass
class AlignResult:
    dist: np.ndarray          # (B,) edit cost of the produced alignment
    cigars: list[str]         # run-length encoded, front-first, '=XID'
    ops: list[np.ndarray]     # raw op arrays
    failed: np.ndarray        # (B,) True if unalignable within rescue budget
    k_used: np.ndarray        # (B,) per-window threshold that succeeded
    read_consumed: np.ndarray | None = None  # (B,) read chars CIGAR consumes
    ref_consumed: np.ndarray | None = None   # (B,) ref chars CIGAR consumes

    def summary(self, n: int | None = None,
                base_k: int | None = None) -> dict:
        """Aggregate stats over the first `n` lanes (all by default) — the
        one summary dict the serving engine, the session front door and the
        benchmarks share instead of ad-hoc per-caller dicts.  Pass `n` to
        exclude padding lanes, `base_k` (the pre-rescue threshold) to also
        count rescued lanes."""
        n = len(self.cigars) if n is None else n
        failed = np.asarray(self.failed[:n], bool)
        ok = ~failed
        out = {
            "n_pairs": int(n),
            "n_aligned": int(ok.sum()),
            "n_failed": int(failed.sum()),
            "total_edits": int(np.asarray(self.dist[:n])[ok].sum()),
            "total_ops": int(sum(len(self.ops[i]) for i in range(n)
                                 if ok[i])),
            "max_k_used": int(np.asarray(self.k_used[:n]).max(initial=0)),
        }
        if base_k is not None:
            out["n_rescued"] = int(
                (np.asarray(self.k_used[:n])[ok] > base_k).sum())
        if self.read_consumed is not None:
            out["read_bp"] = int(np.asarray(self.read_consumed[:n])[ok].sum())
        if self.ref_consumed is not None:
            out["ref_bp"] = int(np.asarray(self.ref_consumed[:n])[ok].sum())
        return out

    @classmethod
    def from_records(cls, recs: list) -> "AlignResult":
        """Assemble a batch AlignResult from per-lane result records (the
        shape produced by core.cigar.records_from_state and returned by
        session futures) — the one assembly both doors share."""
        return cls(
            np.array([r["dist"] for r in recs], np.int64),
            [r["cigar"] for r in recs],
            [r["ops"] for r in recs],
            np.array([not r["ok"] for r in recs], bool),
            np.array([r["k_used"] for r in recs], np.int32),
            np.array([r["read_consumed"] for r in recs], np.int32),
            np.array([r["ref_consumed"] for r in recs], np.int32))


class GenASMAligner:
    """Batch long-read aligner implementing the paper's improved GenASM.

    cfg.store/early_term select the variant (defaults = all three paper
    improvements on); cfg.backend (or the `backend` override) selects the
    execution path — 'jnp', 'pallas' (kernel DC + host traceback) or
    'pallas_fused' (DC+TB fused on-chip, including the rectangular tail
    window).  Pairs whose per-window edit distance exceeds cfg.k are
    retried with doubled k up to `rescue_rounds` times; `rescue_mode`
    selects the on-device masked multi-round path (default) or the legacy
    host loop (see module docstring).

    .. deprecated:: PR 4
        This is the legacy exact-shape door: pad widths derive from each
        batch's max_read_len, so every new length triggers a fresh jit
        trace.  New code should plan a ``repro.api.AlignSession`` (length
        -bucketed AOT-compiled executables, streaming submit/results) —
        see docs/api.md for the migration table.  Kept indefinitely as the
        bit-exactness reference the session is tested against.
    """

    def __init__(self, cfg: AlignerConfig = AlignerConfig(),
                 rescue_rounds: int = 2, backend: str | None = None,
                 rescue_mode: str = "device", mesh=None):
        cfg = resolve_config(cfg, backend=backend)
        assert rescue_mode in ("device", "host")
        self.cfg = cfg
        self.rescue_rounds = rescue_rounds
        self.rescue_mode = rescue_mode
        # mesh: shard every align call's pair axis over the mesh's data
        # axes (shard_map'd Pallas dispatch + GSPMD jnp) — results are
        # bit-identical to mesh=None (tests/test_multidevice.py)
        self.mesh = mesh

    def _pad(self, seqs, width, pad_val):
        B = len(seqs)
        out = np.full((B, width), pad_val, np.uint8)
        lens = np.zeros(B, np.int32)
        for i, s in enumerate(seqs):
            lens[i] = len(s)
            out[i, :len(s)] = s
        return out, lens

    def align(self, reads, refs) -> AlignResult:
        """reads/refs: lists of np.uint8 code arrays (see `encode` /
        `encode_ref`)."""
        assert len(reads) == len(refs)
        if self.rescue_mode == "host":
            return self._align_host_loop(reads, refs)
        return self._align_device(reads, refs)

    def _align_device(self, reads, refs) -> AlignResult:
        """One upload, one jitted multi-round rescue, one download."""
        cfg = self.cfg
        max_read_len = max(len(r) for r in reads)
        # pad ref sentinels for the FINAL rescue round's tail width
        Lr, Lf = pad_geometry(cfg, max_read_len, max(len(f) for f in refs),
                              self.rescue_rounds)
        rpad, rlen = self._pad(reads, Lr, SENTINEL_READ)
        fpad, flen = self._pad(refs, Lf, SENTINEL_REF)
        dev = transfer.to_device((rpad, rlen, fpad, flen))
        out = align_pairs_rescued(*dev, cfg=cfg, max_read_len=max_read_len,
                                  rescue_rounds=self.rescue_rounds,
                                  mesh=self.mesh)
        host = transfer.to_host({key: out[key] for key in
                                 ("ops", "n_ops", "dist", "failed", "k_used",
                                  "read_consumed", "ref_consumed")})
        # the same decode entrypoint the session's retire executor runs
        # off-thread (failed lanes report zeros either way)
        return AlignResult.from_records(
            records_from_state(*decode_batch(host, len(reads), cfg.k)))

    def _align_host_loop(self, reads, refs) -> AlignResult:
        """Legacy rescue: re-pad and re-upload the failed subset per round."""
        B = len(reads)
        cfg = self.cfg
        dist = np.zeros(B, np.int64)
        failed = np.ones(B, bool)
        k_used = np.zeros(B, np.int32)
        rcon = np.zeros(B, np.int32)
        fcon = np.zeros(B, np.int32)
        all_ops: list[np.ndarray | None] = [None] * B
        todo = np.arange(B)
        for rnd in range(self.rescue_rounds + 1):
            if len(todo) == 0:
                break
            sub_reads = [reads[i] for i in todo]
            sub_refs = [refs[i] for i in todo]
            max_read_len = max(len(r) for r in sub_reads)
            Lr, Lf = pad_geometry(cfg, max_read_len,
                                  max(len(f) for f in sub_refs), 0)
            rpad, rlen = self._pad(sub_reads, Lr, SENTINEL_READ)
            fpad, flen = self._pad(sub_refs, Lf, SENTINEL_REF)
            dev = transfer.to_device((rpad, rlen, fpad, flen))
            out = align_pairs(*dev, cfg=cfg, max_read_len=max_read_len,
                              mesh=self.mesh)
            host = transfer.to_host({key: out[key] for key in
                                     ("ops", "n_ops", "dist", "failed",
                                      "read_consumed", "ref_consumed")})
            ops = host["ops"]
            n_ops = host["n_ops"]
            ok = ~host["failed"]
            d = host["dist"]
            for loc, glob in enumerate(todo):
                if ok[loc]:
                    all_ops[glob] = ops[loc, :n_ops[loc]]
                    dist[glob] = d[loc]
                    failed[glob] = False
                    k_used[glob] = cfg.k
                    rcon[glob] = host["read_consumed"][loc]
                    fcon[glob] = host["ref_consumed"][loc]
            todo = np.array([g for g in todo if failed[g]])
            # rescue: double k (capped below W so the band math stays valid)
            new_k = min(cfg.k * 2, cfg.W - 1)
            if new_k == cfg.k:
                break
            cfg = dataclasses.replace(cfg, k=new_k)
        cigars = [ops_to_string(o) if o is not None else "" for o in all_ops]
        ops_out = [o if o is not None else np.zeros(0, np.uint8) for o in all_ops]
        return AlignResult(dist, cigars, ops_out, failed, k_used, rcon, fcon)
