"""Plain reference for windowed GenASM alignment, in numpy.

Imports nothing of the program.  It restates the aligner's semantics as a
textbook edit-distance table and walks it, so a record the served path
returns can be checked field by field:

* A read is aligned window by window.  While more than ``W`` read
  characters remain, the next ``W`` read characters are aligned globally
  against the next ``W`` reference characters (characters past the end of
  the reference never match).  The window's distance is the edit distance
  of the two; a window whose distance exceeds ``k`` fails the pair at this
  ``k``.  The walk starts at the windows' first characters and takes, at
  each cell, the first available of match, substitution, deletion (a
  reference character only), insertion (a read character only); it stops
  once ``W - O`` read characters are committed.  The read advances by those
  characters and the reference by the reference characters the committed
  operations consumed.
* The rest of the read (at most ``W`` characters) is aligned globally
  against the rest of the reference, which must hold between
  ``m - 2k`` and ``W + 4k`` characters, and the whole walk is committed.
* ``dist`` is the sum of the committed operations' costs.  A pair that
  fails at ``k`` is tried again at ``2k`` (up to ``rescue_rounds`` times,
  capped at ``W - 1``); ``k_used`` is the first ``k`` at which it aligned.

Every function works on a batch of pairs at once (numpy over the pair
axis); the loop over table rows uses the running-minimum form of the
in-row recurrence, so one row costs a few array operations.
"""
from __future__ import annotations

import dataclasses

import numpy as np

OP_MATCH, OP_SUBST, OP_INS, OP_DEL = 0, 1, 2, 3
OP_CHARS = "=XID"
#: the walk's preference among available edges (GenASM-TB's order)
PREFERENCE = (OP_MATCH, OP_SUBST, OP_DEL, OP_INS)
#: text code of a reference position past the reference's end
NO_CHAR = 250
#: read code of a read position past the read's end (differs from NO_CHAR)
NO_READ = 251


@dataclasses.dataclass(frozen=True)
class Geometry:
    """The aligner settings the reference needs."""
    W: int
    O: int
    k: int
    rescue_rounds: int
    order: tuple = PREFERENCE

    @property
    def stride(self) -> int:
        return self.W - self.O

    def ladder(self) -> list[int]:
        ks = [self.k]
        for _ in range(self.rescue_rounds):
            nk = min(ks[-1] * 2, self.W - 1)
            if nk == ks[-1]:
                break
            ks.append(nk)
        return ks


def edit_table(p: np.ndarray, t: np.ndarray) -> np.ndarray:
    """Global edit-distance tables of a batch of equal-width strings.

    p: (B, m) read codes, t: (B, n) reference codes.  Returns D of shape
    (B, m + 1, n + 1) int32 with D[b, a, c] the edit distance between the
    first a characters of p[b] and the first c characters of t[b]."""
    B, m = p.shape
    n = t.shape[1]
    D = np.empty((B, m + 1, n + 1), np.int32)
    cols = np.arange(n + 1, dtype=np.int32)
    D[:, 0, :] = cols
    for a in range(1, m + 1):
        prev = D[:, a - 1, :]
        diag = prev[:, :-1] + (p[:, a - 1:a] != t)
        x = np.empty((B, n + 1), np.int32)
        x[:, 0] = a
        np.minimum(diag, prev[:, 1:] + 1, out=x[:, 1:])
        # D[a, c] = min(x[c], D[a, c - 1] + 1) = c + min_{l <= c}(x[l] - l)
        D[:, a, :] = np.minimum.accumulate(x - cols, axis=1) + cols
    return D


def walk(D, p, t, m, n, commit_limit, max_ops, order=PREFERENCE):
    """The front-first walk of each table from cell (m, n) (see module
    docstring), on windows given in reverse so that it runs from the
    windows' first characters; `order` is the preference among the
    available edges.

    p, t: the reversed windows; m, n: (B,) the used lengths.  Returns
    (ops (B, max_ops) uint8 padded with 255, n_ops, read_adv, ref_adv,
    cost)."""
    B = D.shape[0]
    rows = np.arange(B)
    a = m.astype(np.int64).copy()
    c = n.astype(np.int64).copy()
    d = D[rows, a, c].astype(np.int64)
    d0 = d.copy()
    ops = np.full((B, max_ops), 255, np.uint8)
    nops = np.zeros(B, np.int64)
    rd = np.zeros(B, np.int64)
    rf = np.zeros(B, np.int64)
    done = np.zeros(B, bool)
    pw, tw = p.shape[1], t.shape[1]
    while True:
        done |= rd >= commit_limit
        act = ~done
        if not act.any():
            break
        tail = a == 0
        am = np.maximum(a - 1, 0)
        cm = np.maximum(c - 1, 0)
        pa = p[rows, np.minimum(am, pw - 1)]
        tc = t[rows, np.minimum(cm, tw - 1)]
        d_diag = D[rows, am, cm]
        d_left = D[rows, a, cm]
        d_up = D[rows, am, c]
        has_c = c > 0
        avail = {
            OP_MATCH: ~tail & has_c & (pa == tc) & (d_diag <= d),
            OP_SUBST: ~tail & has_c & (d > 0) & (d_diag <= d - 1),
            OP_DEL: ~tail & has_c & (d > 0) & (d_left <= d - 1),
            OP_INS: ~tail & (d > 0) & (d_up <= d - 1)}
        drain = tail & has_c              # read used up: the rest deletes
        op = np.full(B, OP_DEL)
        chosen = np.zeros(B, bool)
        for o in order:
            pick = avail[o] & ~chosen
            op[pick] = o
            chosen |= pick
        emit = act & (chosen | drain)
        if np.any(act & ~emit):
            raise AssertionError("walk found no edge at an unfinished cell")
        take_read = emit & (op != OP_DEL) & ~tail
        take_ref = emit & (op != OP_INS)
        costly = emit & (op != OP_MATCH)
        ops[rows[emit], nops[emit]] = op[emit]
        nops += emit
        a -= take_read
        c -= take_ref
        d -= costly
        rd += take_read
        rf += take_ref
        done |= act & (a == 0) & (c == 0)
    return ops, nops, rd, rf, d0 - d


def _rev_window(seqs, starts, width, fill):
    """(B, width) rows seqs[b][starts[b]:starts[b] + width] reversed,
    `fill` where the slice runs past the end."""
    out = np.full((len(seqs), width), fill, np.uint8)
    for b, (s, p0) in enumerate(zip(seqs, starts)):
        piece = s[p0:p0 + width]
        out[b, width - len(piece):] = piece[::-1]
    return out


def align_at_k(reads, refs, geo: Geometry, k: int) -> dict:
    """Every pair aligned at edit budget k.  Returns per-pair lists:
    ok (bool), dist, ops (uint8 arrays), read_consumed, ref_consumed."""
    B = len(reads)
    W, stride = geo.W, geo.stride
    rlen = np.array([len(r) for r in reads], np.int64)
    flen = np.array([len(f) for f in refs], np.int64)
    rpos = np.zeros(B, np.int64)
    fpos = np.zeros(B, np.int64)
    dist = np.zeros(B, np.int64)
    failed = np.zeros(B, bool)
    pieces: list[list[np.ndarray]] = [[] for _ in range(B)]
    full = np.full(B, W, np.int64)
    while True:
        act = np.nonzero(~failed & (rlen - rpos > W))[0]
        if act.size == 0:
            break
        p = _rev_window([reads[i] for i in act], rpos[act], W, NO_READ)
        t = _rev_window([refs[i] for i in act], fpos[act], W, NO_CHAR)
        D = edit_table(p, t)
        wd = D[:, W, W]
        ok = wd <= k
        failed[act[~ok]] = True
        good = act[ok]
        if good.size == 0:
            continue
        ops, nops, rd, rf, cost = walk(D[ok], p[ok], t[ok], full[good],
                                       full[good], stride, stride + k + 2,
                                       geo.order)
        for j, i in enumerate(good):
            pieces[i].append(ops[j, :nops[j]])
        rpos[good] += rd
        fpos[good] += rf
        dist[good] += cost
    # tail: the rest of the read against the rest of the reference
    wt = W + 4 * k
    m_t = np.clip(rlen - rpos, 0, W)
    n_rem = flen - fpos
    n_t = np.clip(n_rem, 0, wt)
    bad = (n_rem > wt) | (n_rem < np.maximum(m_t - 2 * k, 0))
    live = np.nonzero(~failed & ~bad)[0]
    if live.size:
        mt = int(m_t[live].max())
        nt = int(n_t[live].max())
        p = np.full((live.size, max(mt, 1)), NO_READ, np.uint8)
        t = np.full((live.size, max(nt, 1)), NO_CHAR, np.uint8)
        for j, i in enumerate(live):
            rp = reads[i][rpos[i]:rpos[i] + m_t[i]]
            fp = refs[i][fpos[i]:fpos[i] + n_t[i]]
            p[j, :len(rp)] = rp[::-1]
            t[j, :len(fp)] = fp[::-1]
        D = edit_table(p, t)
        rows = np.arange(live.size)
        td = D[rows, m_t[live], n_t[live]]
        ok = td <= k
        bad[live[~ok]] = True
        good = live[ok]
        if good.size:
            ops, nops, rd, rf, cost = walk(
                D[ok], p[ok], t[ok], m_t[good], n_t[good],
                1 << 30, mt + nt + 1, geo.order)
            for j, i in enumerate(good):
                pieces[i].append(ops[j, :nops[j]])
            rpos[good] += rd
            fpos[good] += rf
            dist[good] += cost
    failed |= bad
    return {"ok": list(~failed),
            "dist": list(dist),
            "ops": [np.concatenate(pc) if pc else np.zeros(0, np.uint8)
                    for pc in pieces],
            "read_consumed": list(rpos),
            "ref_consumed": list(fpos)}


def cigar_string(ops: np.ndarray) -> str:
    """Run-length CIGAR over the =XID alphabet."""
    out, prev, run = [], None, 0
    for op in list(np.asarray(ops)) + [None]:
        if op == prev:
            run += 1
            continue
        if prev is not None:
            out.append(f"{run}{OP_CHARS[prev]}")
        prev, run = op, 1
    return "".join(out)


def align(reads, refs, geo: Geometry) -> list[dict]:
    """The served record of every pair: {ok, dist, k_used, cigar, ops,
    read_consumed, ref_consumed}.  A pair that fails at every rung reads
    ok=False with zeros and an empty CIGAR."""
    B = len(reads)
    recs: list[dict | None] = [None] * B
    todo = list(range(B))
    for k in geo.ladder():
        if not todo:
            break
        got = align_at_k([reads[i] for i in todo], [refs[i] for i in todo],
                         geo, k)
        still = []
        for j, i in enumerate(todo):
            if got["ok"][j]:
                ops = got["ops"][j].astype(np.uint8)
                recs[i] = {"ok": True, "dist": int(got["dist"][j]),
                           "k_used": k, "cigar": cigar_string(ops),
                           "ops": ops,
                           "read_consumed": int(got["read_consumed"][j]),
                           "ref_consumed": int(got["ref_consumed"][j])}
            else:
                still.append(i)
        todo = still
    for i in todo:
        recs[i] = {"ok": False, "dist": 0, "k_used": 0, "cigar": "",
                   "ops": np.zeros(0, np.uint8), "read_consumed": 0,
                   "ref_consumed": 0}
    return recs
