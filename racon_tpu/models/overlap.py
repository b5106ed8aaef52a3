"""The first-party overlapper's plain reference, beside ``nw.py`` and
``poa.py``: reads and targets in, overlap rows out, by the definitions.

- **minimizers** (:func:`minimizers_np`): the canonical k-mer (the
  smaller of the forward and the reverse-complement 2k-bit code; a
  k-mer that equals its own reverse complement or covers a base outside
  ACGT is skipped), hashed with murmur3's 32-bit finalizer, and of each
  window of ``w`` consecutive k-mers the leftmost strict minimum;
- **join** (:func:`match_seeds`): a sorted-hash intersection of the two
  minimizer tables, with the whole-bucket occurrence cap — a hash whose
  occurrences in both tables together exceed ``max_occ`` is dropped
  whole — self hits dropped, reverse-strand query positions flipped to
  chain space;
- **chain** (:func:`chain_np`): per candidate pair the quadratic DP
  over its seeds sorted by ``(t, q)`` with a bounded look-back, integer
  scores, then the back-track of the best chain;
- **rows** (:func:`find_overlaps_np`): per pair whose best chain holds
  ``min_seeds`` seeds, the row ``(query, target, strand, spans)``.

Plain numpy and Python; imports nothing from ``racon_tpu/ops/``. The
device path (``ops/overlap_seed.py``, ``ops/chain.py``) is held to this
file bit for bit by ``tests/test_overlapper.py`` and
``tests/test_overlap_reference.py``; its constants are the program's.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

DEFAULT_K = 15
DEFAULT_W = 5
DEFAULT_MAX_OCC = 64
DEFAULT_MIN_SEEDS = 4
# chain DP shape and score constants
CHAIN_LOOKBACK = 16       # bounded predecessor window H
MAX_GAP = 10_000          # max per-axis seed gap inside one chain
BAND_DIAG = 512           # max |dq - dt| diagonal drift
GAP_UNIT = 16             # score scale: 1 matched base = GAP_UNIT,
                          # 1 gap base costs 1 (i.e. 1/16 of a match)
NEG = -(1 << 30)          # masked-lane score sentinel
# invalid k-mer slots (ambiguous base in the k-mer, fwd == rc palindrome
# tie, past the sequence end) never win a window
HASH_MAX = 0xFFFFFFFF

BASE_LUT = np.full(256, 4, np.uint8)
for _i, _b in enumerate(b"ACGT"):
    BASE_LUT[_b] = _i
for _i, _b in enumerate(b"acgt"):
    BASE_LUT[_b] = _i

ROW_KEYS = ("q_ord", "t_idx", "strand", "q_begin", "q_end",
            "t_begin", "t_end", "n_seeds", "score")


def minimizers_np(seq: bytes, k: int = DEFAULT_K, w: int = DEFAULT_W
                  ) -> List[Tuple[int, int, int]]:
    """One sequence's minimizers, sorted by position: ``(hash, pos,
    strand)`` triples (canonical min, fmix32, palindrome and ambiguity
    skips, leftmost strict-< window minimum)."""
    codes = BASE_LUT[np.frombuffer(seq, np.uint8)]
    L = codes.size
    if L < k + w - 1:
        return []
    P = L - k + 1
    f = np.zeros(P, np.uint32)
    r = np.zeros(P, np.uint32)
    bad = np.zeros(P, bool)
    for j in range(k):
        c = codes[j:j + P].astype(np.uint32)
        bad |= c > 3
        cc = c & np.uint32(3)
        f = (f << np.uint32(2)) | cc
        r = (r >> np.uint32(2)) | ((np.uint32(3) - cc)
                                   << np.uint32(2 * (k - 1)))
    strand = r < f
    h = np.minimum(f, r)
    h = h ^ (h >> np.uint32(16))
    h = h * np.uint32(0x85EBCA6B)
    h = h ^ (h >> np.uint32(13))
    h = h * np.uint32(0xC2B2AE35)
    h = h ^ (h >> np.uint32(16))
    h = np.where(bad | (f == r), np.uint32(HASH_MAX), h)
    sel = np.zeros(P, bool)
    for s in range(P - w + 1):
        win = h[s:s + w]
        m = int(win.min())
        if m != HASH_MAX:
            sel[s + int(np.argmax(win == m))] = True
    return [(int(h[p]), int(p), int(strand[p]))
            for p in np.flatnonzero(sel)]


def seed_table_np(seqs: List[bytes], k: int = DEFAULT_K,
                  w: int = DEFAULT_W) -> tuple:
    """The flat ``(hash uint32, seq_id int32, pos int32, strand bool)``
    table of a sequence set in ``(seq_id, pos)`` order."""
    hs, ids, ps, ss = [], [], [], []
    for sid, seq in enumerate(seqs):
        for h, p, s in minimizers_np(seq, k, w):
            hs.append(h)
            ids.append(sid)
            ps.append(p)
            ss.append(s)
    return (np.asarray(hs, np.uint32), np.asarray(ids, np.int32),
            np.asarray(ps, np.int32), np.asarray(ss, bool))


def match_seeds(read_table, target_table, read_self_t: np.ndarray,
                qlens: np.ndarray, *, k: int, max_occ: int
                ) -> Tuple[Dict[str, np.ndarray], int]:
    """Sorted-hash intersection of the two minimizer tables.

    Returns ``(hits, freq_capped)`` where ``hits`` holds per-hit
    parallel arrays — ``q`` (read ordinal), ``t`` (target index),
    ``rel`` (relative strand), ``tp`` (target seed pos), ``qc`` (query
    seed pos, already flipped for reverse-strand hits) — lexsorted by
    ``(q, t, rel, tp, qc)`` so candidate pairs are consecutive runs.
    Buckets whose total occurrence count (both tables) exceeds
    ``max_occ`` drop whole; ``freq_capped`` counts those of them the
    target table holds (the ones that would have joined)."""
    rh, rid, rpos, rstr = read_table
    th, tid, tpos, tstr = target_table
    empty = {key: np.zeros(0, np.int64) for key in
             ("q", "t", "rel", "tp", "qc")}
    if rh.size == 0 or th.size == 0:
        return empty, 0

    ro = np.argsort(rh, kind="stable")
    rh, rid, rpos, rstr = rh[ro], rid[ro], rpos[ro], rstr[ro]
    to = np.argsort(th, kind="stable")
    th, tid, tpos, tstr = th[to], tid[to], tpos[to], tstr[to]

    uh, uc = np.unique(np.concatenate([rh, th]), return_counts=True)
    hot = uc > max_occ
    # the target's buckets: a hash the reads alone hold joins nothing,
    # frequent or not
    freq_capped = int((hot & np.isin(uh, th)).sum())
    keep_r = ~hot[np.searchsorted(uh, rh)]
    keep_t = ~hot[np.searchsorted(uh, th)]
    rh, rid, rpos, rstr = rh[keep_r], rid[keep_r], rpos[keep_r], rstr[keep_r]
    th, tid, tpos, tstr = th[keep_t], tid[keep_t], tpos[keep_t], tstr[keep_t]
    if rh.size == 0 or th.size == 0:
        return empty, freq_capped

    lo = np.searchsorted(th, rh, "left")
    hi = np.searchsorted(th, rh, "right")
    cnt = (hi - lo).astype(np.int64)
    total = int(cnt.sum())
    if total == 0:
        return empty, freq_capped
    ridx = np.repeat(np.arange(rh.size, dtype=np.int64), cnt)
    ramp = np.arange(total, dtype=np.int64) - np.repeat(
        np.cumsum(cnt) - cnt, cnt)
    tidx = np.repeat(lo.astype(np.int64), cnt) + ramp

    q = rid[ridx].astype(np.int64)
    t = tid[tidx].astype(np.int64)
    rel = (rstr[ridx] != tstr[tidx]).astype(np.int64)
    tp = tpos[tidx].astype(np.int64)
    qp = rpos[ridx].astype(np.int64)
    notself = t != read_self_t[q]
    q, t, rel, tp, qp = (q[notself], t[notself], rel[notself],
                         tp[notself], qp[notself])
    qc = np.where(rel == 1, qlens[q] - qp - k, qp)
    order = np.lexsort((qc, tp, rel, t, q))
    return ({"q": q[order], "t": t[order], "rel": rel[order],
             "tp": tp[order], "qc": qc[order]}, freq_capped)


def chain_np(ts: np.ndarray, qs: np.ndarray, k: int
             ) -> Tuple[int, int, int, int, int, int]:
    """One candidate pair's best chain: integer scoring, bounded
    look-back, nearest-predecessor strict-> tie-break, lowest-index
    best-end tie-break. Returns ``(score, n_chained, q_lo, q_hi, t_lo,
    t_hi)``."""
    n = len(ts)
    if n == 0:
        return (NEG, 0, 0, 0, 0, 0)
    start = k * GAP_UNIT
    f = [0] * n
    par = [0] * n
    for i in range(n):
        best, arg = NEG, -1
        for off in range(1, CHAIN_LOOKBACK + 1):  # nearest first
            j = i - off
            if j < 0:
                break
            dt, dq = ts[i] - ts[j], qs[i] - qs[j]
            gap = abs(dq - dt)
            if dt < 1 or dq < 1 or dt > MAX_GAP or dq > MAX_GAP \
                    or gap > BAND_DIAG:
                continue
            cand = f[j] + min(k, dq, dt) * GAP_UNIT - gap
            if cand > best:  # strict: ties keep the nearer predecessor
                best, arg = cand, off
        f[i] = max(start, best)
        par[i] = arg if best > start else 0
    end = int(np.argmax(np.asarray(f)))
    cur, cnt = end, 0
    while True:
        cnt += 1
        if par[cur] == 0:
            break
        cur -= par[cur]
    return (f[end], cnt, int(qs[cur]), int(qs[end]),
            int(ts[cur]), int(ts[end]))


def find_overlaps_np(read_seqs: List[bytes], target_seqs: List[bytes],
                     read_self_t: np.ndarray, *, k: int = DEFAULT_K,
                     w: int = DEFAULT_W, max_occ: int = DEFAULT_MAX_OCC,
                     min_seeds: int = DEFAULT_MIN_SEEDS
                     ) -> Dict[str, np.ndarray]:
    """The whole overlapper by the definitions above: parallel int64
    arrays under :data:`ROW_KEYS`, sorted by ``(q_ord, t_idx, strand,
    t_begin, q_begin)`` — the order and the columns of
    ``ops.chain.find_overlaps``."""
    qlens = np.fromiter((len(s) for s in read_seqs), np.int64,
                        len(read_seqs))
    hits, _ = match_seeds(seed_table_np(read_seqs, k, w),
                          seed_table_np(target_seqs, k, w),
                          np.asarray(read_self_t, np.int64), qlens,
                          k=k, max_occ=max_occ)
    n = hits["q"].size
    rows = []
    begin = 0
    while begin < n:
        end = begin + 1
        while end < n and all(hits[c][end] == hits[c][begin]
                              for c in ("q", "t", "rel")):
            end += 1
        if end - begin >= min_seeds:
            score, chained, q_lo, q_hi, t_lo, t_hi = chain_np(
                hits["tp"][begin:end], hits["qc"][begin:end], k)
            if chained >= min_seeds:
                q, t, rel = (int(hits[c][begin]) for c in ("q", "t", "rel"))
                ql = int(qlens[q])
                # chain space -> forward query space
                q_begin = ql - (q_hi + k) if rel else q_lo
                q_end = ql - q_lo if rel else q_hi + k
                rows.append((q, t, rel, q_begin, q_end, t_lo, t_hi + k,
                             chained, score))
        begin = end
    rows.sort(key=lambda r: (r[0], r[1], r[2], r[5], r[3]))
    table = np.asarray(rows, np.int64).reshape(-1, len(ROW_KEYS))
    return {key: table[:, i] for i, key in enumerate(ROW_KEYS)}
