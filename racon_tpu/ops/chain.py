"""Seed matching + banded chaining DP — stage two of the first-party
overlapper (``--overlaps auto``, ROADMAP item 5).

Consumes the flat minimizer tables from :mod:`.overlap_seed` and emits
``Overlap``-compatible rows:

- **matching** runs on device by default (``RACON_TPU_OVERLAP_DEVICE_JOIN``):
  both tables sort by hash once on device (``lax.sort``), per-hash
  occurrence totals derive from searchsorted run bounds so super-hot
  repeat buckets over the occurrence cap drop whole (counted in
  ``overlap.freq_capped_buckets`` — never silent), kept entries compact
  to a sorted prefix, and the read→target join expands into hits via
  the ragged searchsorted ramp, self-hit suppression, strand-flip of
  query coordinates, and a device 5-key sort — so under
  ``RACON_TPU_RESIDENT=1`` the matched ``(tp, qc)`` seed coordinates
  never visit the host at all and feed the chain kernel directly. The
  numpy :func:`match_seeds` stays as the byte-parity oracle AND the
  bail-out ladder target (empty tables, arena-overflow table or hit
  counts — counted in ``overlap.join_bailouts``, never approximation);
  hit 5-tuples are unique by construction (tables dedupe on (seq, pos)),
  so any ascending sort produces the oracle's exact lexsort order.
- **chaining** is the device DP: pairs ragged-pack by pow2 seed-count
  bucket into fixed ``[B, S]`` arenas through :class:`_ChainStream` —
  greedy chunk fill by each pair's own seed-count cost, double-buffered
  dispatch/fetch behind an in-flight budget, per-pair results invariant
  to feed batching (the ``_AlignStream`` discipline, warmed via
  :func:`_warmup_shapes`) — and a ``lax.scan`` over seed positions
  scores gap-bounded colinear chains against a bounded lookback window,
  then backtracks on device so only a ``[B, 6]`` summary per launch
  crosses the link — resident-friendly by construction.
- **streaming** (:func:`iter_overlap_groups`): chained overlap rows
  emit per query group as chunks resolve, so the polisher's filter and
  the round-17 align stream consume group N while group N+1 is still
  chaining. The canonical full-run row order is the concatenation of
  the per-group orders (the global lexsort's primary key IS the query
  ordinal), which is what keeps the streamed and phase-barriered paths
  byte-identical.

Scoring is all-integer (seed span minus a gap penalty in 1/16-base
units), so the kernel and the numpy oracle :func:`chain_np` agree
bit-for-bit and byte-identical reruns fall out for free. Reverse-strand
query coordinates flip to ``q' = qlen - pos - k`` before chaining (so
colinearity means ascending in both axes) and flip back on emission.
"""

from __future__ import annotations

import functools
from typing import Callable, Dict, Iterator, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from .. import obs
from ..obs import metrics
from ..parallel import fetch_global
from . import overlap_seed

# chain DP shape/score constants (module-level: one compile surface)
CHAIN_LOOKBACK = 16       # bounded predecessor window H
MAX_GAP = 10_000          # max per-axis seed gap inside one chain
BAND_DIAG = 512           # max |dq - dt| diagonal drift
GAP_UNIT = 16             # score scale: 1 matched base = GAP_UNIT,
                          # 1 gap base costs 1 (i.e. 1/16 of a match)
_NEG = -(1 << 30)         # masked-lane score sentinel
# chain-arena budget in cells (ts/qs operands and the scan history all
# scale with B*S)
CHAIN_ARENA_CELLS = 1 << 21
DEFAULT_MAX_OCC = 64
DEFAULT_MIN_SEEDS = 4
# device-join arena bounds: padded table entries / expanded hits past
# these bail to the host oracle (counted, never silent) so one
# pathological input can't demand an unbounded device sort
JOIN_TABLE_CELLS = 1 << 25
JOIN_MAX_HITS = 1 << 26
# in-flight chain chunks before a fetch is forced (double buffering:
# the device works chunk N while the host packs N+1 and fetches N-1)
CHAIN_INFLIGHT = 2


# -------------------------------------------------------------- geometry

def _seed_bucket(n: int) -> int:
    """pow2 seed-list bucket for one candidate pair (floor 16) — the
    quantizer both dispatch and :func:`_warmup_shapes` derive the
    arena's S axis from."""
    b = 16
    while b < n:
        b *= 2
    return b


def _pair_batch(S: int, n: int) -> int:
    """pow2 pair-batch cap for one chain launch against the fixed
    :data:`CHAIN_ARENA_CELLS` arena (companion of :func:`_seed_bucket`;
    shared with warm-up)."""
    want = min(max(1, n), max(1, CHAIN_ARENA_CELLS // max(1, S)))
    b = 1
    while b < want:
        b *= 2
    return b


def _table_pad(n: int) -> int:
    """pow2 padded length of one minimizer table on the device-join
    path (floor 64) — the quantizer both the join dispatch and
    :func:`_warmup_shapes` derive sort geometry from."""
    b = 64
    while b < n:
        b *= 2
    return b


def _hits_pad(n: int) -> int:
    """pow2 padded length of the expanded hit arena (floor 256; same
    role as :func:`_table_pad` for the join's second kernel)."""
    b = 256
    while b < n:
        b *= 2
    return b


# ---------------------------------------------------------------- kernel

@functools.partial(jax.jit, static_argnames=("S", "k"))
def _chain_kernel(ts, qs, ns, *, S: int, k: int):
    """Gap-scored colinear chaining over a ``[B, S]`` packed seed arena.

    ``ts``/``qs`` are per-pair seed coordinates sorted by ``(t, q)``,
    ``ns`` the live seed count per lane. A scan over seed index scores
    each seed against the :data:`CHAIN_LOOKBACK` previous seeds
    (integer scoring, deterministic nearest-predecessor tie-break),
    then a second scan backtracks the best chain on device. Returns
    ``[B, 6]`` int32 rows ``(score, n_chained, q_lo, q_hi, t_lo,
    t_hi)`` — the only fetch."""
    B = ts.shape[0]
    H = CHAIN_LOOKBACK
    ts_t = ts.T.astype(jnp.int32)       # [S, B]
    qs_t = qs.T.astype(jnp.int32)
    start = jnp.int32(k * GAP_UNIT)

    def score_step(carry, xs):
        ht, hq, hf = carry              # [B, H] histories, newest first
        tc, qc, i = xs
        live = i < ns
        dt = tc[:, None] - ht
        dq = qc[:, None] - hq
        gap = jnp.abs(dq - dt)
        ok = ((dt >= 1) & (dq >= 1) & (dt <= MAX_GAP) & (dq <= MAX_GAP)
              & (gap <= BAND_DIAG) & (hf > jnp.int32(_NEG // 2)))
        span = jnp.minimum(jnp.int32(k), jnp.minimum(dq, dt))
        cand = jnp.where(ok, hf + span * GAP_UNIT - gap, jnp.int32(_NEG))
        best = jnp.max(cand, axis=1)
        arg = jnp.argmax(cand, axis=1).astype(jnp.int32)  # nearest wins ties
        f_i = jnp.where(live, jnp.maximum(start, best), jnp.int32(_NEG))
        parent = jnp.where(live & (best > start), arg + 1, jnp.int32(0))
        ht = jnp.concatenate([tc[:, None], ht[:, :-1]], axis=1)
        hq = jnp.concatenate([qc[:, None], hq[:, :-1]], axis=1)
        hf = jnp.concatenate([f_i[:, None], hf[:, :-1]], axis=1)
        return (ht, hq, hf), (f_i, parent)

    init = (jnp.zeros((B, H), jnp.int32), jnp.zeros((B, H), jnp.int32),
            jnp.full((B, H), _NEG, jnp.int32))
    idx = jnp.arange(S, dtype=jnp.int32)
    _, (f_all, p_all) = lax.scan(score_step, init, (ts_t, qs_t, idx))
    f = f_all.T                          # [B, S]
    parent = p_all.T                     # [B, S] offsets 0..H
    lanes = jnp.arange(B, dtype=jnp.int32)
    end = jnp.argmax(f, axis=1).astype(jnp.int32)  # ties -> lowest index
    score = f[lanes, end]
    live0 = ns > 0

    def back_step(carry, _):
        cur, active, n, q_lo, t_lo = carry
        q_lo = jnp.where(active, qs[lanes, cur], q_lo)
        t_lo = jnp.where(active, ts[lanes, cur], t_lo)
        n = n + active.astype(jnp.int32)
        off = parent[lanes, cur]
        nxt_active = active & (off > 0)
        cur = jnp.where(nxt_active, cur - off, cur)
        return (cur, nxt_active, n, q_lo, t_lo), None

    binit = (end, live0, jnp.zeros(B, jnp.int32),
             jnp.zeros(B, jnp.int32), jnp.zeros(B, jnp.int32))
    (cur, _, n_chained, q_lo, t_lo), _ = lax.scan(
        back_step, binit, None, length=S)
    q_hi = qs[lanes, end]
    t_hi = ts[lanes, end]
    out = jnp.stack([jnp.where(live0, score, jnp.int32(_NEG)), n_chained,
                     q_lo, q_hi, t_lo, t_hi], axis=1)
    return out


# --------------------------------------------------------- device join

def _compact_sorted(h, a, b, c, keep):
    """Order-preserving device compaction of kept table entries to a
    sorted prefix: the cumsum-rank scatter (overlap_seed._compact_kernel
    idiom). Dropped entries all park on one spill slot past the end;
    un-scattered tail slots keep the ``_HASH_MAX`` init, so the prefix
    plus tail is still ascending and searchsorted-safe."""
    n = h.shape[0]
    rank = jnp.cumsum(keep.astype(jnp.int32))
    nk = rank[-1]
    idx = jnp.where(keep, rank - 1, jnp.int32(n))
    out_h = jnp.full((n + 1,), np.uint32(overlap_seed._HASH_MAX),
                     jnp.uint32).at[idx].set(h)
    out_a = jnp.zeros((n + 1,), jnp.int32).at[idx].set(a)
    out_b = jnp.zeros((n + 1,), jnp.int32).at[idx].set(b)
    out_c = jnp.zeros((n + 1,), jnp.int32).at[idx].set(c)
    return out_h[:n], out_a[:n], out_b[:n], out_c[:n], nk


@jax.jit
def _join_sort_kernel(rh, rid, rpos, rstr, th, tid, tpos, tstr, max_occ):
    """Device half one of the seed join: sort both padded tables by
    hash, derive per-hash occurrence totals (both tables) from
    searchsorted run bounds, drop super-hot buckets whole, compact the
    survivors to sorted prefixes, and emit the read→target searchsorted
    join ramp (``lo``/``cnt``/inclusive ``offs``).

    Pad slots carry ``_HASH_MAX``, which no real table entry can (the
    seed builder filters it), so they sort to the tail and the validity
    masks are pure hash compares. Returns the compacted tables, the
    ramp, the total hit count and the unique-hot-hash count — only the
    two scalars need fetching before the expansion kernel launches."""
    hmax = np.uint32(overlap_seed._HASH_MAX)
    rh, rid, rpos, rstr = lax.sort((rh, rid, rpos, rstr), num_keys=1)
    th, tid, tpos, tstr = lax.sort((th, tid, tpos, tstr), num_keys=1)
    rr = (jnp.searchsorted(rh, rh, side="right")
          - jnp.searchsorted(rh, rh, side="left"))
    rt = (jnp.searchsorted(th, rh, side="right")
          - jnp.searchsorted(th, rh, side="left"))
    tt = (jnp.searchsorted(th, th, side="right")
          - jnp.searchsorted(th, th, side="left"))
    tr = (jnp.searchsorted(rh, th, side="right")
          - jnp.searchsorted(rh, th, side="left"))
    valid_r = rh != hmax
    valid_t = th != hmax
    hot_r = (rr + rt) > max_occ
    hot_t = (tt + tr) > max_occ
    # unique hot hashes across the union (numpy oracle's freq_capped):
    # first occurrence in reads, plus first-in-targets absent from reads
    first_r = valid_r & jnp.concatenate(
        [jnp.ones(1, bool), rh[1:] != rh[:-1]])
    first_t = valid_t & jnp.concatenate(
        [jnp.ones(1, bool), th[1:] != th[:-1]])
    capped = (jnp.sum((first_r & hot_r).astype(jnp.int32))
              + jnp.sum((first_t & hot_t & (tr == 0)).astype(jnp.int32)))
    rh, rid, rpos, rstr, nr = _compact_sorted(
        rh, rid, rpos, rstr, valid_r & ~hot_r)
    th, tid, tpos, tstr, nt = _compact_sorted(
        th, tid, tpos, tstr, valid_t & ~hot_t)
    lo = jnp.searchsorted(th, rh, side="left").astype(jnp.int32)
    hi = jnp.searchsorted(th, rh, side="right").astype(jnp.int32)
    live = jnp.arange(rh.shape[0], dtype=jnp.int32) < nr
    cnt = jnp.where(live, hi - lo, jnp.int32(0))
    offs = jnp.cumsum(cnt)
    return (rid, rpos, rstr, tid, tpos, tstr, lo, cnt, offs,
            offs[-1], capped)


_I32_MAX = np.int32(0x7FFFFFFF)


@functools.partial(jax.jit, static_argnames=("E", "k"))
def _join_expand_kernel(rid, rpos, rstr, tid, tpos, tstr, lo, cnt, offs,
                        total, read_self_t, qlens, *, E: int, k: int):
    """Device half two: expand the join ramp into hit rows, drop self
    hits, flip reverse-strand query coordinates, and sort by the
    oracle's 5-key order ``(q, t, rel, tp, qc)`` on device.

    Hit 5-tuples are unique (the seed tables dedupe on (seq, pos)), so
    this unstable ascending sort reproduces numpy's stable lexsort
    byte-for-byte; dropped rows take all-sentinel keys and cluster past
    ``n_valid``, which is never fetched."""
    e = jnp.arange(E, dtype=jnp.int32)
    live = e < total
    # ragged ramp: hit e belongs to the read entry whose inclusive
    # cumsum first exceeds e, at target offset lo + (e - run_begin)
    ridx = jnp.clip(jnp.searchsorted(offs, e, side="right"),
                    0, rid.shape[0] - 1).astype(jnp.int32)
    begin = offs[ridx] - cnt[ridx]
    tix = jnp.clip(lo[ridx] + (e - begin), 0, tid.shape[0] - 1)
    q = rid[ridx]
    qp = rpos[ridx]
    t = tid[tix]
    tp = tpos[tix]
    rel = (rstr[ridx] != tstr[tix]).astype(jnp.int32)
    qsafe = jnp.clip(q, 0, read_self_t.shape[0] - 1)
    keep = live & (t != read_self_t[qsafe])
    qc = jnp.where(rel == 1, qlens[qsafe] - qp - jnp.int32(k), qp)
    s = jnp.where(keep, jnp.int32(0), _I32_MAX)
    ks = lax.sort((jnp.where(keep, q, _I32_MAX) | s,
                   t | s, rel | s, tp | s, qc | s), num_keys=5)
    return ks[0], ks[1], ks[2], ks[3], ks[4], jnp.sum(keep.astype(jnp.int32))


def _pad_table(table, n_pad: int):
    """Host-side pow2 padding of one (hash, id, pos, strand) table for
    the device sort: pad slots take the ``_HASH_MAX`` sentinel (no real
    entry carries it) and strand widens to int32."""
    h, sid, pos, strand = table
    hp = np.full(n_pad, np.uint32(overlap_seed._HASH_MAX), np.uint32)
    ip = np.zeros(n_pad, np.int32)
    pp = np.zeros(n_pad, np.int32)
    sp = np.zeros(n_pad, np.int32)
    hp[:h.size] = h
    ip[:h.size] = sid
    pp[:h.size] = pos
    sp[:h.size] = strand.astype(np.int32)
    return hp, ip, pp, sp


def join_seeds(read_table, target_table, read_self_t: np.ndarray,
               qlens: np.ndarray, *, k: int, max_occ: int,
               device_join: bool = True, resident: bool = False
               ) -> Tuple[Dict[str, object], int]:
    """Seed join front end: the device kernels when eligible, the numpy
    :func:`match_seeds` oracle otherwise.

    Returns ``(hits, freq_capped)``. ``hits`` always carries host
    ``q``/``t``/``rel`` int64 arrays (the group/pair boundary keys the
    host scheduler needs either way) plus EITHER host ``tp``/``qc``
    int64 arrays (oracle layout) OR, under ``resident=True`` on the
    device path, device ``tp_dev``/``qc_dev`` int32 arrays the chain
    stream gathers from directly — the matched seed coordinates then
    never visit the host (ledgered in ``dataflow.bytes_avoided``).

    The bail-out ladder (empty tables, padded tables over
    :data:`JOIN_TABLE_CELLS`, hit counts over :data:`JOIN_MAX_HITS`,
    int32 ramp overflow risk) falls back to the oracle and counts into
    ``overlap.join_bailouts`` — never approximation, never silent."""
    rh, th = read_table[0], target_table[0]

    def _oracle(bail: bool):
        if bail:
            metrics.inc("overlap.join_bailouts")
        hits, capped = match_seeds(read_table, target_table, read_self_t,
                                   qlens, k=k, max_occ=max_occ)
        return hits, capped

    if not device_join:
        return _oracle(bail=False)
    if rh.size == 0 or th.size == 0:
        # rung 1: an empty side joins to nothing — the oracle's trivial
        # path costs less than one kernel launch
        return _oracle(bail=True)
    # graftlint: disable=warmup-coverage (the join runs ONCE per run immediately after seeding produces the very sizes these pow2 buckets quantize — there is no earlier moment to warm them from)
    R2, T2 = _table_pad(rh.size), _table_pad(th.size)
    if R2 + T2 > JOIN_TABLE_CELLS or R2 * max(1, max_occ) >= (1 << 31):
        # rung 2: table arena overflow / int32 ramp overflow risk
        return _oracle(bail=True)

    rpad = _pad_table(read_table, R2)
    tpad = _pad_table(target_table, T2)
    with obs.span("overlap.join.dispatch", reads=int(rh.size),
                  targets=int(th.size)):
        # graftlint: disable=jit-shape-hazard (R2/T2 are the pow2 _table_pad buckets)
        (rid, rpos, rstr, tid, tpos, tstr, lo, cnt, offs, total_d,
         capped_d) = _join_sort_kernel(*rpad, *tpad, np.int32(max_occ))
    with obs.span("overlap.join.fetch"):
        total, capped = (int(x) for x in fetch_global([total_d, capped_d]))
    metrics.inc("dataflow.bytes_fetched", 8)
    if total > JOIN_MAX_HITS:
        # rung 3: hit arena overflow (a repeat-soaked join the chain
        # phase could not absorb anyway)
        return _oracle(bail=True)
    empty = {key: np.zeros(0, np.int64) for key in
             ("q", "t", "rel", "tp", "qc")}
    if total == 0:
        return empty, capped

    # graftlint: disable=warmup-coverage (the expand geometry is the join's own counted output — pow2-bucketed, knowable only mid-join)
    E = _hits_pad(total)
    with obs.span("overlap.join.dispatch", hits=total):
        # graftlint: disable=jit-shape-hazard (E is the pow2 _hits_pad bucket; k is a run-constant flag value — one compile per run)
        q_d, t_d, rel_d, tp_d, qc_d, nv_d = _join_expand_kernel(
            rid, rpos, rstr, tid, tpos, tstr, lo, cnt, offs,
            jnp.int32(total), read_self_t.astype(np.int32),
            qlens.astype(np.int32), E=E, k=k)
    with obs.span("overlap.join.fetch"):
        n = int(fetch_global([nv_d])[0])
        if resident:
            q_h, t_h, rel_h = fetch_global(
                [q_d[:n], t_d[:n], rel_d[:n]])
        else:
            q_h, t_h, rel_h, tp_h, qc_h = fetch_global(
                [q_d[:n], t_d[:n], rel_d[:n], tp_d[:n], qc_d[:n]])
    hits: Dict[str, object] = {"q": q_h.astype(np.int64),
                               "t": t_h.astype(np.int64),
                               "rel": rel_h.astype(np.int64)}
    if resident:
        hits["tp_dev"] = tp_d
        hits["qc_dev"] = qc_d
        metrics.inc("dataflow.bytes_fetched", 12 * n + 4)
        metrics.inc("dataflow.bytes_avoided", 8 * n)
    else:
        hits["tp"] = tp_h.astype(np.int64)
        hits["qc"] = qc_h.astype(np.int64)
    return hits, capped


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
    ``max_occ`` drop whole; ``freq_capped`` counts them."""
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
    freq_capped = int(hot.sum())
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


# ---------------------------------------------------------- numpy oracle

def chain_np(ts: np.ndarray, qs: np.ndarray, k: int
             ) -> Tuple[int, int, int, int, int, int]:
    """Pure-python/numpy chain oracle with exactly the kernel's
    semantics: integer scoring, bounded lookback, nearest-predecessor
    strict-> tie-break, lowest-index best-end tie-break. Returns
    ``(score, n_chained, q_lo, q_hi, t_lo, t_hi)``."""
    n = len(ts)
    if n == 0:
        return (_NEG, 0, 0, 0, 0, 0)
    start = k * GAP_UNIT
    f = [0] * n
    par = [0] * n
    for i in range(n):
        best, arg = _NEG, -1
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


# -------------------------------------------------------------- chaining

def _pair_runs(hits: Dict[str, np.ndarray]
               ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Consecutive-run boundaries of the (q, t, rel) candidate-pair key
    over lexsorted hits: ``(starts, ends, counts)``."""
    nhits = hits["q"].size
    if nhits == 0:
        z = np.zeros(0, np.int64)
        return z, z, z
    key_change = np.zeros(nhits, bool)
    key_change[0] = True
    for col in ("q", "t", "rel"):
        key_change[1:] |= hits[col][1:] != hits[col][:-1]
    starts = np.flatnonzero(key_change)
    ends = np.append(starts[1:], nhits)
    return starts, ends, ends - starts


def _pack_lanes(tp: np.ndarray, qc: np.ndarray, starts: np.ndarray,
                counts: np.ndarray, S: int, B: int
                ) -> Tuple[np.ndarray, np.ndarray]:
    """Vectorized host fill of one ``[B, S]`` chain arena from the flat
    hit arrays — one masked gather instead of the former per-lane
    Python slice loop (the host analog of :func:`_gather_pairs_kernel`;
    ``starts``/``counts`` are length B, zero-padded past the live
    lanes)."""
    lane_starts = starts[:, None] + np.arange(S, dtype=np.int64)[None, :]
    mask = np.arange(S, dtype=np.int64)[None, :] < counts[:, None]
    np.clip(lane_starts, 0, max(0, tp.size - 1), out=lane_starts)
    if tp.size == 0:
        return np.zeros((B, S), np.int32), np.zeros((B, S), np.int32)
    ts = np.where(mask, tp[lane_starts], 0).astype(np.int32)
    qs = np.where(mask, qc[lane_starts], 0).astype(np.int32)
    return ts, qs


@functools.partial(jax.jit, static_argnames=("S",))
def _gather_pairs_kernel(tp_dev, qc_dev, starts, counts, *, S: int):
    """Device fill of one ``[B, S]`` chain arena straight from the
    resident join output — the matched seed coordinates feed
    :func:`_chain_kernel` without ever visiting the host."""
    idx = starts[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    mask = jnp.arange(S, dtype=jnp.int32)[None, :] < counts[:, None]
    idx = jnp.clip(idx, 0, tp_dev.shape[0] - 1)
    ts = jnp.where(mask, tp_dev[idx], jnp.int32(0))
    qs = jnp.where(mask, qc_dev[idx], jnp.int32(0))
    return ts, qs


class _ChainStream:
    """Ragged streaming chain session — the overlapper analog of
    ``nw._AlignStream`` / ``poa._ConsensusStream``.

    Candidate pairs arrive through :meth:`add` (cost = their own seed
    count) and class into pow2 seed-count buckets; each bucket
    greedy-fills fixed ``[B, S]`` arenas against the
    :data:`CHAIN_ARENA_CELLS` budget and dispatches a chunk the moment
    it fills, ASYNCHRONOUSLY — host packing of later pairs overlaps
    device DP of earlier chunks, and fetches happen only when the
    in-flight budget (:data:`CHAIN_INFLIGHT` chunks / 2 arenas of
    cells) forces one or at :meth:`finish`. The DP is per-lane
    independent and each pair always lands in the same pow2 bucket, so
    per-pair rows are invariant to feed batching — the property the
    streamed/barriered byte-identity contract rests on.

    ``tp``/``qc`` may be host arrays (vectorized masked gather) or the
    resident join's device arrays (:func:`_gather_pairs_kernel` — the
    seed coordinates never visit the host). ``on_row(pid, row)`` fires
    as each pair's ``[6]`` summary row lands, in deterministic
    (chunk-completion) order — the group streamer's completion
    signal."""

    def __init__(self, *, k: int, tp, qc, device_src: bool = False,
                 on_row: Optional[Callable] = None):
        self.k = k
        self.tp = tp
        self.qc = qc
        self.device_src = device_src
        self.on_row = on_row
        self.rows: Dict[int, np.ndarray] = {}
        self.pending: Dict[int, List[Tuple[int, int, int]]] = {}
        self.inflight: List[dict] = []
        self.inflight_cells = 0
        self._done = False

    # ------------------------------------------------------------- intake

    def add(self, pid: int, start: int, count: int) -> None:
        """Queue one candidate pair (``count`` seeds at flat-hit offset
        ``start``). Buffered only — call :meth:`pump` after a batch."""
        assert not self._done, "chain stream already finished"
        self.pending.setdefault(_seed_bucket(count), []).append(
            (count, pid, start))

    def pump(self) -> None:
        """Dispatch every chunk that fills (non-blocking unless the
        in-flight budget forces a pipelined fetch)."""
        self._drain(final=False)

    # ----------------------------------------------------------- dispatch

    def _drain(self, final: bool) -> None:
        for S in sorted(self.pending):
            entries = self.pending.pop(S)
            # biggest seed lists first: tail chunks stay dense and the
            # (S, B) geometry per chunk is the bucket's full arena cap,
            # so the warm ladder covers every full chunk
            entries.sort(key=lambda e: (-e[0], e[1]))
            cap = _pair_batch(S, CHAIN_ARENA_CELLS)
            while entries:
                if not final and len(entries) < cap:
                    break
                chunk = entries[:cap]
                del entries[:cap]
                self._launch(chunk, S)
            if entries:
                self.pending[S] = entries

    def _launch(self, chunk: List[Tuple[int, int, int]], S: int) -> None:
        B = _pair_batch(S, len(chunk))
        starts = np.zeros(B, np.int64)
        counts = np.zeros(B, np.int64)
        for lane, (c, _, s0) in enumerate(chunk):
            starts[lane] = s0
            counts[lane] = c
        with obs.span("overlap.chain.dispatch", pairs=len(chunk)):
            if self.device_src:
                # graftlint: disable=jit-shape-hazard (S is the pow2 _seed_bucket rung)
                ts, qs = _gather_pairs_kernel(
                    self.tp, self.qc, starts.astype(np.int32),
                    counts.astype(np.int32), S=S)
                ns = counts.astype(np.int32)
            else:
                ts, qs = _pack_lanes(self.tp, self.qc, starts, counts,
                                     S, B)
                ns = counts.astype(np.int32)
            # graftlint: disable=jit-shape-hazard (k is a run-constant flag value — one compile per run; S is the pow2 bucket)
            out = _chain_kernel(ts, qs, ns, S=S, k=self.k)
        self.inflight.append({"chunk": chunk, "out": out,
                              "cells": B * S})
        self.inflight_cells += B * S
        metrics.inc("overlap.lanes_total", B * S)
        metrics.inc("overlap.lanes_occupied", int(counts.sum()))
        metrics.inc("overlap.chunks", 1)
        # mirrored legacy names (run-report compat with the barrier path)
        metrics.inc("overlap.chain_lanes_total", B * S)
        metrics.inc("overlap.chain_lanes_occupied", int(counts.sum()))
        while (len(self.inflight) > CHAIN_INFLIGHT
               or self.inflight_cells > 2 * CHAIN_ARENA_CELLS):
            self._fetch_oldest()

    def _fetch_oldest(self) -> None:
        la = self.inflight.pop(0)
        self.inflight_cells -= la["cells"]
        with obs.span("overlap.chain.fetch", pairs=len(la["chunk"])):
            out_np = fetch_global([la["out"]])[0]
        for lane, (_, pid, _) in enumerate(la["chunk"]):
            row = out_np[lane].astype(np.int64)
            self.rows[pid] = row
            if self.on_row is not None:
                self.on_row(pid, row)

    # -------------------------------------------------------------- drain

    def finish(self) -> Dict[int, np.ndarray]:
        """Dispatch the partial chunks, drain the pipeline, and return
        the per-pair ``[6]`` rows keyed by pair id."""
        assert not self._done, "chain stream already finished"
        self._done = True
        self._drain(final=True)
        while self.inflight:
            self._fetch_oldest()
        return self.rows


def chain_pairs(hits: Dict[str, np.ndarray], *, k: int, min_seeds: int
                ) -> Tuple[Dict[str, np.ndarray], int, int]:
    """Run the chain DP over every candidate pair in ``hits`` — the
    phase-barriered scheduling (whole-bucket chunks, synchronous
    fetch), kept as the ragged stream's A/B leg and parity oracle.

    Returns ``(chains, kept, dropped)``: parallel arrays ``q``, ``t``,
    ``rel``, ``score``, ``n_seeds``, ``q_lo``, ``q_hi``, ``t_lo``,
    ``t_hi`` (query coords still in chain space — flipped for reverse
    hits), one row per pair whose best chain holds ``min_seeds``+
    seeds. Pairs with fewer matched seeds than ``min_seeds`` drop
    before the DP; both drop classes count into ``dropped``."""
    empty = {key: np.zeros(0, np.int64) for key in
             ("q", "t", "rel", "score", "n_seeds",
              "q_lo", "q_hi", "t_lo", "t_hi")}
    nhits = hits["q"].size
    if nhits == 0:
        return empty, 0, 0
    starts, ends, counts = _pair_runs(hits)
    metrics.inc("overlap.candidate_pairs", int(starts.size))

    eligible = counts >= min_seeds
    dropped = int((~eligible).sum())
    starts, ends, counts = starts[eligible], ends[eligible], counts[eligible]
    if starts.size == 0:
        return empty, 0, dropped

    by_bucket: Dict[int, List[int]] = {}
    for i, c in enumerate(counts):
        by_bucket.setdefault(_seed_bucket(int(c)), []).append(i)

    rows_out = np.zeros((starts.size, 6), np.int64)
    for S in sorted(by_bucket):
        members = by_bucket[S]
        cap = _pair_batch(S, len(members))
        for begin in range(0, len(members), cap):
            part = members[begin:begin + cap]
            B = _pair_batch(S, len(part))
            pstarts = np.zeros(B, np.int64)
            pcounts = np.zeros(B, np.int64)
            for lane, m in enumerate(part):
                pstarts[lane] = starts[m]
                pcounts[lane] = counts[m]
            ts, qs = _pack_lanes(hits["tp"], hits["qc"],
                                 pstarts, pcounts, S, B)
            ns = pcounts.astype(np.int32)
            with obs.span("overlap.chain.dispatch", pairs=len(part)):
                # graftlint: disable=jit-shape-hazard (k is a run-constant flag value — one compile per run; S is the pow2 bucket)
                out = _chain_kernel(ts, qs, ns, S=S, k=k)
            with obs.span("overlap.chain.fetch", pairs=len(part)):
                out_np = fetch_global([out])[0]
            rows_out[part] = out_np[:len(part)].astype(np.int64)
            metrics.inc("overlap.chain_lanes_total", B * S)
            metrics.inc("overlap.chain_lanes_occupied", int(ns.sum()))
            metrics.inc("overlap.lanes_total", B * S)
            metrics.inc("overlap.lanes_occupied", int(ns.sum()))
            metrics.inc("overlap.chunks", 1)

    good = rows_out[:, 1] >= min_seeds
    kept = int(good.sum())
    dropped += int((~good).sum())
    sel = np.flatnonzero(good)
    first = starts[sel]
    return ({"q": hits["q"][first], "t": hits["t"][first],
             "rel": hits["rel"][first],
             "score": rows_out[sel, 0], "n_seeds": rows_out[sel, 1],
             "q_lo": rows_out[sel, 2], "q_hi": rows_out[sel, 3],
             "t_lo": rows_out[sel, 4], "t_hi": rows_out[sel, 5]},
            kept, dropped)


# ---------------------------------------------------------------- driver

_ROW_KEYS = ("q_ord", "t_idx", "strand", "q_begin", "q_end",
             "t_begin", "t_end", "n_seeds", "score")


def _empty_rows() -> Dict[str, np.ndarray]:
    return {key: np.zeros(0, np.int64) for key in _ROW_KEYS}


def _resolve_params(k, w, max_occ, min_seeds, resident, device_join,
                    ragged):
    from .. import flags
    k = flags.get_int("RACON_TPU_OVERLAP_K") if k is None else k
    w = flags.get_int("RACON_TPU_OVERLAP_W") if w is None else w
    if max_occ is None:
        max_occ = flags.get_int("RACON_TPU_OVERLAP_MAX_OCC")
    if min_seeds is None:
        min_seeds = flags.get_int("RACON_TPU_OVERLAP_MIN_SEEDS")
    if resident is None:
        resident = flags.get_bool("RACON_TPU_RESIDENT")
    if device_join is None:
        device_join = flags.get_bool("RACON_TPU_OVERLAP_DEVICE_JOIN")
    if ragged is None:
        ragged = flags.get_bool("RACON_TPU_OVERLAP_RAGGED")
    k = max(4, min(16, k))  # uint32 canonical codes hold 2k bits
    w = max(1, w)
    return k, w, max_occ, min_seeds, resident, device_join, ragged


def _seed_and_join(read_seqs, target_seqs, read_self_t, qlens, *,
                   k, w, max_occ, resident, device_join, cache,
                   resident_hits):
    """Seed both pools (target table through the fingerprint cache)
    and run the join front end. ``resident_hits`` keeps the matched
    seed coordinates on device (only meaningful on the device-join
    path feeding the chain stream)."""
    with obs.span("overlap.seed", reads=len(read_seqs),
                  targets=len(target_seqs)):
        rt = overlap_seed.build_seed_table(read_seqs, k=k, w=w,
                                           resident=resident)
        tt = overlap_seed.build_seed_table(target_seqs, k=k, w=w,
                                           resident=resident,
                                           cache=cache)
    with obs.span("overlap.match"):
        hits, capped = join_seeds(rt, tt, read_self_t, qlens,
                                  k=k, max_occ=max_occ,
                                  device_join=device_join,
                                  resident=resident_hits)
        metrics.inc("overlap.freq_capped_buckets", capped)
    return hits


def _group_rows(q, t, rel, rows6, qlens, k) -> Dict[str, np.ndarray]:
    """Emit one query group's kept chains as canonical overlap rows:
    flip reverse-strand chain coords back to forward query space and
    sort by ``(t, rel, t_begin, q_begin)`` — exactly the global
    canonical lexsort restricted to one value of its primary key, which
    is what makes streamed emission byte-identical to the barrier."""
    ql = qlens[q]
    q_begin = np.where(rel == 1, ql - (rows6[:, 3] + k), rows6[:, 2])
    q_end = np.where(rel == 1, ql - rows6[:, 2], rows6[:, 3] + k)
    t_begin = rows6[:, 4]
    t_end = rows6[:, 5] + k
    order = np.lexsort((q_begin, t_begin, rel, t))
    return {"q_ord": q[order], "t_idx": t[order], "strand": rel[order],
            "q_begin": q_begin[order], "q_end": q_end[order],
            "t_begin": t_begin[order], "t_end": t_end[order],
            "n_seeds": rows6[order, 1], "score": rows6[order, 0]}


def iter_overlap_groups(read_seqs: List[bytes], target_seqs: List[bytes],
                        read_self_t: np.ndarray, *,
                        k: Optional[int] = None, w: Optional[int] = None,
                        max_occ: Optional[int] = None,
                        min_seeds: Optional[int] = None,
                        resident: Optional[bool] = None,
                        device_join: Optional[bool] = None,
                        cache: bool = True
                        ) -> Iterator[Dict[str, np.ndarray]]:
    """Streaming overlapper driver: yield canonical overlap rows per
    query group (ascending query ordinal) as chain chunks resolve.

    The chain stream keeps :data:`CHAIN_INFLIGHT` chunks in flight, so
    while the consumer aligns group N's overlaps the device is already
    chaining groups N+1.. — the phase barrier the round-20 overlapper
    kept between chaining and alignment streams away. Concatenating
    every yield reproduces :func:`find_overlaps` byte-for-byte (the
    global sort's primary key is the query ordinal)."""
    k, w, max_occ, min_seeds, resident, device_join, _ = _resolve_params(
        k, w, max_occ, min_seeds, resident, device_join, None)
    qlens = np.fromiter((len(s) for s in read_seqs), np.int64,
                        len(read_seqs))
    hits = _seed_and_join(
        read_seqs, target_seqs, read_self_t, qlens,
        k=k, w=w, max_occ=max_occ, resident=resident,
        device_join=device_join, cache=cache,
        resident_hits=resident and device_join)
    starts, ends, counts = _pair_runs(hits)
    metrics.inc("overlap.candidate_pairs", int(starts.size))
    if starts.size == 0:
        return
    q_of = hits["q"][starts]
    t_of = hits["t"][starts]
    rel_of = hits["rel"][starts]
    eligible = counts >= min_seeds
    kept_total = 0
    dropped_total = int((~eligible).sum())

    # query-group boundaries over the pair axis (pairs are lexsorted,
    # so groups are consecutive runs of q)
    gchange = np.ones(q_of.size, bool)
    gchange[1:] = q_of[1:] != q_of[:-1]
    gstart = np.flatnonzero(gchange)
    gend = np.append(gstart[1:], q_of.size)
    ngroups = gstart.size
    group_of = np.searchsorted(gstart, np.arange(q_of.size), "right") - 1
    # unresolved eligible pairs per group — the emission gate
    rem = np.zeros(ngroups, np.int64)
    np.add.at(rem, group_of[eligible], 1)

    def on_row(pid, _row):
        rem[group_of[pid]] -= 1

    device_src = "tp_dev" in hits
    stream = _ChainStream(
        k=k, tp=hits["tp_dev"] if device_src else hits["tp"],
        qc=hits["qc_dev"] if device_src else hits["qc"],
        device_src=device_src, on_row=on_row)

    def emit(g: int) -> Optional[Dict[str, np.ndarray]]:
        nonlocal kept_total, dropped_total
        pids = np.arange(gstart[g], gend[g])[eligible[gstart[g]:gend[g]]]
        if pids.size == 0:
            return None
        rows6 = np.stack([stream.rows.pop(int(p)) for p in pids])
        good = rows6[:, 1] >= min_seeds
        kept_total += int(good.sum())
        dropped_total += int((~good).sum())
        if not good.any():
            return None
        sel = pids[good]
        return _group_rows(q_of[sel], t_of[sel], rel_of[sel],
                           rows6[good], qlens, k)

    emit_at = 0
    for g in range(ngroups):
        for p in range(int(gstart[g]), int(gend[g])):
            if eligible[p]:
                stream.add(p, int(starts[p]), int(counts[p]))
        stream.pump()
        while emit_at <= g and rem[emit_at] == 0:
            rows = emit(emit_at)
            emit_at += 1
            if rows is not None:
                yield rows
    stream.finish()
    while emit_at < ngroups:
        rows = emit(emit_at)
        emit_at += 1
        if rows is not None:
            yield rows
    metrics.inc("overlap.stream_groups", ngroups)
    metrics.inc("overlap.chains_kept", kept_total)
    metrics.inc("overlap.chains_dropped", dropped_total)


def find_overlaps(read_seqs: List[bytes], target_seqs: List[bytes],
                  read_self_t: np.ndarray, *,
                  k: Optional[int] = None, w: Optional[int] = None,
                  max_occ: Optional[int] = None,
                  min_seeds: Optional[int] = None,
                  resident: Optional[bool] = None,
                  device_join: Optional[bool] = None,
                  ragged: Optional[bool] = None,
                  cache: bool = True
                  ) -> Dict[str, np.ndarray]:
    """The full first-party overlapper: seed both pools, match, chain,
    and emit forward-strand ``Overlap``-shaped rows.

    ``read_self_t[i]`` names the target index read ``i`` *is* (self-hit
    suppression for C mode, where the draft windows are built from the
    very reads being mapped), or -1. Returns parallel arrays ``q_ord``,
    ``t_idx``, ``strand``, ``q_begin``, ``q_end``, ``t_begin``,
    ``t_end``, ``n_seeds``, ``score`` canonically sorted by ``(q_ord,
    t_idx, strand, t_begin, q_begin)`` — any intermediate ordering
    wobble is erased by that canonical order, which is what makes
    reruns and ``--shards`` replays byte-identical.

    The default path (``RACON_TPU_OVERLAP_RAGGED=1``) collects the
    ragged stream's per-group emission; ``ragged=False`` runs the
    phase-barriered ``chain_pairs`` A/B leg. Both orders are the same
    canonical order, so output bytes never depend on the flag."""
    (k, w, max_occ, min_seeds, resident, device_join,
     ragged) = _resolve_params(k, w, max_occ, min_seeds, resident,
                               device_join, ragged)
    if ragged:
        parts = list(iter_overlap_groups(
            read_seqs, target_seqs, read_self_t, k=k, w=w,
            max_occ=max_occ, min_seeds=min_seeds, resident=resident,
            device_join=device_join, cache=cache))
        if not parts:
            return _empty_rows()
        return {key: np.concatenate([p[key] for p in parts])
                for key in _ROW_KEYS}

    qlens = np.fromiter((len(s) for s in read_seqs), np.int64,
                        len(read_seqs))
    hits = _seed_and_join(
        read_seqs, target_seqs, read_self_t, qlens,
        k=k, w=w, max_occ=max_occ, resident=resident,
        device_join=device_join, cache=cache, resident_hits=False)
    with obs.span("overlap.chain"):
        chains, kept, dropped = chain_pairs(hits, k=k,
                                            min_seeds=min_seeds)
        metrics.inc("overlap.chains_kept", kept)
        metrics.inc("overlap.chains_dropped", dropped)

    q = chains["q"]
    rel = chains["rel"]
    ql = qlens[q] if q.size else np.zeros(0, np.int64)
    # flip reverse-strand chain coords back to forward query space
    q_begin = np.where(rel == 1, ql - (chains["q_hi"] + k), chains["q_lo"])
    q_end = np.where(rel == 1, ql - chains["q_lo"], chains["q_hi"] + k)
    t_begin = chains["t_lo"]
    t_end = chains["t_hi"] + k
    order = np.lexsort((q_begin, t_begin, rel, chains["t"], q))
    return {"q_ord": q[order], "t_idx": chains["t"][order],
            "strand": rel[order],
            "q_begin": q_begin[order], "q_end": q_end[order],
            "t_begin": t_begin[order], "t_end": t_end[order],
            "n_seeds": chains["n_seeds"][order],
            "score": chains["score"][order]}


def paf_bytes_rowwise(rows: Dict[str, np.ndarray],
                      read_names: List[bytes], read_lens: np.ndarray,
                      target_names: List[bytes],
                      target_lens: np.ndarray, *, k: int
                      ) -> List[bytes]:
    """Row-at-a-time PAF writer — the byte-identity oracle for the
    vectorized :func:`paf_bytes` (kept off the hot path)."""
    out: List[bytes] = []
    for i in range(rows["q_ord"].size):
        q = int(rows["q_ord"][i])
        t = int(rows["t_idx"][i])
        qb, qe = int(rows["q_begin"][i]), int(rows["q_end"][i])
        tb, te = int(rows["t_begin"][i]), int(rows["t_end"][i])
        matches = min(int(rows["n_seeds"][i]) * k, qe - qb, te - tb)
        alen = max(qe - qb, te - tb)
        out.append(b"\t".join((
            read_names[q], str(int(read_lens[q])).encode(),
            str(qb).encode(), str(qe).encode(),
            b"-" if int(rows["strand"][i]) else b"+",
            target_names[t], str(int(target_lens[t])).encode(),
            str(tb).encode(), str(te).encode(),
            str(matches).encode(), str(alen).encode(), b"255"))
            + b"\n")
    return out


def paf_bytes(rows: Dict[str, np.ndarray], read_names: List[bytes],
              read_lens: np.ndarray, target_names: List[bytes],
              target_lens: np.ndarray, *, k: int) -> List[bytes]:
    """Serialize overlapper rows as 12-column PAF lines (newline
    included) — deterministic bytes, so the auto-mode PAF a sharded run
    writes is identical across reruns and workers.

    Columns are formatted as whole numpy arrays (``np.char.mod``) and
    joined once per row, instead of the per-row Python format loop
    :func:`paf_bytes_rowwise` keeps as the parity oracle."""
    n = int(rows["q_ord"].size)
    if n == 0:
        return []
    q = rows["q_ord"]
    t = rows["t_idx"]
    qb, qe = rows["q_begin"], rows["q_end"]
    tb, te = rows["t_begin"], rows["t_end"]
    matches = np.minimum(np.minimum(rows["n_seeds"] * k, qe - qb),
                         te - tb)
    alen = np.maximum(qe - qb, te - tb)

    def fmt(col):
        return np.char.mod(b"%d", np.asarray(col, np.int64)
                           ).astype(object)

    qn = np.asarray(read_names, object)[q]
    tn = np.asarray(target_names, object)[t]
    strand = np.where(rows["strand"] != 0, b"-", b"+").astype(object)
    tab = np.full(n, b"\t", object)
    line = qn
    for col in (fmt(np.asarray(read_lens)[q]), fmt(qb), fmt(qe),
                strand, tn, fmt(np.asarray(target_lens)[t]),
                fmt(tb), fmt(te), fmt(matches), fmt(alen)):
        line = np.char.add(np.char.add(line, tab), col)
    line = np.char.add(line, np.full(n, b"\t255\n", object))
    return list(line)


# -------------------------------------------------------------- warm-up

_warmed_shapes: set = set()


def _warmup_shapes(est_seeds: int, est_pairs: int
                   ) -> List[Tuple[int, int]]:
    """The ``(S, B)`` chain-arena geometries a run with ~``est_pairs``
    candidate pairs of ~``est_seeds`` seeds dispatches — derived with
    the same :func:`_seed_bucket` / :func:`_pair_batch` quantizers the
    dispatch path uses (consumed by :func:`warmup_async`).

    The ragged :class:`_ChainStream` buckets each pair by its *own*
    seed count, so real runs dispatch a short ladder of seed classes
    below the top bucket; the warm set covers the top rung and up to
    three halvings (floor 16) at the batch size the arena fill yields
    for each class."""
    if est_seeds <= 0 or est_pairs <= 0:
        return []
    shapes: List[Tuple[int, int]] = []
    S = _seed_bucket(est_seeds)
    for _ in range(4):
        shape = (S, _pair_batch(S, est_pairs))
        if shape not in shapes:
            shapes.append(shape)
        if S <= 16:
            break
        S //= 2
    return shapes


def warmup_async(est_seeds: int, est_pairs: int, k: int = 15):
    """Background warm-up compilation of the expected chain-arena
    shapes while the host matches seeds. Shape-deduped; returns the
    thread (for tests) or None when skipped."""
    shapes = [(S, B, k) for S, B in _warmup_shapes(est_seeds, est_pairs)
              if (S, B, k) not in _warmed_shapes]
    if not shapes:
        return None
    _warmed_shapes.update(shapes)

    def _one(S, B, kk):
        z = np.zeros((B, S), np.int32)
        # graftlint: disable=jit-shape-hazard (k is a run-constant flag value — one compile per run; S is the pow2 bucket)
        out = _chain_kernel(z, z, np.zeros(B, np.int32), S=S, k=kk)
        jax.block_until_ready(out)

    def _run():
        for S, B, kk in shapes:
            try:
                _one(S, B, kk)
            except Exception as e:
                from ..utils.logger import log_swallowed
                log_swallowed(
                    f"chain warm-up shape {(S, B)} failed (the run's "
                    f"own shapes still compile on first use)", e)

    import threading

    # graftlint: disable=thread-lifecycle (droppable best-effort warm-up; daemon dies harmlessly at exit)
    th = threading.Thread(target=_run, daemon=True,
                          name="racon-chain-warmup")
    th.start()
    return th
