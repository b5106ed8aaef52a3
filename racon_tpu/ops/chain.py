"""Seed matching + banded chaining DP — stage two of the first-party
overlapper (``--overlaps auto``, ROADMAP item 5).

Consumes the flat minimizer tables from :mod:`.overlap_seed` and emits
``Overlap``-compatible rows:

- **matching** runs on device by default (``RACON_TPU_OVERLAP_DEVICE_JOIN``):
  the target table sorts by hash on the host (it is the small side),
  the read minimizers that share their hash's low bits with none of
  its distinct hashes drop on the host (:func:`_present_reads`: they
  are misses whatever the search does, nine in ten of a read set
  against its draft), every one left — in table order, never sorted — is looked
  up among those hashes on the device through a directory over the
  hash's top bits, each target bucket's read occurrences are counted
  by a scatter-add so super-hot repeat
  buckets over the occurrence cap drop whole (counted in
  ``overlap.freq_capped_buckets`` — never silent), and the read→target
  join expands into hits via the ragged searchsorted ramp, self-hit
  suppression and strand-flip of query coordinates; the host puts the
  hits (a few per cent of the read table) in candidate-pair order. No
  device sort: each ``lax.sort`` of this join took the chip's compiler
  40 to 200 s (PR 34). The
  numpy :func:`match_seeds` stays as the byte-parity oracle AND the
  bail-out ladder target (empty tables, arena-overflow table or hit
  counts — counted in ``overlap.join_bailouts``, never approximation);
  hit 5-tuples are unique by construction (tables dedupe on (seq, pos)),
  so any ascending sort produces the oracle's exact lexsort order. The
  oracles themselves (``match_seeds``, ``chain_np``) are the plain
  reference's, :mod:`racon_tpu.models.overlap`.
- **chaining** is the device DP: every candidate pair is known when
  the join returns, so the pairs are classed by seed count (powers of
  4) and cut into chunks once (:func:`_plan_chunks`), each chunk the one
  ``[B, S]`` arena of its class, and :class:`_ChainStream` walks the
  plan — double-buffered dispatch/fetch behind an in-flight budget,
  per-pair results invariant to chunk mates (the ``_AlignStream``
  discipline, warmed via :func:`_warmup_shapes`) — and a ``lax.scan``
  over seed positions scores gap-bounded colinear chains against a
  bounded lookback window, then backtracks on device so only a
  ``[B, 6]`` summary per launch crosses the link.
- **streaming** (:func:`iter_overlap_groups`): after each fetched chunk
  the query groups it completed leave, in order, as one block of
  canonical rows; the polisher's filter and the round-17 align stream
  consume a block while the later chunks are still in flight. The
  canonical full-run row order is the concatenation of the blocks (the
  global lexsort's primary key IS the query ordinal), which is what
  keeps the streamed and phase-barriered paths byte-identical. The
  host work of the hand-off is linear in the pairs and vectorised per
  chunk: one sort by class, array fills per launch, one lexsort per
  block.

Scoring is all-integer (seed span minus a gap penalty in 1/16-base
units), so the kernel and the numpy oracle :func:`chain_np` agree
bit-for-bit and byte-identical reruns fall out for free. Reverse-strand
query coordinates flip to ``q' = qlen - pos - k`` before chaining (so
colinearity means ascending in both axes) and flip back on emission.
"""

from __future__ import annotations

import functools
from typing import Dict, Iterator, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from .. import obs
# the plain reference owns the definitions: the chain DP's constants
# and the numpy join (the bail-out ladder's target)
from ..models.overlap import (BAND_DIAG, CHAIN_LOOKBACK, GAP_UNIT,
                              MAX_GAP, match_seeds)
from ..models.overlap import NEG as _NEG
from ..obs import device_time, metrics
from ..parallel import fetch_global
from . import overlap_seed

# chain-arena size in cells (ts/qs operands and the scan history all
# scale with B*S): every launch of a seed class is this one arena
CHAIN_ARENA_CELLS = 1 << 19
# device-join arena bounds: padded table entries / expanded hits past
# these bail to the host oracle (counted, never silent) so one
# pathological input can't demand an unbounded device arena. The read
# side counts after the host's prefilter: of a 30x bacterial read set's
# 20 M minimizers (2 Mbp) the 1.6 M that can match pad to 2^21, beside
# 2^20 of the draft's. What fills 2^26 cells is a table joined with
# itself (`-f`: the prefilter stands aside), 2^25 + 2^25 in about 1.2 GB
# of operands, ramp and look-up temporaries
JOIN_TABLE_CELLS = 1 << 26
JOIN_MAX_HITS = 1 << 26
# the presence table of the prefilter: slots per distinct target hash
# (a read hash that matches nothing passes one time in this many; of a
# read set's minimizers 6 % match its draft, so 32 keeps 8 % where 16
# keeps 10 %, which at 2 Mbp x 30 is the step from 2^21 to 2^22) and the
# most hash bits it takes (2^26 one-byte slots: 64 MB)
PRESENCE_SLOTS_PER_HASH = 32
PRESENCE_MAX_BITS = 26
# in-flight chain chunks before a fetch is forced (double buffering:
# the device works chunk N while the host packs N+1 and fetches N-1)
CHAIN_INFLIGHT = 2


# -------------------------------------------------------------- geometry

def _class_of(n: int, floor: int, step: int) -> int:
    """The smallest ``floor * step**i`` that holds ``n``."""
    b = floor
    while b < n:
        b *= step
    return b


def _seed_bucket(n: int) -> int:
    """Seed-list class for one candidate pair: powers of 4 from 16 —
    the quantizer both dispatch and :func:`_warmup_shapes` derive the
    arena's S axis from. Coarse on purpose (PR 34): a class is a
    compiled program, and a bacterial read set spans three of these
    where it spanned six powers of 2."""
    return _class_of(n, 16, 4)


def _seed_buckets(counts: np.ndarray) -> np.ndarray:
    """:func:`_seed_bucket` of every element (``counts`` non-empty)."""
    rungs = [_seed_bucket(1)]
    while rungs[-1] < counts.max():
        rungs.append(_seed_bucket(rungs[-1] + 1))
    rungs = np.asarray(rungs, np.int64)
    return rungs[np.searchsorted(rungs, counts, "left")]


def _pair_batch(S: int) -> int:
    """Lanes of one chain launch of seed class ``S``: the whole
    :data:`CHAIN_ARENA_CELLS` arena, always — a tail chunk pads to it,
    so a class has ONE geometry whatever the input (companion of
    :func:`_seed_bucket`; shared with warm-up)."""
    return max(1, CHAIN_ARENA_CELLS // max(1, S))


def _table_pad(n: int) -> int:
    """pow2 padded length of one minimizer table on the device-join
    path (floor 64) — the quantizer both the join dispatch and
    :func:`_warmup_shapes` derive sort geometry from."""
    return _class_of(n, 64, 2)


def _hits_pad(n: int) -> int:
    """pow2 padded length of the expanded hit arena (floor 256; same
    role as :func:`_table_pad` for the join's second kernel)."""
    return _class_of(n, 256, 2)


# ---------------------------------------------------------------- kernel

@functools.lru_cache(maxsize=None)
def _chain_geometry(S: int, B: int, k: int) -> str:
    """The occupancy ledger's join key of the chain kernel on a
    ``[B, S]`` arena, from the streams and the warm-up alike."""
    return device_time.geometry(B=B, S=S, k=k)


@functools.lru_cache(maxsize=None)
def _join_geometry(R2: int, T2: int, steps: int = 0, E: int = 0,
                   Q2: int = 0, k: int = 0) -> str:
    """The same for the seed join's two programs: the padded table
    sizes, then the ramp's ``steps`` or the expansion's arena."""
    return device_time.geometry(steps=steps, R2=R2, T2=T2, E=E, Q2=Q2, k=k)


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

def _cumsum_long(x):
    """Inclusive prefix sum of a long 1-D int32 vector as two short
    scans (rows of 4096, then the row totals): the same values as
    ``jnp.cumsum``, a fraction of its compile time at 2^25 entries."""
    n = x.shape[0]
    if n < (1 << 13):
        return jnp.cumsum(x)
    rows = x.reshape(-1, 1 << 12)
    inner = jnp.cumsum(rows, axis=1)
    tot = inner[:, -1]
    return (inner + (jnp.cumsum(tot) - tot)[:, None]).reshape(-1)


# look-up rounds inside one directory bucket, at least: four find a hash
# among up to 15 distinct ones, which a bucket of the half-loaded
# directory holds for no table of mixed hashes (a table that does hold
# more gets the rounds it needs, and a program of its own)
JOIN_BUCKET_STEPS = 4


@functools.partial(jax.jit, static_argnames=("steps",))
def _join_ramp_kernel(rh, uh, ucount, dstart, max_occ, *, steps: int):
    """Device half one of the seed join: look every read minimizer up
    among the target's distinct hashes, count each one's read
    occurrences, drop super-hot buckets whole, and emit the read→target
    join ramp (``u``/``cnt``/inclusive ``offs``).

    ``rh`` is the padded read table's hashes in table order (what the
    host's prefilter left of it: :func:`_present_reads`) — the read
    side is never sorted. The target side comes sorted from the host
    (:func:`_sorted_target`): ``uh`` its distinct hashes, ``ucount``
    each one's entries, and ``dstart`` a directory over the hash's top
    bits (``uh[dstart[p]:dstart[p + 1]]`` share prefix ``p``), so a
    look-up is two directory reads and ``steps`` rounds of a search
    inside one short bucket (``2**steps`` exceeds the longest). On the
    chip every gather over ``rh``'s slots costs by the slots, whatever
    the table gathered from (0.29 s at 2^25: the two 21-round binary
    searches this replaced were 12 of a job's 40 s, PR 34), which is
    why the slots are the reads that can match and not the read set
    (2^21 of 2^25, PR 42). Pad slots carry ``_HASH_MAX``, which no real
    entry can (the seed builder filters it). No sort and no long scan but one
    prefix sum: the sorts all this replaced took the chip's compiler
    minutes. Returns the ramp, the total hit count and the count of
    target buckets dropped — only the two scalars need fetching before
    the expansion launches."""
    hmax = np.uint32(overlap_seed._HASH_MAX)
    U = uh.shape[0]
    bits = (dstart.shape[0] - 1).bit_length() - 1
    p = (rh >> np.uint32(32 - bits)).astype(jnp.int32)
    lo, end = dstart[p], dstart[p + 1]
    hi = end
    # lower bound of rh in its bucket; ``eq`` follows ``hi``: whether
    # the hash it last moved to is rh itself
    eq = jnp.zeros(rh.shape, jnp.bool_)
    for _ in range(steps):
        mid = (lo + hi) >> 1
        v = uh[jnp.minimum(mid, U - 1)]
        right = (lo < hi) & (v < rh)
        left = (lo < hi) & ~right
        eq = jnp.where(left, v == rh, eq)
        hi = jnp.where(left, mid, hi)
        lo = jnp.where(right, mid + 1, lo)
    matched = (rh != hmax) & (lo < end) & eq
    # read occurrences per distinct target hash (misses park past it)
    tr = jnp.zeros((U + 1,), jnp.int32).at[
        jnp.where(matched, lo, jnp.int32(U))].add(1)[:U]
    hot = (uh != hmax) & ((ucount + tr) > max_occ)
    capped = jnp.sum(hot.astype(jnp.int32))
    u = jnp.minimum(lo, U - 1)
    cnt = jnp.where(matched & ~hot[u], ucount[u], jnp.int32(0))
    offs = _cumsum_long(cnt)
    return u, cnt, offs, offs[-1], capped


_I32_MAX = np.int32(0x7FFFFFFF)


@functools.partial(jax.jit, static_argnames=("E", "k"))
def _join_expand_kernel(rid, rpos, rstr, tid, tpos, tstr, ustart, u, cnt,
                        offs, total, read_self_t, qlens, *, E: int, k: int):
    """Device half two: expand the join ramp into hit rows, drop self
    hits and flip reverse-strand query coordinates. A dropped or dead
    row takes ``q = INT32_MAX``; the rows come out in ramp order and
    the host puts them in the oracle's ``(q, t, rel, tp, qc)`` order
    (a 5-key device sort of the hits took the chip's compiler over
    three minutes; the host's lexsort of a bacterial read set's 1.2 M
    hits takes a fraction of a second)."""
    e = jnp.arange(E, dtype=jnp.int32)
    live = e < total
    # ragged ramp: hit e belongs to the read entry whose inclusive
    # cumsum first exceeds e, at its bucket's first target slot
    # (``ustart`` of its distinct hash) + (e - run_begin)
    ridx = jnp.clip(jnp.searchsorted(offs, e, side="right"),
                    0, rid.shape[0] - 1).astype(jnp.int32)
    begin = offs[ridx] - cnt[ridx]
    tix = jnp.clip(ustart[u[ridx]] + (e - begin), 0, tid.shape[0] - 1)
    q = rid[ridx]
    qp = rpos[ridx]
    t = tid[tix]
    tp = tpos[tix]
    rel = (rstr[ridx] != tstr[tix]).astype(jnp.int32)
    qsafe = jnp.clip(q, 0, read_self_t.shape[0] - 1)
    keep = live & (t != read_self_t[qsafe])
    qc = jnp.where(rel == 1, qlens[qsafe] - qp - jnp.int32(k), qp)
    return (jnp.where(keep, q, _I32_MAX), t, rel, tp, qc,
            jnp.sum(keep.astype(jnp.int32)))


def _pad_to(a: np.ndarray, n_pad: int, fill, dtype) -> np.ndarray:
    out = np.full(n_pad, fill, dtype)
    out[:a.size] = a
    return out


def _sorted_target(table, n_pad: int):
    """The target table sorted by hash on the host (stable, as the
    oracle sorts it; the draft's table is small beside the reads'),
    padded for the device: the entries' ``(id, pos, strand)``, then per
    distinct hash ``(hash, first entry, entries)``, then the directory
    of :func:`_join_ramp_kernel` — twice as many buckets as padded
    entries, so at most half loaded — and its longest bucket."""
    h, sid, pos, strand = table
    order = np.argsort(h, kind="stable")
    h = h[order]
    first = np.ones(h.size, bool)
    first[1:] = h[1:] != h[:-1]
    ustart = np.flatnonzero(first)
    uh = h[ustart]
    ucount = np.diff(np.append(ustart, h.size))
    bits = n_pad.bit_length()
    per_bucket = np.bincount((uh >> np.uint32(32 - bits)).astype(np.int64),
                             minlength=1 << bits)
    dstart = np.zeros((1 << bits) + 1, np.int32)
    np.cumsum(per_bucket, out=dstart[1:])
    hmax = np.uint32(overlap_seed._HASH_MAX)
    entries = tuple(_pad_to(a[order], n_pad, 0, np.int32)
                    for a in (sid, pos, strand))
    distinct = (_pad_to(uh, n_pad, hmax, np.uint32),
                _pad_to(ustart, n_pad, 0, np.int32),
                _pad_to(ucount, n_pad, 0, np.int32))
    return entries, distinct, dstart, int(per_bucket.max())


def _presence_bits(n_distinct: int) -> int:
    """Hash bits of the prefilter's presence table: the fewest that
    give every distinct target hash :data:`PRESENCE_SLOTS_PER_HASH`
    slots, between a byte and :data:`PRESENCE_MAX_BITS`."""
    want = (PRESENCE_SLOTS_PER_HASH * max(1, n_distinct) - 1).bit_length()
    return max(8, min(PRESENCE_MAX_BITS, want))


def _present_reads(rh: np.ndarray, uh: np.ndarray) -> Optional[np.ndarray]:
    """The host prefilter of the seed join: ascending indices of the
    read entries ``rh`` whose low hash bits some distinct target hash
    of ``uh`` shares, or ``None`` where they pad to the table's own
    class (a table joined with itself, a handful of minimizers), when
    nothing is worth compacting.

    Exact: a read hash whose low bits no target hash has equals none of
    them, so the ramp would find it unmatched — it adds to no bucket's
    count, to no cap and to no hit; an entry kept by bits it only
    shares is resolved by the kernel's search as before. The LOW bits:
    a minimizer is the least of its window's mixed hashes
    (``overlap_seed._mix32``), so the top bits crowd towards zero (the
    median hash of a read set is 0.18 of the range, and a table over
    them passes twice the misses: PR 42, on the chip) while the low
    bits stay uniform — of the entries that match nothing about one in
    :data:`PRESENCE_SLOTS_PER_HASH` passes."""
    low = np.uint32((1 << _presence_bits(uh.size)) - 1)
    present = np.zeros(int(low) + 1, bool)
    present[uh & low] = True
    kept = np.flatnonzero(present[rh & low])
    return None if _table_pad(kept.size) == _table_pad(rh.size) else kept


def join_seeds(read_table, target_table, read_self_t: np.ndarray,
               qlens: np.ndarray, *, k: int, max_occ: int,
               device_join: bool = True
               ) -> Tuple[Dict[str, np.ndarray], int]:
    """Seed join front end: the device kernels when eligible, the numpy
    :func:`match_seeds` oracle otherwise.

    Returns ``(hits, freq_capped)``. ``hits`` carries host
    ``q``/``t``/``rel``/``tp``/``qc`` int64 arrays in the oracle's
    order.

    Of the read table only the entries that can match cross to the
    device (:func:`_present_reads`; counters ``overlap.join_read_entries``
    offered and ``overlap.join_read_kept`` padded and uploaded, equal
    where the prefilter stood aside), so the two programs' ``R2`` is the
    class of the kept entries. The hits carry values, never table
    indices, and the host orders them: the same hits either way.

    The bail-out ladder (empty tables, padded tables over
    :data:`JOIN_TABLE_CELLS`, hit counts over :data:`JOIN_MAX_HITS`,
    int32 ramp overflow risk — both table rungs by the kept entries)
    falls back to the oracle and counts into ``overlap.join_bailouts``
    — never approximation, never silent."""
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
    T2 = _table_pad(th.size)
    if T2 >= JOIN_TABLE_CELLS:
        # rung 2, before the target is sorted: no read side fits beside it
        return _oracle(bail=True)
    entries_h, (uh_h, ustart_h, ucount_h), dstart_h, longest = \
        _sorted_target(target_table, T2)
    offered = int(rh.size)
    _, rid_h, rpos_h, rstr_h = read_table
    with obs.span("overlap.join.prefilter", reads=offered):
        # the directory's last entry counts the distinct hashes: the
        # rest of ``uh_h`` is padding
        kept = _present_reads(rh, uh_h[:int(dstart_h[-1])])
        if kept is not None:
            rh, rid_h, rpos_h, rstr_h = (
                a[kept] for a in (rh, rid_h, rpos_h, rstr_h))
    # graftlint: disable=warmup-coverage (the join runs ONCE per round immediately after seeding produces the very tables whose kept entries this pow2 bucket quantizes — there is no earlier moment to warm it from)
    R2 = _table_pad(rh.size)
    # a kept read entry joins fewer than max_occ target entries (its
    # bucket would have dropped whole), a dropped one and a pad slot
    # join none, so the int32 ramp holds while kept entries x max_occ
    # stays under 2^31
    if R2 + T2 > JOIN_TABLE_CELLS \
            or int(rh.size) * max(1, max_occ) >= (1 << 31):
        # rung 2: table arena overflow / int32 ramp overflow risk
        return _oracle(bail=True)
    metrics.inc("overlap.join_read_entries", offered)
    metrics.inc("overlap.join_read_kept", int(rh.size))

    hmax = np.uint32(overlap_seed._HASH_MAX)
    steps = max(JOIN_BUCKET_STEPS, longest.bit_length())
    with obs.span("overlap.join.dispatch", reads=int(rh.size),
                  targets=int(th.size)):
        rh_d = jnp.asarray(_pad_to(rh, R2, hmax, np.uint32))
        uh_d, ucount_d, dstart_d = (jnp.asarray(a) for a in (
            uh_h, ucount_h, dstart_h))
        device_time.submit("h2d", "overlap.join.put", dstart_d)
        # graftlint: disable=jit-shape-hazard (R2/T2 are the pow2 _table_pad buckets; steps is JOIN_BUCKET_STEPS for every table of mixed hashes)
        u_d, cnt, offs, total_d, capped_d = _join_ramp_kernel(
            rh_d, uh_d, ucount_d, dstart_d, np.int32(max_occ), steps=steps)
        device_time.submit("exec", "_join_ramp_kernel", total_d,
                           _join_geometry(R2, T2, steps=steps))
        # the expansion's operands cross while the ramp runs
        sides_d = [jnp.asarray(a) for a in (
            _pad_to(rid_h, R2, 0, np.int32),
            _pad_to(rpos_h, R2, 0, np.int32),
            _pad_to(rstr_h, R2, 0, np.int32), *entries_h, ustart_h)]
        device_time.submit("h2d", "overlap.join.put", sides_d[-1])
    with obs.span("overlap.join.fetch"):
        total, capped = (int(x) for x in fetch_global([total_d, capped_d]))
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
    # the per-read vectors pad to a pow2 too: the expand program's
    # shapes are then (R2, T2, E, this), none the read count itself
    Q2 = _table_pad(read_self_t.size)
    with obs.span("overlap.join.dispatch", hits=total):
        # graftlint: disable=jit-shape-hazard (E is the pow2 _hits_pad bucket; k is a run-constant flag value — one compile per run)
        out_d = _join_expand_kernel(
            *sides_d, u_d, cnt, offs, np.int32(total),
            _pad_to(read_self_t, Q2, -1, np.int32),
            _pad_to(qlens, Q2, 0, np.int32), E=E, k=k)
        device_time.submit("exec", "_join_expand_kernel", out_d[5],
                           _join_geometry(R2, T2, E=E, Q2=Q2, k=k))
    with obs.span("overlap.join.fetch"):
        # whole arenas, cut on the host: a device slice [:n] is a
        # program per hit count, new with every input
        q_h, t_h, rel_h, tp_h, qc_h, _ = fetch_global(list(out_d))
        keep = q_h != _I32_MAX
        cols = [c[keep].astype(np.int64)
                for c in (q_h, t_h, rel_h, tp_h, qc_h)]
        # hit 5-tuples are unique (the tables dedupe on (seq, pos)), so
        # this is the oracle's order exactly
        order = np.lexsort(cols[::-1])
        q_h, t_h, rel_h, tp_h, qc_h = (c[order] for c in cols)
    return {"q": q_h, "t": t_h, "rel": rel_h, "tp": tp_h, "qc": qc_h}, capped


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
    Python slice loop (``starts``/``counts`` are length B, zero-padded
    past the live lanes)."""
    lane_starts = starts[:, None] + np.arange(S, dtype=np.int64)[None, :]
    mask = np.arange(S, dtype=np.int64)[None, :] < counts[:, None]
    np.clip(lane_starts, 0, max(0, tp.size - 1), out=lane_starts)
    if tp.size == 0:
        return np.zeros((B, S), np.int32), np.zeros((B, S), np.int32)
    ts = np.where(mask, tp[lane_starts], 0).astype(np.int32)
    qs = np.where(mask, qc[lane_starts], 0).astype(np.int32)
    return ts, qs


def _put_lanes(tp: np.ndarray, qc: np.ndarray, starts: np.ndarray,
               counts: np.ndarray, S: int, B: int):
    """:func:`_pack_lanes`, put on the device and entered in the
    occupancy ledger."""
    ts, qs = (jnp.asarray(a) for a in _pack_lanes(tp, qc, starts, counts,
                                                  S, B))
    device_time.submit("h2d", "overlap.chain.put", qs)
    return ts, qs


def _plan_chunks(counts: np.ndarray) -> List[Tuple[int, np.ndarray]]:
    """Cut candidate pairs (``counts[i]`` seeds each) into chain chunks,
    once, before the first launch: ``(S, idx)`` per chunk, ``idx`` the
    ascending positions of at most :func:`_pair_batch` pairs of seed
    class ``S``. One stable sort by class over all pairs (counted in
    ``overlap.intake_visits``: a visit a pair); a class is cut in
    position order, so every chunk is full but its class's last.
    Chunks are listed by their first pair — the order
    :meth:`_ChainStream.run` launches and fetches them in, which lets
    the query groups below the next chunk's first pair leave as soon
    as a fetch lands. Which pairs share a chunk changes no row (the DP
    is per lane), and every chunk runs its class's one ``[B, S]`` arena
    (:func:`_pair_batch`), so the plan decides no program."""
    n = int(counts.size)
    metrics.inc("overlap.intake_visits", n)
    if n == 0:
        return []
    classes = _seed_buckets(counts)
    order = np.argsort(classes, kind="stable")
    cuts = np.flatnonzero(np.diff(classes[order])) + 1
    chunks = []
    for members in np.split(order, cuts):
        S = int(classes[members[0]])
        cap = _pair_batch(S)
        chunks.extend((S, members[lo:lo + cap])
                      for lo in range(0, members.size, cap))
    chunks.sort(key=lambda chunk: (int(chunk[1][0]), chunk[0]))
    return chunks


class _ChainStream:
    """Ragged chain session — the overlapper analog of
    ``nw._AlignStream`` / ``poa._ConsensusStream``.

    Every candidate pair (``counts[i]`` seeds at flat-hit offset
    ``starts[i]``) is known before the first launch, so the chunks are
    planned once (:func:`_plan_chunks`) and :meth:`run` walks the plan:
    launch a chunk ASYNCHRONOUSLY into the one ``[B, S]`` arena of its
    class (:data:`CHAIN_ARENA_CELLS` cells), and fetch the oldest only
    when the in-flight budget (:data:`CHAIN_INFLIGHT` chunks / 2 arenas
    of cells) forces one, or at the end — the host fills chunk N+1 while
    the device chains chunk N. A fetched chunk leaves as one ``(idx,
    [n, 6])`` block. The DP is per-lane independent and a pair's class
    is its own seed count's, so a pair's row does not depend on its
    chunk mates — the property the streamed/barriered byte-identity
    contract rests on. ``tp``/``qc``/``starts``/``counts`` are host
    arrays."""

    def __init__(self, *, k: int, tp: np.ndarray, qc: np.ndarray,
                 starts: np.ndarray, counts: np.ndarray):
        self.k = k
        self.tp = tp
        self.qc = qc
        self.starts = starts
        self.counts = counts
        self.inflight: List[Tuple[np.ndarray, object, int]] = []
        self.inflight_cells = 0
        # pairs handed to the device so far
        self.launched = 0

    def run(self, chunks: List[Tuple[int, np.ndarray]]
            ) -> Iterator[Tuple[np.ndarray, np.ndarray]]:
        """Launch ``chunks`` in order and yield each one's ``(idx,
        rows)`` — ``rows[j]`` the ``[6]`` summary of pair ``idx[j]`` —
        as it is fetched, oldest first."""
        for S, idx in chunks:
            self._launch(S, idx)
            while (len(self.inflight) > CHAIN_INFLIGHT
                   or self.inflight_cells > 2 * CHAIN_ARENA_CELLS):
                yield self._fetch_oldest()
        while self.inflight:
            yield self._fetch_oldest()

    def _launch(self, S: int, idx: np.ndarray) -> None:
        B = _pair_batch(S)
        n = int(idx.size)
        starts = np.zeros(B, np.int64)
        counts = np.zeros(B, np.int64)
        starts[:n] = self.starts[idx]
        counts[:n] = self.counts[idx]
        with obs.span("overlap.chain.dispatch", pairs=n):
            ns = counts.astype(np.int32)
            ts, qs = _put_lanes(self.tp, self.qc, starts, counts, S, B)
            # graftlint: disable=jit-shape-hazard (k is a run-constant flag value — one compile per run; S is the pow4 bucket)
            out = _chain_kernel(ts, qs, ns, S=S, k=self.k)
            device_time.submit("exec", "_chain_kernel", out,
                               _chain_geometry(S, B, self.k))
        self.inflight.append((idx, out, B * S))
        self.inflight_cells += B * S
        self.launched += n
        occupied = int(counts.sum())
        metrics.inc("overlap.lanes_total", B * S)
        metrics.inc("overlap.lanes_occupied", occupied)
        metrics.inc("overlap.chunks", 1)
        # mirrored legacy names (run-report compat with the barrier path)
        metrics.inc("overlap.chain_lanes_total", B * S)
        metrics.inc("overlap.chain_lanes_occupied", occupied)

    def _fetch_oldest(self) -> Tuple[np.ndarray, np.ndarray]:
        idx, out, cells = self.inflight.pop(0)
        self.inflight_cells -= cells
        with obs.span("overlap.chain.fetch", pairs=int(idx.size)):
            out_np = fetch_global([out])[0]
        return idx, out_np[:idx.size].astype(np.int64)


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
        cap = B = _pair_batch(S)
        for begin in range(0, len(members), cap):
            part = members[begin:begin + cap]
            pstarts = np.zeros(B, np.int64)
            pcounts = np.zeros(B, np.int64)
            for lane, m in enumerate(part):
                pstarts[lane] = starts[m]
                pcounts[lane] = counts[m]
            ns = pcounts.astype(np.int32)
            with obs.span("overlap.chain.dispatch", pairs=len(part)):
                ts, qs = _put_lanes(hits["tp"], hits["qc"], pstarts, pcounts,
                                    S, B)
                # graftlint: disable=jit-shape-hazard (k is a run-constant flag value — one compile per run; S is the pow4 bucket)
                out = _chain_kernel(ts, qs, ns, S=S, k=k)
                device_time.submit("exec", "_chain_kernel", out,
                                   _chain_geometry(S, B, k))
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


def _resolve_params(k, w, max_occ, min_seeds, device_join, ragged):
    from .. import flags
    k = flags.get_int("RACON_TPU_OVERLAP_K") if k is None else k
    w = flags.get_int("RACON_TPU_OVERLAP_W") if w is None else w
    if max_occ is None:
        max_occ = flags.get_int("RACON_TPU_OVERLAP_MAX_OCC")
    if min_seeds is None:
        min_seeds = flags.get_int("RACON_TPU_OVERLAP_MIN_SEEDS")
    if device_join is None:
        device_join = flags.get_bool("RACON_TPU_OVERLAP_DEVICE_JOIN")
    if ragged is None:
        ragged = flags.get_bool("RACON_TPU_OVERLAP_RAGGED")
    k = max(4, min(16, k))  # uint32 canonical codes hold 2k bits
    w = max(1, w)
    return k, w, max_occ, min_seeds, device_join, ragged


def _read_table(read_seqs, held, *, k, w):
    """The read-side seed table: built here, or taken from ``held`` —
    a dict its caller keeps across calls on the SAME reads (the rounds
    of one ``--rounds N`` job: the draft changes between them, the
    reads do not), keyed by what the table depends on beside the
    bytes. A table taken is counted as a cached target table is."""
    key = (k, w)
    if held is not None and key in held:
        rt = held[key]
        metrics.inc("rounds.read_tables_reused")
        metrics.inc("overlap.minimizers", int(rt[0].size))
        return rt
    rt = overlap_seed.build_seed_table(read_seqs, k=k, w=w)
    metrics.inc("rounds.read_tables_built")
    if held is not None:
        held[key] = rt
    return rt


def _seed_and_join(read_seqs, target_seqs, read_self_t, qlens, *,
                   k, w, max_occ, device_join, cache, read_tables=None):
    """Seed both pools (target table through the fingerprint cache,
    read table through ``read_tables`` where the caller holds one:
    :func:`_read_table`) and run the join front end."""
    with obs.span("overlap.seed", reads=len(read_seqs),
                  targets=len(target_seqs)):
        rt = _read_table(read_seqs, read_tables, k=k, w=w)
        tt = overlap_seed.build_seed_table(target_seqs, k=k, w=w,
                                           cache=cache)
    with obs.span("overlap.match"):
        hits, capped = join_seeds(rt, tt, read_self_t, qlens,
                                  k=k, max_occ=max_occ,
                                  device_join=device_join)
        metrics.inc("overlap.freq_capped_buckets", capped)
    return hits


def _group_rows(q, t, rel, rows6, qlens, k) -> Dict[str, np.ndarray]:
    """Emit a run of whole query groups' kept chains as canonical
    overlap rows: flip reverse-strand chain coords back to forward
    query space and sort by ``(q, t, rel, t_begin, q_begin)`` — the
    global canonical lexsort :func:`find_overlaps` defines, restricted
    to a range of its primary key, which is what makes streamed
    emission byte-identical to the barrier."""
    ql = qlens[q]
    q_begin = np.where(rel == 1, ql - (rows6[:, 3] + k), rows6[:, 2])
    q_end = np.where(rel == 1, ql - rows6[:, 2], rows6[:, 3] + k)
    t_begin = rows6[:, 4]
    t_end = rows6[:, 5] + k
    order = np.lexsort((q_begin, t_begin, rel, t, q))
    return {"q_ord": q[order], "t_idx": t[order], "strand": rel[order],
            "q_begin": q_begin[order], "q_end": q_end[order],
            "t_begin": t_begin[order], "t_end": t_end[order],
            "n_seeds": rows6[order, 1], "score": rows6[order, 0]}


def iter_overlap_groups(read_seqs: List[bytes], target_seqs: List[bytes],
                        read_self_t: np.ndarray, *,
                        k: Optional[int] = None, w: Optional[int] = None,
                        max_occ: Optional[int] = None,
                        min_seeds: Optional[int] = None,
                        device_join: Optional[bool] = None,
                        cache: bool = True,
                        read_tables: Optional[dict] = None
                        ) -> Iterator[Dict[str, np.ndarray]]:
    """Streaming overlapper driver: yield canonical overlap rows, whole
    query groups in ascending query ordinal, a block per fetched chain
    chunk.

    The order of events: seed and join (a barrier: every candidate pair
    is known after it); class the eligible pairs and plan their chunks
    once (span ``overlap.chain.plan``, counter
    ``overlap.intake_visits`` beside ``overlap.chain_pairs``); then the
    chain stream launches chunk after chunk, and whenever its in-flight
    budget forces a fetch — or at the end — the fetched block lowers
    its groups' open-pair counts and every group now complete, in
    order, leaves in one block (span ``overlap.emit``). The consumer
    runs between two fetches, so what it feeds the aligner overlaps the
    chunks still in flight; with a handful of chunks a job, most rows
    leave in the last blocks (gauge ``overlap.first_emit_pairs``: pairs
    launched when the first block left). Concatenating every yield
    reproduces :func:`find_overlaps` byte-for-byte (the global sort's
    primary key is the query ordinal). ``read_tables``: a dict the
    caller keeps over several calls on the same ``read_seqs``; the
    read-side seed table is built into it once and taken from it
    after (:func:`_read_table`)."""
    k, w, max_occ, min_seeds, device_join, _ = _resolve_params(
        k, w, max_occ, min_seeds, device_join, None)
    qlens = np.fromiter((len(s) for s in read_seqs), np.int64,
                        len(read_seqs))
    hits = _seed_and_join(
        read_seqs, target_seqs, read_self_t, qlens,
        k=k, w=w, max_occ=max_occ, device_join=device_join, cache=cache,
        read_tables=read_tables)
    starts, _, counts = _pair_runs(hits)
    metrics.inc("overlap.candidate_pairs", int(starts.size))
    if starts.size == 0:
        return
    with obs.span("overlap.chain.plan"):
        q_of = hits["q"][starts]
        t_of = hits["t"][starts]
        rel_of = hits["rel"][starts]
        # the pairs the stream chains; the rest drop before the DP
        eligible = counts >= min_seeds
        pids = np.flatnonzero(eligible)
        metrics.inc("overlap.chain_pairs", int(pids.size))
        # query-group boundaries over the pair axis (pairs are
        # lexsorted, so groups are consecutive runs of q)
        gchange = np.ones(q_of.size, bool)
        gchange[1:] = q_of[1:] != q_of[:-1]
        gstart = np.flatnonzero(gchange)
        gend = np.append(gstart[1:], q_of.size)
        ngroups = gstart.size
        group_of = np.cumsum(gchange) - 1
        # unresolved eligible pairs per group — the emission gate
        rem = np.bincount(group_of[pids], minlength=ngroups)
        stream = _ChainStream(k=k, tp=hits["tp"], qc=hits["qc"],
                              starts=starts[pids], counts=counts[pids])
        chunks = _plan_chunks(stream.counts)
    rows6 = np.zeros((starts.size, 6), np.int64)
    kept_total = 0
    emit_at = 0  # the first group that has not left
    first_rows = True
    for idx, fetched in stream.run(chunks):
        with obs.span("overlap.emit", pairs=int(idx.size)):
            done = pids[idx]
            rows6[done] = fetched
            np.subtract.at(rem, group_of[done], 1)
            open_groups = np.flatnonzero(rem[emit_at:])
            upto = (emit_at + int(open_groups[0]) if open_groups.size
                    else ngroups)
            block = None
            if upto > emit_at:
                # the complete groups' chained pairs, and of them the
                # chains that hold min_seeds
                lo, hi = int(gstart[emit_at]), int(gend[upto - 1])
                sel = lo + np.flatnonzero(eligible[lo:hi])
                sel = sel[rows6[sel, 1] >= min_seeds]
                emit_at = upto
                kept_total += int(sel.size)
                if sel.size:
                    block = _group_rows(q_of[sel], t_of[sel], rel_of[sel],
                                        rows6[sel], qlens, k)
        if block is not None:
            if first_rows:
                metrics.set_gauge("overlap.first_emit_pairs",
                                  stream.launched)
                first_rows = False
            yield block
    metrics.inc("overlap.stream_groups", ngroups)
    metrics.inc("overlap.chains_kept", kept_total)
    metrics.inc("overlap.chains_dropped", int(starts.size) - kept_total)


def find_overlaps(read_seqs: List[bytes], target_seqs: List[bytes],
                  read_self_t: np.ndarray, *,
                  k: Optional[int] = None, w: Optional[int] = None,
                  max_occ: Optional[int] = None,
                  min_seeds: Optional[int] = None,
                  device_join: Optional[bool] = None,
                  ragged: Optional[bool] = None,
                  cache: bool = True,
                  read_tables: Optional[dict] = None
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
    k, w, max_occ, min_seeds, device_join, ragged = _resolve_params(
        k, w, max_occ, min_seeds, device_join, ragged)
    if ragged:
        parts = list(iter_overlap_groups(
            read_seqs, target_seqs, read_self_t, k=k, w=w,
            max_occ=max_occ, min_seeds=min_seeds,
            device_join=device_join, cache=cache,
            read_tables=read_tables))
        if not parts:
            return _empty_rows()
        return {key: np.concatenate([p[key] for p in parts])
                for key in _ROW_KEYS}

    qlens = np.fromiter((len(s) for s in read_seqs), np.int64,
                        len(read_seqs))
    hits = _seed_and_join(
        read_seqs, target_seqs, read_self_t, qlens,
        k=k, w=w, max_occ=max_occ, device_join=device_join, cache=cache,
        read_tables=read_tables)
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
    """The ``(S, B)`` chain-arena geometries a run whose densest
    candidate pair holds about ``est_seeds`` seeds dispatches — derived
    with the same :func:`_seed_bucket` / :func:`_pair_batch` quantizers
    the dispatch path uses (consumed by :func:`warmup_async`).

    The ragged :class:`_ChainStream` classes each pair by its *own*
    seed count, so a run dispatches the top class and the ones below
    it: the warm set is the top rung and two below (floor 16), each at
    its one arena geometry."""
    if est_seeds <= 0 or est_pairs <= 0:
        return []
    shapes: List[Tuple[int, int]] = []
    S = _seed_bucket(est_seeds)
    for _ in range(3):
        shapes.append((S, _pair_batch(S)))
        if S <= 16:
            break
        S //= 4
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
        # the dummy occupies the device like any program: kind "warm"
        device_time.submit("warm", "_chain_kernel", out,
                           _chain_geometry(S, B, kk))
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
