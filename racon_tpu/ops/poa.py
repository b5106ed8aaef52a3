"""Batched window consensus on TPU (cudapoa-equivalent).

Role: the accelerated consensus engine behind ``Polisher.polish`` — one
device batch processes (windows x layers) at once, the analog of a cudapoa
``Batch`` of POA groups (``src/cuda/cudabatch.cpp:54-62``).

Design (TPU-first): instead of porting cudapoa's irregular
one-block-per-group graph POA, consensus is computed as a
**quality-weighted pileup** refined over several device-resident rounds:

1. every layer is globally aligned to its backbone span with the banded
   wavefront NW forward kernel (Pallas with VMEM-resident wavefronts on
   TPU, the XLA scan from ``ops.nw`` elsewhere — all windows' layers in
   one fixed-shape batch, thousands of concurrent alignments);
2. the walk emits weighted votes (A/C/G/T/N/deletion per backbone column,
   plus K insertion slots per junction): on TPU the fused Pallas
   walk+vote kernel (``pallas_walk_vote``) emits each step's vote address
   and weight directly from registers; the XLA path reconstructs them
   from op codes with vectorized prefix sums (``_vote_from_ops``); both
   streams land on bit-identical matrices via the shared TPU-native
   accumulation ``_accumulate_votes`` (a flat scatter-add here costs
   more than the alignment kernels themselves): stable binary-routed
   compaction + per-row alignment + one-hot MXU matmul for the column
   votes; the rare insertion votes compact ONCE to the ``band // 2``
   lanes an accepted pair can fill, and from that narrow stream the
   ``K_INS`` slot planes are routed to their columns and reduced by the
   same matmul — every routing ladder runs at the width of what it
   routes, not of the step stream;
3. consensus = per-column argmax over weighted base votes, a column
   dropped when deletion weight exceeds ``del_beta`` x the summed base
   weights, and insertion slot ``s`` emitted when its summed weight
   exceeds ``ins_theta`` x the column total (see ``_consensus_kernel``),
   with per-base unweighted coverage for the reference's TGS end-trimming
   contract (``src/window.cpp:118-139``);
4. the emitted consensus becomes the next round's backbone **on device**:
   ``refine_round`` rebuilds the backbone rows (the emitted entries
   compact to their prefix-sum positions) and remaps every layer span
   through the emitted-column map; ``refine_loop`` runs a stage's rounds
   in ONE dispatch — the host packs once, dispatches once and fetches
   once per stage (every host round trip carries a fixed dispatch and
   sync overhead that per-round traffic multiplies). Windows whose backbone reproduces
   itself byte-for-byte are **converged**: their layers stop realigning
   (n = m = 0 pairs, which the Pallas kernels' per-block dynamic bounds
   skip nearly for free), the loop exits early once every window is
   converged or frozen, and after ``STAGE_A_ROUNDS`` a mostly-converged
   group re-packs its few stragglers up to ``STAGE_B_MAX_SHRINK`` times
   smaller on each axis for the remaining rounds (clean high-coverage
   windows reach their fixed point in ~2 rounds; noisy real windows
   often never reproduce byte-exactly, so a mostly-live group instead
   continues in place on its device-resident state). Every group of
   ``TWO_STAGE_MIN_PAIRS`` rows or more takes that schedule, alone in
   its bucket or not (:meth:`TpuPoaConsensus.first_stage_rounds`): the
   early exit fires only once EVERY window is done and the vote's
   routing runs dense over all pair rows whatever has converged, so on
   the chip a lone full-size group that ran its whole budget in one
   dispatch swept all its windows six times where two sweeps and a
   small repack do. Recorded goldens are unchanged by all three
   mechanisms: converged/frozen windows reject updates, so skipped
   rounds are provably no-ops.

Like the reference's GPU path, this engine is allowed to differ slightly
from the CPU spoa-semantics engine (upstream records separate CUDA goldens:
1385 vs CPU 1312, ``test/racon_test.cpp:312``); windows the device cannot
handle (oversize backbone/layers, depth, band escapes) fall back to the CPU
engine, mirroring ``StatusType`` rejects (``src/cuda/cudabatch.cpp:135-156``).

Emission thresholds (``ins_theta``/``del_beta``) and the refinement round
count were calibrated against the CPU engine on λ-phage: the recorded
device golden is 1346 vs CPU 1324 (+1.7%, PAF input — bit-identical on
real TPU v5e and the XLA CPU mesh), well inside the reference's own
accelerated-path divergence (cudapoa 1385 vs spoa 1312, +5.6%,
``test/racon_test.cpp:312``).

Engine caps (documented, per ADVICE round 1): insertion runs longer than
``K_INS`` vote only their last ``K_INS`` bases, and insertions before
the first backbone column of a window (junction "-1") only have a vote
slot when the layer starts past column 0; refinement rounds recover most
of both effects. A backbone that grows past its fixed device buffer
(``L + GROW`` columns) freezes at its last refined state — backbones are
consensus estimates of ~window length, so growth beyond GROW columns does
not occur on real data.
"""

from __future__ import annotations

import functools
from typing import List, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from .nw import _nw_wavefront_kernel, _walk_ops_kernel
from .pallas_nw import PallasDispatchMixin
from .. import faults, flags, native, obs, sanitize
from ..core.window import WindowType
from ..obs import device_time, metrics

# Alignment band for layer-vs-backbone-span alignment (layers are ~window
# sized; c=256 covers ~50% divergence at 500 bp).
BAND = 512
# Insertion slots tracked per backbone junction.
K_INS = 4
# Columns of backbone-growth headroom per refinement round loop.
GROW = 256
# Pairs per device group: larger window sets split into several groups
# dispatched in flight (keeps per-launch arrays and the vote scatter at a
# steady size instead of one monolithic batch; the analog of cudapoa's
# fixed per-batch memory, cudapolisher.cpp:219-228). Every group costs
# one dispatch and one blocking host fetch, a per-group overhead larger
# groups amortize; the vote accumulation's MXU matmul grows with
# B x n_windows. The value predates the current host link and has not
# been re-measured on the chip; the device stays empty while the first
# group of this size is packed (ROADMAP S6: first-group latency).
MAX_GROUP_PAIRS = 32768
# Ragged-packing lane arena (round 10, the cudabatch greedy batch-fill
# analog, SURVEY §L3): a group greedy-fills windows until its pair rows
# x lane width reach this budget, so short-window buckets carry
# proportionally MORE pairs per dispatch instead of padding every pair
# row to the global maxima. Sized to keep the w=500 default bucket at
# exactly the proven MAX_GROUP_PAIRS geometry (Lq = 1024 there).
ARENA_LANES = MAX_GROUP_PAIRS * 1024
# Windows per group ceiling: the vote reduction's [B, n_windows] one-hot
# matmul and the [n_windows, Lb*(1+K)*CH] vote matrices grow with the
# window count, so very short windows close a group on this before the
# lane arena fills.
MAX_GROUP_WINDOWS = 4096
# In-flight ceiling for dispatched-but-unfetched groups: each holds its
# packed inputs (~(2*Lq + ~20) bytes/pair) plus a small output state on
# device (the big per-round intermediates live only inside the one
# execution running at a time). Each execution and each fetch carries
# a fixed host overhead, so groups are as large as the vote stream
# affords and as many as this budget affords are dispatched before the
# first fetch blocks; the user's -c pipeline depth acts as a floor.
MAX_INFLIGHT_BYTES = 4 * 1024 * 1024 * 1024
# Refinement rounds run at full group size before the decision point: a
# group whose windows mostly converged (clean high-coverage data reaches
# its byte-exact fixed point in ~2 rounds) re-packs the few stragglers
# into a small stage-B group for the remaining rounds; a group that is
# mostly still refining (noisy real data rarely hits an exact fixed
# point) just continues the remaining rounds IN PLACE on its
# device-resident state — no repack, no re-upload, one extra fetch.
STAGE_A_ROUNDS = 2
# Stage-B repack pays a host pack + upload; it wins only when it shrinks
# the batch a lot. Above this survivor fraction, continue in place.
STAGE_B_MAX_SURVIVOR_FRAC = 0.5
# ... and it never shrinks a group by more than this on either axis
# (pairs, windows; from the largest stage-A group): under an eighth the
# remaining rounds cost a few percent of the group's, while the
# survivors of a well-polished draft are a small random count that
# crosses a power of two from one input to the next — and every distinct
# ``(B, nWp)`` is a Mosaic program: 12-20 s to compile and, compiled
# in-line at the job's heap peak, 1.3 GB of host memory.
STAGE_B_MAX_SHRINK = 8
# Padded pair rows from which a group takes the two-stage schedule: the
# largest group the repack's floor would not shrink at all were it the
# full arena's. On the chip (PERF.md §6, PR 44) a lone group's six
# rounds in one dispatch took 0.098 / 0.182 / 0.423 s of device time at
# 2,048 / 4,096 / 8,192 rows and 2.2 s at 32,768; split, 0.084 / 0.157 /
# 0.335 and 1.5, for a round trip (fetch, repack, upload, dispatch) of
# 0.007 s at the small sizes and 0.05 s at the arena's: 6 / 19 / 81 ms
# and 0.7 s a group won. Under this the split wins less than a
# hundredth of a second and still brings one more Mosaic program to
# compile (12-20 s cold) for every small job's own ``(B, nWp)``.
TWO_STAGE_MIN_PAIRS = MAX_GROUP_PAIRS // STAGE_B_MAX_SHRINK
# Vote channels: A C G T N DEL (stride 8 for cheap addressing).
CH = 8
A, C, G, T, N_CODE, DEL = 0, 1, 2, 3, 4, 5
# Packing codes distinct from every base code, so query padding never
# "matches" target padding in the NW kernel's character compare.
Q_PAD, T_PAD = 6, 7
# Reference default POA scores (src/main.cpp; shared with the CLI so the
# device-engine divergence warning tracks the real defaults).
DEFAULT_MATCH, DEFAULT_MISMATCH, DEFAULT_GAP = 3, -5, -4

_CODE_LUT = np.full(256, N_CODE, dtype=np.uint8)
for i, b in enumerate(b"ACGT"):
    _CODE_LUT[b] = i
_BYTE_LUT = np.frombuffer(b"ACGTN-", dtype=np.uint8)


@functools.partial(jax.jit,
                   static_argnames=("max_len", "band", "L", "K"))
def _vote_from_ops(ops, fi, fj, score, n, m, qpw, begin,
                   *, max_len: int, band: int, L: int, K: int):
    """Turn walked op codes into the (idx, w, ok) vote stream — vectorized.

    ops: uint8 [B, S] backward-walk op codes from ``_walk_ops_kernel``
    (0=M, 1=I, 2=D, >=3 done/stalled); qpw: [B, max_len] uint16 layer
    base codes and phred weights packed ``weight << 3 | code`` (the same
    lane format the fused Pallas emitter consumes — codes 3 bits,
    weights <= 93 in 7); begin: [B] backbone-span start column.

    The walk position *before* step t is recovered with prefix sums of the
    consumed-query/-target indicators (no sequential re-walk), the
    insertion-run length with a prefix max over the last non-insertion
    step, and the layer base+weight lookup is ONE batched gather on the
    packed lanes (it used to be two) — everything is [B, S] elementwise
    work. The XLA twin of the fused Pallas emitter
    (``pallas_walk_vote``): both produce the identical stream consumed
    by :func:`_accumulate_votes`.

    Vote layout: column votes at col*CH+ch, insertion slot s of junction
    col at (L + col*K + s)*CH + ch, sink VOT for non-votes. Insertion
    runs longer than K vote only their last K bases (the rest are
    dropped), which bounds every vote address's count at the layer depth.
    """
    B, S = ops.shape
    Lq = max_len
    VOT = L * (1 + K) * CH

    is_M = ops == 0
    is_I = ops == 1
    is_D = ops == 2
    di = (is_M | is_I).astype(jnp.int32)   # consumed a query base
    dj = (is_M | is_D).astype(jnp.int32)   # consumed a target base
    # position before step t: (n, m) minus everything consumed earlier
    i_t = n[:, None] - jnp.cumsum(di, axis=1) + di
    j_t = m[:, None] - jnp.cumsum(dj, axis=1) + dj

    # ins_run at t = number of consecutive I steps immediately before t
    t_idx = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None, :], (B, S))
    last_ni = lax.cummax(jnp.where(~is_I, t_idx, -1), axis=1)
    last_ni_excl = jnp.concatenate(
        [jnp.full((B, 1), -1, jnp.int32), last_ni[:, :-1]], axis=1)
    ins_run = t_idx - 1 - last_ni_excl
    slot = jnp.minimum(ins_run, K - 1)

    qpos = jnp.clip(i_t - 1, 0, Lq - 1)
    pw = jnp.take_along_axis(qpw, qpos, axis=1).astype(jnp.int32)
    base = pw & 7
    # weights travel packed (integral 0..93 phred, or 1 for no-quality
    # layers) — identical values to the Pallas emitter's
    wgt = pw >> 3
    col = begin[:, None] + j_t - 1
    # vote target: M -> (col, base); D -> (col, DEL); I -> ins slot
    idx = jnp.where(
        is_M, col * CH + base,
        jnp.where(is_D, col * CH + DEL,
                  (L + col * K + slot) * CH + base))
    valid = ((ops < 3) & (j_t >= 1) & (col >= 0) & (col < L)
             & ~(is_I & (ins_run >= K)))
    idx = jnp.where(valid, idx, VOT)  # sink
    w = jnp.where(valid, wgt, 0)

    ok = (fi == 0) & (fj == 0) & (score < (band // 2))
    return idx, w, ok


def _shift_left(x, sh: int):
    """Shift lanes toward index 0 by static ``sh``, zero-filling the tail
    (unlike ``jnp.roll`` nothing wraps)."""
    return jnp.pad(x[:, sh:], ((0, 0), (0, sh)))


def _fit_lanes(x, width: int):
    """``x`` cut or zero-padded at the tail to ``width`` lanes."""
    have = x.shape[1]
    if have >= width:
        return x[:, :width]
    return jnp.pad(x, ((0, 0), (0, width - have)))


def _compact_rows(flag, payload, S: int):
    """Stable per-row compaction: move flagged lanes to [0, rank) keeping
    order; unflagged output lanes are zero. ``payload`` is one int32 array
    (or a tuple of them, routed together) of nonnegative values — callers
    bit-pack what they need.

    Routing is LSB-first binary shifting: pass k moves items whose
    remaining distance has bit k by 2**k lanes. Destinations are the
    strictly-increasing ranks and distances d = t - rank are
    non-decreasing over flagged items, which makes every pass
    collision-free: a mover landing on a stayer would need
    d_j - d_i = c*2^k (c >= 1) with both ranks r_j > r_i and
    r_j - r_i = (1 - c)*2^k <= 0 — a contradiction. ~log2(S) elementwise
    passes; no scatter, no gather."""
    B = flag.shape[0]
    single = not isinstance(payload, tuple)
    pays = (payload,) if single else payload
    f = flag.astype(jnp.int32)
    rank = jnp.cumsum(f, axis=1) - f
    t_idx = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None, :], (B, S))
    d = jnp.where(flag, t_idx - rank, 0)
    alive = flag
    pays = tuple(jnp.where(flag, p, 0) for p in pays)
    for k in range((S - 1).bit_length()):
        sh = 1 << k
        if sh >= S:
            break
        mov = alive & (((d >> k) & 1) == 1)
        stay = alive & ~mov
        mov_s = _shift_left(mov, sh)
        d_s = _shift_left(d, sh)
        pays_s = tuple(_shift_left(p, sh) for p in pays)
        alive = mov_s | stay
        d = jnp.where(mov_s, d_s, jnp.where(stay, d, 0))
        pays = tuple(jnp.where(mov_s, ps, jnp.where(stay, p, 0))
                     for ps, p in zip(pays_s, pays))
    out = pays[0] if single else pays
    return out, alive


def _shift_rows_left(x, amount, max_amount: int):
    """Per-row left shift by a traced per-row ``amount`` (binary
    decomposition of the shift into static-shift selects; zero fill)."""
    for k in range(max(max_amount, 1).bit_length()):
        sh = 1 << k
        if sh > max_amount:
            break
        x = jnp.where((((amount >> k) & 1) == 1)[:, None],
                      _shift_left(x, sh), x)
    return x


def _shift_right(x, sh: int):
    """Shift lanes away from index 0 by static ``sh``, zero-filling the
    head (mirror of :func:`_shift_left`; nothing wraps)."""
    return jnp.pad(x[:, :-sh], ((0, 0), (sh, 0)))


def _expand_rows(alive, payload, dist, S: int):
    """Stable per-row expansion — the mirror of :func:`_compact_rows`:
    move the alive lane at rank position ``r`` RIGHT by ``dist[r]`` lanes
    (``dist`` must be >= 0 and non-decreasing over alive lanes, with
    ``r + dist[r] < S``); vacated and untouched lanes read zero.

    Binary routing like :func:`_compact_rows` but **MSB-first**: pass k
    moves items whose remaining distance has bit k by 2**k lanes toward
    the tail. MSB-first is what makes expansion collision-free (LSB-first
    only works for the dense-rank destinations of compaction): at pass k
    every item sits at ``dest - (d mod 2^(k+1))``, so a mover i landing
    on a stayer j would need ``dest_j - dest_i = (d_j - d_i) mod-parts``
    forcing ``q_i > q_j`` in the bit-k+1 quotients while ``d_i <= d_j``
    — a contradiction. Used to land per-(column, slot) insertion votes
    on their absolute column lanes without a scatter."""
    pays = jnp.where(alive, payload, 0)
    d = jnp.where(alive, dist, 0)
    for k in reversed(range((S - 1).bit_length())):
        sh = 1 << k
        if sh >= S:
            continue
        mov = alive & (((d >> k) & 1) == 1)
        stay = alive & ~mov
        mov_s = _shift_right(mov, sh)
        d_s = _shift_right(d, sh)
        pays_s = _shift_right(pays, sh)
        alive = mov_s | stay
        d = jnp.where(mov_s, d_s, jnp.where(stay, d, 0))
        pays = jnp.where(mov_s, pays_s, jnp.where(stay, pays, 0))
    return pays, alive


def _int_vote_matmul(ohT8, a_ch, a_w, CH: int):
    """Exact integer window-reduction of per-lane (channel, weight) votes
    on the MXU: an int8 x int8 -> int32 matmul pair instead of the f32
    HIGHEST one-hot matmul. Weights (< 2^13 after alpha scaling) split
    into two 7-bit limbs so the operands fit int8; int32 accumulation
    (``preferred_element_type``) is exact at any voting depth up to
    2^31 / 8184 ≈ 262k — where the f32 path lost integer exactness at
    2^24 partial sums, the old depth-2047 cap. Returns (weight sums,
    vote counts), both int32 [nW, L*CH]."""
    ch_iota = jnp.arange(CH, dtype=jnp.int32)
    wop = jnp.where(a_ch[:, :, None] == ch_iota, a_w[:, :, None], 0)
    B = wop.shape[0]
    flat = wop.reshape(B, -1)
    lo = (flat & 127).astype(jnp.int8)
    hi = (flat >> 7).astype(jnp.int8)       # a_w < 2^13 -> hi < 64
    cnt = (flat > 0).astype(jnp.int8)
    w = (jnp.matmul(ohT8, lo, preferred_element_type=jnp.int32)
         + (jnp.matmul(ohT8, hi, preferred_element_type=jnp.int32) << 7))
    c = jnp.matmul(ohT8, cnt, preferred_element_type=jnp.int32)
    return w, c


def _accumulate_votes(idx, w, ok, win_of, span_m, bg, n, score, *,
                      n_windows: int, L: int, K: int, band: int,
                      scores=(DEFAULT_MATCH, DEFAULT_MISMATCH,
                              DEFAULT_GAP), matmul_votes: bool = False):
    """Accumulate the per-step vote stream into per-window matrices —
    shared by both walk backends (identical results by construction).

    TPU-native replacement for a flat scatter-add (XLA's scatter engine
    processes the ~10M updates of a full-size group at ~90M/s, an order
    of magnitude over everything else in the round):

    - **column votes** (M/D steps, one per consumed backbone column, the
      ~98% majority): the r-th column-consuming step of a pair hits
      column ``bg + m - 1 - r``, so a stable per-row compaction
      (:func:`_compact_rows`, over the ``S`` steps) followed by a cut to
      ``L`` lanes (a pair votes a column once, so its ranks are below
      ``L``), a lane reverse and a per-row shift at ``L`` lanes lands
      every vote at its absolute column; a one-hot [B, n_windows]
      matmul then reduces pairs into windows on the MXU (``matmul_votes``:
      exact int8 x int8 -> int32, :func:`_int_vote_matmul`; else f32
      with HIGHEST precision, exact for integer sums < 2^24);
    - **insertion votes** (~2%): ONE compaction of the step stream, cut
      to the first ``IC = min(S, band // 2)`` lanes (an ok pair has
      score < band//2, so it cannot carry more insertion steps than
      that; rejected pairs are masked out of every reduction), shared
      by both branches. ``matmul_votes`` (the engine's path): per slot,
      the narrow stream compacts again (``IC`` lanes), expands onto the
      absolute column lanes (:func:`_expand_rows` at ``max(IC, L)``
      lanes) and goes through the same exact matmul as the columns:
      ``K`` small planes from one stream. Otherwise (tests: the
      independent reference) the stream is scatter-added into a **u32
      pair** per address (weight table + count table): the old
      single-u32 packing (weight bits 0-22, count bits 23-31) silently
      carried the count into the weight field past 511 votes per
      address; the widened pair is exact to depth 2^32 and the depth
      ceiling comes from the column reduction (see
      ``TpuPoaConsensus.__init__``).

    **Score-weighted voting** (the -m/-x/-g contract, the analog of
    cudapoa consuming the CLI scores directly,
    ``src/cuda/cudabatch.cpp:54-62``): every layer's votes are scaled by
    alpha = 64 * (its alignment score under the CLI m/x/g) / (its score
    under the reference defaults 3/-5/-4), so relatively poor layers
    under the chosen scoring lose voting power. The match/mismatch/gap
    counts come from the edit score plus a gap count derived from the
    vote stream itself (gaps = insertion votes + DEL column votes), so
    both walk backends compute identical alphas. The stream-derived gap
    count is an *approximation*: insertion runs longer than K_INS and
    insertions outside [0, L) emit no votes, so their gaps are
    undercounted and mat/mis correspondingly overestimated — alpha is an
    approximate CLI-score weight (consistently for both backends;
    defaults are exact since alpha is the constant 64 there). At the default scores
    alpha == 64 exactly for every layer — a uniform scale that cancels
    in every consensus ratio — so default results are bit-identical to
    unweighted voting (backbone votes are pre-scaled by 64 at pack
    time to keep the competition fair).

    Returns (weighted [n_windows, L*(1+K)*CH] f32, unweighted i32,
    ins_overflow telemetry, its per-window counts [n_windows] i32).
    ``ins_overflow`` (``dropped[:, 2]``, counter
    ``consensus.ins_overflow``) says on each branch that its cap held:
    with ``matmul_votes`` it counts accepted pairs whose insertion
    stream reached past lane ``IC`` (votes the cut lost: the score gate
    makes it 0, and anything else is a fault); on the scatter branch,
    insertion votes past the fold cap (none lost: that round scattered
    the uncapped stream).
    """
    B, S = idx.shape
    VOT = L * (1 + K) * CH
    nW = n_windows

    col_flag = idx < L * CH
    ins_flag = (idx >= L * CH) & (idx < VOT)

    # ---- per-layer score weight alpha (q6 fixed point, 64 == 1.0)
    ms, xs, gs = scores
    ch_all = idx & (CH - 1)
    gaps = jnp.sum((ins_flag | (col_flag & (ch_all == DEL))
                    ).astype(jnp.int32), axis=1)
    mis = jnp.maximum(score - gaps, 0)
    mat = jnp.maximum((n + span_m - gaps) // 2 - mis, 0)
    if (ms, xs, gs) == (DEFAULT_MATCH, DEFAULT_MISMATCH, DEFAULT_GAP):
        alpha = jnp.full((B,), 64, jnp.int32)
    else:
        s_cli = (ms * mat + xs * mis + gs * gaps).astype(jnp.float32)
        s_def = (DEFAULT_MATCH * mat + DEFAULT_MISMATCH * mis
                 + DEFAULT_GAP * gaps).astype(jnp.float32)
        # floor 1 (not 0): a layer must never lose its unweighted
        # coverage counts to down-weighting — counts stay
        # alpha-independent; ceiling 88 keeps 93*88 in the 13-bit field
        alpha = jnp.clip(jnp.round(
            64.0 * jnp.maximum(s_cli, 0.0) / jnp.maximum(s_def, 1.0)
        ).astype(jnp.int32), 1, 88)
    w = w * alpha[:, None]

    # ---- column votes: compact to rank space, reverse, align, matmul
    ch = idx & (CH - 1)  # CH is a power of two
    pay = (ch << 13) | jnp.minimum(w, (1 << 13) - 1)
    comp, _ = _compact_rows(col_flag, pay, S)
    # a pair votes each column at most once, so its ranks are < L: the
    # per-row shift runs at the width of its destination, not of the
    # step stream
    rev = jnp.flip(_fit_lanes(comp, L), axis=1)
    aligned = _shift_rows_left(rev, L - bg - span_m, L)
    a_ch = (aligned >> 13) & (CH - 1)
    onemask = ((win_of[:, None] == jnp.arange(nW, dtype=win_of.dtype))
               & ok[:, None])
    if matmul_votes:
        # exact int8/int32 MXU reduction — no f32 partial sums, but the
        # totals below are still cast to f32 for the consensus kernel,
        # so the ctor's depth cap must keep them f32-representable
        # (64-aligned at default scores -> 65535; 2047 otherwise)
        ohT8 = onemask.astype(jnp.int8).T
        w_icols, c_icols = _int_vote_matmul(
            ohT8, a_ch, aligned & ((1 << 13) - 1), CH)
        w_cols = w_icols.astype(jnp.float32)
        c_cols = c_icols.astype(jnp.float32)
    else:
        a_w = (aligned & ((1 << 13) - 1)).astype(jnp.float32)
        ch_iota = jnp.arange(CH, dtype=jnp.int32)
        wop = jnp.where(a_ch[:, :, None] == ch_iota, a_w[:, :, None], 0.0)
        cop = (wop > 0).astype(jnp.float32)
        onehot = onemask.astype(jnp.float32)
        hi = jax.lax.Precision.HIGHEST
        w_cols = jnp.matmul(onehot.T, wop.reshape(B, L * CH), precision=hi)
        c_cols = jnp.matmul(onehot.T, cop.reshape(B, L * CH), precision=hi)

    # ---- insertion votes, level 1 (per pair, both branches): ONE stable
    # compaction of the step stream, cut to the first IC lanes. An ok
    # pair has < band//2 edits, hence < band//2 insertion steps — lanes
    # beyond IC can only hold votes of pairs that are dropped anyway
    IC = min(S, band // 2)
    ipay = ((idx - L * CH) << 13) | jnp.minimum(w, (1 << 13) - 1)
    icomp, ialive = _compact_rows(ins_flag, ipay, S)
    # the compaction is dense, so lane IC is live exactly when the cut
    # below loses a vote
    past_ic = ialive[:, IC] if IC < S else jnp.zeros((B,), bool)
    icomp = icomp[:, :IC]
    ialive = ialive[:, :IC]

    if matmul_votes:
        # ---- K aligned slot planes through the same exact matmul (no
        # scatter), routed from the narrow stream: per (pair, junction,
        # slot) there is at most ONE vote — slots of one insertion run
        # are distinct and distinct runs sit at distinct junction
        # columns — so each slot's votes compact again in walk order
        # (strictly decreasing junction column; IC lanes, not S) and
        # RIGHT-expand onto absolute column lanes (:func:`_expand_rows`
        # at the width of the destination; ``L-1-col`` is strictly
        # increasing over ranks).
        islot = ((icomp >> 13) // CH) % K
        W3 = max(IC, L)
        lane = jnp.arange(W3, dtype=jnp.int32)[None, :]
        plane_w, plane_c = [], []
        for s in range(K):
            comp_s, alive_s = _compact_rows(ialive & (islot == s), icomp,
                                            IC)
            comp_s = _fit_lanes(comp_s, W3)
            alive_s = _fit_lanes(alive_s, W3)
            dist = jnp.where(
                alive_s, (L - 1) - (comp_s >> 13) // (K * CH) - lane, 0)
            exp_s, _ = _expand_rows(alive_s, comp_s, dist, W3)
            al_s = jnp.flip(exp_s[:, :L], axis=1)
            ws, cs = _int_vote_matmul(ohT8, (al_s >> 13) & (CH - 1),
                                      al_s & ((1 << 13) - 1), CH)
            plane_w.append(ws.reshape(nW, L, CH))
            plane_c.append(cs.reshape(nW, L, CH))
        INS = L * K * CH
        ins_w = jnp.stack(plane_w, axis=2).reshape(nW, INS) \
            .astype(jnp.float32)
        ins_c = jnp.stack(plane_c, axis=2).reshape(nW, INS)
        weighted = jnp.concatenate([w_cols, ins_w], axis=1)
        unweighted = jnp.concatenate(
            [c_cols.astype(jnp.int32), ins_c], axis=1)
        # the reading that says the narrow stream held: accepted pairs
        # (``ok`` is in ``ohT8``) that lost a vote to the cut, by window
        ins_ovf_w = jnp.matmul(ohT8, past_ic.astype(jnp.int8)[:, None],
                               preferred_element_type=jnp.int32)[:, 0]
        return weighted, unweighted, jnp.sum(ins_ovf_w), ins_ovf_w

    # ---- the level-1 stream through one packed scatter
    iaddr = icomp >> 13
    iw = ((icomp & ((1 << 13) - 1))
          * (ialive & ok[:, None]).astype(jnp.int32))
    # live = lanes that actually carry weight: rejected pairs' and
    # zero-weight lanes must not occupy fold-cap slots (they'd trip the
    # overflow fallback without representing any real vote density)
    ialive = ialive & ok[:, None] & (iw > 0)
    INS = L * K * CH
    iflat = jnp.where(ialive, win_of[:, None] * INS + iaddr, nW * INS)
    # level 2: fold G pairs per row and compact again — real insertions
    # are a few percent of steps, so folded rows compact ~CAP_DIV-fold
    # and the scatter engine (the slowest op on TPU at ~90M updates/s)
    # scans CAP_DIV x fewer lanes. A fold row can overflow its cap when
    # its G pairs average > IC/CAP_DIV insertions each (e.g. one very
    # divergent window's layers packed together): votes are never lost —
    # overflow switches that round to scattering the uncapped level-1
    # stream (lax.cond compiles both, the fast path runs when clean);
    # the returned tally counts the overflowing items for telemetry.
    def pack_scatter(flat, w):
        # widened accumulator: weight and count land in separate u32
        # tables (a u64 pair per address) — the old 23-bit weight /
        # 9-bit count split of one u32 saturated silently at depth 511,
        # carrying counts into the weight bits
        fl = flat.reshape(-1)
        wt = jnp.zeros(nW * INS + 1, jnp.uint32).at[fl].add(
            w.reshape(-1).astype(jnp.uint32))
        ct = jnp.zeros(nW * INS + 1, jnp.uint32).at[fl].add(
            (w.reshape(-1) > 0).astype(jnp.uint32))
        return wt, ct

    G, CAP_DIV = 32, 4
    if B % G == 0 and (G * IC) % CAP_DIV == 0:
        rows = B // G
        cap = G * IC // CAP_DIV
        f2 = iflat.reshape(rows, G * IC)
        w2 = iw.reshape(rows, G * IC)
        (f2, w2), alive2 = _compact_rows(
            ialive.reshape(rows, G * IC), (f2, w2), G * IC)
        # per-window overflow attribution (a bare counter hides WHICH
        # window's vote density tripped the uncapped-scatter fallback):
        # an overflowing lane's flat address f2 still encodes its window
        # as f2 // INS, so one tiny scatter tallies them per window
        ovf_live = alive2[:, cap:] & (w2[:, cap:] > 0)
        ins_ovf_w = jnp.zeros(nW + 1, jnp.int32).at[
            jnp.where(ovf_live, f2[:, cap:] // INS, nW)].add(
            ovf_live.astype(jnp.int32))[:nW]
        ins_overflow = jnp.sum(ins_ovf_w)
        itab_w, itab_c = lax.cond(
            ins_overflow == 0,
            lambda: pack_scatter(
                jnp.where(alive2[:, :cap], f2[:, :cap], nW * INS),
                w2[:, :cap]),
            lambda: pack_scatter(iflat, iw))
    else:  # tiny batches: skip the fold
        itab_w, itab_c = pack_scatter(iflat, iw)
        ins_overflow = jnp.int32(0)
        ins_ovf_w = jnp.zeros((nW,), jnp.int32)
    ins_w = itab_w[:nW * INS].astype(jnp.float32).reshape(nW, INS)
    ins_c = itab_c[:nW * INS].astype(jnp.int32).reshape(nW, INS)

    weighted = jnp.concatenate([w_cols, ins_w], axis=1)
    unweighted = jnp.concatenate([c_cols.astype(jnp.int32), ins_c], axis=1)
    return weighted, unweighted, ins_overflow, ins_ovf_w


@functools.partial(jax.jit, static_argnames=("L", "K"))
def _consensus_kernel(weighted, unweighted, bcodes, bweights, blen,
                      ins_theta, del_beta, *, L: int, K: int):
    """Add backbone votes, then pick per-column and insertion winners.

    Emission rules (POA heaviest-bundle analogs, calibrated against the
    CPU engine on λ-phage):
    - a column emits its winning base unless the deletion weight exceeds
      ``del_beta`` x the summed base weights (reads voting *any* base
      jointly defend the column, as substitution variants occupy one
      aligned-ring position in the POA graph);
    - insertion slot ``s`` emits its winning base when the slot's summed
      weight (all bases — the slot is one graph node position, bases are
      its aligned ring) exceeds ``ins_theta`` x the column total.
    """
    n_windows = weighted.shape[0]
    cols = jnp.arange(L)

    w = weighted.reshape(n_windows, L * (1 + K), CH)
    uw = unweighted.reshape(n_windows, L * (1 + K), CH)
    col_votes = w[:, :L, :]      # [n, L, CH]
    ins_votes = w[:, L:, :].reshape(n_windows, L, K, CH)
    col_unw = uw[:, :L, :]
    ins_unw = uw[:, L:, :].reshape(n_windows, L, K, CH)

    # backbone's own votes (weight may be 0 for dummy quality -> still
    # contributes 1 to unweighted coverage, like a spoa sequence label)
    in_range = cols[None, :] < blen[:, None]
    bb_onehot = jax.nn.one_hot(bcodes, CH, dtype=jnp.float32)
    eps_w = jnp.maximum(bweights, 0.01)  # dummy-quality backbones still win
                                         # columns with no layer votes
    col_votes = col_votes + bb_onehot * (eps_w * in_range)[..., None]
    col_unw = col_unw + (bb_onehot * in_range[..., None]).astype(jnp.int32)

    base_winner = jnp.argmax(col_votes[:, :, :N_CODE + 1], axis=-1)
    base_total = col_votes[:, :, :N_CODE + 1].sum(-1)
    del_w = col_votes[:, :, DEL]
    winner = jnp.where(del_w > del_beta * base_total, DEL, base_winner)
    # winner-channel lookups as one-hot selects (take_along_axis lowers to
    # a generic gather, which is slow on TPU)
    ch_iota = jnp.arange(CH, dtype=winner.dtype)
    coverage = jnp.sum(
        jnp.where(winner[..., None] == ch_iota, col_unw, 0), axis=-1)
    col_total = col_votes.sum(-1)

    ins_winner = jnp.argmax(ins_votes[:, :, :, :N_CODE + 1], axis=-1)
    ins_total = ins_votes[:, :, :, :N_CODE + 1].sum(-1)
    ins_cov = jnp.sum(
        jnp.where(ins_winner[..., None] == ch_iota, ins_unw, 0), axis=-1)
    ins_emit = ins_total > ins_theta * col_total[:, :, None]

    return winner, coverage, ins_winner, ins_emit, ins_cov


@functools.partial(jax.jit, static_argnames=("n_windows", "max_len", "band",
                                             "Lb", "K", "steps",
                                             "use_pallas", "use_swar",
                                             "Lq2", "scores",
                                             "matmul_votes"))
def refine_round(n, qpw, win_of, real, bg, ed,
                 bcodes, bweights, blen, covs, ever, frozen, conv,
                 dropped, ins_theta, del_beta, *, n_windows: int,
                 max_len: int, band: int, Lb: int, K: int, steps: int = 0,
                 use_pallas: bool = False, use_swar: bool = False,
                 Lq2: int = 0,
                 scores=(DEFAULT_MATCH, DEFAULT_MISMATCH, DEFAULT_GAP),
                 matmul_votes: bool = False):
    """One fully-device-resident refinement round.

    Align every layer against its current backbone span, vote, pick
    winners, then *rebuild the backbone rows on device* (emitted-entry
    prefix sums give each emitted base its output column; one scatter
    writes the new backbone and its coverage) and remap every layer span
    through the emitted-column map. The host never sees intermediate
    backbones — it packs once before round 1 and fetches once after the
    last round. Replaces the per-round pack/fetch/Python-rebuild loop
    (_apply_shard) whose host round trips dominated wall-clock.

    Per-window state: ``bcodes/bweights/blen`` backbone rows (codes, Lb
    columns), ``covs`` coverage of the current backbone, ``ever`` whether
    any round succeeded (false -> CPU fallback), ``frozen`` stop-refining
    flag (backbone outgrew Lb), ``conv`` converged flag (backbone
    reproduced itself; layers stop realigning). ``dropped`` accumulates telemetry
    counters ([nd, 4 + n_windows] i32: rejected layer alignments,
    sweep-truncated spans, insertion overflows — accepted pairs past the
    ``band // 2`` insertion lanes on the matmul path, 0 unless the score
    gate failed; fold-cap overflows on the scatter path, which lose no
    vote — executed post-gating wavefront steps, then the overflows
    attributed to their windows). The single source of truth for the
    round wiring, wrapped by :func:`refine_loop` (all rounds in one
    dispatch) and the ``shard_map`` path
    (``racon_tpu.parallel.sharded_refine_loop``).

    Layer codes and phred weights travel packed (``qpw`` uint16 lanes,
    ``weight << 3 | code`` — one transfer array instead of two, one
    gather in the vote prep, one VMEM block in the fused Pallas
    emitter); ``use_swar`` runs the forward DP on int16x2-packed score
    lanes (bit-identical outputs, see ``ops.swar``).
    """
    Lq = max_len
    # the vote emitters only read query lanes < the longest real layer —
    # slicing their blocks to Lq2 cuts the fused kernel's per-step
    # base/weight selects by Lq/Lq2 (the fwd row layout still needs Lq)
    Lq2 = Lq2 or Lq
    c = band // 2
    width = c + Lq + band
    B = qpw.shape[0]
    qcodes = (qpw & 7).astype(jnp.uint8)  # unpacked codes for the rows
    # convergence gating: pairs of a window whose backbone reproduced
    # itself last round are zeroed out (n = m = 0) — their walk ends
    # immediately, they emit no votes, and the Pallas kernels' per-block
    # dynamic bounds skip whole blocks of them; the window's state is
    # frozen below via ok_upd, so its final consensus is the fixed point
    conv_p = jnp.take(conv | frozen, win_of)  # frozen windows' results
                                              # are discarded anyway
    n = jnp.where(conv_p, 0, n)
    m = jnp.where(conv_p, 0, ed - bg + 1)

    # ---- reversed query rows derived on device (the host sends only the
    # forward codes once; the reversed NW layout is a flip + mask)
    core = jnp.where((Lq - 1 - jnp.arange(Lq, dtype=jnp.int32))[None, :]
                     < n[:, None],
                     jnp.flip(qcodes, axis=1), jnp.uint8(Q_PAD))
    qrp = jnp.concatenate(
        [jnp.full((B, c), Q_PAD, jnp.uint8), core,
         jnp.full((B, band), Q_PAD, jnp.uint8)], axis=1)

    # ---- target rows from the backbone state: one row gather, then a
    # per-pair lane shift by ``bg`` via binary-decomposed rolls (wrapped
    # lanes always fall outside [0, m) and are masked) — the elementwise
    # rolls are ~8x cheaper than the generic 2-D gather they replace
    cols = jnp.arange(width, dtype=jnp.int32)[None, :] - c
    bbrow = jnp.take(bcodes, win_of, axis=0)            # (B, Lb)
    y = jnp.pad(bbrow, ((0, 0), (c, width - c - Lb)))
    for k in range((Lb - 1).bit_length()):
        y = jnp.where(((bg[:, None] >> k) & 1).astype(bool),
                      jnp.roll(y, -(1 << k), axis=1), y)
    tp = jnp.where((cols >= 0) & (cols < m[:, None]), y, jnp.uint8(T_PAD))

    if use_pallas:
        from .pallas_nw import pallas_nw_fwd, pallas_walk_vote
        packed, score = pallas_nw_fwd(qrp, tp, n, m,
                                      max_len=Lq, band=band, steps=steps,
                                      use_swar=use_swar)
        idx, w8, fi, fj = pallas_walk_vote(packed, n, m, bg,
                                           qpw[:, :Lq2], band=band,
                                           L=Lb, K=K, CH=CH, DEL=DEL)
        okp = (fi == 0) & (fj == 0) & (score < (band // 2))
        wv = w8.astype(jnp.int32)
    else:
        packed, score = _nw_wavefront_kernel(qrp, tp, n, m,
                                             max_len=Lq, band=band,
                                             steps=steps, swar=use_swar)
        ops, fi, fj = _walk_ops_kernel(packed, n, m, band=band)
        idx, wv, okp = _vote_from_ops(
            ops, fi, fj, score, n, m, qpw[:, :Lq2],
            bg, max_len=Lq2, band=band, L=Lb, K=K)
    weighted, unweighted, ins_ovf, ins_ovf_w = _accumulate_votes(
        idx, wv, okp, win_of, m, bg, n, score, n_windows=n_windows,
        L=Lb, K=K, band=band, scores=scores, matmul_votes=matmul_votes)
    winner, coverage, ins_winner, ins_emit, ins_cov = _consensus_kernel(
        weighted, unweighted, bcodes, bweights, blen, ins_theta, del_beta,
        L=Lb, K=K)
    # telemetry: [0] total dropped layer alignments, [1] the subset whose
    # span outgrew the sweep bound (n + m > steps keeps the walk from
    # finishing — a quality cliff distinct from band escapes, ADVICE r3),
    # [2] insertion overflows (_accumulate_votes: accepted pairs past
    # the band // 2 insertion lanes on the matmul path, which must read
    # 0; votes past the fold cap on the scatter path, none lost),
    # [3] executed wavefront steps (sum of n+m AFTER convergence gating
    # — the honest numerator for device-utilization estimates: gated
    # pairs do no DP);
    # columns [4:] attribute the overflows of [2] to their windows
    dropped = dropped + jnp.concatenate(
        [jnp.stack([jnp.sum((~okp) & real),
                    jnp.sum(real & (n + m > steps)),
                    ins_ovf,
                    jnp.sum(jnp.where(real, jnp.minimum(n + m, steps),
                                      0))]),
         ins_ovf_w])[None, :]

    # ---- rebuild backbone rows from emitted columns/slots.
    # Entry order within a column: its base first, then insertion slots
    # high-to-low (slot s holds the s-th base from the END of an insertion
    # run — the walk is backwards — so high slots come first in sequence).
    colr = jnp.arange(Lb, dtype=jnp.int32)[None, :]
    in_range = colr < blen[:, None]
    base_emit = (winner <= N_CODE) & in_range
    ins_e = ins_emit & in_range[:, :, None]
    ent_emit = jnp.concatenate([base_emit[:, :, None], ins_e[:, :, ::-1]], 2)
    ent_code = jnp.concatenate(
        [jnp.clip(winner, 0, N_CODE).astype(jnp.uint8)[:, :, None],
         ins_winner.astype(jnp.uint8)[:, :, ::-1]], 2)
    ent_cov = jnp.concatenate([coverage[:, :, None],
                               ins_cov[:, :, ::-1]], 2)
    E = Lb * (1 + K)
    fe = ent_emit.reshape(n_windows, E).astype(jnp.int32)
    pos = jnp.cumsum(fe, axis=1) - fe           # exclusive prefix sum
    new_len = jnp.sum(fe, axis=1)
    c2n = pos[:, ::(1 + K)]                     # old col -> new position

    # emitted entries compact to their output columns (ranks == the
    # prefix-sum positions, entries past Lb fall off the slice) — same
    # routing primitive as the vote accumulation, no scatter. Packing:
    # codes fit 3 bits; covs are winner-channel counts <= depth+1.
    epay = ((ent_cov.reshape(n_windows, E).astype(jnp.int32) << 3)
            | ent_code.reshape(n_windows, E).astype(jnp.int32))
    ecomp, _ = _compact_rows(fe > 0, epay, E)
    nb_mat = (ecomp[:, :Lb] & 7).astype(jnp.uint8)
    nc_mat = ecomp[:, :Lb] >> 3

    # empty consensus keeps the previous state (host analog: `continue`);
    # overflow freezes the window at its last refined backbone; converged
    # windows keep everything (their votes this round were backbone-only)
    ok_upd = (~frozen) & (~conv) & (new_len > 0) & (new_len <= Lb)
    frozen = frozen | (new_len > Lb)
    # a window converges when the refined backbone reproduces itself
    # byte-for-byte: later rounds would keep emitting the same fixed
    # point, so stop realigning its layers (the output is unchanged
    # except where an un-gated engine would oscillate between states)
    conv = conv | (ok_upd & (new_len == blen)
                   & jnp.all(jnp.where(in_range, nb_mat == bcodes, True),
                             axis=1))
    bcodes = jnp.where(ok_upd[:, None], nb_mat, bcodes)
    covs = jnp.where(ok_upd[:, None], nc_mat, covs)
    bweights = jnp.where(ok_upd[:, None], 0.0, bweights)  # refined backbone
                                                          # carries no phred
    ever = ever | ok_upd

    # ---- remap layer spans through the emitted-column map
    blen_g = jnp.take(blen, win_of)
    nl_g = jnp.take(new_len, win_of)

    def lookup(col):
        cl = jnp.minimum(col, blen_g)
        v = jnp.take(c2n.reshape(-1),
                     win_of * Lb + jnp.clip(cl, 0, Lb - 1))
        return jnp.where(cl >= blen_g, nl_g, v)  # col_to_new[blen] = len

    nb = lookup(bg)
    ne = jnp.maximum(nb + 1, lookup(ed + 1) - 1)
    nb = jnp.minimum(nb, nl_g - 1)
    ne = jnp.minimum(ne, nl_g - 1)
    upd_p = jnp.take(ok_upd, win_of)
    bg = jnp.where(upd_p, nb, bg)
    ed = jnp.where(upd_p, ne, ed)
    blen = jnp.where(ok_upd, new_len, blen)

    return (bg, ed, bcodes, bweights, blen, covs, ever, frozen, conv,
            dropped)


@functools.partial(jax.jit, static_argnames=("rounds", "n_windows",
                                             "max_len", "band", "Lb", "K",
                                             "steps", "use_pallas",
                                             "use_swar", "Lq2", "scores",
                                             "matmul_votes"))
def refine_loop(n, qpw, win_of, real, bg, ed,
                bcodes, bweights, blen, covs, ever, frozen, conv,
                dropped, ins_theta, del_beta, *, rounds: int,
                n_windows: int,
                max_len: int, band: int, Lb: int, K: int, steps: int = 0,
                use_pallas: bool = False, use_swar: bool = False,
                Lq2: int = 0,
                scores=(DEFAULT_MATCH, DEFAULT_MISMATCH, DEFAULT_GAP),
                matmul_votes: bool = False):
    """All refinement rounds of a group in ONE device dispatch.

    ``lax.while_loop`` over :func:`refine_round` — a per-round host
    dispatch would add its fixed overhead to every round; with the loop
    on device a group costs one dispatch
    and one fetch regardless of ``rounds``. The loop **exits early** once
    every window with real pairs is converged or frozen: further rounds
    are provably no-ops (converged/frozen windows reject updates via
    ``ok_upd`` and their gated pairs emit no votes and no telemetry), so
    the early exit is bit-invisible — it only skips work."""
    nW_rows = bcodes.shape[0]
    win_real = (jnp.zeros((nW_rows,), jnp.int32)
                .at[win_of].max(real.astype(jnp.int32)) > 0)

    def cond(carry):
        return (carry[0] < rounds) & ~jnp.all(carry[9] | carry[8]
                                              | ~win_real)

    def body(carry):
        out = refine_round(
            n, qpw, win_of, real, *carry[1:], ins_theta,
            del_beta, n_windows=n_windows, max_len=max_len, band=band,
            Lb=Lb, K=K, steps=steps, use_pallas=use_pallas,
            use_swar=use_swar, Lq2=Lq2, scores=scores,
            matmul_votes=matmul_votes)
        return (carry[0] + 1,) + tuple(out)

    state = (bg, ed, bcodes, bweights, blen, covs, ever, frozen, conv,
             dropped)
    return lax.while_loop(cond, body, (jnp.int32(0),) + state)[1:]


@functools.lru_cache(maxsize=None)
def _loop_geometry(Lq: int, Lb: int, band: int, steps: int, Lq2: int,
                   B: int, nWp: int, rounds: int, swar: bool) -> str:
    """The occupancy ledger's join key of one group geometry's
    refinement loop: the static arguments and batch its executable is
    compiled for. Formatted once per geometry; the stream's dispatch and
    the warm-up thread both take it from here, so a warm-up's compile
    and the dispatch that runs its executable carry the same key."""
    return device_time.geometry(band=band, steps=steps, B=B, nWp=nWp,
                                Lq=Lq, Lb=Lb, Lq2=Lq2, rounds=rounds,
                                swar=swar)


@jax.jit
def _fetch_pack(bcodes, blen, covs, ever, frozen, conv, dropped, bg, ed):
    """Coalesce a group's fetch into TWO device arrays: every transfer
    pays a fixed latency, and these nine arrays are small enough for it
    to dominate their bytes. ``mat`` packs coverage and
    backbone code per column (cov << 3 | code — the same packing the
    rebuild uses, both values already bounded); ``meta`` concatenates
    every per-window/per-pair vector."""
    mat = (covs << 3) | bcodes.astype(jnp.int32)
    meta = jnp.concatenate([
        blen, ever.astype(jnp.int32), frozen.astype(jnp.int32),
        conv.astype(jnp.int32), dropped.reshape(-1), bg, ed])
    return mat, meta


@functools.partial(jax.jit, static_argnames=("rounds", "n_windows",
                                             "max_len", "band", "Lb", "K",
                                             "steps", "use_pallas",
                                             "use_swar", "Lq2", "scores",
                                             "matmul_votes"))
def _refine_loop_packed(*args, **kw):
    """refine_loop + the coalesced-fetch packing in ONE jitted program:
    a second program would pay the per-dispatch host overhead again
    for a few microseconds of packing."""
    out = refine_loop(*args, **kw)
    (bg, ed, bcodes, _, blen, covs, ever, frozen, conv, dropped) = out
    mat, meta = _fetch_pack(bcodes, blen, covs, ever, frozen, conv,
                            dropped, bg, ed)
    return out + (mat, meta)


class _Work:
    """Per-window packing view (layers capped at ``max_depth``).

    Two storage modes share one packing surface: columnar windows
    (``win.layer_view`` attached by the polisher) keep ``rows`` indices
    into the shared :class:`~racon_tpu.core.layers.LayerStore` plus the
    store's flat ``lens``/``begin``/``end`` slices — the packer then
    writes the whole group's lane block by one row copy per layer;
    hand-built windows (``add_layer``) keep the legacy bytes tuples and
    pack through the join-and-LUT path."""

    __slots__ = ("win", "backbone", "bqual", "layers", "n_seqs", "store",
                 "rows", "lens", "begins", "ends", "n_layers",
                 "max_layer_len")

    def __init__(self, win, max_depth, stats):
        self.win = win
        self.backbone = win.backbone
        self.bqual = win.backbone_quality
        total = win.layer_count
        # the depth cap keeps a window's first ``max_depth`` layers in
        # arrival (overlap-stream) order and drops the rest; the
        # counters are written for every window, zeros included
        over = max(0, total - max_depth)
        if over:
            stats["dropped_layers"] += over
        metrics.inc("consensus.windows")
        metrics.inc("consensus.layers", total)
        metrics.inc("consensus.dropped_layers", over)
        metrics.inc("consensus.windows_capped", int(over > 0))
        depth = min(total, max_depth)
        self.n_seqs = total + 1
        self.n_layers = depth
        store, r0, _ = win.layer_view
        self.store = store
        if store is not None:
            self.rows = np.arange(r0, r0 + depth, dtype=np.int64)
            self.lens = store.length[r0:r0 + depth]
            self.begins = store.begin[r0:r0 + depth]
            self.ends = store.end[r0:r0 + depth]
            self.layers = None
            self.max_layer_len = int(self.lens.max()) if depth else 0
        else:
            self.layers = []  # (seq, qual, begin, end)
            for li in range(1, depth + 1):
                b, e = win.positions[li]
                self.layers.append((win.sequences[li], win.qualities[li],
                                    b, e))
            self.lens = np.array([len(s) for s, _, _, _ in self.layers],
                                 np.int64)
            self.begins = np.array([b for _, _, b, _ in self.layers],
                                   np.int64)
            self.ends = np.array([e for _, _, _, e in self.layers],
                                 np.int64)
            self.rows = None
            self.max_layer_len = int(self.lens.max()) if depth else 0


class _ConsensusStream:
    """Ragged streaming consensus session (round 10).

    Windows arrive through :meth:`feed` in any number of batches; live
    windows bucket by the power-of-two lane width their OWN backbone and
    layers need (``_bucket_L``) instead of padding to a global maximum,
    and every bucket greedy-fills groups against the fixed
    ``ARENA_LANES`` pair arena — short windows pack proportionally more
    pairs per dispatch (the cudabatch batch-fill design,
    ``cudabatch.cpp:54-62``). Full groups dispatch ASYNCHRONOUSLY the
    moment they close: host packing of the next range overlaps device
    compute of the previous ones through the bounded in-flight pipeline,
    and fetches happen only when the in-flight byte budget forces one or
    at :meth:`finish` — the double-buffered dispatch that stops host
    fetch/emit from gating the device.

    The alignment **band is frozen at the first dispatch** from the
    windows seen so far (plus the caller's ``band_hint``), because the
    band alters alignment outcomes (the ``score < band//2`` accept gate)
    and per-window consensus must not depend on which batch a window
    arrived in. ``run()``-style usage (one feed of everything, then
    finish) therefore reproduces the padded path's band exactly; per-
    window output is bit-identical to the padded path by construction —
    windows are independent and the vote accumulation is exact integer
    arithmetic at any grouping.

    Two-stage refinement carries over per bucket: a group of
    ``TWO_STAGE_MIN_PAIRS`` rows or more runs ``STAGE_A_ROUNDS`` and
    collects its unconverged windows, whether or not another group
    shares its bucket (:meth:`TpuPoaConsensus.first_stage_rounds`, the
    padded path's rule too); :meth:`finish` coalesces each bucket's
    stragglers into small stage-B groups. A smaller group runs the
    full budget in its one dispatch."""

    def __init__(self, eng: "TpuPoaConsensus", trim: bool,
                 band_hint: int = 0, progress=None):
        self.eng = eng
        self.trim = trim
        self.band_hint = band_hint
        self.windows: List = []            # every fed window, feed order
        self.results: List[Optional[bool]] = []
        self.buffer: List = []             # live works awaiting band/bucket
        self.buffered_pairs = 0
        self.max_bb_live = 0
        self.band: Optional[int] = None    # frozen at first dispatch
        self._Lq_pad = 0                   # padded-path reject caps,
        self._Lb_pad = 0                   # set when the band freezes
        self.pending: dict = {}            # bucket L -> [(slot, work)]
        self.bucket_state: dict = {}       # bucket L -> {steps,Lq2}
        self.survivors: dict = {}          # bucket L -> stage-B collect
        self.inflight: List[dict] = []
        self.inflight_bytes = 0
        self.fetched = 0
        self.progress = progress
        self._done = False
        self._stats_before = dict(eng.stats)

    # ------------------------------------------------------------- intake

    def feed(self, windows) -> None:
        """Add a window range; packs and dispatches every group that
        fills. Returns immediately — dispatch is async, only the
        in-flight byte budget can force a (pipelined) fetch here."""
        assert not self._done, "stream already finished"
        eng = self.eng
        for win in windows:
            self.windows.append(win)
            if win.layer_count + 1 < 3:
                win.consensus = win.backbone
                self.results.append(False)
                eng.stats["passthrough"] += 1
                continue
            self.results.append(None)      # None -> CPU fallback unless
            slot = len(self.results) - 1   # a device group resolves it
            w = _Work(win, eng.max_depth, eng.stats)
            if w.n_layers < 2:
                continue
            self.buffer.append((slot, w))
            self.buffered_pairs += w.n_layers
            self.max_bb_live = max(self.max_bb_live, len(w.backbone))
        self._flush(final=False)

    # ----------------------------------------------------------- geometry

    def _bucket_L(self, w: "_Work", band: int) -> Optional[int]:
        """Power-of-two lane-width bucket for one window (None -> the
        window exceeds every device bucket and takes the CPU fallback,
        the same reject contract as the padded path's global caps).
        The pow2 rule itself is the engine's shared
        :meth:`TpuPoaConsensus.bucket_L_for`."""
        max_dev_L = (1 << 18) // (K_INS * CH) - GROW
        bb = len(w.backbone)
        if bb > max_dev_L:
            # the padded geometry admits backbones into the GROW margin
            # at the device ceiling (its accept test is bb <= Lb =
            # min(L + GROW, L + band) with L capped at max_dev_L);
            # mirror that accept set exactly — the reject set is part
            # of the ragged/padded byte-identity contract
            if bb > max_dev_L + min(GROW, band):
                return None
            bb = max_dev_L
        return self.eng.bucket_L_for(max(256, bb,
                                         w.max_layer_len - band))

    # ----------------------------------------------------------- dispatch

    def _flush(self, final: bool) -> None:
        eng = self.eng
        if self.band is None:
            # freeze the band only once there is enough buffered work to
            # justify a dispatch (or at finish): a full feed batch has
            # already been absorbed into max_bb_live at this point, so
            # run()-style usage sees the batch-global maximum exactly
            if not self.buffer:
                return
            if not final and self.buffered_pairs < eng.group_pairs_cap:
                return
            max_bb = max(self.max_bb_live, self.band_hint)
            # the padded path's geometry from the same live maximum:
            # its band AND its reject caps. Windows the padded path
            # would send to the CPU fallback (layers past Lq, backbones
            # past Lb) must take the CPU fallback here too — the reject
            # set is part of the byte-identity contract, and per-window
            # consensus is invariant to bucket size only for windows
            # both paths actually polish on device
            self.band, _, self._Lq_pad, self._Lb_pad = \
                eng._bucket_geometry(max_bb)
            eng.stats["band"] = self.band
        band = self.band
        for slot, w in self.buffer:
            if (w.max_layer_len > self._Lq_pad
                    or len(w.backbone) > self._Lb_pad):
                continue                   # CPU fallback via results None
            L = self._bucket_L(w, band)
            if L is None:
                continue                   # CPU fallback via results None
            self.pending.setdefault(L, []).append((slot, w))
        self.buffer = []
        self.buffered_pairs = 0

        for L in list(self.pending):
            items = self.pending[L]
            # straight to the engine's shared formula (the ragged path
            # and the warm-up estimate must read one cap rule)
            cap = eng.cap_pairs_for(L, band)
            while items:
                total = sum(w.n_layers for _, w in items)
                if (total < cap and len(items) <= MAX_GROUP_WINDOWS
                        and not final):
                    break                  # wait for more windows
                group: List = []
                pairs = 0
                while items and len(group) < MAX_GROUP_WINDOWS:
                    _, w = items[0]
                    if group and pairs + w.n_layers > cap:
                        break
                    pairs += w.n_layers
                    group.append(items.pop(0))
                self._dispatch(L, group)
            if not items:
                del self.pending[L]

    def _dispatch(self, L: int, group: List) -> None:
        eng = self.eng
        band = self.band
        Lq = L + band
        Lb = min(L + GROW, Lq)
        max_nm = max(
            int(np.max(w.lens + np.minimum(w.ends - w.begins + 65, Lb)))
            for _, w in group)
        max_n = max(w.max_layer_len for _, w in group)
        steps, Lq2 = eng._sweep_geometry(Lq, max_nm, max_n)
        bk = self.bucket_state.setdefault(L, {"steps": 0, "Lq2": 0})
        bk["steps"] = max(bk["steps"], steps)
        bk["Lq2"] = max(bk["Lq2"], Lq2)
        la = eng._launch_group(group, Lq, Lb)
        la["geom"] = (Lq, Lb, steps, Lq2)
        la["band"] = band
        la["bucket"] = L
        eng._first_stage(la)
        # resident bytes of this launch (packed pair inputs + per-window
        # state + coalesced fetch arrays) — the in-flight budget's unit
        la["bytes"] = (2 * Lq + 24) * la["B"] + 16 * Lb * la["nWp"]
        eng._rounds(la, Lq, Lb, steps, Lq2)
        self.inflight.append(la)
        self.inflight_bytes += la["bytes"]
        while (len(self.inflight) > max(eng.num_batches, 1)
               and self.inflight_bytes > MAX_INFLIGHT_BYTES):
            self._finish_oldest()

    def _finish_oldest(self) -> None:
        la = self.inflight.pop(0)
        self.inflight_bytes -= la["bytes"]
        collect = (self.survivors.setdefault(la["bucket"], [])
                   if la["collect"] else None)
        self.eng._finish_group(la, self.trim, self.results,
                               collect=collect)
        self.fetched += 1
        if self.progress is not None:
            est = self.fetched + len(self.inflight) + 1
            self.progress(self.fetched, est)

    # -------------------------------------------------------------- drain

    def finish(self, progress=None) -> List[bool]:
        """Dispatch the partial groups, drain the pipeline, run stage B
        per bucket and the CPU fallback; flags for every fed window."""
        assert not self._done, "stream already finished"
        self._done = True
        eng = self.eng
        if progress is not None:   # keep a callback set at stream() time
            self.progress = progress
        progress = self.progress
        self._flush(final=True)
        while self.inflight:
            self._finish_oldest()
        for L, surv in self.survivors.items():
            if not surv:
                continue
            band = self.band
            Lq = L + band
            Lb = min(L + GROW, Lq)
            bk = self.bucket_state[L]
            eng._run_stage_b(surv, self.trim, self.results,
                             Lq, Lb, bk["steps"], bk["Lq2"], band)
        cpu_idx = [i for i, r in enumerate(self.results) if r is None]
        if cpu_idx:
            eng.stats["fallback_windows"] += len(cpu_idx)
            metrics.inc("consensus.fallback_windows", len(cpu_idx))
            if eng.fallback is None:
                raise RuntimeError(
                    f"{len(cpu_idx)} windows rejected, no CPU fallback")
            flags_cpu = eng.fallback.run(
                [self.windows[i] for i in cpu_idx], self.trim)
            for i, f in zip(cpu_idx, flags_cpu):
                self.results[i] = f
        if progress is not None:
            progress(1, 1)
        eng._warn_dropped(self._stats_before)
        return [bool(r) for r in self.results]


class TpuPoaConsensus(PallasDispatchMixin):
    """Batched device consensus with CPU fallback for rejects.

    ``rounds`` controls iterative refinement: round r re-aligns every layer
    against the round r-1 consensus (with layer spans remapped through the
    emitted-column map), which recovers most of the gap between one-shot
    pileup voting and graph POA. All rounds run device-resident
    (:func:`refine_round`); the host packs once and fetches once.

    ``mesh``: optional 1-D :class:`jax.sharding.Mesh`; window groups are
    LPT-split across shards and the whole refinement loop runs under
    ``shard_map`` (multi-chip analog of cudapoa's per-GPU batch binning,
    ``src/cuda/cudapolisher.cpp:72-83``).
    """

    # pipelined-polish chunk sizing hint (Polisher.run): window ranges
    # streamed into run() should carry about one device group's worth of
    # layer pairs, so the pipelining never shrinks the fused executions
    group_pairs_hint = MAX_GROUP_PAIRS

    def __init__(self, match: int, mismatch: int, gap: int, fallback=None,
                 max_depth: int = 200, band: int = BAND, rounds: int = 6,
                 mesh=None, ins_theta: float = 0.25, del_beta: float = 0.65,
                 num_batches: int = 1, use_swar: bool = True,
                 use_matmul_votes: bool = True,
                 use_ragged: bool = True, device=None):
        self.fallback = fallback
        # per-engine chip pin (mutually exclusive with a mesh): the
        # in-process chip scheduler builds one consensus engine per
        # local device; pack/dispatch/fetch run under
        # jax.default_device(device) so this engine's whole working set
        # lives on its chip (PallasDispatchMixin._pinned)
        self.device = device
        # int8/i32 MXU vote reduction: exact integer accumulation, no
        # fold cap — ins_overflow counts accepted pairs past the
        # band // 2 insertion lanes and reads 0; False selects the
        # f32-matmul + packed scatter (tests)
        self.use_matmul_votes = use_matmul_votes
        # ragged window packing: windows bucket by their own size,
        # groups greedy-fill a fixed lane arena — the cudabatch
        # batch-fill design (SURVEY §L3). A mesh takes the
        # single-geometry padded path whatever this says; False selects
        # it off-mesh (tests)
        self.use_ragged = use_ragged
        # device ceiling (companion to the K_INS/CH caps in the module
        # docstring): the insertion accumulator is exact on both paths
        # (u32-pair scatter / int32 matmul), so the binding limit is the
        # COLUMN vote reduction. On the f32 one-hot matmul per-column
        # weighted sums must stay < 2^24 — a vote carries at most
        # 93 * 88 (phred x alpha) plus the backbone's 64 * 60, making
        # 2047 the largest exact depth (2047 * 8184 + 3840 < 2^24). The
        # int8-limb matmul accumulates in int32, but the sums are still
        # handed to the f32 consensus kernel; at the DEFAULT scores
        # alpha is the constant 64, every weight (and the pre-scaled
        # backbone votes) is a multiple of 64, and multiples of 64 are
        # f32-exact up to 2^30 — 65535 * 5952 stays under that, so the
        # cap lifts to a conservative 65535. Custom -m/-x/-g scores make
        # alpha vary in [1, 88], sums are no longer 64-aligned, and the
        # f32 handoff re-binds the cap at 2047. Deeper requests clamp
        # rather than silently losing integer exactness.
        default_scores = (match, mismatch, gap) == (
            DEFAULT_MATCH, DEFAULT_MISMATCH, DEFAULT_GAP)
        self.max_depth = min(max_depth,
                             65535 if (self.use_matmul_votes
                                       and default_scores) else 2047)
        self.band = band
        self.rounds = rounds
        self.mesh = mesh
        # The pileup engine votes by base quality rather than alignment
        # score, so the reference's POA scores map onto the emission
        # thresholds instead of the DP (cudapoa consumes them directly,
        # ``src/cuda/cudabatch.cpp:54-62``): a stronger gap penalty makes
        # indels proportionally harder to emit — identity at the default
        # ``-g -4``, so the recorded goldens are untouched. ``-m/-x`` have
        # no quality-weighted analog; flag the divergence rather than
        # silently ignoring them.
        # indel-emission scale: gap cost *relative to the match reward*
        # (g=-8 with m=8 makes gaps relatively cheaper than the default
        # g=-4/m=3, not costlier), identity at the reference defaults
        scale = ((max(abs(gap), 1) * DEFAULT_MATCH)
                 / (abs(DEFAULT_GAP) * max(match, 1)))
        self.ins_theta = min(ins_theta * scale, 0.95)
        # cap mirrors the ins_theta cap: past it a stronger -g would make
        # column deletion effectively impossible while insertions saturate
        # at 0.95, an asymmetry users tuning -g don't expect (ADVICE r3)
        self.del_beta = min(del_beta * scale, 2.5)
        # -m/-x/-g reach the device engine as score-weighted voting
        # (alpha per layer, _accumulate_votes) on top of the -g emission
        # scaling; identity at the reference defaults
        self.scores = (match, mismatch, gap)
        # Batch count (reference -c N, cudapolisher.cpp:215-228): windows
        # are LPT-split into N groups, every group's whole refinement loop
        # is dispatched before the first result is fetched (JAX async
        # dispatch), so host packing overlaps device compute.
        self.num_batches = max(1, num_batches)
        # SWAR-packed forward DP (int16x2 score lanes); bit-identical
        # outputs, guarded per geometry by swar.swar_fits and globally
        # by the swar_ok probe — the knob exists for A/B measurement
        self.use_swar = use_swar
        # memory backpressure (round 12): the shard runner's
        # degradation ladder halves the effective pair-arena/group
        # capacity on a device RESOURCE_EXHAUSTED and re-dispatches —
        # output bytes are invariant to grouping, only the per-launch
        # working set shrinks. 1 = full capacity; doubled per
        # reduce_capacity() call up to _MAX_CAPACITY_SCALE.
        self.capacity_scale = 1
        # sanitizer: per-engine shadow sampler for the refine loop (the
        # first SWAR group of every run is always checked) — the
        # consensus-side analog of TpuAligner._shadow
        self._shadow = sanitize.ShadowSampler()
        self._warmup = None
        # shapes already submitted for warm-up compilation: the
        # resident polishing service calls warmup_async per admitted
        # job (so a NEW geometry starts compiling while the job waits
        # in queue), and repeat geometries — the service's whole point
        # — must cost nothing, not a redundant background compile
        self._warmed_shapes: set = set()
        # wavefront_steps: executed (post-gating) DP anti-diagonal steps,
        # the honest numerator for utilization estimates;
        # lanes_occupied/lanes_total/groups/group_windows: real packing
        # efficiency of every dispatched pair arena (occupied lanes =
        # sum of real layer lengths, total = B x Lq per launch) — the
        # round-10 occupancy telemetry that replaces the coarse
        # consensus_vpu_util_est
        self.stats = {"device_windows": 0, "fallback_windows": 0,
                      "dropped_layers": 0, "sweep_truncated": 0,
                      "ins_overflow": 0, "passthrough": 0,
                      "stage_b_windows": 0, "wavefront_steps": 0,
                      "lanes_occupied": 0, "lanes_total": 0,
                      "groups": 0, "group_windows": 0}
        # per-window attribution of the ins_overflow counter (keyed by
        # result index): WHICH window's insertion density tripped the
        # uncapped-scatter fallback — kept out of ``stats`` so numeric
        # consumers (the run report, stat-reset loops) stay untouched
        self.ins_overflow_by_window: dict = {}

    # the floor keeps groups large enough that per-group fixed costs
    # (fetch round trips) stay amortized: 16x reduction is already a
    # 94% working-set cut — past that the device is simply too small
    _MAX_CAPACITY_SCALE = 16

    @property
    def group_pairs_cap(self) -> int:
        """Pairs per device group under the current backpressure scale
        (``MAX_GROUP_PAIRS`` at scale 1)."""
        return max(2048, MAX_GROUP_PAIRS // self.capacity_scale)

    @property
    def arena_lanes_cap(self) -> int:
        """Ragged lane-arena budget under the current backpressure
        scale (``ARENA_LANES`` at scale 1)."""
        return max(2048 * 1024, ARENA_LANES // self.capacity_scale)

    def cap_pairs_for(self, L: int, band: int) -> int:
        """Greedy-fill pair budget for one ragged bucket: the lane
        arena (fixed, until OOM backpressure halves it) divided by the
        bucket's lane width — short windows pack more pairs per group,
        the whole point of ragged packing."""
        return max(2048, min(self.arena_lanes_cap // (L + band),
                             4 * self.group_pairs_cap))

    def first_stage_rounds(self, B: int) -> int:
        """THE two-stage rule: the rounds a first-stage group of ``B``
        padded pair rows runs. ``STAGE_A_ROUNDS`` where the rounds it
        skips pay for the round trip of a second stage (fetch, repack,
        upload, dispatch) — by the group's own size, whatever else its
        bucket holds — and the whole budget otherwise. A group that
        runs fewer than ``self.rounds`` collects its survivors. Shared
        by the ragged stream, the padded path and
        :meth:`_warmup_shapes`."""
        if self.rounds > STAGE_A_ROUNDS and B >= TWO_STAGE_MIN_PAIRS:
            return STAGE_A_ROUNDS
        return self.rounds

    def _first_stage(self, launch) -> None:
        """Give a packed first-stage group its schedule (``rounds``,
        ``collect``) and count it."""
        launch["rounds"] = self.first_stage_rounds(launch["B"])
        launch["collect"] = launch["rounds"] < self.rounds
        metrics.inc("consensus.first_stage_groups")
        metrics.inc("consensus.stage_a_groups", int(launch["collect"]))

    @staticmethod
    def bucket_L_for(L_req: int) -> Optional[int]:
        """THE power-of-two lane-width rule: the smallest pow2 bucket
        >= ``L_req`` (floor 256), capped at the device insertion-payload
        ceiling; None when it cannot fit.  Shared by the ragged
        stream's per-window bucketing (``_ConsensusStream._bucket_L``)
        and :meth:`_warmup_shapes`, so the dispatch and warm-up
        geometries derive from one formula (the ``warmup-coverage``
        lint checks exactly this)."""
        max_dev_L = (1 << 18) // (K_INS * CH) - GROW
        L = 256
        while L < L_req:
            if L >= max_dev_L:
                return None
            L = min(L * 2, max_dev_L)
        return L

    def reduce_capacity(self) -> bool:
        """Halve the pair-arena/group capacity (device-OOM
        backpressure). Returns False once at the floor — the caller's
        ladder then falls through to the CPU engines. Grouping never
        changes output bytes (windows are independent; the vote
        accumulation is exact at any batch size), so a reduced
        re-dispatch is byte-identical, just smaller."""
        if self.capacity_scale >= self._MAX_CAPACITY_SCALE:
            return False
        self.capacity_scale *= 2
        metrics.set_gauge("consensus.capacity_scale", self.capacity_scale)
        metrics.inc("faults.backpressure_halvings")
        return True

    def pack_metrics(self) -> dict:
        """Derived occupancy view of :attr:`stats` (zeros before any
        launch): ``pack_efficiency`` = occupied / total pair-arena
        lanes, ``pad_fraction`` = 1 - efficiency, ``windows_per_group``
        = mean windows per dispatched group."""
        tot = self.stats.get("lanes_total", 0)
        eff = self.stats.get("lanes_occupied", 0) / tot if tot else 0.0
        grp = self.stats.get("groups", 0)
        wpg = self.stats.get("group_windows", 0) / grp if grp else 0.0
        return {"pack_efficiency": round(eff, 4),
                "pad_fraction": round(1.0 - eff, 4) if tot else 0.0,
                "windows_per_group": round(wpg, 2),
                "groups": grp}

    # -------------------------------------------------------------- public

    def run(self, windows, trim: bool, progress=None) -> List[bool]:
        """Consensus over a window batch. Default routing is the ragged
        packer (:meth:`stream` — per-size-bucket geometry with greedy
        arena fill); ``use_ragged=False`` or a device mesh take the
        padded single-geometry path. Outputs are bit-identical across
        the two (windows are independent and the vote accumulation is
        exact at any grouping)."""
        if self.use_ragged and self.mesh is None:
            sess = self.stream(trim)
            sess.feed(windows)
            return sess.finish(progress=progress)
        before = dict(self.stats)
        out = self._run_padded(windows, trim, progress)
        self._warn_dropped(before)
        return out

    def stream(self, trim: bool, band_hint: int = 0):
        """Open a ragged streaming session (round 10): ``feed()`` packs
        and **asynchronously dispatches** full groups as window ranges
        arrive — host packing/fetch/emit overlaps device compute through
        the in-flight launch pipeline — and ``finish()`` drains, runs
        stage B and the CPU fallback, and returns the flags for every
        fed window in feed order. The ``Polisher.run()`` bounded queue
        feeds this directly, so the device never idles on the host
        between window ranges (double-buffered dispatch). Returns None
        when the ragged packer is unavailable (mesh runs,
        ``use_ragged=False``) —
        callers then fall back to per-batch :meth:`run` calls.

        ``band_hint``: optional backbone-length upper bound used to
        freeze the alignment band before the full window set has been
        fed (the padded path derives band from the global live maximum;
        a streaming caller that knows its window length passes it here
        so both surfaces pick the same band)."""
        if not self.use_ragged or self.mesh is not None:
            return None
        return _ConsensusStream(self, trim, band_hint)

    def _warn_dropped(self, before: dict) -> None:
        """One-line per-run visibility for dropped layers: depth-cap
        drops and rejected layer alignments both land in the counter."""
        d = self.stats["dropped_layers"] - before.get("dropped_layers", 0)
        if d > 0:
            from ..utils.logger import warn
            warn(f"consensus: {d} layer alignments dropped this run "
                 f"(voting depth cap {self.max_depth} and/or rejected "
                 f"alignments) — see consensus_stats.dropped_layers")

    def _run_padded(self, windows, trim: bool, progress=None) -> List[bool]:
        results: List[Optional[bool]] = [None] * len(windows)
        works: List[_Work] = []
        for i, win in enumerate(windows):
            if win.layer_count + 1 < 3:
                win.consensus = win.backbone
                results[i] = False
                self.stats["passthrough"] += 1
            else:
                works.append((i, _Work(win, self.max_depth, self.stats)))

        live = [(i, w) for i, w in works if w.n_layers >= 2]
        for i, w in works:
            if w.n_layers < 2:
                results[i] = None  # CPU fallback

        if live:
            max_bb = max(len(w.backbone) for _, w in live)
            band, L, Lq, Lb = self._bucket_geometry(max_bb)
            self.stats["band"] = band
            # windows whose layers exceed the pair buffer (or backbones the
            # backbone buffer) go to the CPU fallback via results[i] None
            live = [(i, w) for i, w in live
                    if w.max_layer_len <= Lq and len(w.backbone) <= Lb]

        if live:
            # anti-diagonal sweep bound: longest real pair plus span-growth
            # slack (dead wavefronts past the last finish are pure waste;
            # a span that outgrows the slack drops that pair's votes for
            # the round, like a band escape)
            max_nm = max(
                int(np.max(w.lens + np.minimum(w.ends - w.begins + 65,
                                               Lb)))
                for _, w in live)
            max_n = max(w.max_layer_len for _, w in live)
            steps, Lq2 = self._sweep_geometry(Lq, max_nm, max_n)
            from ..parallel import partition_balanced
            total_pairs = sum(w.n_layers for _, w in live)
            n_groups = max(self.num_batches,
                           -(-total_pairs // self.group_pairs_cap))
            if n_groups == 1:
                groups = [list(live)]
            else:
                bins = partition_balanced([w.n_layers for _, w in live],
                                          n_groups)
                groups = [[live[i] for i in b] for b in bins if b]
            # bounded pipeline: at most inflight_cap+1 groups'
            # inputs/state live on device at once (launch group k+1,
            # then fetch the oldest once the cap is exceeded); the big
            # per-round intermediates exist only inside the single
            # executing program — the MAX_INFLIGHT_BYTES budget is the
            # analog of cudapoa's fixed per-batch memory
            # (cudapolisher.cpp:219-228), sized to amortize the
            # per-group host round trip rather than to fill device RAM
            total_units = len(groups) + 1
            self._last_total_units = total_units
            done_units = 0
            inflight = []
            # two-stage refinement: stage A runs the first STAGE_A_ROUNDS
            # at full group size; windows still unconverged after it are
            # re-packed (with their refined backbones and remapped spans)
            # into far smaller stage-B groups for the remaining rounds.
            # Which groups split is first_stage_rounds' to say, by their
            # size alone: on the chip a lone group of 32,768 rows that
            # ran its six rounds in one dispatch cost 0.58 s more than
            # the split (ledger, PR 43: four such groups a job, 9.26 s
            # of consensus programs against the one-shot job's 6.95; the
            # in-loop early exit waits for every window, and the vote's
            # routing does not shrink with convergence), so being alone
            # in a run is no reason.
            survivors: List = []
            # per-launch resident bytes: packed pair inputs (the qpw
            # uint16 lanes are 2*Lq bytes/pair — codes and weights
            # travel in ONE array; +24 covers n/bg/ed/win_of/real) PLUS
            # the per-window state and coalesced-fetch arrays each
            # un-fetched launch pins (bcodes u8 + covs/mat i32 +
            # bweights f32 ~ 13 bytes per backbone column, padded to
            # the worst group's power-of-two window count)
            max_wins = max(len(g) for g in groups)
            nWp_max = self._pow2_at_least(max_wins + 1)
            group_bytes = ((2 * Lq + 24) * self.group_pairs_cap
                           + 16 * Lb * nWp_max)
            inflight_cap = max(self.num_batches,
                               MAX_INFLIGHT_BYTES // max(group_bytes, 1))

            def finish(la):
                self._finish_group(
                    la, trim, results,
                    collect=survivors if la["collect"] else None)

            for g in groups:
                la = self._launch_group(g, Lq, Lb)
                la["geom"] = (Lq, Lb, steps, Lq2)
                la["band"] = band
                self._first_stage(la)
                self._rounds(la, Lq, Lb, steps, Lq2)
                done_units += 1
                if progress is not None:
                    # ticks show groups entering the device pipeline
                    # (dispatch is async; only fetches block — syncing
                    # mid-group would reintroduce the host round trips
                    # this engine exists to avoid)
                    progress(done_units, total_units)
                inflight.append(la)
                if len(inflight) > inflight_cap:
                    finish(inflight.pop(0))
            for la in inflight:
                finish(la)
            if survivors:
                self._run_stage_b(survivors, trim, results,
                                  Lq, Lb, steps, Lq2, band)

        cpu_idx = [i for i, r in enumerate(results) if r is None]
        if cpu_idx:
            self.stats["fallback_windows"] += len(cpu_idx)
            metrics.inc("consensus.fallback_windows", len(cpu_idx))
            if self.fallback is None:
                raise RuntimeError(
                    f"{len(cpu_idx)} windows rejected, no CPU fallback")
            flags = self.fallback.run([windows[i] for i in cpu_idx], trim)
            for i, f in zip(cpu_idx, flags):
                results[i] = f
        if progress is not None:
            # close the bar with the same denominator the in-loop ticks
            # used (falls back to a single unit when nothing was live)
            total_units = getattr(self, "_last_total_units", 1)
            progress(total_units, total_units)
        return [bool(r) for r in results]

    # ----------------------------------------------------------- geometry

    def _bucket_geometry(self, max_bb: int):
        """Static kernel geometry from the longest backbone — THE single
        source of truth shared by :meth:`run` and :meth:`warmup_async`
        (drift between them would silently waste the warm-up compile).

        The alignment band scales with the window length (cudapoa's
        banded width is proportional to its matrix size too): a fixed
        512-lane band caps acceptable per-layer edits at 256, which
        w>=1000 windows at ONT divergence routinely exceed — those
        layers' alignments were dropped wholesale, the r4 w=1000 quality
        cliff (device 2591 vs CPU 1289 with ~1.2k dropped alignments).
        Identity for <=512 bp windows, so every recorded w=500 golden is
        untouched. Device ceiling: the packed insertion payload holds
        addr << 13 in an int32, so Lb*K_INS*CH must fit 18 bits
        (Lb <= 8192); longer backbones take the CPU fallback like any
        other reject."""
        band = min(self.band * -(-max_bb // 512), 4096)
        max_dev_L = (1 << 18) // (K_INS * CH) - GROW
        L = max(256, min(-(-max_bb // 256) * 256, max_dev_L))
        Lq = L + band
        Lb = min(L + GROW, Lq)  # backbone buffer (span fit: Lb <= Lq)
        return band, L, Lq, Lb

    @staticmethod
    def _sweep_geometry(Lq: int, max_nm: int, max_n: int):
        """Sweep bound and vote-kernel query width, both multiples of
        128 (the Pallas kernels chunk/flush at 128-lane granularity and
        statically require it). Shared by :meth:`run` and
        :meth:`warmup_async` like :meth:`_bucket_geometry`."""
        steps = -(-min(-(-max_nm // 128) * 128, 2 * Lq) // 128) * 128
        Lq2 = min(Lq, -(-max_n // 128) * 128)
        return steps, Lq2

    # ------------------------------------------------------------- warm-up

    @staticmethod
    def _pow2_at_least(x: int) -> int:
        p = 1
        while p < max(1, x):
            p *= 2
        return p

    def _warmup_shapes(self, window_length: int, est_pairs: int,
                       est_windows: int, est_layer_len: int,
                       est_contigs: int):
        """The refinement-loop shapes a run is expected to dispatch, as
        ``(Lq, Lb, band, steps, Lq2, B, nWp, rounds)`` tuples — ONE
        source of truth consumed by :meth:`warmup_async`, derived with
        the same geometry rules :meth:`run` / :class:`_ConsensusStream`
        use."""
        band, L, Lq, Lb = self._bucket_geometry(window_length)
        depth = max(1.0, est_pairs / max(1, est_windows))
        shapes = []

        def add(L_b, pairs, wins):
            lq = L_b + band
            lb = min(L_b + GROW, lq)
            ell = min(est_layer_len or window_length + 64, lq)
            max_nm = ell + min(ell + 64, lb)
            steps, Lq2 = self._sweep_geometry(lq, max_nm, ell)
            B = self._pow2_at_least(pairs)
            shapes.append((lq, lb, band, steps, Lq2, B,
                           self._pow2_at_least(wins + 1),
                           self.first_stage_rounds(B)))

        if not self.use_ragged:
            cap = self.group_pairs_cap
            n_groups = max(self.num_batches, -(-est_pairs // cap))
            add(L, -(-est_pairs // n_groups), -(-est_windows // n_groups))
            return shapes

        # ragged stream geometry: windows bucket by their own
        # power-of-two lane width and groups greedy-fill the arena, so
        # the dominant bucket's FULL groups close just under
        # cap_pairs_for(L) and pad to pow2(cap) — est_pairs/n_groups
        # undershoots that shape whenever the estimate is not an exact
        # multiple of the cap, wasting the warm compile precisely on
        # big runs. A run smaller than one arena dispatches a single
        # group of everything — at the rounds its own size gives it, as
        # every group (first_stage_rounds): a shard of 31,150 pairs runs
        # stage A's two, and a warm-up at the full budget compiled a
        # program no group ran.
        max_dev_L = (1 << 18) // (K_INS * CH) - GROW
        # the dominant bucket width through THE shared pow2 rule (the
        # L_req is capped at the device ceiling, so this never rejects)
        Ld = self.bucket_L_for(min(window_length, max_dev_L))
        cap = self.cap_pairs_for(Ld, band)
        if est_pairs > cap:
            wins = min(est_windows, max(1, int(cap / depth)),
                       MAX_GROUP_WINDOWS)
            add(Ld, cap, wins)
        else:
            add(Ld, est_pairs, min(est_windows, MAX_GROUP_WINDOWS))
        # contig-tail windows (<= one per contig, shorter than the
        # window length) coalesce in the half-width bucket and flush as
        # one group at finish: a few rows, so the full budget at once
        if est_contigs > 0 and Ld > 256 and est_pairs > cap:
            # capped like any greedy-filled group: a fragmented assembly
            # (10^5 contigs) must not warm a multi-GB batch the stream
            # would never dispatch
            t_pairs = min(max(1, int(est_contigs * depth)),
                          self.cap_pairs_for(Ld // 2, band))
            add(Ld // 2, t_pairs, min(est_contigs, MAX_GROUP_WINDOWS))
        return shapes

    def warmup_async(self, window_length: int, est_pairs: int,
                     est_windows: int, est_layer_len: int = 0,
                     est_contigs: int = 0):
        """Background warm-up compilation of the expected refinement-loop
        shapes. The first consensus compile (~16 s) used to land inside
        ``polish()``; ``Polisher.initialize`` calls this on a thread
        while it aligns overlaps, so ``polish()`` starts hot.

        Derives the same static geometry :meth:`run` /
        :class:`_ConsensusStream` compute — for a ragged engine that is
        the power-of-two *bucket* shapes the stream will actually
        dispatch (the dominant bucket's greedy-filled full-group shape,
        plus the half-width contig-tail bucket when ``est_contigs`` is
        given), not the padded single geometry — and executes the jitted
        loop once per shape on zero state: ``win_real`` is all-false, so
        the device loop exits before round 1 and each shape costs
        exactly one compile (which the persistent XLA cache then also
        remembers across runs). Runs under the engine's pinned device
        (:meth:`_pinned`), so per-chip engines warm their own chip. A
        wrong estimate wastes a background compile and nothing else:
        run()'s own shapes still compile on first use. Returns the
        thread (for tests), or None when skipped (mesh runs, zero
        estimates, every derived shape already warmed — repeat calls
        with the same geometry are deliberately free, so the resident
        service can warm per admitted job)."""
        if self.mesh is not None or est_pairs <= 0:
            return None
        shapes = [s for s in self._warmup_shapes(
            window_length, est_pairs, est_windows, est_layer_len,
            est_contigs) if s not in self._warmed_shapes]
        if not shapes:
            return None
        self._warmed_shapes.update(shapes)

        def _compile_one(Lq, Lb, band, steps, Lq2, B, nWp, rounds):
            # the availability probes themselves compile and run
            # kernels, so they belong on this thread too — the whole
            # point is keeping the caller's critical path clear
            from .swar import swar_fits, swar_ok
            sw = self.use_swar and swar_fits(Lq) and swar_ok()
            use_pallas = self._use_pallas((Lq, band, steps, Lb, Lq2))
            if use_pallas:
                from .pallas_nw import pallas_swar_ok
                sw = sw and pallas_swar_ok()
            static = (jnp.zeros((B,), jnp.int32),
                      jnp.zeros((B, Lq), jnp.uint16),
                      jnp.full((B,), nWp - 1, jnp.int32),
                      jnp.zeros((B,), bool))
            state = (jnp.zeros((B,), jnp.int32),
                     jnp.zeros((B,), jnp.int32),
                     jnp.zeros((nWp, Lb), jnp.uint8),
                     jnp.zeros((nWp, Lb), jnp.float32),
                     jnp.zeros((nWp,), jnp.int32),
                     jnp.zeros((nWp, Lb), jnp.int32),
                     jnp.zeros((nWp,), bool),
                     jnp.zeros((nWp,), bool),
                     jnp.zeros((nWp,), bool),
                     jnp.zeros((1, 4 + nWp), jnp.int32))
            out = _refine_loop_packed(
                *static, *state, jnp.float32(self.ins_theta),
                jnp.float32(self.del_beta), rounds=rounds,
                n_windows=nWp, max_len=Lq, band=band, Lb=Lb,
                K=K_INS, steps=steps, use_pallas=use_pallas,
                use_swar=sw, Lq2=Lq2, scores=self.scores,
                matmul_votes=self.use_matmul_votes)
            device_time.submit(
                "warm", "_refine_loop_packed", out[9],
                _loop_geometry(Lq, Lb, band, steps, Lq2, B, nWp, rounds,
                               bool(sw)))
            jax.block_until_ready(out[10])

        # behind the warm-up before it, where a polisher that waits for
        # none (``final`` off) left one running: the last thread's end
        # is then every thread's, and drain_warmup waits for them all
        before = self._warmup

        def _compile():
            if before is not None:
                before.join()
            try:
                with self._pinned():
                    for shape in shapes:
                        _compile_one(*shape)
            except Exception as e:  # warm-up is an optimization, never fatal
                from ..utils.logger import log_swallowed
                log_swallowed("poa: background warm-up compile failed "
                              "(polish will compile on first use)", e)

        import threading
        # fire-and-forget by design: the warm-up is a droppable
        # optimization (its own except arm says so) — a daemon thread
        # killed at exit loses nothing but a speculative compile, and
        # the engine it warms outlives it
        # graftlint: disable=thread-lifecycle (droppable best-effort warm-up; daemon dies harmlessly at exit)
        self._warmup = threading.Thread(target=_compile, daemon=True,
                                        name="racon-tpu-warmup")
        self._warmup.start()
        return self._warmup

    def drain_warmup(self) -> None:
        """Wait for the background warm-up, if one is still compiling:
        the job that started it ends only when it has. Left running, its
        last compile lands in the process's NEXT job, which is then
        charged a compile it never asked for (PR 34: 2 of 7 runs of
        ``bact2m-auto30x`` on a warm cache, where one shape missed the
        cache and took the chip's compiler 42 s)."""
        if self._warmup is not None:
            self._warmup.join()

    # -------------------------------------------------------------- device

    def _launch_group(self, live, Lq, Lb, overrides=None, floor=(1, 1)):
        """Span-wrapped :meth:`_launch_group_impl` — the host-pack half
        of the consensus dispatch pipeline."""
        with self._pinned(), obs.span("poa.pack", windows=len(live)):
            return self._launch_group_impl(live, Lq, Lb, overrides, floor)

    def _rounds(self, launch, Lq, Lb, steps, Lq2=0) -> None:
        """Span-wrapped :meth:`_rounds_impl` — the async kernel dispatch
        of a group's whole refinement loop (and the ``consensus.dispatch``
        fault-injection site: a real device OOM surfaces here as a
        RESOURCE_EXHAUSTED, which is exactly what the injected one
        mimics)."""
        faults.check("consensus.dispatch")
        with self._pinned(), obs.span("poa.dispatch", pairs=launch["B"]):
            self._rounds_impl(launch, Lq, Lb, steps, Lq2)

    def _finish_group(self, launch, trim: bool, results,
                      collect=None) -> None:
        """Span-wrapped :meth:`_finish_group_impl` — the blocking fetch
        + decode half (a continued-in-place stage B nests under this
        span)."""
        with self._pinned(), obs.span("poa.fetch", windows=launch["nWp"]):
            self._finish_group_impl(launch, trim, results,
                                    collect=collect)

    def _run_stage_b(self, survivors, trim, results, Lq, Lb, steps,
                     Lq2, band) -> None:
        """Span-wrapped :meth:`_run_stage_b_impl`."""
        with self._pinned(), obs.span("poa.stage_b",
                                      windows=len(survivors)):
            self._run_stage_b_impl(survivors, trim, results, Lq, Lb,
                                   steps, Lq2, band)

    def _pack_shard(self, items, Lq, B, nWp, Lb, overrides=None):
        """Pack one shard's windows into fixed-shape pair/window arrays.

        ``items`` is a list of ``(result_index, _Work)``; pair rows beyond
        the shard's real pairs vote into the sink window ``nWp - 1``.
        ``overrides`` (stage-B repack) maps a result index to that
        window's fetched stage-A state ``(bcodes_row, blen, covs_row,
        ever, bg_per_layer, ed_per_layer)`` so the window resumes from
        its refined backbone and remapped spans instead of restarting.
        """
        n = np.ones(B, np.int32)
        bg = np.zeros(B, np.int32)
        ed = np.zeros(B, np.int32)
        win_of = np.full(B, nWp - 1, np.int32)  # padding -> sink window
        real = np.zeros(B, bool)

        counts = np.array([w.n_layers for _, w in items], np.int64)
        offs = np.zeros(len(items) + 1, np.int64)
        np.cumsum(counts, out=offs[1:])
        k = int(offs[-1])
        if k:
            # per-pair metadata straight from the works' flat arrays —
            # no per-layer Python loop in either storage mode
            lens = np.concatenate([w.lens for _, w in items])
            bb_len = np.repeat([len(w.backbone) for _, w in items], counts)
            n[:k] = lens
            bg[:k] = np.minimum(np.concatenate(
                [w.begins for _, w in items]), bb_len - 1)
            ed[:k] = np.minimum(np.concatenate(
                [w.ends for _, w in items]), bb_len - 1)
            win_of[:k] = np.repeat(np.arange(len(items)), counts)
            real[:k] = True
        # a leaf of poa.pack: the lane block's construction (a timer
        # only: device idle under it stays poa.pack's, contracts.py)
        with obs.span("poa.lanes", pairs=k):
            qpw = self._pack_lanes(items, offs, Lq, B)

        bcodes = np.zeros((nWp, Lb), np.uint8)
        bweights = np.zeros((nWp, Lb), np.float32)
        blen = np.zeros(nWp, np.int32)
        covs = np.zeros((nWp, Lb), np.int32)
        ever = np.zeros(nWp, bool)
        for wi, (_, w) in enumerate(items):
            bb = w.backbone
            bcodes[wi, :len(bb)] = _CODE_LUT[np.frombuffer(bb, np.uint8)]
            if w.bqual is not None:
                # x64: layer votes carry the q6 alpha scale (64 == 1.0),
                # so backbone votes are pre-scaled to compete at par
                bweights[wi, :len(bb)] = 64.0 * (
                    np.frombuffer(w.bqual, np.uint8).astype(np.float32)
                    - 33.0)
            blen[wi] = len(bb)

        if overrides:
            off = 0
            for wi, (ri, w) in enumerate(items):
                kw = w.n_layers
                st = overrides.get(ri)
                if st is not None:
                    st_bc, st_bl, st_cov, st_ever, st_bg, st_ed = st
                    bcodes[wi] = st_bc
                    blen[wi] = st_bl
                    covs[wi] = st_cov
                    ever[wi] = st_ever
                    if st_ever:
                        # a refined backbone carries no phred
                        bweights[wi] = 0.0
                    bg[off:off + kw] = st_bg
                    ed[off:off + kw] = st_ed
                off += kw

        return (n, qpw, win_of, real, bg, ed), \
               (bcodes, bweights, blen, covs, ever)

    def _pack_lanes(self, items, offs, Lq, B):
        """One shard's ``[B, Lq]`` lane block, written once: ``weight <<
        3 | code`` per base (codes 3 bits, phred weights <= 93 in 7) —
        codes and weights travel as ONE uint16 array, the format both
        vote emitters consume directly. Window ``wi``'s layers land in
        rows ``offs[wi]:offs[wi + 1]``; rows and lanes beyond stay 0."""
        qpw = np.zeros((B, Lq), np.uint16)
        # columnar windows: one row copy per layer, straight into this
        # block, lands every layer's finished uint16 lanes (codes +
        # phred weights were packed once at store build)
        by_store = {}
        legacy = []
        for wi, (_, w) in enumerate(items):
            if not w.n_layers:
                continue
            if w.store is not None:
                by_store.setdefault(id(w.store), []).append(wi)
            else:
                legacy.append(wi)
        for wis in by_store.values():
            store = items[wis[0]][1].store
            rows = np.concatenate([items[wi][1].rows for wi in wis])
            dest = np.concatenate(
                [np.arange(offs[wi], offs[wi + 1]) for wi in wis])
            metrics.inc("consensus.lane_rows", len(rows))
            store.gather_qpw(rows, Lq, out=qpw, dest=dest)
            if native.available():
                metrics.inc("consensus.lane_rows_copied", len(rows))

        # hand-built windows (tests): the round-7 join-and-
        # LUT path over just their layers
        if legacy:
            lay = [(s, q) for wi in legacy
                   for s, q, _, _ in items[wi][1].layers]
            cat = np.frombuffer(b"".join(s for s, _ in lay), np.uint8)
            codes_cat = _CODE_LUT[cat]
            llens = np.array([len(s) for s, _ in lay], np.int64)
            starts = np.concatenate(([0], np.cumsum(llens)[:-1]))
            pos = np.arange(Lq)[None, :]
            valid = pos < llens[:, None]
            src = starts[:, None] + np.minimum(pos, llens[:, None] - 1)
            qual_cat = np.frombuffer(
                b"".join((q if q is not None else b"\x22" * len(s))
                         for s, q in lay), np.uint8)
            # integral weights: phred-33 (clipped at 0 — a quality
            # byte below '!' would otherwise wrap) or 1 for
            # no-quality
            weights = np.maximum(qual_cat[src].astype(np.int16) - 33, 0)
            has_q = np.array([q is not None for _, q in lay])
            weights = np.where(has_q[:, None], weights, 1)
            dest = np.concatenate(
                [np.arange(offs[wi], offs[wi + 1]) for wi in legacy])
            qpw[dest] = np.where(
                valid,
                (weights.astype(np.uint16) << 3) | codes_cat[src],
                0).astype(np.uint16)
        return qpw

    def _launch_group_impl(self, live, Lq, Lb, overrides=None,
                           floor=(1, 1)):
        """Pack one window group (per-mesh-shard when a mesh is set — pairs
        of a window never cross shards, so votes stay shard-local) into the
        device-resident refinement state. ``overrides`` carries fetched
        stage-A state for a stage-B repack (see :meth:`_pack_shard`), and
        ``floor`` that repack's smallest ``(B, nWp)``."""
        from ..parallel import mesh_size, partition_balanced
        # graftlint: disable=warmup-coverage (mesh size is fixed at engine construction; warm-up runs on the same engine so its shapes see the same nd)
        nd = mesh_size(self.mesh)
        if nd == 1:
            shards = [list(live)]
        else:
            bins = partition_balanced([w.n_layers for _, w in live], nd)
            shards = [[live[i] for i in b] for b in bins]

        max_pairs = max(sum(w.n_layers for _, w in sh) for sh in shards)
        max_wins = max(len(sh) for sh in shards)
        # pow2 batch/window-count padding through the same helper the
        # warm-up derivation uses (warmup-coverage keeps them shared)
        B = max(self._pow2_at_least(max_pairs), floor[0])
        nWp = max(self._pow2_at_least(max_wins + 1), floor[1])

        packs = [self._pack_shard(sh, Lq, B, nWp, Lb, overrides)
                 for sh in shards]
        # one shard (every run without a mesh): the shard's arrays ARE
        # the group's, handed to the put as they were written
        if nd == 1:
            pair_np, win_np = list(packs[0][0]), list(packs[0][1])
        else:
            pair_np = [np.concatenate([p[0][a] for p in packs])
                       for a in range(6)]
            win_np = [np.concatenate([p[1][a] for p in packs])
                      for a in range(5)]
        # occupancy telemetry (round 10): real lane occupancy of this
        # launch's pair arena — occupied = sum of real layer lengths,
        # total = padded rows x the bucket's lane width
        occupied = int(pair_np[0][pair_np[3]].sum())
        lanes = int(pair_np[0].shape[0]) * Lq
        self.stats["lanes_occupied"] += occupied
        self.stats["lanes_total"] += lanes
        self.stats["groups"] += 1
        self.stats["group_windows"] += len(live)
        # registry mirror: the heartbeat / run report read occupancy
        # from the one process-wide registry, not this engine's dict
        metrics.inc("consensus.lanes_occupied", occupied)
        metrics.inc("consensus.lanes_total", lanes)
        metrics.inc("consensus.groups")
        metrics.inc("consensus.group_windows", len(live))
        # single-host: plain device puts; multi-host: every process packs
        # the (deterministic) full arrays and materializes only its
        # addressable shards of the global array
        from ..parallel import to_global
        put = ((lambda a: to_global(self.mesh, a)) if self.mesh is not None
               else jnp.asarray)
        # the other leaf of poa.pack: the host->device puts
        with obs.span("poa.put", windows=len(live)):
            state, static = self._put_group(put, pair_np, win_np, nd, nWp)
        device_time.submit("h2d", "poa.put", state[-1])
        return {"shards": shards, "static": static, "state": state,
                "nWp": nWp, "nd": nd, "B": B}

    def _put_group(self, put, pair_np, win_np, nd, nWp):
        """The packed group's arrays on the device: ``(state, static)``."""
        static = tuple(put(a) for a in pair_np[:4])  # n qpw win_of real
        bg, ed = (put(pair_np[4]), put(pair_np[5]))
        bcodes, bweights, blen, covs, ever = (put(a) for a in win_np)
        zput = (lambda a: put(np.asarray(a)))
        frozen = zput(np.zeros(nd * nWp, bool))
        conv = zput(np.zeros(nd * nWp, bool))
        # telemetry row per shard: [dropped, sweep-truncated, ins-overflow,
        # executed wavefront steps, then nWp per-window overflow tallies]
        dropped = zput(np.zeros((nd, 4 + nWp), np.int32))
        state = [bg, ed, bcodes, bweights, blen, covs, ever, frozen, conv,
                 dropped]
        return state, static

    def _rounds_impl(self, launch, Lq, Lb, steps, Lq2=0) -> None:
        """Dispatch a group's full refinement loop (no host sync).

        The kernel family is the platform's (``_use_pallas``): on the
        chip a Mosaic kernel that does not compile or run for this
        geometry fails the run — it is never re-dispatched to the XLA
        kernels (``tests/test_chip_compile.py`` guards the production
        geometries)."""
        from .swar import swar_fits, swar_ok
        sw = self.use_swar and swar_fits(Lq) and swar_ok()
        if self.use_swar and not swar_fits(Lq):
            # SWAR -> int32 re-dispatch (geometry outgrew the packed
            # lanes' overflow headroom) — counted like the aligner's
            metrics.inc("consensus.swar_guard_int32")
        use_pallas = self._use_pallas(
            (Lq, launch.get("band", self.band), steps, Lb, Lq2))
        if use_pallas:
            from .pallas_nw import pallas_swar_ok
            sw = sw and pallas_swar_ok()
        # which kernel family ran (every dispatch on the chip, none off
        # it) — stage-B and continued-in-place dispatches count too
        metrics.inc("consensus.pallas_groups", int(use_pallas))
        self._dispatch_rounds(launch, Lq, Lb, steps, Lq2, use_pallas, sw)

    _STATE_NAMES = ("bg", "ed", "bcodes", "bweights", "blen", "covs",
                    "ever", "frozen", "conv", "dropped")

    def _dispatch_rounds(self, launch, Lq, Lb, steps, Lq2,
                         use_pallas, use_swar=False) -> None:
        pre_state = launch["state"]
        out = self._dispatch_loop(launch, pre_state, Lq, Lb, steps, Lq2,
                                  use_pallas, use_swar)
        # the small telemetry rows, never the lane blocks
        device_time.submit(
            "exec", "_refine_loop_packed" if launch["nd"] == 1
            else "sharded_refine_loop", out[9],
            _loop_geometry(Lq, Lb, launch.get("band", self.band), steps,
                           Lq2, launch["B"], launch["nWp"],
                           launch.get("rounds", self.rounds),
                           bool(use_swar)))
        launch["state"] = list(out[:10])
        if launch["nd"] == 1:
            launch["fetch2"] = out[10:12]
        if use_swar and self._shadow.should_shadow():
            # int32 shadow execution of the WHOLE refine loop from the
            # same pre-round state (the packed forward DP is the only
            # difference — its bit-exactness contract makes every output
            # comparable, telemetry included). Sampled per group, so the
            # sanitizer's cost stays bounded on long runs.
            shadow = self._dispatch_loop(launch, pre_state, Lq, Lb, steps,
                                         Lq2, use_pallas, False)
            from ..parallel import fetch_global
            sanitize.shadow_compare(
                fetch_global(list(out[:10])),
                fetch_global(list(shadow[:10])),
                self._STATE_NAMES,
                f"consensus SWAR group (Lq={Lq}, "
                f"band={launch.get('band', self.band)}, steps={steps})")

    def _dispatch_loop(self, launch, state, Lq, Lb, steps, Lq2,
                       use_pallas, use_swar):
        """One full refinement-loop dispatch from an explicit state (the
        shadow path re-runs the identical launch with ``use_swar`` off)."""
        static = launch["static"]
        rounds = launch.get("rounds", self.rounds)
        band = launch.get("band", self.band)
        theta = jnp.float32(self.ins_theta)
        beta = jnp.float32(self.del_beta)
        if launch["nd"] == 1:
            # single execution: rounds + the coalesced-fetch packing
            # (single-device only: the packed concat would force
            # cross-shard gathers under a mesh)
            return _refine_loop_packed(
                *static, *state, theta, beta, rounds=rounds,
                n_windows=launch["nWp"], max_len=Lq, band=band,
                Lb=Lb, K=K_INS, steps=steps, use_pallas=use_pallas,
                use_swar=use_swar, Lq2=Lq2, scores=self.scores,
                matmul_votes=self.use_matmul_votes)
        from ..parallel import sharded_refine_loop
        return sharded_refine_loop(
            self.mesh, static, state, theta, beta, rounds=rounds,
            n_windows_local=launch["nWp"], max_len=Lq, band=band,
            Lb=Lb, K=K_INS, steps=steps, use_pallas=use_pallas,
            use_swar=use_swar, Lq2=Lq2, scores=self.scores,
            matmul_votes=self.use_matmul_votes)

    def _run_stage_b_impl(self, survivors, trim, results, Lq, Lb, steps,
                          Lq2, band) -> None:
        """Remaining rounds for the stage-A stragglers, re-packed small.

        ``survivors`` is ``[(result_index, work, fetched_state,
        (B, nWp) of its stage-A group), ...]``
        collected by :meth:`_finish_group` across ALL stage-A groups, so
        the handful of unconverged windows of a big run coalesce into one
        (or few) groups — B and n_windows shrink by the convergence
        factor, at most ``STAGE_B_MAX_SHRINK`` times from the largest
        stage-A group's, while rounds 4+ compute the identical
        per-window fixed points (windows are independent; the vote
        accumulation is exact integer arithmetic at any batch size)."""
        rb = self.rounds - STAGE_A_ROUNDS
        live = [(i, w) for i, w, _, _ in survivors]
        overrides = {i: st for i, _, st, _ in survivors}
        floor = tuple(max(1, max(shape[a] for *_, shape in survivors)
                          // STAGE_B_MAX_SHRINK) for a in (0, 1))
        self.stats["stage_b_windows"] += len(live)
        total_pairs = sum(w.n_layers for _, w in live)
        n_groups = max(1, -(-total_pairs // self.group_pairs_cap))
        if n_groups == 1:
            groups = [live]
        else:
            from ..parallel import partition_balanced
            bins = partition_balanced([w.n_layers for _, w in live],
                                      n_groups)
            groups = [[live[i] for i in b] for b in bins if b]
        inflight = []
        for g in groups:
            la = self._launch_group(g, Lq, Lb, overrides=overrides,
                                    floor=floor)
            la["geom"] = (Lq, Lb, steps, Lq2)
            la["band"] = band
            la["rounds"] = rb
            self._rounds(la, Lq, Lb, steps, Lq2)
            inflight.append(la)
            if len(inflight) > self.num_batches:
                self._finish_group(inflight.pop(0), trim, results)
        for la in inflight:
            self._finish_group(la, trim, results)

    def _finish_group_impl(self, launch, trim: bool, results,
                           collect=None) -> None:
        """One host fetch per group; decode consensus bytes + trim.

        With ``collect`` (a list — stage A of a two-stage run), windows
        that are neither converged nor frozen are NOT decoded: their
        fetched state is appended to ``collect`` for the stage-B repack
        and their result stays pending.

        JAX dispatch is async, so a kernel's *runtime* fault surfaces
        here at the fetch — and fails the run (no XLA re-dispatch)."""
        shards, nWp = launch["shards"], launch["nWp"]
        # single-device groups fetch TWO coalesced arrays (_fetch_pack —
        # per-transfer latency dominates the bytes); mesh groups fetch
        # per array (bweights always stays on device)
        from ..parallel import fetch_global
        if "fetch2" in launch:
            wanted = list(launch["fetch2"])
        else:
            (bg_d, ed_d, bcodes, _, blen, covs, ever, frozen, conv,
             dropped) = launch["state"]
            wanted = [bcodes, blen, covs, ever, dropped]
            if collect is not None:  # straggler-resume state
                wanted += [frozen, conv, bg_d, ed_d]
        # the three leaves of poa.fetch: waiting for the device and
        # nothing else, the device->host copy, the host decode
        leaf = dict(windows=nWp)
        with obs.span("poa.wait", **leaf):
            jax.block_until_ready(wanted)
        with obs.span("poa.get", **leaf):
            fetched = fetch_global(wanted)
        with obs.span("poa.decode", **leaf):
            host = self._unpack_fetch(launch, fetched, collect)
            resume = collect is not None and self._mostly_unconverged(
                launch, host)
        if resume:
            # a mostly-unconverged group (noisy data rarely reaches an
            # exact fixed point) continues its remaining rounds on the
            # state already resident on device — no repack, no
            # re-upload; its own dispatch and fetch spans follow
            Lq, Lb, steps, Lq2 = launch["geom"]
            launch["rounds"] = self.rounds - STAGE_A_ROUNDS
            self._rounds(launch, Lq, Lb, steps, Lq2)
            self._finish_group(launch, trim, results, collect=None)
            return
        with obs.span("poa.decode", **leaf):
            self._decode_group(launch, host, trim, results, collect)

    def _unpack_fetch(self, launch, fetched, collect) -> dict:
        """The fetched arrays by name (``frozen`` / ``conv`` / ``bg`` /
        ``ed`` only where they were fetched)."""
        nWp = launch["nWp"]
        host = {}
        if "fetch2" in launch:
            mat, meta = fetched
            nWr = launch["nd"] * nWp
            ndt = launch["nd"] * (4 + nWp)
            B_all = launch["nd"] * launch["B"]
            host["bcodes"] = (mat & 7).astype(np.uint8)
            host["covs"] = mat >> 3
            offs = np.cumsum([nWr, nWr, nWr, nWr, ndt, B_all])
            (host["blen"], ever, host["frozen"], host["conv"], dropped,
             host["bg"], host["ed"]) = np.split(meta, offs)
            host["ever"] = ever.astype(bool)
            host["dropped"] = dropped.reshape(launch["nd"], 4 + nWp)
        else:
            (host["bcodes"], host["blen"], host["covs"], host["ever"],
             host["dropped"]) = fetched[:5]
            if collect is not None:
                (host["frozen"], host["conv"], host["bg"],
                 host["ed"]) = fetched[5:]
        from .. import sanitize
        if sanitize.enabled():
            sanitize.check_consensus_canaries(
                host["bcodes"], host["blen"], host["covs"],
                Lb=launch["geom"][1],
                context=f"consensus group (nWp={nWp})")
        return host

    def _mostly_unconverged(self, launch, host) -> bool:
        """Stage A's decision point: repack the stragglers only when
        few survive. Counts what stage A left, the one place its
        ``conv`` / ``frozen`` are walked for it."""
        shards, nWp = launch["shards"], launch["nWp"]
        done = host["conv"].astype(bool) | host["frozen"].astype(bool)
        n_real = sum(len(sh) for sh in shards)
        n_surv = sum(len(sh) - int(done[s * nWp:s * nWp + len(sh)].sum())
                     for s, sh in enumerate(shards))
        metrics.inc("consensus.stage_a_windows", n_real)
        metrics.inc("consensus.stage_a_survivors", n_surv)
        return n_surv > STAGE_B_MAX_SURVIVOR_FRAC * n_real

    def _decode_group(self, launch, host, trim: bool, results,
                      collect) -> None:
        """Consensus bytes + trim of a fetched group; stage A's
        stragglers go to ``collect`` undecoded."""
        shards, nWp = launch["shards"], launch["nWp"]
        bcodes, blen, covs = host["bcodes"], host["blen"], host["covs"]
        ever, dropped = host["ever"], host["dropped"]
        if collect is not None:
            frozen_h, conv_h = host["frozen"], host["conv"]
            bg_h, ed_h = host["bg"], host["ed"]
        self.stats["dropped_layers"] += int(dropped[:, 0].sum())
        self.stats["sweep_truncated"] += int(dropped[:, 1].sum())
        self.stats["ins_overflow"] += int(dropped[:, 2].sum())
        self.stats["wavefront_steps"] += int(dropped[:, 3].sum())
        metrics.inc("consensus.dropped_layers", int(dropped[:, 0].sum()))
        metrics.inc("consensus.sweep_truncated", int(dropped[:, 1].sum()))
        metrics.inc("consensus.ins_overflow", int(dropped[:, 2].sum()))
        metrics.inc("consensus.wavefront_steps", int(dropped[:, 3].sum()))
        # columns [4:] attribute the overflow counter to shard-local
        # window rows (accumulated across this launch's rounds)
        ovf_tail = dropped[:, 4:]
        B = launch["B"]
        for s, sh in enumerate(shards):
            off = 0  # pair-row offset within this shard's pack
            for wi, (i, w) in enumerate(sh):
                row = s * nWp + wi
                ovf = int(ovf_tail[s, wi])
                if ovf:
                    self.ins_overflow_by_window[i] = \
                        self.ins_overflow_by_window.get(i, 0) + ovf
                    metrics.inc("consensus.ins_overflow_windows")
                kw = w.n_layers
                p0 = s * B + off
                off += kw
                if (collect is not None and not conv_h[row]
                        and not frozen_h[row]):
                    collect.append((i, w, (
                        bcodes[row].copy(), int(blen[row]),
                        covs[row].copy(), bool(ever[row]),
                        bg_h[p0:p0 + kw].copy(), ed_h[p0:p0 + kw].copy()),
                        (B, nWp)))
                    continue
                if not ever[row]:
                    results[i] = None  # no successful round -> CPU fallback
                    continue
                bl = int(blen[row])
                consensus = _BYTE_LUT[bcodes[row, :bl]].tobytes()
                if w.win.type == WindowType.TGS and trim:
                    # threshold uses the *voted* depth: layers beyond
                    # max_depth never vote, so counting them would make
                    # trimming a no-op on windows deeper than ~2x max_depth
                    avg_cov = min(w.n_seqs - 1, self.max_depth) // 2
                    good = np.flatnonzero(covs[row, :bl] >= avg_cov)
                    if len(good) and good[0] < good[-1]:
                        consensus = consensus[good[0]:good[-1] + 1]
                w.win.consensus = consensus
                results[i] = True
                self.stats["device_windows"] += 1
