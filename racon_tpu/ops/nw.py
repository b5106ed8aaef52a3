"""Batched banded Needleman-Wunsch on TPU (cudaaligner-equivalent).

Design (TPU-first, not a CUDA port):

- pairs are bucketed by padded length and packed into fixed-shape uint8
  batches (struct-of-arrays), so XLA compiles one kernel per bucket shape;
- the O(n*m) DP runs on device as a banded anti-diagonal wavefront:
  ``vmap`` over the batch, ``lax.scan`` over wavefronts ``a = i + j``;
  every data dependency is a static +-1 lane shift and character loads are
  contiguous slices, so each step is pure VPU elementwise work (see
  ``_nw_wavefront_kernel`` for the coordinate frame);
- the kernel emits 2-bit direction codes packed 4-per-byte into HBM;
- the O(n+m) traceback also runs on device (``_traceback_kernel``, a
  vmapped pointer chase) so the direction matrix never crosses the slow
  host link; only per-step op codes (~2 bytes/base) are fetched;
- pairs that exceed the largest bucket or whose optimum cannot be proven
  inside the band get per-pair status flags and are re-routed to the host
  aligner — the same reject contract as the reference's
  ``StatusType::exceeded_max_length`` / ``exceeded_max_alignment_difference``
  (``src/cuda/cudaaligner.cpp:64-72``).

Reference call-site parity: replaces edlib/cudaaligner behind
``Polisher.find_overlap_breaking_points`` (``src/cuda/cudapolisher.cpp:86-200``).
"""

from __future__ import annotations

import functools
from typing import List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

# (max query length, band width). Band covers error rates up to ~W/(2L).
BUCKETS: Tuple[Tuple[int, int], ...] = (
    (256, 128),
    (1024, 384),
    (4096, 1024),
    (8192, 2048),
    (16384, 4096),
    (16384, 8192),
)
# Expected divergence used to pick the initial band (escalation corrects
# underestimates; ONT reads of the reference's era run 15-30%).
TYPICAL_DIVERGENCE = 0.25
# Adaptive band-ladder rungs (round 17): a pair's starting band is seeded
# from its overlap's estimated divergence, quantized to this 1.5x-step
# geometric ladder and capped at the pair's bucket band (the terminal
# rung, so the accept/reject SET is identical to the fixed-band path's —
# part of the byte-identity contract). DP work is linear in band, so a
# pair accepted two rungs down sheds most of its wavefront lanes;
# escapees re-dispatch batched at the rung >= 2x their failed band (the
# reference host's band doubling, but batched — cudaaligner sizes
# per-alignment work from each pair's own length/band the same way,
# src/cuda/cudaaligner.cpp:39-44). Every rung keeps the kernels' static
# constraints (band % 8 == 0, band/2 even); each distinct rung is one
# extra compile per bucket, remembered by the persistent XLA cache.
BAND_RUNGS = (64, 96, 128, 192, 256, 384, 512, 768, 1024, 1536, 2048,
              3072, 4096)
# The adaptive half of the ladder: seeds trust the run's OBSERVED
# clean-walk score divergence once this many pairs have resolved — the
# overlap filter's span-asymmetry error only sees net indels, so a
# substitution-heavy run would otherwise seed low and escape every pair.
ADAPT_MIN_PAIRS = 256
# Cold-start probe batch: the ragged stream seeds/dispatches/fetches
# this many leading pairs FIRST (one pipeline bubble), so every later
# seed uses observed divergence rather than the blind span proxy.
ALIGN_PROBE_PAIRS = 1024
# Bound on pairs per device chunk: the ragged packer's memory-budget cap
# can reach 6 figures for short-pair classes, but each pair also pins a
# transient host span copy until its chunk is fetched — the same
# O(slice) contract the polisher's 64k overlap slices enforce.
MAX_CHUNK_PAIRS = 65536
# Companion bound on the stream's in-flight PAIRS: short-pair chunks are
# tiny in direction-matrix bytes (the budget that normally forces
# fetches), so without this a 10M-overlap short-read run would pin
# millions of unresolved span copies before the byte budget ever bit.
MAX_INFLIGHT_PAIRS = 4 * MAX_CHUNK_PAIRS
# Upper bound on the packed direction-matrix bytes held across in-flight
# device batches (v5e has 16 GiB HBM; the matrix never leaves the
# device). Small caps fragment long-bucket batches into many chunks and
# each chunk pays a fixed dispatch + fetch overhead; huge chunks coarsen
# the pack/transfer/compute pipeline overlap. 8 GiB across the pipeline
# depth keeps per-chunk matrices at ~2 GiB 4-deep; at depth 1 one chunk
# may take the whole budget (1024 ONT read pairs), which fits the chip
# only because the Mosaic sweep+walk is one program
# (_pallas_align_chain) and the stream makes room before it dispatches
# (_AlignStream._launch) — tests/test_chip_compile.py holds both. The
# value itself is to be re-measured on the chip.
MAX_DIRS_BYTES = 8 * 1024 * 1024 * 1024

@functools.partial(jax.jit, static_argnames=("max_len", "band", "steps",
                                             "swar"))
def _nw_wavefront_kernel(qrp, tp, n, m, *, max_len: int, band: int,
                         steps: int = 0, swar: bool = False):
    """Banded anti-diagonal wavefront DP for one bucket batch.

    Coordinate frame: wavefront ``a = i + j`` (scan axis), diagonal
    ``k = j - i + band/2``; lanes hold every-other diagonal (parity of k is
    fixed per wavefront), so a wavefront is ``W/2`` lanes indexed by ``u``
    with ``k = 2u + p(a)``, ``p(a) = (a + band/2) & 1``. All data
    dependencies are static +-1 lane shifts of the previous two wavefronts,
    and the per-step character loads are two contiguous ``dynamic_slice``
    reads — no gathers and no inner scans, which is what makes this fast
    on the TPU VPU (the earlier row-scan formulation was ~100x slower).

    Inputs (host-prepacked, see ``TpuAligner._run_chunk``):
      qrp: uint8 [B, band/2 + max_len + band] — reversed query at offset
           ``band/2 + max_len - n`` (so lane reads share one slice start);
      tp:  uint8 [B, band/2 + max_len + band] — target at offset ``band/2``;
      n, m: int32 [B] true lengths.

    Returns (dirs_packed uint8 [B, steps, band/8], score int32 [B]):
    per-wavefront 2-bit direction codes (0=M diag, 1=I consume-query,
    2=D consume-target), 4 lanes per byte (planar).

    ``steps`` bounds the anti-diagonal sweep (default ``2*max_len``):
    callers that know the longest real pair pass ``ceil(max(n+m))``
    rounded to 256, cutting the dead wavefronts past the last finish
    (pairs with ``n + m > steps`` never reach their final cell, keep
    score BIG, and are rejected like band escapes).

    ``swar`` runs the SWAR-packed variant: wavefront scores travel as
    **int16 lanes** — two per 32-bit VPU lane (2x arithmetic density;
    the vectorizer does the in-register packing) — saturating at
    ``swar.BIG16`` instead of ``1 << 28``. Every cell value is bounded
    by ``max_len`` (:func:`swar.swar_fits` is the callers' overflow
    guard), so the {real, BIG, BIG+1} value classes and hence every
    direction-code comparison are identical: the direction matrix is
    **byte-identical** to the int32 path's, and scores are remapped
    (``BIG16 -> 1 << 28``) so the outputs match bit-for-bit.
    """
    W = band
    c = W // 2
    L = max_len
    U = W // 2  # lanes per wavefront
    S = steps if steps else 2 * L
    if swar:
        from .swar import BIG16, BIG32
        assert max_len + 2 < BIG16, (max_len, BIG16)
        vdt = jnp.int16
        BIG = jnp.int16(BIG16)
    else:
        vdt = jnp.int32
        BIG = jnp.int32(1 << 28)

    us = jnp.arange(U, dtype=jnp.int32)

    def per_pair(qv, tv, nn, mm):
        def step(carry, a):
            v1, v2, score = carry  # wavefronts a-1 and a-2
            p = (a + c) & 1
            # lane -> (i, j):  i = I0 - u, j = J0 + u
            I0 = (a + c - p) // 2
            J0 = (a - c + p) // 2
            i_vec = I0 - us
            j_vec = J0 + us

            # shifted views of wavefront a-1 (parity alternates):
            #   p == 0: D-source = v1[u-1], I-source = v1[u]
            #   p == 1: D-source = v1[u],   I-source = v1[u+1]
            v1_left = jnp.concatenate([jnp.full((1,), BIG, vdt), v1[:-1]])
            v1_right = jnp.concatenate([v1[1:], jnp.full((1,), BIG, vdt)])
            d_src = jnp.where(p == 0, v1_left, v1)
            i_src = jnp.where(p == 0, v1, v1_right)

            # characters: q[i-1] and t[j-1] as contiguous slices
            qchars = lax.dynamic_slice_in_dim(qv, c + L - I0, U)
            tchars = lax.dynamic_slice_in_dim(tv, c + J0 - 1, U)
            sub = jnp.where(qchars == tchars, 0, 1).astype(vdt)

            cd = v2 + sub          # diagonal (i-1, j-1)
            ci = i_src + vdt(1)    # consume query (i-1, j)
            cdel = d_src + vdt(1)  # consume target (i, j-1)
            best = jnp.minimum(cd, jnp.minimum(ci, cdel))
            d = jnp.where(cd == best, jnp.uint8(0),
                          jnp.where(ci == best, jnp.uint8(1), jnp.uint8(2)))

            interior = (i_vec >= 1) & (i_vec <= nn) & (j_vec >= 1) & (j_vec <= mm)
            v = jnp.where(interior, jnp.minimum(best, BIG), BIG)
            # boundary rows/cols of the DP table (values <= max_len, so
            # the int16 cast in the packed path is lossless)
            v = jnp.where((i_vec == 0) & (j_vec >= 0) & (j_vec <= mm),
                          j_vec.astype(vdt), v)
            v = jnp.where((j_vec == 0) & (i_vec >= 1) & (i_vec <= nn),
                          i_vec.astype(vdt), v)

            # final score lives at a == n + m, u_final = (m - n + c - p) / 2
            u_fin = (mm - nn + c - p) // 2
            fin = jnp.take(v, jnp.clip(u_fin, 0, U - 1))
            score = jnp.where(a == nn + mm, fin, score)

            # planar 2-bit pack: byte k holds lanes k, k+RB, k+2RB, k+3RB
            # (static contiguous slices — no cross-lane reshuffle, so the
            # same format is cheap in both this kernel and the Pallas one)
            RB = U // 4
            packed = (d[:RB] | (d[RB:2 * RB] << 2) | (d[2 * RB:3 * RB] << 4)
                      | (d[3 * RB:] << 6))
            return (v, v1, score), packed

        # wavefront 0: only (0,0) at u0 = (c - p0)/2
        p0 = c & 1
        u0 = (c - p0) // 2
        v0 = jnp.where(us == u0, 0, BIG).astype(vdt)
        vm1 = jnp.full((U,), BIG, vdt)  # "wavefront -1"
        score0 = jnp.where(nn + mm == 0, 0, BIG).astype(vdt)
        (v, v1, score), packed = lax.scan(
            step, (v0, vm1, score0),
            jnp.arange(1, S + 1, dtype=jnp.int32))
        if swar:
            # restore the int32 saturation constant so consumers (and
            # the parity harness) see the exact int32-path scores
            score = jnp.where(score == BIG, jnp.int32(BIG32),
                              score.astype(jnp.int32))
        return packed, score

    return jax.vmap(per_pair)(qrp, tp, n, m)


def _walk_op(pk, i, j, *, c, RB, S, U):
    """Shared one-step decode of the packed direction matrix during a
    backward walk from (i, j). Returns (op, di, dj): op 0=M, 1=I, 2=D,
    3=done-or-stalled (band escape stalls so final (i,j) != 0 flags it).
    Planar layout: lane u lives in byte ``u % RB`` at shift ``2*(u//RB)``."""
    a = i + j
    p = (a + c) & 1
    u = (j - i + c - p) // 2
    pos = (a - 1) * RB + u % RB
    byte = jnp.take(pk, jnp.clip(pos, 0, S * RB - 1))
    # clip the plane index: escaped u (< 0 or >= U) decodes garbage, but
    # the `escaped` flag below overrides the op — just keep the shift legal
    plane = jnp.clip(u // RB, 0, 3).astype(jnp.uint8)
    d = ((byte >> (2 * plane)) & 3).astype(jnp.uint8)
    d = jnp.where(i == 0, jnp.uint8(2), d)              # only D left
    d = jnp.where((j == 0) & (i > 0), jnp.uint8(1), d)  # only I left
    escaped = (i > 0) & (j > 0) & ((u < 0) | (u >= U))
    done = ((i == 0) & (j == 0)) | escaped
    op = jnp.where(done, jnp.uint8(3), d)
    di = jnp.where((op == 0) | (op == 1), 1, 0)
    dj = jnp.where((op == 0) | (op == 2), 1, 0)
    return op, di, dj


@functools.partial(jax.jit, static_argnames=("band", "swar"))
def _walk_ops_kernel(packed, n, m, *, band: int, swar: bool = False):
    """On-device traceback: vmapped pointer chase over the packed direction
    matrix (which never leaves HBM — downloading it dominated wall-clock
    otherwise). Emits one op code per step, consumed backwards from (n, m):
    0=M, 1=I, 2=D, 3=done-or-band-escape. Exactly n+m real steps per pair
    (a band escape stalls the walk, leaving the final ``(fi, fj) != 0``).
    Walk length follows ``packed``'s wavefront-row count (the producer's
    ``steps`` bound, default ``2*max_len``). Returns unpacked
    ``(ops [B, steps] u8, fi, fj)`` — stays on device for the consensus
    vote path; the aligner packs via :func:`_traceback_kernel`.

    ``swar`` runs the SWAR-packed variant (the round-6 layout extended
    to the walk, the ROADMAP open item): the ``(i, j)`` walk state
    travels as ONE int32 halfword pair — positions are bounded by the
    bucket cap (16384 < 2^15, the same ``swar.swar_fits`` ceiling the
    forward kernel's guard enforces), so the scan carry and its
    per-step update halve. Decode math is shared with the unpacked path
    (:func:`_walk_op`), so the op stream is **byte-identical**; the
    sanitizer's int32 shadow execution covers it (the shadow leg runs
    ``swar=False`` end to end)."""
    W = band
    c = W // 2
    U = W // 2
    RB = W // 8
    B, S = packed.shape[0], packed.shape[1]
    flat = packed.reshape(B, S * RB)

    def per_pair(pk, nn, mm):
        if swar:
            def step(carry, _):
                ij = carry  # (i << 16) | j, both < 2^15 (swar_fits)
                op, di, dj = _walk_op(pk, ij >> 16, ij & 0xFFFF,
                                      c=c, RB=RB, S=S, U=U)
                return ij - ((di << 16) | dj), op

            ijf, ops = lax.scan(step, (nn << 16) | mm, None, length=S)
            return ops, ijf >> 16, ijf & 0xFFFF

        def step(carry, _):
            i, j = carry
            op, di, dj = _walk_op(pk, i, j, c=c, RB=RB, S=S, U=U)
            return (i - di, j - dj), op

        (fi, fj), ops = lax.scan(step, (nn, mm), None, length=S)
        return ops, fi, fj

    return jax.vmap(per_pair)(flat, n, m)


@functools.partial(jax.jit, static_argnames=("max_len", "band", "swar"))
def _traceback_kernel(packed, score, n, m, *, max_len: int, band: int,
                      swar: bool = False):
    """Aligner-facing traceback: walks on device, then packs the op codes
    2-bit x 4-per-byte so one host round trip fetches everything (each
    transfer pays a fixed latency). ``swar``
    forwards to the packed-carry walk (byte-identical op stream)."""
    ops, fi, fj = _walk_ops_kernel(packed, n, m, band=band, swar=swar)
    return _pack_ops(ops), score, fi, fj


def _pack_ops(ops):
    """2-bit x 4-per-byte op packing for the host fetch (one consumer:
    ``TpuAligner._finish_chunk``'s unpacker)."""
    B, S = ops.shape
    o4 = ops.reshape(B, S // 4, 4)
    return (o4[:, :, 0] | (o4[:, :, 1] << 2) | (o4[:, :, 2] << 4)
            | (o4[:, :, 3] << 6))


def align_chain(qrp, tp, n, m, *, max_len: int, band: int, steps: int = 0,
                use_pallas: bool = False, use_swar: bool = False):
    """Wavefront NW + on-device traceback — the single source of truth for
    the aligner's kernel wiring, wrapped unchanged by both the plain path
    (``TpuAligner._run_chunk``) and the ``shard_map`` path
    (``racon_tpu.parallel.sharded_align``). With ``use_pallas`` the
    VMEM-resident Mosaic kernels produce the identical direction matrix
    and (gap-interleaved) op codes; with ``use_swar`` the forward DP runs
    on packed int16x2 score lanes (bit-identical outputs — the walks
    consume the same direction matrix either way)."""
    if use_pallas:
        return _pallas_align_chain(qrp, tp, n, m, max_len=max_len,
                                   band=band, steps=steps,
                                   use_swar=use_swar)
    packed, score = _nw_wavefront_kernel(qrp, tp, n, m,
                                         max_len=max_len, band=band,
                                         steps=steps, swar=use_swar)
    return _traceback_kernel(packed, score, n, m, max_len=max_len,
                             band=band, swar=use_swar)


@functools.partial(jax.jit, static_argnames=("max_len", "band", "steps",
                                             "use_swar"))
def _pallas_align_chain(qrp, tp, n, m, *, max_len: int, band: int,
                        steps: int, use_swar: bool):
    """The Mosaic forward sweep and walk as ONE program. Dispatched as
    two, the direction matrix crosses a program boundary as a
    [B, steps, band/8] array whose tiled layout differs from the
    kernels' flat [B, steps * band/8] view: each side then holds a
    relayout COPY of the whole matrix next to the matrix itself — twice
    the chunk's direction-matrix budget, which the chip's compiler
    refuses outright for a budget-sized chunk (8 GiB + 8 GiB on a 16 GB
    v5e). Inside one program the reshape pair cancels and the matrix is
    a temporary of exactly its own size."""
    from .pallas_nw import pallas_nw_fwd, pallas_walk_ops
    packed, score = pallas_nw_fwd(qrp, tp, n, m, max_len=max_len,
                                  band=band, steps=steps, out_quant=512,
                                  use_swar=use_swar)
    # the Pallas walk emits the packed op stream directly
    ops_packed, fi, fj = pallas_walk_ops(packed, n, m, band=band)
    return ops_packed, score, fi, fj


def _banded_rows(qcat, tcat, n, m, *, max_len: int, band: int, bits: int):
    """The banded NW row layout from flat blocks of ``bits``-wide codes
    (pair k's at ``k * max_len``; ``8 // bits`` per byte, LSB-first):
    qrp holds the reversed query ending at column ``c + max_len``, tp
    the forward target at offset ``c`` — exactly the layout the host
    used to pack; codes past ``n`` / ``m`` become the pad code 0. A
    shifted copy (unpack, mask, reverse, pad), all streaming
    element-wise work: XLA's element-wise gather runs orders of
    magnitude under the memory system's rate."""
    B = n.shape[0]
    per = 8 // bits
    shifts = jnp.arange(per, dtype=jnp.uint8) * bits
    col = jnp.arange(max_len, dtype=jnp.int32)[None, :]

    def codes(cat, length):
        c = (cat.reshape(B, max_len // per, 1) >> shifts) & ((1 << bits) - 1)
        return jnp.where(col < length[:, None], c.reshape(B, max_len),
                         jnp.uint8(0))

    pad = ((0, 0), (band // 2, band))
    return jnp.pad(codes(qcat, n)[:, ::-1], pad), jnp.pad(codes(tcat, m), pad)


@functools.partial(jax.jit, static_argnames=("max_len", "band"))
def _build_rows(qcat, tcat, n, m, *, max_len: int, band: int):
    """Build the banded NW row layout on device from dense byte blocks
    (pair k's query/target at ``k * max_len``)."""
    return _banded_rows(qcat, tcat, n, m, max_len=max_len, band=band, bits=8)


@functools.partial(jax.jit, static_argnames=("max_len", "band"))
def _build_rows_packed(q4, t4, n, m, *, max_len: int, band: int):
    """``_build_rows`` over nibble-packed inputs (two 4-bit codes per
    byte; code 0 is padding). Unpacking is a shift/mask on the device,
    so the wide row arrays never cross the host link."""
    return _banded_rows(q4, t4, n, m, max_len=max_len, band=band, bits=4)


@functools.partial(jax.jit, static_argnames=("max_len", "band"))
def _build_rows_packed2(q2, t2, n, m, *, max_len: int, band: int):
    """``_build_rows`` over 2-bit-packed inputs (four codes per byte, 16
    per int32 word — the SWAR transfer format for chunks whose alphabet
    fits 4 symbols). The bytes sent drop 4x vs raw and 2x vs the nibble
    pack; code 0 doubles as padding, which is sound because the
    wavefront kernel only consumes characters at interior cells (pad
    lanes' direction codes are never read by any walk)."""
    return _banded_rows(q2, t2, n, m, max_len=max_len, band=band, bits=2)


def _sweep_bound(max_nm: int, max_len: int) -> int:
    """Anti-diagonal sweep bound for a bucket/chunk, multiple of 512
    (the Pallas kernels' granularity: every band's flush period
    F = FL/RB divides 128 and the packed walk flushes 128-byte output
    groups of 512 steps). Long buckets quantize to 2048: every distinct
    ``steps`` value is a separate XLA/Mosaic compile (~30 s) and a
    longest-first chunk stream over a real read set walks through a
    handful of them, while the static bound only sizes the direction
    matrix — the kernels' per-block dynamic bounds already skip the
    quantization's dead wavefronts, so the coarse quantum costs memory
    (<= 1 MB/pair), not compute. Shared by the chunk launcher and the
    memory-budget sizing so they account identically."""
    quant = 512 if max_len <= 1024 else 2048
    steps = min(-(-max_nm // quant) * quant, 2 * max_len)
    return -(-steps // 512) * 512


@functools.lru_cache(maxsize=None)
def _chunk_geometry(max_len: int, band: int, steps: int, B: int, w: int,
                    swar: bool):
    """The occupancy ledger's join keys of one chunk geometry's three
    programs — row build, align chain, breaking points — each the
    static arguments and batch its executable is compiled for.
    Formatted once per geometry; the stream's submit site and the
    warm-up thread both take them from here, so a warm-up's compile and
    the dispatch that runs its executable carry the same key."""
    geometry = device_time.geometry
    return (geometry(max_len=max_len, band=band, B=B),
            geometry(max_len=max_len, band=band, steps=steps, B=B,
                     swar=swar),
            geometry(steps=steps, B=B, w=w, NW=max_len // max(w, 1) + 2))


@functools.partial(jax.jit, static_argnames=("w", "NW"))
def _breaking_points_kernel(ops_packed, n, m, first_rel, nb, *, w: int,
                            NW: int):
    """Per-window breaking points straight from the packed walk op codes —
    the device analog of :func:`core.overlap.breaking_points_from_cigar`,
    so only ~8 bytes per window boundary ever cross the host link instead
    of the whole op stream (~2 bits/base — bytes fetched, not the DP,
    would otherwise bound the aligner).

    Coordinates are span-relative and packed ``tpos << 14 | qpos`` (both
    < 16384, the bucket cap). For boundary interval k (boundaries at
    ``first_rel + j*w`` for j < nb-1, plus ``m-1``):

    - ``bp_first[b, k]`` = packed coords of the first match in interval k
      (BIG when the interval has no match — nothing is emitted, exactly
      the walker's found_first rule);
    - ``bp_last[b, k]`` = packed coords of the last match at or before
      boundary k (a running prefix max; the walker's ``last``/M-crossing
      cases unify to this).

    Identical for both walk backends: gap-code placement differs but the
    M steps' (tpos, qpos) sets are equal and min/max are order-free.

    Per-interval aggregation is ``NW`` (static, ~10-34) masked reduces
    over the [B, S] step stream rather than a scatter-min/max: XLA's
    scatter engine crawls the ~4M updates of a full chunk at ~90M/s
    (~45 ms per table — it used to cost more than the DP itself), while
    the masked reduces are streaming VPU passes (~5 ms total).
    """
    B, S4 = ops_packed.shape
    S = S4 * 4
    shifts = jnp.arange(4, dtype=jnp.uint8) * 2
    ops = ((ops_packed[:, :, None] >> shifts) & 3).reshape(B, S)
    is_real = ops < 3
    is_M = ops == 0
    di = (is_M | (ops == 1)).astype(jnp.int32)
    dj = (is_M | (ops == 2)).astype(jnp.int32)
    i_t = n[:, None] - jnp.cumsum(di, axis=1) + di
    j_t = m[:, None] - jnp.cumsum(dj, axis=1) + dj
    tpos = j_t - 1          # 0-based span-relative target pos of an M base
    qpos = i_t - 1
    BIG = jnp.int32(1 << 30)

    # boundary-interval index: number of boundaries < tpos (the final
    # boundary m-1 is never < tpos since tpos <= m-1)
    widx = jnp.clip(
        -(-(tpos - first_rel[:, None]) // w), 0, nb[:, None] - 1)
    valid = is_M & is_real & (tpos >= 0)
    packed = jnp.where(valid, (tpos << 14) | jnp.maximum(qpos, 0), BIG)

    bp_first = jnp.stack(
        [jnp.min(jnp.where(widx == k, packed, BIG), axis=1)
         for k in range(NW)], axis=1)
    bp_last = jnp.stack(
        [jnp.max(jnp.where(valid & (widx == k), packed, -1), axis=1)
         for k in range(NW)], axis=1)
    bp_last = lax.cummax(bp_last, axis=1)
    return bp_first, bp_last


def _alphabet(pools) -> np.ndarray:
    """The distinct byte values of ``pools`` (bytes objects), sorted,
    without 0 (the blocks' pad). ACGT are looked for by ``in`` and only
    what is left after deleting them is counted, so a chunk of plain
    bases is never histogrammed."""
    present = np.zeros(256, bool)
    for pool in pools:
        for base in b"ACGT":
            if not present[base] and base in pool:
                present[base] = True
        rest = pool.translate(None, b"ACGT")
        if rest:
            present[np.frombuffer(rest, np.uint8)] = True
    present[0] = False
    return np.flatnonzero(present)


def _copy_rows(pool: bytes, lens, block) -> None:
    """Row ``k`` of the zeroed ``[B, max_len]`` uint8 ``block`` gets the
    next ``lens[k]`` bytes of ``pool`` (the rows' bytes back to back) at
    its head: one native memcpy per row (a chunk of short reads is
    65,536 rows a side), or a slice assignment per row where the native
    core is absent."""
    from .. import native
    if native.available():
        native.copy_byte_rows(pool, lens, block)
        return
    flat = np.frombuffer(pool, dtype=np.uint8)
    at = 0
    for k, ln in enumerate(np.asarray(lens).tolist()):
        block[k, :ln] = flat[at:at + ln]
        at += ln


def _ops_to_cigar(path: np.ndarray) -> str:
    """Run-length encode a backward-order op path into a CIGAR string
    (callers pre-filter ``ops < 3`` — the Pallas walk interleaves
    inactive-gap codes after M steps, the XLA walk only trails them)."""
    if len(path) == 0:
        return ""
    arr = path[::-1]
    change = np.flatnonzero(np.diff(arr)) + 1
    starts = np.concatenate(([0], change))
    ends = np.concatenate((change, [len(arr)]))
    sym = {0: "M", 1: "I", 2: "D"}
    return "".join(f"{e - s}{sym[int(arr[s])]}" for s, e in zip(starts, ends))


from .pallas_nw import PallasDispatchMixin
from .. import faults, obs
from ..obs import device_time, metrics


class _PackedChunk(NamedTuple):
    """A chunk after the host half of its launch
    (``TpuAligner._pack_chunk``): nothing of it is on the device yet."""
    pairs: object             # list or slot dict: ``pairs[i]`` -> (q, t)
    chunk: Sequence[int]
    max_len: int
    band: int
    bp_meta: Optional[tuple]
    n: np.ndarray
    m: np.ndarray
    seqs: Tuple[np.ndarray, np.ndarray]
    kind: str                 # "2bit" | "nibble" | "raw"
    bp_host: Optional[tuple]
    steps: int
    sw: bool

    @property
    def dirs_bytes(self) -> int:
        """What the chunk's direction matrix will hold on the device:
        the bytes the in-flight budget counts."""
        return self.n.shape[0] * self.steps * (self.band // 8)


class TpuAligner(PallasDispatchMixin):
    """Batched device aligner with on-device traceback and host fallback.

    ``mesh``: optional 1-D :class:`jax.sharding.Mesh`; when given, every
    device batch is split along its batch dimension over the mesh with
    ``shard_map`` (multi-chip analog of the reference's per-GPU batch
    binning, ``src/cuda/cudapolisher.cpp:163-171``).
    """

    def __init__(self, fallback=None, buckets=BUCKETS,
                 max_dirs_bytes=MAX_DIRS_BYTES, mesh=None,
                 num_batches: int = 1, use_swar: bool = True,
                 device=None, use_ragged: bool = True,
                 use_ladder: bool = True):
        self.fallback = fallback
        self.buckets = buckets
        self.max_dirs_bytes = max_dirs_bytes
        self.mesh = mesh
        # per-engine chip pin (mutually exclusive with a mesh): the
        # in-process chip scheduler builds one aligner per local device
        # and every launch/fetch runs under jax.default_device(device)
        self.device = device
        # Batch count (reference --cudaaligner-batches N,
        # cudapolisher.cpp:91): the device pipeline depth. N chunks are
        # kept in flight (JAX async dispatch), each capped at 1/N of the
        # direction-matrix memory budget, so host packing of chunk k+1
        # overlaps device compute of chunk k.
        self.num_batches = max(1, num_batches)
        # SWAR-packed forward DP (int16x2 score lanes + 2-bit bases when
        # the chunk alphabet fits 4 symbols). Guarded per bucket by the
        # overflow guard (swar.swar_fits) and globally by the bit-exact
        # availability probe (swar.swar_ok) — both identical-output, so
        # this knob only exists for A/B measurement and escape hatches.
        self.use_swar = use_swar
        # ragged pair packing: pairs greedy-fill a fixed
        # direction-matrix arena by their own sweep cost through the
        # streaming _AlignStream session — the aligner analog of
        # poa._ConsensusStream. A mesh takes the bucketed wave driver
        # whatever this says; False selects it off-mesh (tests)
        self.use_ragged = use_ragged
        # adaptive band ladder: seed each pair's band from its
        # overlap's estimated divergence, escalate escapees batched —
        # see BAND_RUNGS; False starts every pair at its bucket's full
        # band (tests)
        self.use_ladder = use_ladder
        # memory backpressure (round 12 ladder parity, round 17): a
        # device RESOURCE_EXHAUSTED halves the effective direction-
        # matrix budget (reduce_capacity) and the chunk re-dispatches —
        # grouping never changes output bytes, only launch size
        self.capacity_scale = 1
        # shapes already submitted for warm-up compilation (the
        # resident service warms per admitted job; repeats are free)
        self._warmed_shapes: set = set()
        # adaptive ladder state: [count, sum, sum_sq] of the realized
        # divergence (score / longer span) of every accepted pair
        self._div_obs = [0, 0.0, 0.0]
        # sanitizer: per-aligner shadow sampler (first chunk always)
        from .. import sanitize
        self._shadow = sanitize.ShadowSampler()
        # occupancy telemetry (round 17): chunks/lanes_occupied/
        # lanes_total count every dispatched wavefront arena (occupied
        # = sum of real pairs' n+m anti-diagonals, total = B x steps
        # per launch); steps_wasted is their gap and wavefront_work
        # (total x band, summed over rungs) is the banded-DP cost —
        # the aligner's efficiency signal
        self.stats = {"device": 0, "fallback_length": 0, "fallback_band": 0,
                      "band_escalated": 0, "swar_chunks": 0,
                      "swar_guard_int32": 0, "chunks": 0,
                      "lanes_occupied": 0, "lanes_total": 0,
                      "steps_wasted": 0, "wavefront_work": 0,
                      "ladder_narrow": 0}

    # the floor keeps OOM backpressure from shrinking chunks below the
    # point where per-chunk fixed costs dominate (mirrors the consensus
    # engine's _MAX_CAPACITY_SCALE contract)
    _MAX_CAPACITY_SCALE = 16

    @property
    def dirs_budget_cap(self) -> int:
        """Total in-flight direction-matrix byte budget under the
        current OOM-backpressure scale (``max_dirs_bytes`` at 1). The
        floor derives from the CONFIGURED budget at the maximum scale —
        an absolute floor would both override small caller-sized
        budgets and let reduce_capacity() report shrinkage it no
        longer delivers (the exec ladder would re-dispatch at
        unchanged memory and OOM again)."""
        return max(1, self.max_dirs_bytes // self.capacity_scale)

    def chunk_dirs_budget(self) -> int:
        """Per-chunk direction-matrix budget: the in-flight budget split
        over the pipeline depth — shared by the bucketed wave driver,
        the ragged stream's greedy fill and the warm-up shape estimate
        so all three account identically."""
        return max(1, self.dirs_budget_cap // self.num_batches)

    def reduce_capacity(self) -> bool:
        """Halve the direction-matrix arena (device-OOM backpressure,
        the exec ladder's ``reduce-capacity`` rung). Returns False once
        at the floor — the ladder then falls through to the CPU
        engines. Chunk grouping never changes output bytes (pairs are
        independent), so a reduced re-dispatch is byte-identical."""
        if self.capacity_scale >= self._MAX_CAPACITY_SCALE:
            return False
        self.capacity_scale *= 2
        metrics.set_gauge("aligner.capacity_scale", self.capacity_scale)
        metrics.inc("faults.backpressure_halvings")
        return True

    def pack_metrics(self) -> dict:
        """Derived occupancy view of :attr:`stats` (the aligner twin of
        ``TpuPoaConsensus.pack_metrics``): ``align_pad_fraction`` =
        wavefront-arena lanes spent on padding (batch pow2 pad + dead
        anti-diagonals past each pair's own n+m), ``align_chunks`` =
        dispatched device chunks."""
        tot = self.stats.get("lanes_total", 0)
        eff = self.stats.get("lanes_occupied", 0) / tot if tot else 0.0
        return {"align_pack_efficiency": round(eff, 4),
                "align_pad_fraction": round(1.0 - eff, 4) if tot else 0.0,
                "align_chunks": self.stats.get("chunks", 0),
                "align_steps_wasted": self.stats.get("steps_wasted", 0)}

    def _swar_choice(self, max_len: int) -> bool:
        """Packed-lane eligibility for a bucket: the global availability
        probe plus the per-bucket overflow guard — a band/length
        combination whose scores could exceed the int16 saturation
        ceiling re-dispatches to the int32 path (counted in stats)."""
        from .swar import swar_fits, swar_ok
        if not self.use_swar:
            return False
        if not swar_fits(max_len):
            self.stats["swar_guard_int32"] += 1
            metrics.inc("aligner.swar_guard_int32")
            return False
        return swar_ok()

    def _pad_batch(self, count: int) -> int:
        """Batch sizes are ``mesh_size * 2^k`` — always divisible by the
        mesh (shard_map splits evenly) and geometric (compile-cache hits);
        plain power of two without a mesh."""
        from ..parallel import mesh_size
        B = mesh_size(self.mesh)
        while B < count:
            B *= 2
        return B

    def _bucket_index(self, qlen: int, tlen: int, start: int = 0):
        need = abs(qlen - tlen) + 16
        want = need + int(TYPICAL_DIVERGENCE * max(qlen, tlen))
        fallback_bi = None
        for bi in range(start, len(self.buckets)):
            max_len, band = self.buckets[bi]
            if qlen <= max_len and tlen <= max_len and need <= band // 2:
                if want <= band // 2:
                    return bi
                if fallback_bi is None:
                    fallback_bi = bi
        return fallback_bi

    def _observe_divergence(self, scores, maxlens) -> None:
        """Feed accepted pairs' realized edit divergence (score over the
        longer span) into the run's running estimate — the adaptive half
        of the band ladder."""
        cnt, s, s2 = self._div_obs
        d = np.asarray(scores, dtype=np.float64) / np.maximum(
            np.asarray(maxlens, dtype=np.float64), 1.0)
        self._div_obs = [cnt + d.size, s + float(d.sum()),
                         s2 + float((d * d).sum())]

    def _adaptive_divergence(self):
        """Observed-divergence upper estimate (mean + 2 sigma) once
        enough pairs have resolved; None while cold."""
        cnt, s, s2 = self._div_obs
        if cnt < ADAPT_MIN_PAIRS:
            return None
        mean = s / cnt
        var = max(0.0, s2 / cnt - mean * mean)
        return mean + 2.0 * var ** 0.5

    def _est_divergence(self, err) -> float:
        """Divergence estimate for the band ladder. COLD (no resolved
        pairs yet): ``TYPICAL_DIVERGENCE`` — deliberately conservative,
        so a run never gambles narrow bands on the span-asymmetry proxy
        alone (the overlap filter's ``o.error`` only sees NET indels; a
        substitution-heavy run would seed low and escape every pair).
        WARM: the observed divergence (:meth:`_adaptive_divergence`),
        raised per pair by the span proxy (2x + 5% margin) when that
        reads higher. An underestimate costs one batched re-dispatch
        (band escape), never a wrong alignment — the accept gate is the
        same optimality certificate at every rung."""
        ad = self._adaptive_divergence()
        if ad is None:
            return TYPICAL_DIVERGENCE
        proxy = 0.0 if err is None else 2.0 * float(err) + 0.05
        return min(TYPICAL_DIVERGENCE, max(proxy, ad))

    def _seed_geometry(self, qlen: int, tlen: int, err=None,
                       record: bool = True):
        """Starting ``(bucket_index, band)`` for one pair: the fixed
        path's bucket, at the narrowest ladder rung the divergence
        estimate admits (the bucket's full band with the ladder off, or
        when no rung is plausibly wide enough). None -> host fallback,
        exactly the fixed path's length-reject set. ``record=False``
        skips the ladder telemetry — the warm-up's shape ESTIMATE must
        not count phantom pairs (nor write the stats dict from the
        service's admission thread)."""
        bi = self._bucket_index(qlen, tlen)
        if bi is None:
            return None
        bucket_band = self.buckets[bi][1]
        if not self.use_ladder:
            return (bi, bucket_band)
        need = abs(qlen - tlen) + 16
        want = need + int(self._est_divergence(err) * max(qlen, tlen))
        for rung in BAND_RUNGS:
            if rung >= bucket_band:
                break
            if want <= rung // 2:
                if record:
                    self.stats["ladder_narrow"] += 1
                    metrics.inc("aligner.ladder_narrow")
                return (bi, rung)
        return (bi, bucket_band)

    def _chunk_cap(self, steps: int, band: int, base: int = 1) -> int:
        """Pairs per device chunk for one sweep geometry: the largest
        ``base * 2^k`` batch whose direction matrix fits the per-chunk
        budget, bounded by ``MAX_CHUNK_PAIRS`` (transient host span
        copies). THE one cap rule — shared by the bucketed wave driver,
        the ragged stream's greedy fill and the warm-up shape estimate,
        so the warm-cache claim cannot drift from the live caps."""
        raw = self.chunk_dirs_budget() // (steps * (band // 8))
        cap = base
        while cap * 2 <= raw and cap * 2 <= MAX_CHUNK_PAIRS:
            cap *= 2
        return cap

    def _next_geometry(self, qlen: int, tlen: int, bi: int, band: int):
        """Escalation after a band escape: the next ladder rung inside
        the same bucket (skipping rungs the pair's length difference
        already rules out), then the fixed path's bucket escalation —
        so the ladder's terminal geometry sequence IS the fixed path's,
        and the two reject sets coincide. None -> host fallback."""
        bucket_band = self.buckets[bi][1]
        if band < bucket_band:
            # an escape means the seed was wrong, so jump, don't creep:
            # the rung the CURRENT divergence estimate (adaptive once
            # warm, TYPICAL when cold — conservative) says should hold,
            # at least 2x the failed band — a 1.5x walk would waste a
            # re-dispatch per step
            need = abs(qlen - tlen) + 16
            want = need + int(self._est_divergence(None)
                              * max(qlen, tlen))
            nb = bucket_band
            for rung in BAND_RUNGS:
                if rung >= 2 * band and rung < bucket_band \
                        and want <= rung // 2:
                    nb = rung
                    break
            return (bi, nb)
        nbi = self._bucket_index(qlen, tlen, bi + 1)
        if nbi is None:
            return None
        return (nbi, self.buckets[nbi][1])

    # the polisher hands this backend the whole overlap stream (it buckets
    # and chunks internally) instead of pre-chunked 1024-pair slices
    wants_full_stream = True

    def align_batch(self, pairs: Sequence[Tuple[bytes, bytes]],
                    progress=None, errors=None) -> List[str]:
        """CIGAR strings for every pair (test surface; the pipeline
        uses :meth:`breaking_points_batch`, which never fetches the op
        stream). ``errors`` optionally carries per-pair divergence
        estimates for the band ladder (overlap ``error`` values)."""
        return self._drive(pairs, progress, None, errors)

    def breaking_points_batch(self, pairs, metas, window_length: int,
                              progress=None, errors=None):
        """Per-window breaking points for every (query-span, target-span)
        pair — the production surface behind
        ``Polisher.find_overlap_breaking_points``. ``metas[i]`` is the
        overlap's ``(t_begin, q_off)`` (global target start; strand-aware
        global query offset); ``errors[i]`` (optional) its filter-time
        ``error`` estimate, seeding the band ladder. The walk stays on
        device and only ~8 bytes per window boundary are fetched
        (:func:`_breaking_points_kernel`); rejects fall back to the host
        aligner + the shared CIGAR walker. Returns one **columnar** int32
        ndarray of shape (k, 4) per pair — rows of (t_first, q_first,
        t_end_excl, q_end_excl), row-identical to the walker's pairs on
        every path."""
        return self._drive(pairs, progress, (window_length, metas), errors)

    def bp_stream(self, window_length: int, progress=None, total: int = 0):
        """Open a ragged streaming breaking-points session (round 17):
        ``feed()`` buckets pairs by their own sweep cost and band rung
        and **asynchronously dispatches** greedy-filled chunks as
        overlap slices arrive — packing/dispatch/fetch pipeline across
        slice boundaries instead of draining per slice — and
        ``finish()`` drains the pipeline, runs the batched band-ladder
        escalations and the host fallback, and returns breaking points
        for every fed pair in feed order. ``Polisher._align_need`` feeds
        this directly. Returns None when the ragged packer is
        unavailable (mesh runs, ``use_ragged=False``) — callers
        then fall back to per-slice :meth:`breaking_points_batch`."""
        if not self.use_ragged or self.mesh is not None:
            return None
        return _AlignStream(self, window_length=window_length,
                            progress=progress, total_hint=total)

    def _drive(self, pairs, progress, bp_meta, errors=None):
        if self.use_ragged and self.mesh is None:
            # one-feed session: the same ragged packer the polisher's
            # streaming feed uses, so batch surfaces and the pipeline
            # share one dispatch path (and one A/B axis)
            sess = _AlignStream(
                self, window_length=bp_meta[0] if bp_meta else None,
                progress=progress, total_hint=len(pairs))
            sess.feed(pairs, metas=bp_meta[1] if bp_meta else None,
                      errors=errors)
            return sess.finish()
        return self._drive_bucketed(pairs, progress, bp_meta, errors)

    def _drive_bucketed(self, pairs, progress, bp_meta, errors=None):
        # progress counts pairs whose final result is settled — escaped
        # pairs re-enter a wider geometry and are only counted once, on
        # their last visit; fallback/empty pairs are counted when resolved
        done_pairs = 0
        empty_bp = np.zeros((0, 4), dtype=np.int32)
        cigars: List = [("" if bp_meta is None else empty_bp)
                        for _ in range(len(pairs))]
        by_class = {}  # (bucket_index, band) -> indices
        reject: List[int] = []
        for idx, (q, t) in enumerate(pairs):
            if len(q) == 0 or len(t) == 0:
                if bp_meta is None:
                    cigars[idx] = (f"{len(t)}D" if len(t) else
                                   (f"{len(q)}I" if len(q) else ""))
                else:
                    cigars[idx] = empty_bp  # no matches -> no breaking pts
                done_pairs += 1
                continue
            g = self._seed_geometry(len(q), len(t),
                                    None if errors is None
                                    else errors[idx])
            if g is None:
                reject.append(idx)
            else:
                by_class.setdefault(g, []).append(idx)
        self.stats["fallback_length"] += len(reject)
        metrics.inc("aligner.fallback_length", len(reject))

        # Band escapes retry on device at the next rung (ladder) or the
        # next wider-band bucket — the analog of the reference host's
        # band-doubling, but batched. All classes of a wave share one
        # in-flight window (num_batches deep): with num_batches > 1,
        # chunk k+1 of any class is packed and dispatched while chunk k
        # computes, hiding the per-fetch host round trip;
        # escape handling is batched per wave either way. Only escapes
        # from the widest geometry go to the host fallback.
        from ..parallel import mesh_size
        # cold-estimator eager fetch (see _AlignStream._launch): fetch
        # the wave's first chunk immediately so the adaptive ladder
        # seeds the rest of the wave from real scores
        eager = (self.use_ladder
                 and self._adaptive_divergence() is None)
        while by_class:
            inflight = []
            escaped = {}  # class -> indices that escaped its band
            for cls in sorted(by_class):
                bi, band = cls
                # longest first: chunks (and the Pallas kernels' 64-pair
                # blocks within them) hold similar-length pairs, so the
                # per-block dynamic sweep bound cuts the short blocks'
                # dead wavefronts instead of averaging against the max
                indices = sorted(
                    by_class[cls],
                    key=lambda i: -(len(pairs[i][0]) + len(pairs[i][1])))
                max_len = self.buckets[bi][0]
                # budget by the real sweep bound, not the worst case: the
                # direction matrix is (B, steps, band/8) and steps tracks
                # the longest pair in the class — budgeting 2*max_len
                # halved the chunk size (and doubled the dispatch syncs)
                # for typical pairs well under the bucket cap (indices
                # are sorted longest-first, so the head is the max)
                max_nm = (len(pairs[indices[0]][0])
                          + len(pairs[indices[0]][1]))
                steps_est = _sweep_bound(max_nm, max_len)
                raw_cap = self.chunk_dirs_budget() // (steps_est
                                                       * (band // 8))
                # chunks pad to mesh_size * 2^k (see _pad_batch), so cap
                # at the largest such size to keep the memory bound honest
                batch_cap = mesh_size(self.mesh)
                if batch_cap > max(1, raw_cap):
                    import warnings
                    warnings.warn(
                        f"mesh size {batch_cap} exceeds the direction-"
                        f"matrix memory budget ({raw_cap} pairs of bucket "
                        f"({max_len},{band}) fit in "
                        f"{self.chunk_dirs_budget()} "
                        f"bytes); lower num_batches or use a smaller mesh",
                        RuntimeWarning)
                batch_cap = self._chunk_cap(steps_est, band,
                                            base=batch_cap)
                esc = escaped.setdefault(cls, [])
                # keep num_batches chunks in flight so the host packs
                # chunk k+1 while the device computes chunk k (reference
                # analog: per-batch fill/process loops on pool threads,
                # cudapolisher.cpp:98-160)
                for start in range(0, len(indices), batch_cap):
                    chunk = indices[start:start + batch_cap]
                    inflight.append(
                        (band, esc, self._launch_chunk(pairs, chunk,
                                                       max_len, band,
                                                       bp_meta)))
                    if len(inflight) >= (1 if eager
                                         else self.num_batches):
                        eager = False
                        band0, esc0, launched = inflight.pop(0)
                        n_chunk = len(launched[0])
                        n_esc = len(esc0)
                        self._finish_chunk(launched, band0, cigars, esc0,
                                           bp_meta)
                        done_pairs += n_chunk - (len(esc0) - n_esc)
                        if progress is not None:
                            progress(done_pairs, len(pairs))
            while inflight:
                band0, esc0, launched = inflight.pop(0)
                n_chunk = len(launched[0])
                n_esc = len(esc0)
                self._finish_chunk(launched, band0, cigars, esc0, bp_meta)
                done_pairs += n_chunk - (len(esc0) - n_esc)
                if progress is not None:
                    progress(done_pairs, len(pairs))
            by_class = {}
            for cls, idxs in escaped.items():
                bi, band = cls
                for idx in idxs:
                    q, t = pairs[idx]
                    # graftlint: disable=warmup-coverage (escalation rungs are data-dependent and rare by design; the terminal rung — the bucket band — IS warmed as the escape shape)
                    ng = self._next_geometry(len(q), len(t), bi, band)
                    if ng is None:
                        self.stats["fallback_band"] += 1
                        metrics.inc("aligner.fallback_band")
                        reject.append(idx)
                    else:
                        self.stats["band_escalated"] += 1
                        metrics.inc("aligner.band_escalated")
                        by_class.setdefault(ng, []).append(idx)

        self._resolve_rejects(pairs, reject, cigars, bp_meta)
        if progress is not None and done_pairs < len(pairs):
            progress(len(pairs), len(pairs))
        return cigars

    def _resolve_rejects(self, pairs, reject, results, bp_meta) -> None:
        """Host-fallback resolution for length/band rejects, shared by
        the bucketed wave driver and the ragged stream (``pairs`` only
        needs ``pairs[i]`` indexing — a list or a slot dict)."""
        if not reject:
            return
        if self.fallback is None:
            raise RuntimeError(
                f"{len(reject)} pairs rejected and no fallback aligner")
        fb = self.fallback.align_batch([pairs[i] for i in reject])
        if bp_meta is None:
            for i, cig in zip(reject, fb):
                results[i] = cig
        else:
            from ..core.overlap import decode_breaking_points_batch
            w, metas = bp_meta
            arrs = decode_breaking_points_batch(
                fb, [metas[i][1] for i in reject],
                [metas[i][0] for i in reject],
                [metas[i][0] + len(pairs[i][1]) for i in reject], w)
            for i, arr in zip(reject, arrs):
                results[i] = arr

    def _launch_chunk(self, pairs, chunk, max_len, band, bp_meta=None):
        """The dispatch half of the aligner's dispatch-vs-fetch split:
        :meth:`_pack_chunk` and :meth:`_submit_chunk` back to back (the
        device computes after this returns). The ragged stream calls
        the two halves itself, with its wait for room between them."""
        return self._submit_chunk(
            self._pack_chunk(pairs, chunk, max_len, band, bp_meta))

    def _pack_chunk(self, pairs, chunk, max_len, band,
                    bp_meta=None) -> "_PackedChunk":
        """The host half of a launch: everything that touches no
        device, so it may run while an earlier chunk holds the whole
        direction-matrix budget.

        Sequences cross the host link as dense ``B * max_len`` byte
        blocks; the banded row layout (reversal, band offsets, padding) is
        built on device (:func:`_build_rows`) — the padded row arrays are
        ~3x the raw bases, so building them there cuts the bytes sent."""
        faults.check("align.dispatch")
        leaf = dict(pairs=len(chunk), max_len=max_len, band=band)
        with self._pinned(), obs.span("align.dispatch", **leaf), \
                obs.span("align.pack", **leaf):
            sw = self._swar_choice(max_len)
            n, m, seqs, kind, bp_host = self._pack_blocks(
                pairs, chunk, max_len, bp_meta, sw)
            steps = _sweep_bound(int((n + m).max()), max_len)
            self._count_arena(n, m, len(chunk), steps, band)
            # which bucket the pairs took (an escapee counts again at
            # the bucket of its re-dispatch)
            metrics.inc(f"align.pairs_by_bucket.{max_len}", len(chunk))
        return _PackedChunk(pairs, chunk, max_len, band, bp_meta, n, m,
                            seqs, kind, bp_host, steps, sw)

    def _submit_chunk(self, packed: "_PackedChunk"):
        """The device half of a launch: put a packed chunk's blocks and
        dispatch its kernels; returns the in-flight handle consumed by
        ``_finish_chunk``. Device work proceeds asynchronously after
        dispatch."""
        (pairs, chunk, max_len, band, bp_meta, n, m, seqs, kind, bp_host,
         steps, sw) = packed
        # the other two leaves of align.dispatch (align.pack is the
        # first): the host->device puts, the jit calls until they return
        leaf = dict(pairs=len(chunk), max_len=max_len, band=band)
        with self._pinned(), obs.span("align.dispatch", **leaf):
            # multi-host: every process packs the (deterministic) chunk
            # and materializes only its addressable shards of the global
            # arrays (the flat char blocks shard evenly too: B is a mesh
            # multiple, so [B * max_len] splits on row boundaries —
            # max_len is a multiple of 4, so the 2-bit blocks split
            # evenly as well)
            from ..parallel import to_global
            put = ((lambda a: to_global(self.mesh, a))
                   if self.mesh is not None else jnp.asarray)
            with obs.span("align.put", **leaf):
                nd, md = put(n), put(m)
                bp_dev = (None if bp_host is None
                          else [put(a) for a in bp_host])
                q_d, t_d = put(seqs[0]), put(seqs[1])
            device_time.submit("h2d", "align.put", t_d)
            with obs.span("align.launch", **leaf):
                build = {"2bit": _build_rows_packed2,
                         "nibble": _build_rows_packed,
                         "raw": _build_rows}[kind]
                B = n.shape[0]
                use_pallas = self._use_pallas((max_len, band, steps, B))
                if use_pallas:
                    from .pallas_nw import pallas_swar_ok
                    from .swar import mosaic_swar_fits
                    # the packed Mosaic kernel's XOR+mask equality reads
                    # 4-bit codes, so raw-byte chunks (alphabet > 15,
                    # rows not remapped) must never take it — bytes
                    # differing only in bits 4-7 would compare equal
                    # there; nor may a band under its geometry guard
                    sw = (sw and kind != "raw" and mosaic_swar_fits(band)
                          and pallas_swar_ok())
                g_build, g_chain, g_bp = _chunk_geometry(
                    max_len, band, steps, B, bp_meta[0] if bp_meta else 0,
                    bool(sw))
                qrp, tp = build(q_d, t_d, nd, md, max_len=max_len,
                                band=band)
                device_time.submit("exec", build.__name__, tp, g_build)
                args = (qrp, tp, nd, md)
                # no try/except around the dispatch: a Mosaic kernel
                # that does not compile or run for this shape fails the
                # run (the jit error names the function and shapes)
                out = self._dispatch(args, max_len, band, steps,
                                     use_pallas, sw)
                # the score vector, never the tables: the watcher must
                # hold nothing the direction-matrix budget counts
                device_time.submit(
                    "exec", "sharded_align" if self.mesh is not None
                    else "_pallas_align_chain" if use_pallas
                    else "align_chain", out[1], g_chain)
                if bp_dev is not None:
                    out = self._attach_bp(out, nd, md, bp_dev, bp_meta,
                                          max_len)
                    device_time.submit("exec", "_breaking_points_kernel",
                                       out[1], g_bp)
        # counted on the path actually taken: the Pallas-level
        # decision can differ from the XLA-level one
        self.stats["swar_chunks"] += int(sw)
        metrics.inc("aligner.swar_chunks", int(sw))
        # which kernel family ran: align.chunks counts every dispatch,
        # this the Mosaic ones (all of them on the chip, none off it)
        metrics.inc("aligner.pallas_chunks", int(use_pallas))
        return chunk, pairs, n, m, out, max_len

    def _pack_blocks(self, pairs, chunk, max_len, bp_meta, sw: bool):
        """A chunk's host arrays: ``(n, m, (q, t) packed sequence
        blocks, their kind, the bp kernel's host inputs | None)``;
        ``sw``: the bucket may run packed lanes (2-bit blocks then)."""
        # Pad the batch to a power of two: B is part of the compiled shape,
        # so arbitrary batch sizes would recompile the kernels every call.
        B = self._pad_batch(len(chunk))
        C = len(chunk)
        n = np.ones(B, dtype=np.int32)
        m = np.ones(B, dtype=np.int32)
        pools = []
        for side, lens in enumerate((n, m)):
            spans = [pairs[idx][side] for idx in chunk]
            lens[:C] = np.fromiter(map(len, spans), np.int64, C)
            pools.append(b"".join(spans))

        # host->device bytes are the bottleneck on thin links: when the
        # chunk's alphabet fits 4 symbols (ACGT does) and the SWAR path
        # is live, remap to 2-bit codes packed 16 per int32 word (4x
        # fewer bytes than raw); up to 15 symbols (ACGTN does) remap to
        # nibble codes (2x). Equality-preserving bijections either way —
        # the kernels only ever compare characters for equality. The
        # remap runs over the spans back to back (``bytes.translate``),
        # before the rows are spread into their padded blocks: a chunk
        # of 65,536 short pairs is 10 MB of bases in 17 MB of block
        alphabet = _alphabet(pools)
        lut = np.zeros(256, np.uint8)      # 0 is pad, and stays 0
        if sw and len(alphabet) <= 4:
            lut[alphabet] = np.arange(len(alphabet), dtype=np.uint8)
            kind = "2bit"
        elif len(alphabet) <= 15:
            lut[alphabet] = np.arange(1, len(alphabet) + 1, dtype=np.uint8)
            kind = "nibble"
        else:
            kind = "raw"
        qcat, tcat = (np.zeros(B * max_len, dtype=np.uint8)
                      for _ in range(2))
        for pool, lens, cat in zip(pools, (n, m), (qcat, tcat)):
            if kind != "raw":
                pool = pool.translate(lut.tobytes())
            _copy_rows(pool, lens[:C], cat.reshape(B, max_len))
        if kind == "2bit":
            from .swar import pack_bases_2bit
            seqs = (pack_bases_2bit(qcat), pack_bases_2bit(tcat))
        elif kind == "nibble":
            seqs = (qcat[0::2] | (qcat[1::2] << 4),
                    tcat[0::2] | (tcat[1::2] << 4))
        else:
            seqs = (qcat, tcat)
        bp_host = None
        if bp_meta is not None:
            # the breaking-points kernel's per-pair window geometry
            w, metas = bp_meta
            first_rel = np.zeros(B, np.int32)
            nb = np.ones(B, np.int32)
            t_begin = np.fromiter((metas[idx][0] for idx in chunk),
                                  np.int64, C)
            n_reg = (t_begin + m[:C] - 1) // w - t_begin // w
            nb[:C] = n_reg + 1
            first_rel[:C] = np.where(
                n_reg != 0, (t_begin // w + 1) * w - 1 - t_begin, m[:C] - 1)
            bp_host = (first_rel, nb)
        return n, m, seqs, kind, bp_host

    def _count_arena(self, n, m, pairs: int, steps: int, band: int) -> None:
        B = n.shape[0]
        # occupancy telemetry (round 17): the launch's wavefront arena
        # is B x steps band-wide DP rows; each real pair only produces
        # work on its own n+m anti-diagonals — the rest (batch pow2
        # padding + dead wavefronts past each pair's finish) is the
        # waste the ragged packer and band ladder exist to cut
        occ = int(n[:pairs].sum()) + int(m[:pairs].sum())
        total = B * steps
        self.stats["chunks"] += 1
        self.stats["lanes_occupied"] += occ
        self.stats["lanes_total"] += total
        self.stats["steps_wasted"] += total - occ
        self.stats["wavefront_work"] += total * band
        metrics.inc("align.chunks")
        metrics.inc("align.lanes_occupied", occ)
        metrics.inc("align.lanes_total", total)
        metrics.inc("align.steps_wasted", total - occ)
        metrics.inc("align.wavefront_work", total * band)

    def _attach_bp(self, out, nd, md, bp_dev, bp_meta, max_len: int):
        """In breaking-points mode, derive the per-boundary tables on
        device from the (device-resident) packed op stream; the stream
        itself is never fetched."""
        w, _ = bp_meta
        ops_packed, score, fi, fj = out
        NW = max_len // max(w, 1) + 2
        bp_first, bp_last = _breaking_points_kernel(
            ops_packed, nd, md, *bp_dev, w=w, NW=NW)
        return bp_first, bp_last, score, fi, fj

    def _dispatch(self, args, max_len, band, steps, use_pallas,
                  use_swar=False):
        if self.mesh is not None:
            from ..parallel import sharded_align
            out = sharded_align(self.mesh, *args, max_len=max_len,
                                band=band, steps=steps,
                                use_pallas=use_pallas, use_swar=use_swar)
        else:
            out = align_chain(*args, max_len=max_len, band=band,
                              steps=steps, use_pallas=use_pallas,
                              use_swar=use_swar)
        if use_swar:
            from .. import sanitize
            if self._shadow.should_shadow():
                # int32 shadow execution on the SAME walk backend (the
                # two walks place inactive-gap codes differently, so a
                # cross-backend compare would flag legitimate deltas):
                # isolates exactly the packed-lane arithmetic. Both
                # tuples come down through fetch_global — mesh runs hand
                # back global sharded arrays np.asarray cannot read.
                from ..parallel import fetch_global
                shadow = self._dispatch(args, max_len, band, steps,
                                        use_pallas, False)
                sanitize.shadow_compare(
                    fetch_global(list(out)), fetch_global(list(shadow)),
                    ("ops_packed", "score", "fi", "fj"),
                    f"aligner SWAR chunk (max_len={max_len}, "
                    f"band={band}, steps={steps})")
        return out

    def _finish_chunk(self, launched, band, cigars, reject, bp_meta=None):
        """Span-wrapped :meth:`_finish_chunk_impl` — the fetch half of
        the dispatch-vs-fetch split (blocks on the device result)."""
        faults.check("align.fetch")
        with self._pinned(), obs.span("align.fetch",
                                      pairs=len(launched[0]), band=band):
            self._finish_chunk_impl(launched, band, cigars, reject,
                                    bp_meta)

    def _finish_chunk_impl(self, launched, band, cigars, reject,
                           bp_meta=None):
        chunk, pairs, n, m, out, _max_len = launched
        from ..parallel import fetch_global
        wanted = list(out)
        # the three leaves of align.fetch: waiting for the device and
        # nothing else, the device->host copy, the host decode
        leaf = dict(pairs=len(chunk), band=band)
        with obs.span("align.wait", **leaf):
            jax.block_until_ready(wanted)
        with obs.span("align.get", **leaf):
            fetched = fetch_global(wanted)
        with obs.span("align.decode", **leaf):
            if bp_meta is not None:
                self._finish_chunk_bp(launched, fetched, band, cigars,
                                      reject, bp_meta)
            else:
                self._finish_chunk_cigar(launched, fetched, band, cigars,
                                         reject)

    def _finish_chunk_cigar(self, launched, fetched, band, cigars,
                            reject) -> None:
        chunk, pairs, n, m, out, _max_len = launched
        ops_packed, score, fi, fj = fetched
        from .. import sanitize
        if sanitize.enabled():
            sanitize.check_aligner_canaries(
                score, fi, fj, big=1 << 28,
                context=f"aligner chunk (band={band})")
        # unpack 4 codes/byte -> [B, 2L] uint8
        shifts = np.array([0, 2, 4, 6], dtype=np.uint8)
        ops = ((ops_packed[:, :, None] >> shifts) & 3).reshape(
            ops_packed.shape[0], -1)

        obs_scores: List[int] = []
        obs_maxlens: List[int] = []
        for k, idx in enumerate(chunk):
            diff = abs(int(n[k]) - int(m[k]))
            # real path codes are < 3 (a band escape stalls the walk,
            # leaving (fi, fj) != 0); inactive-gap codes interleave on the
            # Pallas walk and only trail on the XLA walk — filtering
            # handles both
            path = ops[k][ops[k] < 3]
            clean = (len(path) > 0 and int(fi[k]) == 0 and int(fj[k]) == 0)
            # adaptive-ladder signal: any CLEAN walk's finite score —
            # accepted (the true distance) or gate-failed (the banded
            # distance, an upper bound, i.e. a conservative estimate) —
            # a run whose first chunks all escape still teaches the
            # estimator to stop seeding low
            if clean and int(score[k]) < (1 << 28):
                obs_scores.append(int(score[k]))
                obs_maxlens.append(max(int(n[k]), int(m[k])))
            # optimality certificate: an optimal path's diagonal wander is
            # bounded by its edit count; require it inside the half band.
            if int(score[k]) <= band // 2 - diff - 2 and clean:
                cigars[idx] = _ops_to_cigar(path)
                self.stats["device"] += 1
            else:
                reject.append(idx)
        if obs_scores:
            self._observe_divergence(obs_scores, obs_maxlens)

    def _finish_chunk_bp(self, launched, fetched, band, results, reject,
                         bp_meta):
        """Breaking-points decode: convert the fetched per-boundary tables
        to columnar (k, 4) int32 row arrays for the WHOLE chunk in one
        vectorized pass (same accept/reject gate as the CIGAR path — the
        walk is complete and provably optimal inside the band, else
        escalate). The per-pair arrays are views into one flat buffer."""
        chunk, pairs, n, m, out, _max_len = launched
        w, metas = bp_meta
        bp_first, bp_last, score, fi, fj = fetched
        from .. import sanitize
        if sanitize.enabled():
            sanitize.check_aligner_canaries(
                score, fi, fj, big=1 << 28,
                context=f"aligner bp chunk (band={band})")
        BIG = 1 << 30
        C = len(chunk)
        n_h = np.asarray(n[:C], dtype=np.int64)
        m_h = np.asarray(m[:C], dtype=np.int64)
        diff = np.abs(n_h - m_h)
        clean = (np.asarray(fi[:C]) == 0) & (np.asarray(fj[:C]) == 0)
        score_h = np.asarray(score[:C], dtype=np.int64)
        accept = (score_h <= band // 2 - diff - 2) & clean
        # adaptive-ladder signal: every clean walk's finite score (see
        # the CIGAR path) — gate-failed ones are banded upper bounds,
        # so the estimate errs wide, never low
        seen = clean & (score_h < (1 << 28))
        if seen.any():
            self._observe_divergence(score_h[seen],
                                     np.maximum(n_h, m_h)[seen])
        tb, qo = np.array([metas[idx] for idx in chunk],
                          np.int64).reshape(C, 2).T
        n_reg = (tb + m_h - 1) // w - tb // w
        fp = np.asarray(bp_first[:C], dtype=np.int64)
        lp = np.asarray(bp_last[:C], dtype=np.int64)
        col = np.arange(fp.shape[1], dtype=np.int64)
        valid = (col[None, :] <= n_reg[:, None]) & (fp < BIG) \
            & accept[:, None]
        rows = np.stack(
            [tb[:, None] + (fp >> 14), qo[:, None] + (fp & 0x3FFF),
             tb[:, None] + (lp >> 14) + 1, qo[:, None] + (lp & 0x3FFF) + 1],
            axis=-1)
        flat = rows[valid].astype(np.int32)
        ends = np.cumsum(valid.sum(axis=1)).tolist()
        begin = 0
        for idx, ok, end in zip(chunk, accept.tolist(), ends):
            if ok:
                results[idx] = flat[begin:end]
            else:
                reject.append(idx)
            begin = end
        self.stats["device"] += int(accept.sum())

    # ------------------------------------------------------------- warm-up

    def _warmup_shapes(self, est_len: int, est_pairs: int,
                       window_length: int):
        """The ``(max_len, band, steps, B, window_length)`` chunk shapes
        the align stream is expected to dispatch for pairs of roughly
        ``est_len`` bases — the ladder seed rung for a typical
        low-divergence overlap plus the bucket-band escape rung — ONE
        source of truth consumed by :meth:`warmup_async`, derived with
        the same geometry/cap rules the stream uses."""
        g = self._seed_geometry(est_len, est_len, 0.05, record=False)
        if g is None:
            return []
        bi, band = g
        max_len, bucket_band = self.buckets[bi]
        bands = [band]
        if bucket_band not in bands:
            bands.append(bucket_band)
        shapes = []
        for bd in bands:
            steps = _sweep_bound(2 * est_len, max_len)
            cap = self._chunk_cap(steps, bd)
            # the launcher's own batch-padding rule (plain pow2 here:
            # warm-up never runs under a mesh) — warmup-coverage keeps
            # this shared with _pack_blocks
            B = self._pad_batch(min(cap, est_pairs))
            shapes.append((max_len, bd, steps, B, window_length))
        return shapes

    def warmup_async(self, est_len: int, est_pairs: int,
                     window_length: int = 500):
        """Background warm-up compilation of the expected align-chunk
        shapes (the aligner analog of ``TpuPoaConsensus.warmup_async``):
        the resident polishing service calls this at startup and per
        admitted job so job #1's alignment phase dispatches into a hot
        jit cache. Derives the ragged stream's chunk geometry
        (:meth:`_warmup_shapes`) and executes the full kernel chain —
        row build, wavefront DP, packed walk, breaking-points tables —
        once per shape on near-empty inputs (real lengths of 1, so the
        Pallas dynamic sweep bound makes the execution itself cheap;
        the compile is the product). Shape-deduped like the consensus
        warm-up, so repeat geometries are free; a wrong estimate wastes
        a background compile and nothing else. Returns the thread (for
        tests) or None when skipped (mesh runs, zero estimates, every
        shape already warmed)."""
        if self.mesh is not None or est_pairs <= 0 or est_len <= 0:
            return None
        shapes = [s for s in self._warmup_shapes(est_len, est_pairs,
                                                 window_length)
                  if s not in self._warmed_shapes]
        if not shapes:
            return None
        self._warmed_shapes.update(shapes)

        def _compile_one(max_len, band, steps, B, w):
            # the availability probes compile and run kernels, so they
            # belong on this thread too (same choice order as
            # _pack_chunk / _submit_chunk: ACGT chunks take the 2-bit path);
            # probed directly rather than via _swar_choice so the warm
            # thread never writes the stats dict the main thread owns
            from .swar import swar_fits, swar_ok
            sw = self.use_swar and swar_fits(max_len) and swar_ok()
            n = jnp.ones((B,), jnp.int32)
            m = jnp.ones((B,), jnp.int32)
            if sw:
                from .swar import pack_bases_2bit
                blk = jnp.asarray(pack_bases_2bit(
                    np.zeros(B * max_len, np.uint8)))
                build, blocks = _build_rows_packed2, (blk, blk)
            else:
                z = jnp.zeros((B * max_len,), jnp.uint8)
                build, blocks = _build_rows, (z, z)
            use_pallas = self._use_pallas((max_len, band, steps, B))
            if use_pallas and sw:
                from .pallas_nw import pallas_swar_ok
                from .swar import mosaic_swar_fits
                sw = mosaic_swar_fits(band) and pallas_swar_ok()
            g_build, g_chain, g_bp = _chunk_geometry(
                max_len, band, steps, B, w, bool(sw))
            qrp, tp = build(*blocks, n, m, max_len=max_len, band=band)
            # the warm-up's dummy programs occupy the device like any
            # other: the occupancy ledger counts them, as kind "warm"
            device_time.submit("warm", build.__name__, tp, g_build)
            out = align_chain(qrp, tp, n, m, max_len=max_len, band=band,
                              steps=steps, use_pallas=use_pallas,
                              use_swar=sw)
            device_time.submit(
                "warm", "_pallas_align_chain" if use_pallas
                else "align_chain", out[1], g_chain)
            if w:
                NW = max_len // max(w, 1) + 2
                bp = _breaking_points_kernel(
                    out[0], n, m, jnp.zeros((B,), jnp.int32),
                    jnp.ones((B,), jnp.int32), w=w, NW=NW)
                device_time.submit("warm", "_breaking_points_kernel",
                                   bp[1], g_bp)
            jax.block_until_ready(out[1])

        def _run():
            with self._pinned():
                for shape in shapes:
                    try:
                        _compile_one(*shape)
                    except Exception as e:
                        from ..utils.logger import log_swallowed
                        log_swallowed(
                            f"aligner warm-up shape {shape} failed "
                            f"(run()'s own shapes still compile on "
                            f"first use)", e)

        import threading

        # fire-and-forget by design: a daemon thread killed at exit
        # loses nothing but a speculative compile (same contract as the
        # consensus warm-up thread)
        # graftlint: disable=thread-lifecycle (droppable best-effort warm-up; daemon dies harmlessly at exit)
        th = threading.Thread(target=_run, daemon=True,
                              name="racon-align-warmup")
        th.start()
        return th


class _AlignStream:
    """Ragged streaming align session (round 17) — the aligner analog of
    ``poa._ConsensusStream``.

    Pairs arrive through :meth:`feed` in any number of slices; each is
    seeded a ``(bucket, band)`` geometry class (the band ladder's rung
    when an overlap-error estimate admits one) and classes greedy-fill
    device chunks against the engine's fixed direction-matrix arena
    budget **by each pair's actual sweep cost**: within a class, pairs
    sort longest-first and every chunk's pair cap is re-derived from its
    OWN head's sweep bound — short tail chunks both shrink their
    compiled step count and grow their batch, instead of every chunk
    paying one cap sized for the bucket's longest pair (the cudabatch
    batch-fill shape, ``cudabatch.cpp:54-62``; ``reduce_capacity``
    halves the arena under OOM backpressure).

    Full chunks dispatch ASYNCHRONOUSLY the moment they close, in two
    halves (:meth:`_launch`): the host pack of chunk k+1
    (``TpuAligner._pack_chunk``, no device touched) runs FIRST, while
    the chunks in flight compute; only then is room made under the
    in-flight byte budget (a fetch of chunk k, when k+1 does not fit
    beside it), and only after that do the puts and the kernel
    dispatch follow (``_submit_chunk``). The budget bounds what the
    device HOLDS, so put and launch wait for room — chunks that each
    take the whole budget still run one at a time on the device, never
    k+1's row arrays beside k's direction matrix — but the host pack
    holds nothing there and does not wait: per chunk the feeding
    thread pays max(pack k+1, device k), not their sum. Counter
    ``align.packed_ahead``: chunks whose submit had to fetch an earlier
    chunk after their pack. Otherwise fetches happen only at
    :meth:`finish` or under the pair bound. Band escapes re-enter
    the pending classes at their escalated rung and re-dispatch
    *batched*; geometry strictly escalates, so the drain loop
    terminates. Accepted alignments are byte-identical at every rung
    (the ``score <= band/2 - diff - 2`` accept gate is an optimality
    certificate: any cell whose value can influence a traceback
    decision is provably uninflated by the banding), and the terminal
    geometry sequence is the fixed path's, so the host-fallback reject
    set matches too — the {bucketed, ragged} x {fixed-band, ladder}
    byte-identity contract ``tests/test_align_stream.py`` locks.

    Resolved pairs release their span bytes immediately; the resident
    set is bounded by the in-flight pipeline plus one partial chunk per
    geometry class (``MAX_CHUNK_PAIRS`` bounds each), preserving the
    polisher's O(slice) transient-copy contract."""

    def __init__(self, eng: "TpuAligner", window_length=None,
                 progress=None, total_hint: int = 0):
        self.eng = eng
        self.w = window_length             # None -> CIGAR mode
        self.progress = progress
        self.total_hint = total_hint
        self.results: List = []            # per fed pair, feed order
        self.pairs: dict = {}              # slot -> (q, t), until resolved
        self.metas: dict = {}              # slot -> (t_begin, q_off)
        self.buffer: List = []             # (slot, err) awaiting a seed
        self.pending: dict = {}            # (bucket, band) -> [slot]
        self.reject: List[int] = []        # host-fallback slots
        self.inflight: List[dict] = []
        self.inflight_bytes = 0
        self.inflight_pairs = 0
        self.done_pairs = 0
        self._done = False
        self._est_warmed = False  # first-chunk eager fetch fired
        self._empty_bp = np.zeros((0, 4), dtype=np.int32)

    def _bp_meta(self):
        return None if self.w is None else (self.w, self.metas)

    def _tick(self) -> None:
        if self.progress is not None:
            self.progress(self.done_pairs,
                          max(self.total_hint, len(self.results)))

    # ------------------------------------------------------------- intake

    def feed(self, pairs, metas=None, errors=None) -> None:
        """Add a pair slice; packs and dispatches every chunk that
        fills. Returns without blocking unless the in-flight byte
        budget forces a (pipelined) fetch."""
        assert not self._done, "align stream already finished"
        for k, (q, t) in enumerate(pairs):
            slot = len(self.results)
            if len(q) == 0 or len(t) == 0:
                # resolved inline: no span, no meta retained
                if self.w is None:
                    self.results.append(f"{len(t)}D" if len(t) else
                                        (f"{len(q)}I" if len(q) else ""))
                else:
                    self.results.append(self._empty_bp)
                self.done_pairs += 1
                continue
            if self.w is not None:
                self.metas[slot] = metas[k]
            self.results.append("" if self.w is None else self._empty_bp)
            self.pairs[slot] = (q, t)
            # seeds are assigned at FLUSH time, not here: with the
            # ladder on, pairs buffered behind the cold-start probe are
            # seeded from OBSERVED divergence instead of the blind
            # span-asymmetry proxy
            self.buffer.append((slot,
                                None if errors is None else errors[k]))
        self._flush(final=False)
        self._tick()

    # ----------------------------------------------------------- dispatch

    def _classify(self, buffered) -> None:
        """Seed buffered pairs into (bucket, band) geometry classes
        with the estimator's CURRENT knowledge."""
        eng = self.eng
        # the estimator learns only at a fetch, so within one call a
        # seed is a function of (lengths, error): short reads share a
        # few hundred such keys among their hundreds of thousands of
        # pairs, and each key is seeded once
        seeds: dict = {}
        narrow = 0
        for slot, err in buffered:
            q, t = self.pairs[slot]
            key = (len(q), len(t), err)
            if key not in seeds:
                seeds[key] = eng._seed_geometry(*key, record=False)
            g = seeds[key]
            if g is None:
                eng.stats["fallback_length"] += 1
                metrics.inc("aligner.fallback_length")
                self.reject.append(slot)
            else:
                narrow += g[1] < eng.buckets[g[0]][1]
                self.pending.setdefault(g, []).append(slot)
        if narrow:
            # _seed_geometry's own count, taken once per call
            eng.stats["ladder_narrow"] += narrow
            metrics.inc("aligner.ladder_narrow", narrow)

    def _flush(self, final: bool) -> None:
        eng = self.eng
        # cold-start ladder probe: seed + force-dispatch + fetch a
        # small leading batch first (the eager fetch in _launch), so
        # every LATER seed uses observed divergence — without it, a
        # substitution-heavy run whose span-asymmetry estimates read
        # near zero would seed every chunk low and escape them all
        if (eng.use_ladder and self.buffer and not self._est_warmed
                and eng._adaptive_divergence() is None):
            if not final and len(self.buffer) < ALIGN_PROBE_PAIRS:
                return                     # wait for a probe's worth
            probe = self.buffer[:ALIGN_PROBE_PAIRS]
            self.buffer = self.buffer[ALIGN_PROBE_PAIRS:]
            self._classify(probe)
            self._drain(final=True)        # partial probe chunks too
        if self.buffer:
            self._classify(self.buffer)
            self.buffer = []
        self._drain(final)

    def _drain(self, final: bool) -> None:
        eng = self.eng
        for cls in sorted(self.pending):
            # drain a DETACHED list: _launch below may force a fetch
            # (_finish_oldest) whose escapees escalate into this very
            # class — they must land in a fresh pending entry, not be
            # appended behind the one-time longest-first sort (the head
            # invariant sizes the chunk cap and the in-flight bytes)
            slots = self.pending.pop(cls)
            bi, band = cls
            max_len = eng.buckets[bi][0]
            # longest first: a chunk's compiled sweep bound tracks its
            # OWN head, so similar-length pairs share chunks and short
            # tail chunks shrink their steps AND grow their batch
            # (the keys in one pass, then a stable descending sort:
            # the order a key of minus the cost gave, without a Python
            # call a pair inside the sort)
            cost = {s: len(q) + len(t)
                    for s, (q, t) in zip(slots, map(self.pairs.get, slots))}
            slots.sort(key=cost.__getitem__, reverse=True)
            while slots:
                q0, t0 = self.pairs[slots[0]]
                steps = _sweep_bound(len(q0) + len(t0), max_len)
                cap = eng._chunk_cap(steps, band)
                if not final and len(slots) < cap:
                    break                  # wait for more pairs
                chunk = slots[:cap]
                del slots[:cap]
                self._launch(cls, chunk, max_len, band)
            if slots:
                # re-merge the unfilled remainder with any escapees
                # that arrived mid-drain (order is irrelevant — the
                # next drain re-sorts)
                self.pending.setdefault(cls, []).extend(slots)

    def _launch(self, cls, chunk, max_len: int, band: int) -> None:
        eng = self.eng
        # the host half first: packing holds nothing on the device, so
        # it runs while the chunks in flight compute. The chunk's
        # members were fixed by _drain; a fetch below cannot change them
        # (its escapees land in ``pending``)
        packed = eng._pack_chunk(self.pairs, chunk, max_len, band,
                                 self._bp_meta())
        nbytes = packed.dirs_bytes
        # the direction-matrix budget bounds what is HELD on the device,
        # so room is made BEFORE the puts and the dispatch allocate the
        # new chunk's row arrays and matrix: launching first and
        # fetching after let two budget-sized chunks (8 GiB each)
        # coexist on a 16 GB chip
        ahead = False
        while (self.inflight
               and self.inflight_bytes + nbytes > eng.dirs_budget_cap):
            self._finish_oldest()
            ahead = True
        # packed while a chunk it could not sit beside was in flight
        metrics.inc("align.packed_ahead", int(ahead))
        launched = eng._submit_chunk(packed)
        entry = {"cls": cls, "chunk": chunk, "launched": launched,
                 "bytes": nbytes}
        self.inflight.append(entry)
        self.inflight_bytes += entry["bytes"]
        self.inflight_pairs += len(chunk)
        # cold-estimator eager fetch: with the ladder on, the FIRST
        # chunk fetches immediately so the adaptive divergence
        # estimator learns real scores before the pipeline fills —
        # otherwise a substitution-heavy run (whose span-asymmetry
        # estimates read near zero) seeds EVERY chunk low and escapes
        # them all; one pipeline bubble at run start buys the whole
        # run's seeds
        if (eng.use_ladder and not self._est_warmed
                and eng._adaptive_divergence() is None):
            self._finish_oldest()
        self._est_warmed = True
        # the pair bound keeps unresolved host span copies O(slice)
        # even when the chunks are byte-cheap (short pairs at narrow
        # rungs) — each unresolved pair pins its q/t byte copies
        while (len(self.inflight) > max(eng.num_batches, 1)
               and self.inflight_pairs > MAX_INFLIGHT_PAIRS):
            self._finish_oldest()

    def _finish_oldest(self) -> None:
        eng = self.eng
        la = self.inflight.pop(0)
        self.inflight_bytes -= la["bytes"]
        self.inflight_pairs -= len(la["chunk"])
        esc: List[int] = []
        eng._finish_chunk(la["launched"], la["cls"][1], self.results,
                          esc, self._bp_meta())
        esc_set = set(esc)
        for slot in la["chunk"]:
            if slot not in esc_set:
                # resolved: release the span bytes AND the meta tuple —
                # a whole-run session must not retain O(total) of either
                self.pairs.pop(slot, None)
                self.metas.pop(slot, None)
                self.done_pairs += 1
        bi, band = la["cls"]
        for slot in esc:
            q, t = self.pairs[slot]
            # graftlint: disable=warmup-coverage (escalation rungs are data-dependent and rare by design; the terminal rung — the bucket band — IS warmed as the escape shape)
            ng = eng._next_geometry(len(q), len(t), bi, band)
            if ng is None:
                eng.stats["fallback_band"] += 1
                metrics.inc("aligner.fallback_band")
                self.reject.append(slot)
            else:
                eng.stats["band_escalated"] += 1
                metrics.inc("aligner.band_escalated")
                self.pending.setdefault(ng, []).append(slot)
        self._tick()

    # -------------------------------------------------------------- drain

    def finish(self) -> List:
        """Dispatch the partial chunks, drain the pipeline (escapees
        re-dispatch batched at their wider rungs until none remain),
        run the host fallback; results for every fed pair in feed
        order."""
        assert not self._done, "align stream already finished"
        self._done = True
        eng = self.eng
        self._flush(final=True)
        while self.inflight or self.pending:
            while self.inflight:
                self._finish_oldest()
            self._flush(final=True)
        self.done_pairs += len(self.reject)
        eng._resolve_rejects(self.pairs, self.reject, self.results,
                             self._bp_meta())
        for slot in self.reject:
            self.pairs.pop(slot, None)
            self.metas.pop(slot, None)
        if self.progress is not None:
            total = max(self.total_hint, len(self.results))
            self.progress(total, total)
        return self.results
