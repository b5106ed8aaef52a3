"""Pallas TPU kernels for the banded wavefront NW forward pass + walk.

Why Pallas here (SURVEY §7's "centerpiece" kernel): the XLA ``lax.scan``
formulations round-trip their carries through HBM every wavefront step —
at ``band/2`` lanes per pair that is ~8 MB of carry traffic per step for a
2048-pair batch, making the kernel HBM-bound at ~45 µs/step. These kernels
keep the two live wavefronts **in VMEM/registers for the whole sweep** and
stream only the 2-bit direction planes to HBM (the data actually needed
later), which is the TPU analog of cudaaligner's shared-memory DP tiles
(``src/cuda/cudaaligner.cpp:39-44`` batch contract; one fused kernel per
batch like ``src/cuda/cudabatch.cpp:188-199``).

Layout contract (shared bit-for-bit with the XLA kernels in ``ops.nw`` so
either backend's output feeds either consumer):

- direction matrix: per wavefront ``a`` a row of ``RB = band/8`` bytes,
  planar 2-bit packing — lane ``u`` lives in byte ``u % RB`` at bit shift
  ``2 * (u // RB)`` (static contiguous slices in both producers);
- walk op codes: uint8, 0=M, 1=I, 2=D, >=3 inactive. The Pallas walk is
  *wavefront-synchronized*: one step per global anti-diagonal ``a`` from
  ``S`` down to 1, each pair acting only when its position sits on ``a``
  (an M step skips one diagonal, leaving an inactive-gap code 3). Codes
  stay in backward-walk order, so consumers that mask on ``op < 3``
  (``_vote_from_ops``, CIGAR RLE after filtering) accept both backends'
  outputs unchanged.

Mosaic's vector unit only addresses 128-lane-aligned windows, so every
dynamic access goes through one of two shapes:

- *aligned-load + dynamic roll* for the per-step character windows (load
  ``U + 128`` lanes at the enclosing 128-multiple, then ``pltpu.roll`` by
  the traced remainder — dynamic shifts are supported);
- *rolling 128-lane buffers* for sub-128 stores (direction rows and walk
  ops accumulate in a register buffer shifted ``RB``/1 lanes per step and
  flush to the output ref every 128 lanes at a ``pl.multiple_of`` offset).

The walk streams direction rows through a double-buffered VMEM window in
*descending-a* chunks (the only order the walk needs), so the matrix never
materializes in VMEM and arbitrarily long buckets fit.

Selection is by platform (``pallas_ok()``): off the TPU the XLA kernels
are the path and no Mosaic call is attempted; on the TPU both kernels are
probed once on a random small batch, bit-for-bit against the XLA reference
kernels, and a probe that raises or mismatches fails the run.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

_BIG = 1 << 28
# extra tail lanes so aligned-window loads never run off the char arrays
_LOAD_PAD = 256
# pair-block (sublane) caps: the TPU grid is sequential, so bigger blocks
# amortize per-step loop/DMA overhead across more pairs; 64 measured best
# on v5e for both kernels (32 leaves ~30% on the table, 128 regresses the
# walk); module constants so the profiling harness can sweep them
FWD_P_CAP = 64
WALK_P_CAP = 64
# VMEM budget for the walk kernels' double-buffered chunk window — long
# aligner buckets shrink the pair-block (P) instead of overflowing VMEM
# (the fwd kernel streams its direction rows to HBM by DMA, so it has no
# comparable per-block buffer)
_WALK_BUF_BYTES = 4 * 1024 * 1024


def _cap_block(B: int, per_pair_bytes: int, budget: int) -> int:
    # Mosaic block sublane counts below 8 fail to lower ("Sublane
    # broadcast" errors at B < 4, tiling pessimization below 8), so P
    # never drops below 8 — wrappers pad tiny batches up to 8 rows first.
    # B is always a power of two >= 8 here (wrappers pad), so the halving
    # loop keeps P a power-of-two divisor of B; assert rather than
    # silently truncating grid rows if a future caller breaks that.
    assert B >= 8 and (B & (B - 1)) == 0, f"batch {B} not a power of two"
    P = min(WALK_P_CAP, B)
    while P > 8 and P * per_pair_bytes > budget:
        P //= 2
    return P


def _pad_rows(arrs, B: int, fills):
    """Pad each (B, ...) array to 8 rows (the minimum Mosaic-legal pair
    block); padded rows get ``fill`` and callers slice outputs back."""
    pad = 8 - B
    return [jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1),
                    constant_values=f) for a, f in zip(arrs, fills)]


def _rup(x: int, k: int) -> int:
    return -(-x // k) * k


def _load_window(ref, off, width: int, U: int):
    """Load ``U`` lanes at traced offset ``off`` (clamped like XLA's
    ``dynamic_slice_in_dim``) via an aligned wide load + dynamic roll
    (Mosaic's vector unit only addresses 128-lane-aligned windows, and
    ``tpu.dynamic_rotate`` wants int32 at 128-multiple widths)."""
    offc = jnp.clip(off, 0, width - U)
    base = pl.multiple_of((offc // 128) * 128, 128)
    W2 = _rup(U, 128) + 128
    win = ref[:, pl.ds(base, W2)].astype(jnp.int32)
    r = offc - base
    return pltpu.roll(win, shift=(W2 - r) % W2, axis=1)[:, :U]


def _fwd_kernel(qrp_ref, tp_ref, n_ref, m_ref, dirs_ref, score_ref,
                stage, dsems, *, max_len: int, band: int, P: int,
                width: int, steps: int, PER: int, out_quant: int):
    W = band
    c = W // 2
    L = max_len
    U = W // 2
    RB = U // 4
    S = steps
    # flush F wavefront rows per 128-aligned stage write (F*RB =
    # lcm(RB, 128)); every PER stage writes, DMA the staged rows to HBM —
    # the direction matrix streams out instead of occupying a VMEM output
    # block, so arbitrarily long buckets fit
    FL = RB
    while FL % 128:
        FL += RB
    F = FL // RB
    FPL = FL * PER
    blk = pl.program_id(0)
    nn = n_ref[:, :]  # (P, 1) i32
    mm = m_ref[:, :]
    us = lax.broadcasted_iota(jnp.int32, (P, U), 1)

    def stage_dma(slot, fidx):
        # DMA stage slot -> dirs rows ending at flush index fidx
        base = (fidx + 1) * FL - FPL
        return pltpu.make_async_copy(
            stage.at[slot],
            dirs_ref.at[pl.ds(blk * P, P),
                        pl.ds(pl.multiple_of(base, 128), FPL)],
            dsems.at[slot])

    p0 = c & 1
    u0 = (c - p0) // 2
    # `zrow` is zero for every real length but opaque to constant folding:
    # adding it forces a row-varying (non-sublane-replicated) Mosaic layout
    # on the loop carries — the body's outputs are row-varying and Mosaic
    # cannot relayout varying data into a replicated carry
    zrow = jnp.minimum(nn, 0)
    v0 = jnp.where(us == u0, 0, _BIG) + zrow
    vm1 = jnp.full((P, U), _BIG, jnp.int32) + zrow
    # final scores accumulate elementwise into a (P, U) vector (one lane
    # per pair is ever written); the cross-lane reduce happens ONCE after
    # the sweep instead of once per wavefront
    svec0 = jnp.full((P, U), _BIG, jnp.int32) + zrow
    dbuf0 = jnp.zeros((P, FL), jnp.int32) + zrow

    def substep(a, p, v1, v2, svec, dbuf, qchars, tchars, trim):
        """One wavefront with *statically known* parity ``p`` (the
        two-step loop body alternates p=1 then p=0, so every branch on
        parity folds at trace time). ``trim`` (static) drops the DP
        boundary-row/column handling: for a > c the band sits strictly
        inside the table (i >= 1 and j >= 1 on every lane), so only the
        upper length bounds remain — the bulk of the sweep runs ~6 fewer
        VPU ops per lane."""
        I0 = (a + c - p) // 2
        J0 = (a - c + p) // 2
        i_vec = I0 - us
        j_vec = J0 + us

        # shifted views of wavefront a-1 (parity alternates):
        #   p == 0: D-source = v1[u-1], I-source = v1[u]
        #   p == 1: D-source = v1[u],   I-source = v1[u+1]
        if p == 0:
            d_src = jnp.where(us == 0, _BIG,
                              pltpu.roll(v1, shift=1, axis=1))
            i_src = v1
        else:
            d_src = v1
            i_src = jnp.where(us == U - 1, _BIG,
                              pltpu.roll(v1, shift=U - 1, axis=1))

        sub = jnp.where(qchars == tchars, 0, 1)
        cd = v2 + sub          # diagonal (i-1, j-1)
        ci = i_src + 1         # consume query (i-1, j)
        cdel = d_src + 1       # consume target (i, j-1)
        best = jnp.minimum(cd, jnp.minimum(ci, cdel))
        d = jnp.where(cd == best, 0, jnp.where(ci == best, 1, 2))

        if trim:
            interior = (i_vec <= nn) & (j_vec <= mm)
            v = jnp.where(interior, jnp.minimum(best, _BIG), _BIG)
        else:
            interior = ((i_vec >= 1) & (i_vec <= nn)
                        & (j_vec >= 1) & (j_vec <= mm))
            v = jnp.where(interior, jnp.minimum(best, _BIG), _BIG)
            v = jnp.where((i_vec == 0) & (j_vec >= 0) & (j_vec <= mm),
                          j_vec, v)
            v = jnp.where((j_vec == 0) & (i_vec >= 1) & (i_vec <= nn),
                          i_vec, v)

        # final score lives at a == n + m, u_fin = (m - n + c - p) / 2
        u_fin = jnp.clip((mm - nn + c - p) // 2, 0, U - 1)
        svec = jnp.where((a == nn + mm) & (us == u_fin), v, svec)

        packed = (d[:, :RB] | (d[:, RB:2 * RB] << 2)
                  | (d[:, 2 * RB:3 * RB] << 4) | (d[:, 3 * RB:] << 6))
        if FL == RB:
            # rows are already 128-aligned (F == 1): no accumulation
            dbuf = packed
        else:
            # rolling flush buffer: row a lands in the last RB lanes; every
            # F wavefronts it holds rows a-F+1..a and moves to the stage
            dbuf = pltpu.roll(dbuf, shift=FL - RB, axis=1)
            dbuf = jnp.concatenate([dbuf[:, :FL - RB], packed], axis=1)

        @pl.when(a % F == 0)
        def _():
            fidx = a // F - 1            # 0-based flush index
            slot = (fidx // PER) % 2

            # reusing a slot: its previous DMA must have drained
            @pl.when((fidx % PER == 0) & (fidx >= 2 * PER))
            def _():
                stage_dma(slot, fidx - PER).wait()

            stage[slot, :, pl.ds(pl.multiple_of((fidx % PER) * FL, 128),
                                 FL)] = dbuf.astype(jnp.uint8)

            @pl.when(fidx % PER == PER - 1)
            def _():
                stage_dma(slot, fidx).start()

        return v, v1, svec, dbuf

    # two wavefronts per iteration: with even c, parity is a & 1, so the
    # body sees p statically — and the character windows only advance on
    # one parity each (q on even a, t on odd a), halving the expensive
    # aligned-load + dynamic-roll work to one q- and one t-load per pair
    # of steps (odd a reuses the previous even step's query window; even
    # a reuses the odd step's target window)
    assert c % 2 == 0, "band/2 must be even for the two-step parity fold"
    qch0 = _load_window(qrp_ref, c + L - c // 2, width, U)

    def two_steps(k, carry, trim):
        v1, v2, svec, dbuf, qch = carry
        a1 = 2 * k + 1                   # p = 1
        tch = _load_window(tp_ref, c + (a1 - c + 1) // 2 - 1, width, U)
        v1, v2, svec, dbuf = substep(a1, 1, v1, v2, svec, dbuf,
                                     qch, tch, trim)
        a2 = 2 * k + 2                   # p = 0
        qch = _load_window(qrp_ref, c + L - (a2 + c) // 2, width, U)
        v1, v2, svec, dbuf = substep(a2, 0, v1, v2, svec, dbuf,
                                     qch, tch, trim)
        return v1, v2, svec, dbuf, qch

    # per-block dynamic sweep bound: no wavefront beyond the block's
    # longest pair ever matters (scores land at a == n+m; the walks only
    # read rows a <= n+m), so the trip count is traced — blocks of short
    # (or zero-length) pairs stop early. Unwritten dirs rows past the
    # bound are never read.
    # round to whole flush-DMA groups (F*PER steps) AND whole consumer
    # read groups (``out_quant``: 512 rows = 4 chunks for the packed
    # aligner walk, which rounds its start DOWN to a 512-row group; 128
    # for the consensus vote walk), so the staging protocol stays intact
    # and the walks' chunk DMAs never read unwritten rows; F and PER are
    # powers of two <= 256, so one quantum divides the other
    QB = max(out_quant, F * PER)
    assert QB % 128 == 0 and QB % (F * PER) == 0, (F, PER)
    maxnm = jnp.max(nn + mm)
    bound = jnp.minimum(jnp.int32(S), ((maxnm + QB - 1) // QB) * QB)

    # split the sweep at a == c: boundary rows/columns can only appear on
    # wavefronts a <= c (i == 0 needs I0 < U, j == 0 needs J0 <= 0), so
    # every later wavefront runs the trimmed substep
    ksplit = jnp.minimum(jnp.int32(c // 2), bound // 2)
    carry = lax.fori_loop(
        0, ksplit, functools.partial(two_steps, trim=False),
        (v0, vm1, svec0, dbuf0, qch0))
    _, _, svec, _, _ = lax.fori_loop(
        ksplit, bound // 2, functools.partial(two_steps, trim=True), carry)
    score = jnp.min(svec, axis=1, keepdims=True)
    score_ref[:, :] = jnp.where(nn + mm == 0, 0, score)

    # drain outstanding DMAs (one or two slots in flight at the end).
    # Slot indices stay static: each slot's last flush group is derived
    # from the traced bound and guarded by whether it ever fired.
    NFb = bound // F
    last = NFb // PER - 1  # last flush-group index (groups are PER flushes)
    for s in (0, 1):
        g = last - ((last - s) % 2)

        @pl.when((NFb > 0) & (g >= 0))
        def _(s=s, g=g):
            stage_dma(s, (g + 1) * PER - 1).wait()


def _fwd_kernel_swar(qrp_ref, tp_ref, n_ref, m_ref, dirs_ref, score_ref,
                     stage, dsems, *, max_len: int, band: int, P: int,
                     width: int, steps: int, PER: int, out_quant: int):
    """SWAR-packed forward kernel: two int16 wavefront scores per int32
    lane, biased-unsigned halfword arithmetic (``ops.swar``), so the
    carry state, the rolls and every min/add run on half the vector
    lanes. **Planar** halfword layout — packed word ``k`` holds lanes
    ``u = k`` (low) and ``u = k + U/4`` (high) — so the DP's +-1 lane
    shifts stay single-word rolls (one seam word fixed per shift) and
    the 2-bit direction planes fall out of the halfword halves with no
    cross-lane shuffle. Bit-identical direction matrix and scores vs
    ``_fwd_kernel`` (see the ``ops.swar`` module docstring for why the
    saturation classes line up); probed by ``pallas_swar_ok()``."""
    from .swar import (BIG16, LO16, ONES16, TWOS16, swar16_eq, swar16_ge,
                       swar16_ne_small, swar16_sel)
    W = band
    c = W // 2
    L = max_len
    U = W // 2
    U2 = U // 2           # packed words per wavefront
    RB = U // 4
    S = steps
    FL = RB
    while FL % 128:
        FL += RB
    F = FL // RB
    FPL = FL * PER
    blk = pl.program_id(0)
    nn = n_ref[:, :]
    mm = m_ref[:, :]
    lane = lax.broadcasted_iota(jnp.int32, (P, U2), 1)
    # packed u iota: low field u = k, high field u = k + U2 (planar)
    usp = lane | ((lane + U2) << 16)
    usp1 = usp + ONES16   # u + 1 (inclusive upper bounds compare via +1)
    BIGS = jnp.int32(BIG16 * 0x00010001)

    def stage_dma(slot, fidx):
        base = (fidx + 1) * FL - FPL
        return pltpu.make_async_copy(
            stage.at[slot],
            dirs_ref.at[pl.ds(blk * P, P),
                        pl.ds(pl.multiple_of(base, 128), FPL)],
            dsems.at[slot])

    assert c % 2 == 0, "band/2 must be even for the two-step parity fold"
    p0 = c & 1
    u0 = (c - p0) // 2
    zrow = jnp.minimum(nn, 0)  # row-varying layout forcer (_fwd_kernel)
    lo0 = jnp.where(lane == u0, 0, BIG16)
    hi0 = jnp.where(lane == u0 - U2, 0, BIG16)
    v0 = (lo0 | (hi0 << 16)) + zrow
    vm1 = jnp.full((P, U2), BIGS, jnp.int32) + zrow
    svec0 = jnp.full((P, U2), BIGS, jnp.int32) + zrow
    dbuf0 = jnp.zeros((P, FL), jnp.int32) + zrow

    def substep(a, p, v1, v2, svec, dbuf, qpl, tpl, trim):
        I0 = (a + c - p) // 2
        J0 = (a - c + p) // 2

        # +-1 lane shifts: both planar halves shift together, so one
        # word roll + one seam-word fixup replaces the halfword shuffle
        # an interleaved layout would need on every lane
        if p == 0:
            r = pltpu.roll(v1, shift=1, axis=1)   # word k <- v1[k-1]
            # seam word 0: low = BIG (u = -1), high = v1[U2-1].low
            d_src = jnp.where(lane == 0, (r << 16) | BIG16, r)
            i_src = v1
        else:
            d_src = v1
            r = pltpu.roll(v1, shift=U2 - 1, axis=1)  # word k <- v1[k+1]
            # seam word U2-1: low = v1[0].high (u = U2), high = BIG
            i_src = jnp.where(lane == U2 - 1,
                              ((r >> 16) & LO16) | (BIG16 << 16), r)

        # XOR + mask SWAR equality on the packed 4-bit codes
        sub = swar16_ne_small(qpl ^ tpl, 4)
        cd = v2 + sub          # diagonal (i-1, j-1)
        ci = i_src + ONES16    # consume query (i-1, j)
        cdel = d_src + ONES16  # consume target (i, j-1)
        mB = swar16_ge(cdel, ci)    # I beats D on ties (walker order)
        m2 = swar16_sel(ci, cdel, mB)
        mA = swar16_ge(m2, cd)      # diagonal wins ties
        best = swar16_sel(cd, m2, mA)
        d = swar16_sel(ONES16, TWOS16, mB) & ~mA  # 0 where diag won

        # interior as a contiguous lane range [lo, hi] (the four i/j
        # bounds are monotone in u), checked per halfword against the
        # packed u iota; saturation folds into the same select
        if trim:
            lo = jnp.maximum(I0 - nn, 0)
            hi1 = jnp.clip(mm - J0 + 1, 0, U)
        else:
            lo = jnp.maximum(jnp.maximum(I0 - nn, 1 - J0), 0)
            hi1 = jnp.clip(jnp.minimum(mm - J0, I0 - 1) + 1, 0, U)
        rng_m = (swar16_ge(usp, lo * ONES16)
                 & swar16_ge(hi1 * ONES16, usp1))
        v = swar16_sel(best, BIGS, swar16_ge(BIGS, best) & rng_m)
        if not trim:
            # DP boundary rows/cols (only reachable at a <= c): at
            # i == 0 the value is j = a, at j == 0 it is i = a — one
            # shared select with per-pair validity predicates
            pj = jnp.where(a <= mm, -1, 0)
            pi = jnp.where(a <= nn, -1, 0)
            bm = ((swar16_eq(usp, I0 * ONES16) & pj)
                  | (swar16_eq(usp, (-J0) * ONES16) & pi))
            v = swar16_sel(a * ONES16, v, bm)

        # final score lives at a == n + m, u_fin = (m - n + c - p) / 2
        u_fin = jnp.clip((mm - nn + c - p) // 2, 0, U - 1)
        fm = (swar16_eq(usp, u_fin * ONES16)
              & jnp.where(a == nn + mm, -1, 0))
        svec = swar16_sel(v, svec, fm)

        # planar 2-bit pack straight off the halfword halves: byte k =
        # lanes (k, k+RB, k+2RB, k+3RB) = (t1.lo, t2.lo, t1.hi, t2.hi)
        t1 = d[:, :RB]
        t2 = d[:, RB:]
        packed = ((t1 & 3) | ((t2 & 3) << 2) | (((t1 >> 16) & 3) << 4)
                  | (((t2 >> 16) & 3) << 6))
        if FL == RB:
            dbuf = packed
        else:
            dbuf = pltpu.roll(dbuf, shift=FL - RB, axis=1)
            dbuf = jnp.concatenate([dbuf[:, :FL - RB], packed], axis=1)

        @pl.when(a % F == 0)
        def _():
            fidx = a // F - 1            # 0-based flush index
            slot = (fidx // PER) % 2

            @pl.when((fidx % PER == 0) & (fidx >= 2 * PER))
            def _():
                stage_dma(slot, fidx - PER).wait()

            stage[slot, :, pl.ds(pl.multiple_of((fidx % PER) * FL, 128),
                                 FL)] = dbuf.astype(jnp.uint8)

            @pl.when(fidx % PER == PER - 1)
            def _():
                stage_dma(slot, fidx).start()

        return v, v1, svec, dbuf

    def planar(win):
        return win[:, :U2] | (win[:, U2:] << 16)

    qpl0 = planar(_load_window(qrp_ref, c + L - c // 2, width, U))

    def two_steps(k, carry, trim):
        v1, v2, svec, dbuf, qpl = carry
        a1 = 2 * k + 1                   # p = 1
        tpl = planar(_load_window(tp_ref, c + (a1 - c + 1) // 2 - 1,
                                  width, U))
        v1, v2, svec, dbuf = substep(a1, 1, v1, v2, svec, dbuf,
                                     qpl, tpl, trim)
        a2 = 2 * k + 2                   # p = 0
        qpl = planar(_load_window(qrp_ref, c + L - (a2 + c) // 2,
                                  width, U))
        v1, v2, svec, dbuf = substep(a2, 0, v1, v2, svec, dbuf,
                                     qpl, tpl, trim)
        return v1, v2, svec, dbuf, qpl

    QB = max(out_quant, F * PER)
    assert QB % 128 == 0 and QB % (F * PER) == 0, (F, PER)
    maxnm = jnp.max(nn + mm)
    bound = jnp.minimum(jnp.int32(S), ((maxnm + QB - 1) // QB) * QB)

    ksplit = jnp.minimum(jnp.int32(c // 2), bound // 2)
    carry = lax.fori_loop(
        0, ksplit, functools.partial(two_steps, trim=False),
        (v0, vm1, svec0, dbuf0, qpl0))
    _, _, svec, _, _ = lax.fori_loop(
        ksplit, bound // 2, functools.partial(two_steps, trim=True), carry)
    s16 = jnp.minimum(
        jnp.min(svec & LO16, axis=1, keepdims=True),
        jnp.min((svec >> 16) & LO16, axis=1, keepdims=True))
    s32 = jnp.where(s16 == BIG16, jnp.int32(_BIG), s16)
    score_ref[:, :] = jnp.where(nn + mm == 0, 0, s32)

    NFb = bound // F
    last = NFb // PER - 1
    for s in (0, 1):
        g = last - ((last - s) % 2)

        @pl.when((NFb > 0) & (g >= 0))
        def _(s=s, g=g):
            stage_dma(s, (g + 1) * PER - 1).wait()


@functools.partial(jax.jit, static_argnames=("max_len", "band", "steps",
                                             "out_quant", "use_swar"))
def pallas_nw_fwd(qrp, tp, n, m, *, max_len: int, band: int,
                  steps: int = 0, out_quant: int = 128,
                  use_swar: bool = False):
    """Drop-in Pallas replacement for ``_nw_wavefront_kernel``: same
    inputs, same packed direction matrix [B, steps, RB] and scores [B]
    (``steps`` defaults to the full ``2*max_len`` sweep). ``out_quant``
    is the downstream walk's read granularity in rows: 512 when the
    packed-output aligner walk consumes the matrix, 128 (default) for
    the consensus vote walk — the dynamic sweep bound rounds up to it so
    the consumer never reads unwritten rows. ``use_swar`` runs the
    int16x2-packed variant (``_fwd_kernel_swar``, bit-identical
    outputs); callers gate it on ``pallas_swar_ok()`` plus the
    ``swar.swar_fits`` overflow guard."""
    B0, width = qrp.shape
    if B0 < 8:
        qrp, tp, n, m = _pad_rows([qrp, tp, n, m], B0, [0, 0, 1, 1])
    B = qrp.shape[0]
    U = band // 2
    RB = U // 4
    S = steps if steps else 2 * max_len
    P = min(FWD_P_CAP, B)
    FL = RB
    while FL % 128:
        FL += RB
    F = FL // RB
    if S % F or S % 2:
        raise ValueError(
            f"steps={S} must be even and divisible by the dirs flush "
            f"period {F} (band={band}); round steps up to a multiple "
            f"of 128")
    # stage ~2-4 KB per DMA, PER a power-of-two divisor of the flush count
    PER = 1
    while (PER * 2 * FL <= 4096 and (S // F) % (PER * 2) == 0):
        PER *= 2
    qrp = jnp.pad(qrp, ((0, 0), (0, _LOAD_PAD)))
    tp = jnp.pad(tp, ((0, 0), (0, _LOAD_PAD)))
    fwd = _fwd_kernel_swar if use_swar else _fwd_kernel
    kernel = functools.partial(fwd, max_len=max_len, band=band,
                               P=P, width=width, steps=S, PER=PER,
                               out_quant=out_quant)
    dirs, score = pl.pallas_call(
        kernel,
        grid=(B // P,),
        in_specs=[
            pl.BlockSpec((P, width + _LOAD_PAD), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((P, width + _LOAD_PAD), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((P, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((P, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((P, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S * RB), jnp.uint8),
            jax.ShapeDtypeStruct((B, 1), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, P, FL * PER), jnp.uint8),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )(qrp, tp, n.reshape(B, 1).astype(jnp.int32),
      m.reshape(B, 1).astype(jnp.int32))
    return dirs.reshape(B, S, RB)[:B0], score.reshape(B)[:B0]


def _chunk_dma_factory(dirs_ref, buf, sems, blk, *, P, C, RB, S):
    """Double-buffered descending-a chunk DMA: chunk k holds direction
    rows [S - (k+1)*C, S - k*C) — the walk consumes rows backwards."""
    def chunk_dma(slot, k):
        lo = S - (k + 1) * C
        return pltpu.make_async_copy(
            dirs_ref.at[pl.ds(blk * P, P),
                        pl.ds(pl.multiple_of(lo * RB, 128), C * RB)],
            buf.at[slot, :, pl.ds(0, C * RB)],
            sems.at[slot])
    return chunk_dma


def _walk_step_decode(buf, slot, lo, a, i, j, lane_ww, *, c, U, RB, WW):
    """One wavefront-synchronized walk step, shared by the plain walk and
    the fused walk+vote kernel (the trickiest logic in this file — keep
    one copy): decode the pair's direction byte from an aligned window of
    the chunk buffer, apply boundary overrides, and gate on activity.
    Returns (op, di, dj, active) as (P, 1) vectors."""
    p = (a + c) & 1
    u = (j - i + c - p) // 2
    done = (i == 0) & (j == 0)
    escaped = (i > 0) & (j > 0) & ((u < 0) | (u >= U))
    active = ((i + j) == a) & ~done & ~escaped

    # the row may straddle a 128-lane boundary (offsets are RB-granular);
    # WW covers it, masked tail reads are never selected
    uc = jnp.clip(u, 0, U - 1)
    roff = (a - 1 - lo) * RB
    rbase = pl.multiple_of((roff // 128) * 128, 128)
    win = buf[slot, :, pl.ds(rbase, WW)]
    bidx = (roff - rbase) + uc % RB
    sel = jnp.sum(jnp.where(lane_ww == bidx, win.astype(jnp.int32), 0),
                  axis=1, keepdims=True)
    d = (sel >> (2 * (uc // RB))) & 3
    d = jnp.where(i == 0, 2, d)               # only D left
    d = jnp.where((j == 0) & (i > 0), 1, d)   # only I left
    op = jnp.where(active, d, 3)
    di = jnp.where(active & (op != 2), 1, 0)  # M/I consume query
    dj = jnp.where(active & (op != 1), 1, 0)  # M/D consume target
    return op, di, dj, active


def _walk_start(nn, mm, chunk_dma, blank_group, *, S: int, C: int,
                CHUNKS: int, group_chunks: int = 1):
    """Shared dynamic-start preamble of both walk kernels: compute the
    first live chunk (the walk begins at a = n + m, so leading
    descending-a chunks with no active pair are skipped), blank the
    skipped output range via ``blank_group(g)`` (group ``g`` covers
    chunks ``[g*group_chunks, (g+1)*group_chunks)`` — the packed-output
    walk needs 4 chunks per 128-byte-aligned store) so consumers see
    exactly what the XLA walk emits there, and prefetch the first live
    chunk's DMA (skipped entirely when the block has nothing to walk)."""
    maxnm = jnp.max(nn + mm)
    k0 = (S - jnp.minimum(jnp.int32(S), ((maxnm + C - 1) // C) * C)) // C
    k0 = (k0 // group_chunks) * group_chunks

    def blank(g, _):
        blank_group(g)
        return 0

    lax.fori_loop(0, k0 // group_chunks, blank, 0)

    @pl.when(k0 < CHUNKS)
    def _():
        chunk_dma(k0 % 2, k0).start()

    return k0


def _walk_kernel(dirs_ref, n_ref, m_ref, ops_ref, fi_ref, fj_ref,
                 buf, sems, *, band: int, P: int, C: int, steps: int):
    """Walk emitting the aligner's 2-bit x 4-per-byte PACKED op stream
    directly (``ops_ref`` is [B, S//4] uint8): the downstream `_pack_ops`
    pass disappears, the output writes shrink 4x, and the rolling output
    buffer shifts once per 4 steps instead of every step. The inner loop
    is unrolled 4 steps per iteration so the 2-bit shifts stay static."""
    W = band
    c = W // 2
    U = W // 2
    RB = U // 4
    S = steps
    CHUNKS = S // C
    GC = 512 // C              # chunks per 128-byte output flush group
    WW = _rup(128 + RB, 128)   # byte-select window (row may straddle 128s)
    blk = pl.program_id(0)
    nn = n_ref[:, :]
    mm = m_ref[:, :]
    lane_ww = lax.broadcasted_iota(jnp.int32, (P, WW), 1)
    chunk_dma = _chunk_dma_factory(dirs_ref, buf, sems, blk,
                                   P=P, C=C, RB=RB, S=S)

    def blank_group(g):
        # 4 steps of the inactive code 3 pack to 0xFF
        ops_ref[:, pl.ds(pl.multiple_of(g * 128, 128), 128)] = \
            jnp.full((P, 128), 255, jnp.uint8)

    k0 = _walk_start(nn, mm, chunk_dma, blank_group, S=S, C=C,
                     CHUNKS=CHUNKS, group_chunks=GC)
    # min(nn, 0) == 0 forces a row-varying carry layout (_fwd_kernel note)
    obuf0 = jnp.full((P, 128), 255, jnp.int32) + jnp.minimum(nn, 0)

    def chunk_body(k, carry):
        i, j, obuf = carry
        slot = k % 2

        @pl.when(k + 1 < CHUNKS)
        def _():
            chunk_dma((k + 1) % 2, k + 1).start()

        chunk_dma(slot, k).wait()
        lo = S - (k + 1) * C

        def quad_body(s4, carry):
            i, j, obuf = carry            # (P, 1) positions before step
            cur = jnp.zeros((P, 1), jnp.int32)
            for r in range(4):
                t = k * C + s4 * 4 + r    # emitted step index, asc.
                a = S - t                 # global anti-diagonal, desc.
                op, di, dj, _ = _walk_step_decode(buf, slot, lo, a, i, j,
                                                  lane_ww, c=c, U=U, RB=RB,
                                                  WW=WW)
                cur = cur | (op << (2 * r))
                i = i - di
                j = j - dj

            # rolling packed-byte buffer, flushed 128-aligned every
            # 128 bytes (= 512 steps)
            obuf = pltpu.roll(obuf, shift=127, axis=1)
            obuf = jnp.concatenate([obuf[:, :127], cur], axis=1)
            q = (k * C) // 4 + s4         # global packed-byte index

            @pl.when((q + 1) % 128 == 0)
            def _():
                off = pl.multiple_of(q + 1 - 128, 128)
                ops_ref[:, pl.ds(off, 128)] = obuf.astype(jnp.uint8)

            return i, j, obuf

        return lax.fori_loop(0, C // 4, quad_body, (i, j, obuf))

    fi, fj, _ = lax.fori_loop(k0, CHUNKS, chunk_body, (nn, mm, obuf0))
    fi_ref[:, :] = fi
    fj_ref[:, :] = fj


@functools.partial(jax.jit, static_argnames=("band",))
def pallas_walk_ops(dirs, n, m, *, band: int):
    """Wavefront-synchronized walk over the packed direction matrix.

    Returns ``(ops_packed [B, S//4] u8, fi, fj)`` — the same 2-bit x
    4-per-byte packing `_pack_ops` produces from the XLA walk, and the
    same op semantics up to inactive-gap placement (codes >= 3 interleave
    with the path after M steps); all consumers mask on ``op < 3`` after
    unpacking.
    """
    B0 = dirs.shape[0]
    if B0 < 8:
        dirs, n, m = _pad_rows([dirs, n, m], B0, [0, 1, 1])
    B, S, RB = dirs.shape
    C = min(128, S)
    P = _cap_block(B, 2 * (C * RB + _rup(128 + RB, 128)), _WALK_BUF_BYTES)
    if S % 512:
        raise ValueError(
            f"steps={S} must be a multiple of 512 (the packed walk "
            f"flushes 128-byte output groups of 4 chunks); round steps "
            f"up to a multiple of 512")
    kernel = functools.partial(_walk_kernel, band=band, P=P, C=C, steps=S)
    ops, fi, fj = pl.pallas_call(
        kernel,
        grid=(B // P,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((P, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((P, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((P, S // 4), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((P, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((P, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S // 4), jnp.uint8),
            jax.ShapeDtypeStruct((B, 1), jnp.int32),
            jax.ShapeDtypeStruct((B, 1), jnp.int32),
        ],
        scratch_shapes=[
            # +WW tail lanes: the aligned byte-select window may read past
            # the chunk's last row (reads are masked, never selected)
            pltpu.VMEM((2, P, C * RB + _rup(128 + RB, 128)), jnp.uint8),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )(dirs.reshape(B, S * RB), n.reshape(B, 1).astype(jnp.int32),
      m.reshape(B, 1).astype(jnp.int32))
    return ops[:B0], fi.reshape(B)[:B0], fj.reshape(B)[:B0]


class PallasDispatchMixin:
    """Kernel-family choice shared by both device engines, plus the
    per-engine device pin.

    :meth:`_use_pallas` is the one place an engine asks whether a shape
    runs on the Mosaic kernels: the platform decides (``pallas_ok()``),
    minus shapes a *static* rule excludes. There is no runtime
    downgrade — on the chip a Mosaic kernel that fails to compile or
    run for a shape fails the run with the shape in the message
    (``tests/test_chip_compile.py`` guards the main-path shapes without
    a chip).

    The device pin (``device`` ctor kwarg of both engines): the
    in-process chip scheduler gives every local chip its own engine
    pair, and :meth:`_pinned` is the thread-local
    ``jax.default_device`` context the engines wrap their launch/fetch
    halves in so host->device puts (and the computations that follow
    them) land on that chip."""

    device = None  # optional per-engine jax.Device pin

    def _pinned(self):
        if self.device is None:
            import contextlib
            return contextlib.nullcontext()
        import jax
        return jax.default_device(self.device)

    def _use_pallas(self, shape_key) -> bool:
        return pallas_ok()


_PALLAS_OK = None


def pallas_ok() -> bool:
    """Whether this process runs the Mosaic kernels, decided from the
    platform: ``False`` off the TPU (the XLA kernels *are* the CPU
    path). On a TPU the kernels are probed once — compile, run, and
    compare bit-for-bit against the XLA reference kernels on a random
    small batch — and a probe that raises or mismatches is a hard error
    (:class:`~racon_tpu.ops.swar.KernelProbeError` naming the kernel and
    the first differing output), never a logged downgrade: the XLA
    kernels would give the right bytes and hide a broken chip path. The
    value-level comparison matters because tests pin JAX to the CPU and
    never execute a Mosaic kernel."""
    global _PALLAS_OK
    if _PALLAS_OK is None:
        on_tpu = jax.default_backend() == "tpu"
        if on_tpu:
            _probe_pallas()
        _PALLAS_OK = on_tpu
    return _PALLAS_OK


def _probe_pallas() -> None:
    import numpy as np
    from .nw import _nw_wavefront_kernel, _walk_ops_kernel
    from .swar import (PROBE_BAND, PROBE_MAX_LEN, probe_batch,
                       probe_equal)

    max_len, band = PROBE_MAX_LEN, PROBE_BAND
    args, n, m, rng = probe_batch(7, 60, 200, 4, pad_q=6, pad_t=7)
    B = len(n)
    # out_quant=512: this matrix feeds the packed aligner walk
    dp, sp = pallas_nw_fwd(*args, max_len=max_len, band=band,
                           out_quant=512)
    dx, sx = _nw_wavefront_kernel(*args, max_len=max_len, band=band)
    opk, fip, fjp = pallas_walk_ops(dp, args[2], args[3], band=band)
    ox, fix, fjx = _walk_ops_kernel(dx, args[2], args[3], band=band)
    # rows past the block's dynamic sweep bound are never written
    # by the Pallas kernel (and never read by any consumer) —
    # compare only the guaranteed-computed rows
    mx = int((n + m).max())
    probe_equal("pallas_nw_fwd", "dirs", np.asarray(dp)[:, :mx],
                np.asarray(dx)[:, :mx])
    probe_equal("pallas_nw_fwd", "score", sp, sx)
    probe_equal("pallas_walk_ops", "fi", fip, fix)
    probe_equal("pallas_walk_ops", "fj", fjp, fjx)
    # the Pallas walk's output is 2-bit packed — unpack to compare;
    # inactive-gap codes (>= 3) interleave there and only trail on the
    # XLA walk, so compare the real path codes
    opk, ox = np.asarray(opk), np.asarray(ox)
    shifts4 = np.arange(4, dtype=np.uint8) * 2
    op_ = ((opk[:, :, None] >> shifts4) & 3).reshape(opk.shape[0], -1)
    for k in range(B):
        probe_equal("pallas_walk_ops", f"ops[pair {k}]",
                    op_[k][op_[k] < 3], ox[k][ox[k] < 3])

    # fused walk+vote path must land on identical vote matrices
    from .poa import CH, DEL, _accumulate_votes, _vote_from_ops
    L, K, nW = max_len, 4, 4
    qcodes = rng.integers(0, 5, (B, max_len)).astype(np.uint8)
    qweights = rng.integers(0, 60, (B, max_len)).astype(np.uint8)
    qpw = jnp.asarray((qweights.astype(np.uint16) << 3) | qcodes)
    bg = jnp.asarray(rng.integers(0, 8, B).astype(np.int32))
    win_of = jnp.asarray((np.arange(B) % (nW - 1)).astype(np.int32))
    idxx, wx8, okx = _vote_from_ops(
        jnp.asarray(ox), fix, fjx, sx, args[2], args[3], qpw, bg,
        max_len=max_len, band=band, L=L, K=K)
    wx, ux, _ovx, _owx = _accumulate_votes(
        idxx, wx8, okx, win_of, args[3], bg, args[2], sx,
        n_windows=nW, L=L, K=K, band=band)
    idx, w8, fiv, fjv = pallas_walk_vote(
        dp, args[2], args[3], bg, qpw, band=band, L=L, K=K, CH=CH,
        DEL=DEL)
    okv = (fiv == 0) & (fjv == 0) & (sp < (band // 2))
    wp, up, _ovp, _owp = _accumulate_votes(
        idx, w8.astype(jnp.int32), okv, win_of, args[3], bg, args[2],
        sp, n_windows=nW, L=L, K=K, band=band)
    probe_equal("pallas_walk_vote", "vote weights", wp, wx)
    probe_equal("pallas_walk_vote", "vote counts", up, ux)


_PALLAS_SWAR_OK = None


def pallas_swar_ok() -> bool:
    """Whether the SWAR-packed Mosaic forward kernel
    (``_fwd_kernel_swar``) runs: ``False`` off the TPU like
    :func:`pallas_ok`; on a TPU it is probed once bit-for-bit against
    the XLA reference, and a failure is a hard error."""
    global _PALLAS_SWAR_OK
    if _PALLAS_SWAR_OK is None:
        ok = pallas_ok()
        if ok:
            import numpy as np
            from .nw import _nw_wavefront_kernel
            from .swar import (PROBE_BAND, PROBE_MAX_LEN, probe_batch,
                               probe_equal)

            max_len, band = PROBE_MAX_LEN, PROBE_BAND
            args, n, m, _rng = probe_batch(17, 60, 200, 4)
            # graftlint: disable=swar-guard (probe bucket: 256 + 2 < BIG16 by construction)
            dp, sp = pallas_nw_fwd(*args, max_len=max_len, band=band,
                                   out_quant=512, use_swar=True)
            dx, sx = _nw_wavefront_kernel(*args, max_len=max_len,
                                          band=band)
            mx = int((n + m).max())
            probe_equal("pallas_nw_fwd(use_swar=True)", "dirs",
                        np.asarray(dp)[:, :mx], np.asarray(dx)[:, :mx])
            probe_equal("pallas_nw_fwd(use_swar=True)", "score", sp, sx)
        _PALLAS_SWAR_OK = ok
    return _PALLAS_SWAR_OK


def _walk_vote_kernel(dirs_ref, n_ref, m_ref, bg_ref, qpw_ref,
                      idx_ref, w_ref, fi_ref, fj_ref, buf, sems, *,
                      band: int, P: int, C: int, steps: int, Lq: int,
                      L: int, K: int, CH: int, DEL: int):
    """Fused walk + vote emission for the consensus engine.

    Same traversal as ``_walk_kernel`` (shared ``_walk_step_decode``), but
    instead of op codes it emits each step's vote address (``idx``,
    column/insertion-slot layout of ``ops.poa._vote_from_ops``; the sink
    ``VOT`` when invalid) and its quality weight — the walk already holds
    (i, j, op) and the insertion-run counter in registers, so the
    XLA-side [B, S] prefix-sum reconstruction (two cumsums, a cummax, two
    batched gathers) disappears entirely; the XLA side only folds in
    ``win_of``, applies the per-pair ``ok`` gate, and scatter-adds.

    The layer base/weight lookup is ONE per-pair masked max-reduce over
    the (P, Lq) query rows held in VMEM (only one lane matches ``i - 1``,
    so max == select): codes and weights **travel packed** from the host
    as ``weight << 3 | code`` uint16 lanes (codes are 0..4, weights
    integral 0..93 — ``poa._pack_shard``), so one VMEM block and one
    per-step O(Lq) scan serve both lookups.
    """
    W = band
    c = W // 2
    U = W // 2
    RB = U // 4
    S = steps
    VOT = L * (1 + K) * CH
    CHUNKS = S // C
    WW = _rup(128 + RB, 128)
    blk = pl.program_id(0)
    nn = n_ref[:, :]
    mm = m_ref[:, :]
    bg = bg_ref[:, :]
    # packed i32 view for the per-step select (Mosaic only reduces
    # i32/f32): weight<<3 | code per lane, one reduce recovers both
    qpw = qpw_ref[:, :].astype(jnp.int32)      # (P, Lq)
    lane_ww = lax.broadcasted_iota(jnp.int32, (P, WW), 1)
    lane_q = lax.broadcasted_iota(jnp.int32, (P, Lq), 1)
    chunk_dma = _chunk_dma_factory(dirs_ref, buf, sems, blk,
                                   P=P, C=C, RB=RB, S=S)

    def blank_group(g):
        off = pl.multiple_of(g * C, 128)
        idx_ref[:, pl.ds(off, C)] = jnp.full((P, C), VOT, jnp.int32)
        w_ref[:, pl.ds(off, C)] = jnp.zeros((P, C), jnp.uint8)

    k0 = _walk_start(nn, mm, chunk_dma, blank_group, S=S, C=C,
                     CHUNKS=CHUNKS)
    zrow = jnp.minimum(nn, 0)
    ibuf0 = jnp.full((P, 128), VOT, jnp.int32) + zrow
    wbuf0 = jnp.zeros((P, 128), jnp.int32) + zrow

    def chunk_body(k, carry):
        i, j, run, ibuf, wbuf = carry
        slot = k % 2

        @pl.when(k + 1 < CHUNKS)
        def _():
            chunk_dma((k + 1) % 2, k + 1).start()

        chunk_dma(slot, k).wait()
        lo = S - (k + 1) * C

        def step_body(s, carry):
            i, j, run, ibuf, wbuf = carry
            a = S - (k * C + s)
            t = k * C + s
            op, di, dj, active = _walk_step_decode(buf, slot, lo, a, i, j,
                                                   lane_ww, c=c, U=U,
                                                   RB=RB, WW=WW)

            # layer base code + weight at query position i-1 (clipped like
            # the XLA path; a single lane matches, so max == select)
            qmask = lane_q == jnp.clip(i - 1, 0, Lq - 1)
            sel_pw = jnp.max(jnp.where(qmask, qpw, 0), axis=1,
                             keepdims=True)
            base = sel_pw & 7
            wq = sel_pw >> 3

            slot_i = jnp.minimum(run, K - 1)
            col = bg + j - 1
            addr = jnp.where(
                op == 0, col * CH + base,
                jnp.where(op == 2, col * CH + DEL,
                          (L + col * K + slot_i) * CH + base))
            # drop-collapse: an insertion run votes only its last K bases
            # (keeps every vote address's count bounded by layer depth,
            # which the packed-u32 accumulation relies on)
            valid = (active & (j >= 1) & (col >= 0) & (col < L)
                     & ~((op == 1) & (run >= K)))
            addr = jnp.where(valid, addr, VOT)
            wv = jnp.where(valid, wq, 0)
            run = jnp.where(active, jnp.where(op == 1, run + 1, 0), run)

            ibuf = pltpu.roll(ibuf, shift=127, axis=1)
            ibuf = jnp.concatenate([ibuf[:, :127], addr], axis=1)
            wbuf = pltpu.roll(wbuf, shift=127, axis=1)
            wbuf = jnp.concatenate([wbuf[:, :127], wv], axis=1)

            @pl.when((t + 1) % 128 == 0)
            def _():
                off = pl.multiple_of(t + 1 - 128, 128)
                idx_ref[:, pl.ds(off, 128)] = ibuf
                w_ref[:, pl.ds(off, 128)] = wbuf.astype(jnp.uint8)

            return i - di, j - dj, run, ibuf, wbuf

        return lax.fori_loop(0, C, step_body, (i, j, run, ibuf, wbuf))

    fi, fj, _, _, _ = lax.fori_loop(
        k0, CHUNKS, chunk_body, (nn, mm, zrow, ibuf0, wbuf0))
    fi_ref[:, :] = fi
    fj_ref[:, :] = fj


@functools.partial(jax.jit, static_argnames=("band", "L", "K", "CH", "DEL"))
def pallas_walk_vote(dirs, n, m, bg, qpw, *, band: int,
                     L: int, K: int, CH: int, DEL: int):
    """Fused walk + vote emission over the packed ``weight << 3 | code``
    uint16 query block. Returns (idx [B,S] i32 — vote address or the
    sink VOT, w [B,S] u8, fi, fj). Replaces ``pallas_walk_ops`` + the
    XLA prefix-sum vote prep on the consensus path."""
    B0 = dirs.shape[0]
    if B0 < 8:
        dirs, n, m, bg, qpw = _pad_rows(
            [dirs, n, m, bg, qpw], B0, [0, 1, 1, 0, 0])
    B, S, RB = dirs.shape
    Lq = qpw.shape[1]
    C = min(128, S)
    P = _cap_block(B, 2 * (C * RB + _rup(128 + RB, 128)), _WALK_BUF_BYTES)
    if S % C:
        raise ValueError(
            f"steps={S} must be a multiple of the walk chunk ({C}); "
            f"round steps up to a multiple of 128")
    kernel = functools.partial(_walk_vote_kernel, band=band, P=P, C=C,
                               steps=S, Lq=Lq, L=L, K=K, CH=CH, DEL=DEL)
    idx, w, fi, fj = pl.pallas_call(
        kernel,
        grid=(B // P,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((P, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((P, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((P, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((P, Lq), lambda i: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((P, S), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((P, S), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((P, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((P, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S), jnp.int32),
            jax.ShapeDtypeStruct((B, S), jnp.uint8),
            jax.ShapeDtypeStruct((B, 1), jnp.int32),
            jax.ShapeDtypeStruct((B, 1), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, P, C * RB + _rup(128 + RB, 128)), jnp.uint8),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )(dirs.reshape(B, S * RB), n.reshape(B, 1).astype(jnp.int32),
      m.reshape(B, 1).astype(jnp.int32),
      bg.reshape(B, 1).astype(jnp.int32), qpw.astype(jnp.uint16))
    return idx[:B0], w[:B0], fi.reshape(B)[:B0], fj.reshape(B)[:B0]
