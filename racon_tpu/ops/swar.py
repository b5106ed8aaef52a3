"""SWAR (SIMD-within-a-register) primitives for the packed DP kernels.

The round-5 telemetry showed the DP kernels using <2% of the VPU: int32
vector lanes carry 2-bit bases and scores that are provably bounded by
the alignment band. Two packed formats recover the wasted lane width:

- **int16x2 score lanes**: wavefront scores are bounded by
  ``max(n, m) <= max_len`` (every banded-NW cell is an edit distance of a
  prefix pair), so two scores share one 32-bit lane. The XLA kernels use
  the ``int16`` dtype directly (the VPU/AVX vectorizer packs two values
  per 32-bit lane); the Pallas kernel packs explicitly into int32 words
  (planar halves, see ``pallas_nw._fwd_kernel_swar``) and runs min/select
  with the **biased-unsigned** halfword trick below, so per-lane min/add
  never borrows across the halfword boundary.
- **2-bit bases**: when a chunk's alphabet fits 4 symbols (ACGT does),
  bases travel host->device 4 per byte (16 per int32 word) and equality
  runs as XOR + mask instead of per-byte compares.

Saturation ceiling: packed scores saturate at ``BIG16`` (the int16 analog
of the int32 kernels' ``1 << 28``). Any band/length combination whose
real scores could reach ``BIG16`` must re-dispatch to the int32 path —
:func:`swar_fits` is that overflow guard (all current buckets fit:
``max_len <= 16384 < BIG16``).

Bit-exactness contract (relied on by the goldens): for the same input
rows, the packed kernels emit **byte-identical direction matrices and
scores** — real scores are < ``BIG16`` in both paths, the saturated
cells form the same {BIG, BIG+1} classes, and every comparison the
direction code depends on sees the same ordering. :func:`swar_ok` probes
this once per process on a random batch (the same philosophy as
``pallas_nw.pallas_ok``); a probe that fails is a hard error, never a
quiet downgrade to int32.
"""

from __future__ import annotations

import numpy as np

import jax.numpy as jnp

# Saturation value for packed int16 score lanes. Must exceed every real
# cell value (<= max_len, see module docstring) and keep BIG16 + 1 inside
# int16 (boundary cells add +1 per step off a saturated source). 0x4800
# leaves 2x headroom over the largest bucket (16384).
BIG16 = 0x4800
# int32 analog restored on the way out so consumers (and the parity
# harness) see the exact int32-path scores.
BIG32 = 1 << 28

# Halfword SWAR constants (int32 words carrying two unsigned 16-bit
# fields whose values stay < 2^15, so bit 15 of each field is a free
# guard bit for borrow-free compares).
ONES16 = int(np.int32(0x00010001))
TWOS16 = int(np.int32(0x00020002))
# guard-bit mask 0x80008000 as a (negative) int32
H16 = int(np.uint32(0x80008000).view(np.int32))
LO16 = int(np.int32(0x0000FFFF))


def swar16_ge(a, b):
    """Per-halfword full-field mask (0xFFFF) where ``a >= b``.

    Both operands' fields must be unsigned values < 2^15 (guard bit 15
    clear). Biased-unsigned compare: ``(a | H) - b`` adds 2^15 to each
    field before subtracting, so the per-field result stays in 16 bits
    and no borrow crosses the halfword boundary; field bit 15 then reads
    ``a >= b``. The shift is arithmetic (int32) — the ``& ONES16`` mask
    discards the sign smear before the mask-expansion multiply."""
    m = ((a | H16) - b) & H16
    return ((m >> 15) & ONES16) * LO16


def swar16_sel(a, b, m):
    """Per-halfword select: ``a`` where the full-field mask ``m`` is set,
    else ``b`` (masks come from :func:`swar16_ge` / :func:`swar16_eq`)."""
    return (a & m) | (b & ~m)


def swar16_min(a, b):
    """Per-halfword minimum (fields < 2^15): keep ``b`` where a >= b."""
    return swar16_sel(b, a, swar16_ge(a, b))


def swar16_eq(a, b):
    """Per-halfword full-field mask where ``a == b`` (fields < 2^15):
    XOR + or-tree nonzero detect, inverted, expanded to field masks."""
    x = a ^ b
    t = x | (x >> 8)
    t = t | (t >> 4)
    t = t | (t >> 2)
    t = t | (t >> 1)
    return ((t & ONES16) ^ ONES16) * LO16


def swar16_ne_small(x, bits: int = 4):
    """Per-halfword 0/1 nonzero detect for XOR results of codes < 2^bits
    (the SWAR base-equality substitute for a per-byte compare): cross-
    field shift contamination lands above bit ``bits`` and is masked."""
    t = x
    sh = 1
    while sh < bits:
        t = t | (t >> sh)
        sh *= 2
    return t & ONES16


def swar_fits(max_len: int) -> bool:
    """Overflow guard: True when every cell value a ``max_len`` bucket can
    produce (boundary values <= max_len, interior edit distances
    <= max(i, j) <= max_len, +1 per step of saturated-source slack) stays
    strictly below the packed saturation ceiling. Combinations that fail
    re-dispatch to the int32 path."""
    return max_len + 2 < BIG16


# The packed Mosaic forward kernel (``pallas_nw._fwd_kernel_swar``) keeps a
# wavefront in ``band / 4`` 32-bit words a pair, and on the chip it is
# right only where that is whole 128-lane registers (PR 37, against the XLA
# kernel on the same device: bands 512 and 1024 right at 8 and at 64 rows a
# block; 128, 192, 256 wrong at 64 rows; 384 and 768 wrong at 8 rows too).
# Where it is wrong it scores every substitution 0 and walks the length
# difference off at the pair's head: 25,010 of a short-read chunk's 65,536
# pairs at (256, 128 / 96 / 64), the probes' own batch too once tiled to
# 64 rows (its 8 rows alone pass, which is all ``pallas_swar_ok()`` runs).
# The int32 kernel is right at every geometry tried, and under one
# register a row it needs no more of them (64 int32 lanes or 32 packed
# words are one register either way), so bands under 512 take it. Band
# 768, which the long-read cells' narrowest rung runs, is left packed
# here: routing it moves those cells' bytes and device seconds, which is
# a change of its own (PERF.md section 7).
MOSAIC_SWAR_MIN_BAND = 512


def mosaic_swar_fits(band: int) -> bool:
    """Geometry guard of the packed MOSAIC forward kernel (the XLA
    packed kernel has no such limit): False -> the int32 Mosaic kernel."""
    return band >= MOSAIC_SWAR_MIN_BAND


# geometry of the availability probes' one small bucket
PROBE_MAX_LEN, PROBE_BAND = 256, 128


class KernelProbeError(RuntimeError):
    """A kernel family failed its bit-exactness probe on this backend."""


def probe_batch(seed: int, lo: int, hi: int, n_del: int,
                pad_q: int = 0, pad_t: int = 0):
    """Random small batch (8 related pairs, ``max_len`` 256, ``band``
    128) laid out as the wavefront kernels' padded rows — the shared
    input of the three availability probes. Returns ``(args, n, m,
    rng)``: the device arrays ``(qrp, tp, n, m)``, the host lengths and
    the generator (for probes that draw more data)."""
    max_len, band = PROBE_MAX_LEN, PROBE_BAND
    B, c = 8, band // 2
    width = c + max_len + band
    rng = np.random.default_rng(seed)
    bases = np.frombuffer(b"ACGT", np.uint8)
    qrp = np.full((B, width), pad_q, np.uint8)
    tp = np.full((B, width), pad_t, np.uint8)
    n = np.zeros(B, np.int32)
    m = np.zeros(B, np.int32)
    for k in range(B):
        ln = int(rng.integers(lo, hi))
        t = bases[rng.integers(0, 4, ln)]
        q = np.delete(t.copy(), rng.integers(0, ln, n_del))
        flips = rng.random(len(q)) < 0.2
        q[flips] = bases[rng.integers(0, 4, int(flips.sum()))]
        qrp[k, c + max_len - len(q): c + max_len] = q[::-1]
        tp[k, c: c + ln] = t
        n[k], m[k] = len(q), ln
    args = (jnp.asarray(qrp), jnp.asarray(tp),
            jnp.asarray(n), jnp.asarray(m))
    return args, n, m, rng


def probe_equal(kernel: str, name: str, got, want) -> None:
    """Raise :class:`KernelProbeError` naming ``kernel`` and the first
    differing element of output ``name`` unless ``got == want``."""
    got, want = np.asarray(got), np.asarray(want)
    if got.shape != want.shape:
        raise KernelProbeError(
            f"{kernel}: output {name!r} has shape {got.shape}, the "
            f"reference kernel gives {want.shape}")
    diff = np.argwhere(got != want)
    if len(diff):
        at = tuple(int(i) for i in diff[0])
        raise KernelProbeError(
            f"{kernel}: output {name!r} differs from the reference "
            f"kernel in {len(diff)} of {got.size} elements, first at "
            f"{at}: got {got[at].item()!r}, reference "
            f"{want[at].item()!r}")


_SWAR_OK = None


def swar_ok() -> bool:
    """Probe once whether the packed (int16-lane) XLA wavefront kernel
    reproduces the int32 kernel bit-for-bit on a random small batch —
    dirs, scores, and walked tracebacks. A backend whose 16-bit lowering
    misbehaves fails the run (:class:`KernelProbeError` naming the
    first differing output): quietly selecting int32 would hide a
    broken kernel family behind right bytes. ``RACON_TPU_SWAR=0`` is
    the explicit way to run without the packed kernels."""
    global _SWAR_OK
    from .. import flags
    if not flags.get_bool("RACON_TPU_SWAR"):
        return False  # the operator's explicit way past the packed kernels
    if _SWAR_OK is None:
        from .nw import _nw_wavefront_kernel, _walk_ops_kernel

        max_len, band = PROBE_MAX_LEN, PROBE_BAND
        args, _n, _m, _rng = probe_batch(13, 50, 220, 3)
        # graftlint: disable=swar-guard (probe bucket: 256 + 2 < BIG16 by construction)
        dp, sp = _nw_wavefront_kernel(*args, max_len=max_len,
                                      band=band, swar=True)
        dx, sx = _nw_wavefront_kernel(*args, max_len=max_len,
                                      band=band)
        # packed walk (round 17): the SWAR path's traceback carries
        # (i, j) as one halfword pair — probed against the unpacked
        # walk on the same matrices, so the packed path (fwd + walk)
        # stands or falls together
        # graftlint: disable=swar-guard (probe bucket: 256 + 2 < BIG16 by construction)
        op_, fip, fjp = _walk_ops_kernel(dp, args[2], args[3],
                                         band=band, swar=True)
        ox, fix, fjx = _walk_ops_kernel(dx, args[2], args[3],
                                        band=band)
        kernel = "XLA SWAR wavefront (_nw_wavefront_kernel swar=True)"
        probe_equal(kernel, "dirs", dp, dx)
        probe_equal(kernel, "score", sp, sx)
        kernel = "XLA SWAR walk (_walk_ops_kernel swar=True)"
        probe_equal(kernel, "ops", op_, ox)
        probe_equal(kernel, "fi", fip, fix)
        probe_equal(kernel, "fj", fjp, fjx)
        _SWAR_OK = True
    return _SWAR_OK


def pack_bases_2bit(codes: np.ndarray) -> np.ndarray:
    """Host-side 2-bit base packing: 4 codes per byte (16 per int32
    word), LSB-first. ``codes`` values must be < 4; length is padded to a
    multiple of 4. The device unpacker is ``nw._build_rows_packed2``."""
    pad = (-len(codes)) % 4
    if pad:
        codes = np.concatenate([codes, np.zeros(pad, np.uint8)])
    c4 = codes.reshape(-1, 4)
    return (c4[:, 0] | (c4[:, 1] << 2) | (c4[:, 2] << 4)
            | (c4[:, 3] << 6)).astype(np.uint8)
