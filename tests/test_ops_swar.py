"""SWAR-packed kernel parity harness (runs on the CPU XLA backend via
conftest; the same code paths run on TPU).

The packed paths (int16x2 score lanes, 2-bit bases, packed qpw layer
lanes, the widened insertion accumulator) must be **bit-exact** against
the int32 paths — scores, direction matrices, tracebacks, breaking
points and consensus bytes all equal. These tests are the tier-1 gate
for that contract (wired as a dedicated shard in ci/cpu/test.sh)."""

import numpy as np
import pytest

import jax.numpy as jnp

from racon_tpu.ops import swar
from racon_tpu.ops.nw import (BAND_RUNGS, BUCKETS, _build_rows,
                              _build_rows_packed, _build_rows_packed2,
                              _nw_wavefront_kernel, _walk_ops_kernel,
                              TpuAligner)

BASES = np.frombuffer(b"ACGT", np.uint8)


# ------------------------------------------------------------ primitives

def _fields(x):
    x = np.asarray(x).astype(np.int64)
    return x & 0xFFFF, (x >> 16) & 0xFFFF


def test_swar16_primitives_match_per_field_reference():
    rng = np.random.default_rng(2)
    a = rng.integers(0, 1 << 15, 8192).astype(np.int32)
    b = rng.integers(0, 1 << 15, 8192).astype(np.int32)
    ap = jnp.asarray(a[0::2] | (a[1::2] << 16))
    bp = jnp.asarray(b[0::2] | (b[1::2] << 16))

    lo, hi = _fields(swar.swar16_ge(ap, bp))
    assert np.array_equal(lo, (a[0::2] >= b[0::2]) * 0xFFFF)
    assert np.array_equal(hi, (a[1::2] >= b[1::2]) * 0xFFFF)

    lo, hi = _fields(swar.swar16_min(ap, bp))
    assert np.array_equal(lo, np.minimum(a[0::2], b[0::2]))
    assert np.array_equal(hi, np.minimum(a[1::2], b[1::2]))

    lo, hi = _fields(swar.swar16_eq(ap, bp))
    assert np.array_equal(lo, (a[0::2] == b[0::2]) * 0xFFFF)
    assert np.array_equal(hi, (a[1::2] == b[1::2]) * 0xFFFF)

    # XOR + mask equality on 4-bit codes
    c = rng.integers(0, 16, 8192).astype(np.int32)
    d = rng.integers(0, 16, 8192).astype(np.int32)
    cp = jnp.asarray(c[0::2] | (c[1::2] << 16))
    dp = jnp.asarray(d[0::2] | (d[1::2] << 16))
    lo, hi = _fields(swar.swar16_ne_small(cp ^ dp, 4))
    assert np.array_equal(lo, (c[0::2] != d[0::2]).astype(np.int64))
    assert np.array_equal(hi, (c[1::2] != d[1::2]).astype(np.int64))


def test_swar_probe_and_overflow_guard():
    assert swar.swar_ok()
    assert swar.swar_fits(16384)       # every current bucket
    assert not swar.swar_fits(32768)   # a hypothetical 32k bucket


# --------------------------------------------------------- kernel parity

def _pack_batch(pairs, max_len, band):
    c = band // 2
    width = c + max_len + band
    B = len(pairs)
    qrp = np.zeros((B, width), np.uint8)
    tp = np.zeros((B, width), np.uint8)
    n = np.zeros(B, np.int32)
    m = np.zeros(B, np.int32)
    for k, (q, t) in enumerate(pairs):
        qrp[k, c + max_len - len(q): c + max_len] = q[::-1]
        tp[k, c: c + len(t)] = t
        n[k], m[k] = len(q), len(t)
    return (jnp.asarray(qrp), jnp.asarray(tp), jnp.asarray(n),
            jnp.asarray(m)), n, m


def _assert_kernel_parity(pairs, max_len, band, steps=0):
    args, n, m = _pack_batch(pairs, max_len, band)
    dp, sp = _nw_wavefront_kernel(*args, max_len=max_len, band=band,
                                  steps=steps, swar=True)
    dx, sx = _nw_wavefront_kernel(*args, max_len=max_len, band=band,
                                  steps=steps)
    assert np.array_equal(np.asarray(dp), np.asarray(dx))
    assert np.array_equal(np.asarray(sp), np.asarray(sx))
    op_p, fip, fjp = _walk_ops_kernel(dp, args[2], args[3], band=band)
    op_x, fix, fjx = _walk_ops_kernel(dx, args[2], args[3], band=band)
    assert np.array_equal(np.asarray(op_p), np.asarray(op_x))
    assert np.array_equal(np.asarray(fip), np.asarray(fix))
    assert np.array_equal(np.asarray(fjp), np.asarray(fjx))


def _mutated_pair(rng, ln, err, ndel=4, nins=4):
    t = BASES[rng.integers(0, 4, ln)]
    q = t.copy()
    flips = rng.random(ln) < err
    q[flips] = BASES[rng.integers(0, 4, int(flips.sum()))]
    q = np.delete(q, rng.integers(0, len(q), ndel))
    q = np.insert(q, rng.integers(0, len(q), nins),
                  BASES[rng.integers(0, 4, nins)])
    return q, t


def test_randomized_1k_pair_parity_sweep():
    """The acceptance-criteria sweep: 1k random pairs, packed vs int32 —
    scores, direction matrices and walked tracebacks all bit-equal."""
    rng = np.random.default_rng(41)
    pairs = [_mutated_pair(rng, int(rng.integers(16, 240)),
                           float(rng.uniform(0.0, 0.35)))
             for _ in range(1000)]
    _assert_kernel_parity(pairs, max_len=256, band=128)


def test_band_edge_saturation_parity():
    """Pairs engineered to escape the band (structural rearrangement)
    keep score BIG in both paths and produce identical dirs — the
    saturation classes {BIG, BIG+1} line up across the encodings."""
    rng = np.random.default_rng(42)
    pairs = []
    for _ in range(16):
        ln = int(rng.integers(150, 250))
        t = BASES[rng.integers(0, 4, ln)]
        q = np.concatenate([t[ln // 2:], t[:ln // 2]])  # off-diagonal
        pairs.append((q, t))
    args, n, m = _pack_batch(pairs, 256, 128)
    dp, sp = _nw_wavefront_kernel(*args, max_len=256, band=128, swar=True)
    dx, sx = _nw_wavefront_kernel(*args, max_len=256, band=128)
    assert np.array_equal(np.asarray(dp), np.asarray(dx))
    assert np.array_equal(np.asarray(sp), np.asarray(sx))
    assert np.asarray(sp).max() >= 128 // 2  # at least one real escape


def test_odd_lane_counts_and_bucket_boundaries():
    """Odd (unpaired) batch rows and n/m pinned at the bucket caps: the
    packed path must agree where lengths sit exactly on max_len, on the
    steps bound, and at zero."""
    rng = np.random.default_rng(43)
    max_len = 256
    full = BASES[rng.integers(0, 4, max_len)]
    fullq = full.copy()
    flips = rng.random(max_len) < 0.1
    fullq[flips] = BASES[rng.integers(0, 4, int(flips.sum()))]
    pairs = [
        (fullq, full),                    # n = m = max_len (hits steps)
        (full[:0], full[:7]),             # n = 0
        (full[:7], full[:0]),             # m = 0
        (full[:1], full[:1]),             # minimal
        (fullq[:max_len - 1], full),      # one off the cap
        (full, full),                     # identity at the cap
        (fullq[:129], full[:128]),        # straddling band/2
    ]  # 7 rows: odd count, not a power of two
    _assert_kernel_parity(pairs, max_len=max_len, band=128)


def test_aligner_end_to_end_swar_parity():
    """TpuAligner with and without SWAR: identical CIGARs and breaking
    points, including an N-bearing batch (alphabet > 4 symbols falls
    back to the nibble pack) and band-escalation pairs."""
    from racon_tpu.core.backends import PythonAligner

    rng = np.random.default_rng(44)
    pairs, metas = [], []
    for k in range(48):
        q, t = _mutated_pair(rng, int(rng.integers(60, 240)),
                             0.3 if k % 7 == 0 else 0.1)
        if k % 5 == 0:  # sprinkle Ns -> 5-symbol alphabet chunks
            q = q.copy()
            q[rng.integers(0, len(q), 3)] = ord("N")
        pairs.append((q.tobytes(), t.tobytes()))
        metas.append((int(rng.integers(0, 500)), int(rng.integers(0, 200))))
    a_sw = TpuAligner(fallback=PythonAligner())
    a_32 = TpuAligner(fallback=PythonAligner(), use_swar=False)
    assert a_sw.align_batch(pairs) == a_32.align_batch(pairs)
    assert ([a.tolist() for a in a_sw.breaking_points_batch(pairs, metas,
                                                            64)]
            == [a.tolist() for a in a_32.breaking_points_batch(pairs,
                                                               metas, 64)])
    assert a_sw.stats["swar_chunks"] > 0
    assert a_32.stats["swar_chunks"] == 0


def test_build_rows_packed2_matches_nibble_rows():
    """The 2-bit row builder must place exactly the bytes the nibble
    builder places (same codes modulo the encoding bijection) at every
    in-range position; out-of-range lanes are pad in both."""
    from racon_tpu.ops.swar import pack_bases_2bit

    rng = np.random.default_rng(45)
    max_len, band = 256, 128
    B = 8
    codes = rng.integers(0, 4, (B, max_len)).astype(np.uint8)
    n = rng.integers(1, max_len + 1, B).astype(np.int32)
    m = rng.integers(1, max_len + 1, B).astype(np.int32)
    flat = codes.reshape(-1)
    q2 = pack_bases_2bit(flat)
    # nibble encoding of the same data shifted +1 (nibble code 0 is pad)
    q4 = (flat + 1).astype(np.uint8)
    q4 = q4[0::2] | (q4[1::2] << 4)
    nd, md = jnp.asarray(n), jnp.asarray(m)
    qr2, tp2 = _build_rows_packed2(jnp.asarray(q2), jnp.asarray(q2),
                                   nd, md, max_len=max_len, band=band)
    qr4, tp4 = _build_rows_packed(jnp.asarray(q4), jnp.asarray(q4),
                                  nd, md, max_len=max_len, band=band)
    qr4 = np.asarray(qr4).astype(np.int16)
    tp4 = np.asarray(tp4).astype(np.int16)
    # in-range lanes: code2 == code4 - 1; pad lanes are 0 in both
    assert np.array_equal(np.asarray(qr2),
                          np.where(qr4 > 0, qr4 - 1, 0).astype(np.uint8))
    assert np.array_equal(np.asarray(tp2),
                          np.where(tp4 > 0, tp4 - 1, 0).astype(np.uint8))


# the three row builders: (entry point, codes per byte)
ROW_BUILDERS = {"raw": (_build_rows, 1), "nibble": (_build_rows_packed, 2),
                "2bit": (_build_rows_packed2, 4)}
# (max_len, band) of BUCKETS x BAND_RUNGS: the smallest bucket at the
# smallest rung, every bucket up to 8192 at its own band, two rungs under
ROW_GEOMETRIES = [(256, 64), (256, 128), (1024, 384), (4096, 1024),
                  (4096, 256), (8192, 2048), (8192, 768)]


def _rows_oracle(block, per, lengths, max_len, band, reverse):
    """One side of the banded row layout in plain numpy, pair by pair:
    unpack ``per`` codes per byte LSB-first; the query's first
    ``length`` codes reversed, ending at column ``c + max_len``, or the
    target's from column ``c``; every other byte is the pad code 0."""
    B, c, bits = len(lengths), band // 2, 8 // per
    pos = np.arange(B * max_len)
    codes = ((block[pos // per] >> (bits * (pos % per)))
             & ((1 << bits) - 1)).astype(np.uint8).reshape(B, max_len)
    rows = np.zeros((B, c + max_len + band), np.uint8)
    for k, length in enumerate(lengths):
        if reverse:
            rows[k, c + max_len - length:c + max_len] = \
                codes[k, :length][::-1]
        else:
            rows[k, c:c + length] = codes[k, :length]
    return rows


@pytest.mark.parametrize("max_len,band", ROW_GEOMETRIES)
@pytest.mark.parametrize("kind", sorted(ROW_BUILDERS))
def test_build_rows_match_the_numpy_oracle(kind, max_len, band):
    """Each builder against the oracle: full and one-base lengths,
    random ones, and random (non-zero) bytes past every length — the
    mask must hold, the Mosaic sweep reads these bytes as they are."""
    assert max_len in dict(BUCKETS) and band in BAND_RUNGS
    build, per = ROW_BUILDERS[kind]
    rng = np.random.default_rng(max_len + band + per)
    B = 8
    n = rng.integers(1, max_len + 1, B).astype(np.int32)
    m = rng.integers(1, max_len + 1, B).astype(np.int32)
    n[0], m[0] = max_len, 1
    n[1], m[1] = 1, max_len
    qb = rng.integers(0, 256, B * max_len // per).astype(np.uint8)
    tb = rng.integers(0, 256, B * max_len // per).astype(np.uint8)
    qrp, tp = build(jnp.asarray(qb), jnp.asarray(tb), jnp.asarray(n),
                    jnp.asarray(m), max_len=max_len, band=band)
    assert qrp.dtype == tp.dtype == jnp.uint8
    assert np.array_equal(
        np.asarray(qrp), _rows_oracle(qb, per, n, max_len, band, True))
    assert np.array_equal(
        np.asarray(tp), _rows_oracle(tb, per, m, max_len, band, False))


@pytest.mark.parametrize("kind", sorted(ROW_BUILDERS))
def test_build_rows_lower_without_a_gather(kind):
    """The layout is a shifted copy. As one element-wise gather per
    output byte it cost more device time than the Mosaic aligner it
    feeds (PERF.md, PR 32): that form must not come back unnoticed."""
    build, per = ROW_BUILDERS[kind]
    max_len, band, B = 4096, 1024, 16
    blk = jnp.zeros((B * max_len // per,), jnp.uint8)
    lens = jnp.ones((B,), jnp.int32)
    text = build.lower(blk, blk, lens, lens, max_len=max_len,
                       band=band).as_text()
    assert "gather" not in text
    assert "stablehlo.pad" in text and "stablehlo.reverse" in text


def test_pallas_swar_kernel_interpret_parity():
    """The explicit int32-word SWAR Mosaic kernel, executed in Pallas
    interpret mode (the only way to run it off-TPU): direction matrix
    and scores bit-equal to the XLA reference. On real hardware the
    same comparison is `pallas_swar_ok()`."""
    from jax.experimental import pallas as pl
    import racon_tpu.ops.pallas_nw as pnw

    rng = np.random.default_rng(50)
    pairs = [_mutated_pair(rng, int(rng.integers(60, 200)), 0.2)
             for _ in range(8)]
    args, n, m = _pack_batch(pairs, 256, 128)
    orig = pl.pallas_call

    def interpreted(*a, **k):
        k["interpret"] = True
        return orig(*a, **k)

    pl.pallas_call = interpreted
    try:
        try:
            dp, sp = pnw.pallas_nw_fwd(*args, max_len=256, band=128,
                                       out_quant=512, use_swar=True)
        except Exception as e:  # interpret-mode support varies by jax
            pytest.skip(f"pallas interpret mode unavailable: {e!r}")
    finally:
        pl.pallas_call = orig
    dx, sx = _nw_wavefront_kernel(*args, max_len=256, band=128)
    mx = int((n + m).max())
    assert np.array_equal(np.asarray(dp)[:, :mx], np.asarray(dx)[:, :mx])
    assert np.array_equal(np.asarray(sp), np.asarray(sx))


# ------------------------------------------------------------- consensus

def _consensus_windows(rng, n_w=8, wl=400, depth=10, with_quality=True):
    from racon_tpu.core.window import Window, WindowType

    windows = []
    for wi in range(n_w):
        truth = BASES[rng.integers(0, 4, wl)]
        bb = truth.copy()
        flips = rng.random(wl) < 0.1
        bb[flips] = BASES[rng.integers(0, 4, int(flips.sum()))]
        win = Window(0, wi, WindowType.TGS, bb.tobytes(), b"!" * wl)
        for _ in range(depth):
            layer, _ = _mutated_pair(rng, wl, 0.08, ndel=5, nins=5)
            qual = (bytes(33 + int(x) for x in
                          rng.integers(5, 50, len(layer)))
                    if with_quality else None)
            win.add_layer(layer.tobytes(), qual, 0, wl - 1)
        windows.append(win)
    return windows


def _clone_windows(windows):
    from racon_tpu.core.window import Window

    out = []
    for w in windows:
        c = Window(w.id, w.rank, w.type, w.sequences[0], w.qualities[0])
        for i in range(1, len(w.sequences)):
            b, e = w.positions[i]
            c.add_layer(w.sequences[i], w.qualities[i], b, e)
        out.append(c)
    return out


def test_consensus_swar_parity_bit_exact():
    from racon_tpu.ops.poa import TpuPoaConsensus

    rng = np.random.default_rng(46)
    w1 = _consensus_windows(rng)
    w2 = _clone_windows(w1)
    e_sw = TpuPoaConsensus(3, -5, -4)
    e_32 = TpuPoaConsensus(3, -5, -4, use_swar=False)
    r1 = e_sw.run(w1, trim=True)
    r2 = e_32.run(w2, trim=True)
    assert r1 == r2
    for a, b in zip(w1, w2):
        assert a.consensus == b.consensus
    assert e_sw.stats["device_windows"] == len(w1)


def test_insertion_accumulator_deep_window_regression():
    """Regression for the silent 23-bit-weight / 9-bit-count saturation:
    more than 511 insertion votes at ONE address must accumulate exactly
    (the old single-u32 packing carried the count into the weight bits —
    at 640 votes it wrapped u32 entirely). Covers both the folded and
    the unfolded scatter paths."""
    from racon_tpu.ops.poa import CH, _accumulate_votes

    L, K, nW, band = 64, 4, 2, 64
    addr = (L + 3 * K + 1) * CH + 2   # insertion slot 1 of junction 3
    for B in (640, 600):              # 640 folds (B % 32 == 0), 600 not
        S = 16
        idx = np.full((B, S), L * (1 + K) * CH, np.int32)
        idx[:, 0] = addr
        w = np.zeros((B, S), np.int32)
        w[:, 0] = 9
        ok = np.ones(B, bool)
        win_of = np.zeros(B, np.int32)
        span_m = np.ones(B, np.int32)
        n = np.full(B, 2, np.int32)
        score = np.ones(B, np.int32)
        args = [jnp.asarray(a) for a in
                (idx, w, ok, win_of, span_m, np.zeros(B, np.int32), n,
                 score)]
        weighted, unweighted, ovf, _ = _accumulate_votes(
            *args, n_windows=nW, L=L, K=K, band=band)
        # alpha == 64 at default scores: every vote lands as 9 * 64
        assert float(np.asarray(weighted)[0, addr]) == B * 9 * 64
        assert int(np.asarray(unweighted)[0, addr]) == B
        assert int(ovf) == 0


def test_max_depth_cap_lifted_past_511():
    """The 511 voting-depth clamp existed only to protect the 9-bit
    count field; the widened accumulator moved the ceiling to the f32
    matmul-exactness bound (2047), and the round-10 int8/int32 matmul
    vote path removes that bound at the default scores — the cap moves
    to a conservative 65535 (both values of use_matmul_votes given
    explicitly)."""
    from racon_tpu.ops.poa import TpuPoaConsensus

    assert TpuPoaConsensus(3, -5, -4, max_depth=4096,
                           use_matmul_votes=False).max_depth == 2047
    assert TpuPoaConsensus(3, -5, -4, max_depth=4096,
                           use_matmul_votes=True).max_depth == 4096
    assert TpuPoaConsensus(3, -5, -4, max_depth=10 ** 6,
                           use_matmul_votes=True).max_depth == 65535
    assert TpuPoaConsensus(3, -5, -4, max_depth=200,
                           use_matmul_votes=True).max_depth == 200
    # custom -m/-x/-g: vote sums lose 64-alignment, the f32 handoff to
    # the consensus kernel re-binds the cap at 2047 even on matmul votes
    assert TpuPoaConsensus(4, -5, -4, max_depth=4096,
                           use_matmul_votes=True).max_depth == 2047


def test_matmul_votes_deep_address_regression():
    """Round 10: >= 4096 votes on ONE address through the int8-matmul
    vote path accumulate exactly, bit-compared against an integer numpy
    reference — the per-address weighted sum here (4608 x 5760 ≈ 26.5M)
    is past the 2^24 f32-exactness bound that set the old 2047 depth
    cap, so only an exact integer reduction can pass. Extends the
    round-6 600+640-vote test (which stayed under the f32 bound)."""
    from racon_tpu.ops.poa import CH, _accumulate_votes

    L, K, nW, band = 64, 4, 2, 64
    B, S = 4608, 16
    col_addr = 5 * CH + 1             # column 5 (bg=0, span 6), base C
    ins_addr = (L + 3 * K + 1) * CH + 2  # junction 3, slot 1, base G
    idx = np.full((B, S), L * (1 + K) * CH, np.int32)
    idx[:, 0] = col_addr
    idx[:, 1] = ins_addr
    w = np.zeros((B, S), np.int32)
    w[:, 0] = 90                      # x alpha 64 -> 5760 per vote
    w[:, 1] = 90
    ok = np.ones(B, bool)
    win_of = np.zeros(B, np.int32)
    span_m = np.full(B, 6, np.int32)  # one col step -> lands column 5
    n = np.full(B, 2, np.int32)
    score = np.ones(B, np.int32)
    args = [jnp.asarray(a) for a in
            (idx, w, ok, win_of, span_m, np.zeros(B, np.int32), n,
             score)]
    weighted, unweighted, ovf, _ = _accumulate_votes(
        *args, n_windows=nW, L=L, K=K, band=band, matmul_votes=True)
    expect = np.int64(B) * 90 * 64
    assert expect > (1 << 24)         # past the old f32 exactness bound
    for addr in (col_addr, ins_addr):
        assert int(np.asarray(weighted)[0, addr]) == expect
        assert int(np.asarray(unweighted)[0, addr]) == B
    assert int(ovf) == 0
    # the unweighted counts (exact ints on both paths) must agree with
    # the scatter/f32 reference emitter bit-for-bit
    _, unw_ref, _, _ = _accumulate_votes(
        *args, n_windows=nW, L=L, K=K, band=band, matmul_votes=False)
    assert np.array_equal(np.asarray(unweighted), np.asarray(unw_ref))


def _walk_stream(rng, S, L, K, CH, bg, m, ins_runs, n_del=0):
    """One pair's backward-walk vote stream, as both emitters write it:
    the walk runs from column ``bg + m - 1`` down to ``bg``; before it
    consumes column ``c`` it takes ``ins_runs.get(c, 0)`` insertion
    steps at junction ``c`` (slot = position in the run, walk order;
    steps past the K-th are non-votes on the sink), then an M step, or
    a D step for the first ``n_del`` columns that carry no insertion.
    Returns (idx [S], w [S], edits) with the sink past the last step."""
    VOT = L * (1 + K) * CH
    idx, w, edits = [], [], 0
    for c in range(bg + m - 1, bg - 1, -1):
        for r in range(ins_runs.get(c, 0)):
            edits += 1
            if r < K:
                idx.append((L + c * K + r) * CH + int(rng.integers(0, 5)))
                w.append(int(rng.integers(1, 94)))
            else:
                idx.append(VOT)
                w.append(0)
        if n_del and c not in ins_runs:
            n_del -= 1
            edits += 1
            idx.append(c * CH + 5)
        else:
            idx.append(c * CH + int(rng.integers(0, 5)))
        w.append(int(rng.integers(1, 94)))
    assert len(idx) <= S, (len(idx), S)
    pad = S - len(idx)
    return (np.array(idx + [VOT] * pad, np.int32),
            np.array(w + [0] * pad, np.int32), edits)


def _runs_for(rng, n_votes, bg, m, K, first_col=None):
    """Insertion runs (at most K long) carrying ``n_votes`` votes at
    distinct junctions of [bg, bg + m), ``first_col`` among them."""
    n_runs = -(-n_votes // K)
    cols = rng.choice(np.arange(bg, bg + m), size=n_runs, replace=False)
    if first_col is not None and n_runs and first_col not in cols:
        cols[0] = first_col
    runs = {int(c): K for c in cols}
    if n_votes % K:
        runs[int(cols[-1])] = n_votes % K
    assert sum(runs.values()) == n_votes
    return runs


def _ins_room(S, K):
    """The most insertion votes a stream of S steps holds beside the
    columns their junctions need (K votes a junction)."""
    return (S * K) // (K + 1) - K


def _vote_streams(S, L, band, seed):
    """A 32-pair batch over 3 windows: typical pairs, one with IC - 1
    insertion votes (accepted; as many as S holds where S < IC), pairs
    with IC and IC + 9 (rejected by the score gate), insertion runs
    longer than K, a junction with all K slots at column L - 1."""
    from racon_tpu.ops.poa import CH, K_INS as K
    rng = np.random.default_rng(seed)
    B, nW, IC = 32, 3, min(S, band // 2)
    room = _ins_room(S, K)
    rows, meta = [], []

    def add(bg, m, runs, n_del=0, mism=0):
        idx, w, edits = _walk_stream(rng, S, L, K, CH, bg, m, runs, n_del)
        rows.append((idx, w))
        meta.append((bg, m, edits + mism))

    def crafted(n_votes):
        m = min(L, S - n_votes)
        bg = L - m
        add(bg, m, _runs_for(rng, n_votes, bg, m, K, first_col=L - 1))

    crafted(min(IC - 1, room))                      # accepted
    if IC + 9 <= room:
        crafted(IC)                                 # rejected, none lost
        crafted(IC + 9)                             # rejected, cut
    # all K slots at the window's last column and a run of 2 K + 1
    m = min(L, S // 2)
    add(L - m, m, {L - 1: K, L - m + 3: 2 * K + 1, L - m: 1}, n_del=3)
    while len(rows) < B:
        m = int(rng.integers(8, min(L, S - 40)))
        bg = int(rng.integers(0, L - m + 1))
        cols = rng.choice(np.arange(bg, bg + m), size=min(m, 8),
                          replace=False)
        runs = {int(c): int(rng.integers(1, K + 3)) for c in cols[:5]}
        if S - m - sum(runs.values()) < 0:
            runs = {}
        add(bg, m, runs, n_del=int(rng.integers(0, 4)),
            mism=int(rng.integers(0, 30)))
    idx = np.stack([r[0] for r in rows])
    w = np.stack([r[1] for r in rows])
    bg, span_m, score = (np.array(v, np.int32) for v in zip(*meta))
    win_of = (np.arange(B) % nW).astype(np.int32)
    n = (span_m + rng.integers(0, 20, B)).astype(np.int32)
    ok = score < band // 2
    return idx, w, ok, win_of, span_m, bg, n, score, nW


def _scatter_reference(idx, w, ok, win_of, span_m, n, score, nW, L,
                       scores):
    """Per-pair scatter of the stream in plain numpy integers (alpha as
    ``_accumulate_votes`` documents it)."""
    from racon_tpu.ops.poa import (CH, DEL, K_INS as K, DEFAULT_MATCH,
                                   DEFAULT_MISMATCH, DEFAULT_GAP)
    VOT = L * (1 + K) * CH
    weighted = np.zeros((nW, VOT), np.int64)
    unweighted = np.zeros((nW, VOT), np.int64)
    for p in range(idx.shape[0]):
        if not ok[p]:
            continue
        vote = idx[p] < VOT
        gaps = int(np.sum(vote & ((idx[p] >= L * CH)
                                  | (idx[p] % CH == DEL))))
        alpha = 64
        if scores != (DEFAULT_MATCH, DEFAULT_MISMATCH, DEFAULT_GAP):
            mis = max(int(score[p]) - gaps, 0)
            mat = max((int(n[p]) + int(span_m[p]) - gaps) // 2 - mis, 0)
            cli = np.float32(scores[0] * mat + scores[1] * mis
                             + scores[2] * gaps)
            dfl = np.float32(DEFAULT_MATCH * mat + DEFAULT_MISMATCH * mis
                             + DEFAULT_GAP * gaps)
            alpha = int(np.clip(np.round(
                np.float32(64.0) * max(cli, np.float32(0.0))
                / max(dfl, np.float32(1.0))), 1, 88))
        for a, wt in zip(idx[p][vote], w[p][vote]):
            weighted[win_of[p], a] += int(wt) * alpha
            unweighted[win_of[p], a] += 1
    return weighted, unweighted


@pytest.mark.parametrize("scores", [(3, -5, -4), (8, -6, -8)],
                         ids=["default", "golden"])
@pytest.mark.parametrize("S,L,band", [(1280, 768, 512), (384, 768, 512),
                                      (128, 256, 512), (640, 384, 512)])
def test_matmul_votes_route_the_narrow_stream_exactly(S, L, band, scores):
    """The matmul path routes all insertion votes from ONE compaction
    cut to IC = min(S, band // 2) lanes, then K narrow slot planes, and
    the column votes at L lanes: equal, element for element, to the
    scatter branch and to a per-pair numpy scatter at the long-read
    geometry, the short-read one, S < band // 2 and S > L."""
    from racon_tpu.ops.poa import CH, K_INS, _accumulate_votes

    idx, w, ok, win_of, span_m, bg, n, score, nW = _vote_streams(
        S, L, band, seed=S + L)
    IC, room = min(S, band // 2), _ins_room(S, K_INS)
    n_ins = np.sum((idx >= L * CH) & (idx < L * (1 + K_INS) * CH), axis=1)
    assert ok[0] and n_ins[0] == min(IC - 1, room)
    if IC + 9 <= room:
        assert n_ins[1] == IC and n_ins[2] > IC and not ok[1:3].any()
    args = [jnp.asarray(a) for a in
            (idx, w, ok, win_of, span_m, bg, n, score)]
    kw = dict(n_windows=nW, L=L, K=K_INS, band=band, scores=scores)
    wm, um, ovf, ovf_w = _accumulate_votes(*args, matmul_votes=True, **kw)
    ws, us, _, _ = _accumulate_votes(*args, matmul_votes=False, **kw)
    wr, ur = _scatter_reference(idx, w, ok, win_of, span_m, n, score, nW,
                                L, scores)
    assert ur[:, L * CH:].sum() >= n_ins[0]     # the planes carry votes
    assert np.array_equal(np.asarray(um), ur)
    assert np.array_equal(np.asarray(wm).astype(np.int64), wr)
    assert np.array_equal(np.asarray(um), np.asarray(us))
    assert np.array_equal(np.asarray(wm), np.asarray(ws))
    assert int(ovf) == 0 and not np.asarray(ovf_w).any()


def test_matmul_votes_count_what_the_cut_loses():
    """``dropped[:, 2]`` on the matmul path: accepted pairs whose
    insertion stream reached past lane IC. The score gate makes it 0;
    a crafted ``ok`` trips it, per window, and a pair with exactly IC
    votes loses none."""
    from racon_tpu.ops.poa import K_INS, _accumulate_votes

    S, L, band = 640, 384, 512
    idx, w, ok, win_of, span_m, bg, n, score, nW = _vote_streams(
        S, L, band, seed=7)
    assert not ok[1] and not ok[2]
    kw = dict(n_windows=nW, L=L, K=K_INS, band=band, matmul_votes=True)

    def run(ok_):
        args = [jnp.asarray(a) for a in
                (idx, w, ok_, win_of, span_m, bg, n, score)]
        wm, um, ovf, ovf_w = _accumulate_votes(*args, **kw)
        return (np.asarray(wm).astype(np.int64), np.asarray(um), int(ovf),
                np.asarray(ovf_w))

    *_, ovf, ovf_w = run(ok)
    assert ovf == 0 and not ovf_w.any()
    exact = ok.copy()
    exact[1] = True                    # IC votes: all of them fit
    wm, um, ovf, ovf_w = run(exact)
    wr, ur = _scatter_reference(idx, w, exact, win_of, span_m, n, score,
                                nW, L, (3, -5, -4))
    assert ovf == 0 and np.array_equal(um, ur) and np.array_equal(wm, wr)
    over = ok.copy()
    over[2] = True                     # IC + 9 votes: 9 are cut
    wm, um, ovf, ovf_w = run(over)
    _, ur = _scatter_reference(idx, w, over, win_of, span_m, n, score,
                               nW, L, (3, -5, -4))
    assert ovf == 1 and ovf_w.tolist() == [0, 0, 1]    # pair 2, window 2
    assert ur.sum() - um.sum() == 9


# --------------------------------------------------------------- warm-up

def test_warmup_async_compiles_and_engine_still_exact():
    from racon_tpu.ops.poa import TpuPoaConsensus

    rng = np.random.default_rng(47)
    eng = TpuPoaConsensus(3, -5, -4)
    th = eng.warmup_async(64, est_pairs=64, est_windows=8)
    assert th is not None
    th.join(timeout=300)
    assert not th.is_alive()
    # the engine still produces the exact non-warmed results
    w1 = _consensus_windows(rng, n_w=4, wl=120, depth=6)
    w2 = _clone_windows(w1)
    ref = TpuPoaConsensus(3, -5, -4)
    assert eng.run(w1, trim=True) == ref.run(w2, trim=True)
    for a, b in zip(w1, w2):
        assert a.consensus == b.consensus


def test_drain_warmup_ends_the_background_compile_with_its_job():
    """A job that started a warm-up compile ends only when the compile
    has (PR 34: left running it was charged to the process's next job);
    the polisher drains it at the end of every job."""
    import inspect
    import threading
    import time

    from racon_tpu.core.polisher import Polisher
    from racon_tpu.ops.poa import TpuPoaConsensus

    eng = TpuPoaConsensus(3, -5, -4)
    eng.drain_warmup()                      # nothing started: returns
    eng._warmup = threading.Thread(target=time.sleep, args=(0.3,))
    eng._warmup.start()
    eng.drain_warmup()
    assert not eng._warmup.is_alive()
    assert "drain_warmup" in inspect.getsource(Polisher._stitch)


def test_warmup_skipped_for_empty_estimates():
    from racon_tpu.ops.poa import TpuPoaConsensus

    assert TpuPoaConsensus(3, -5, -4).warmup_async(500, 0, 0) is None


# ------------------------------------------------------ streaming parser

def test_native_parser_streams_multi_chunk_gzip(tmp_path):
    """Records spanning the chunked-inflate boundaries (>1 MiB buffer)
    parse identically to the Python oracle — the bounded-buffer rewrite
    must not change a byte."""
    from racon_tpu.io import parsers
    from racon_tpu import native

    if not native.available():
        pytest.skip("native core unavailable")
    import gzip

    rng = np.random.default_rng(48)
    chunks = []
    for i in range(300):
        seq = BASES[rng.integers(0, 4, 12000)].tobytes()
        qual = bytes(33 + int(x) for x in rng.integers(0, 60, len(seq)))
        chunks.append(b"@read_%d some description\n%s\n+\n%s\n"
                      % (i, seq, qual))
    raw = b"".join(chunks)
    assert len(raw) > 3 << 20  # several LineReader chunks
    path = tmp_path / "big.fastq.gz"
    path.write_bytes(gzip.compress(raw))
    nat = list(parsers.parse_fastq(str(path)))
    ora = list(parsers._parse_fastq_py(str(path)))
    assert len(nat) == len(ora) == 300
    for a, b in zip(nat, ora):
        assert (a.name, a.data, a.quality) == (b.name, b.data, b.quality)


def test_native_parser_long_single_line_fasta(tmp_path):
    """A FASTA record on one line longer than the read chunk exercises
    the rolling buffer's growth path."""
    from racon_tpu.io import parsers
    from racon_tpu import native

    if not native.available():
        pytest.skip("native core unavailable")
    rng = np.random.default_rng(49)
    seq = BASES[rng.integers(0, 4, (1 << 20) + 12345)].tobytes()
    path = tmp_path / "one_line.fasta"
    path.write_bytes(b">contig_long trailing meta\n" + seq + b"\n")
    recs = list(parsers.parse_fasta(str(path)))
    assert len(recs) == 1
    assert recs[0].name == b"contig_long"
    assert recs[0].data == seq


def test_probe_equal_names_kernel_and_first_difference():
    """The probes' comparison raises with the kernel, the output and the
    first differing element (what a chip run prints when a kernel family
    miscomputes) and passes silently on equality."""
    a = np.arange(12, dtype=np.int32).reshape(3, 4)
    swar.probe_equal("k", "out", a, a.copy())
    b = a.copy()
    b[1, 2] = 99
    b[2, 0] = 98
    with pytest.raises(swar.KernelProbeError,
                       match=r"k: output 'out' differs .* 2 of 12 "
                             r"elements, first at \(1, 2\): got 6, "
                             r"reference 99"):
        swar.probe_equal("k", "out", a, b)
    with pytest.raises(swar.KernelProbeError, match="shape"):
        swar.probe_equal("k", "out", a, a[:2])


def test_swar_probe_mismatch_raises(monkeypatch):
    """``swar_ok()`` no longer selects int32 quietly: a packed kernel
    that disagrees with the int32 kernel fails the run."""
    from racon_tpu.ops import nw

    real = nw._nw_wavefront_kernel

    def skewed(*a, swar=False, **kw):
        dirs, score = real(*a, swar=swar, **kw)
        return dirs, (score + 1 if swar else score)

    monkeypatch.setattr(swar, "_SWAR_OK", None)
    monkeypatch.setattr(nw, "_nw_wavefront_kernel", skewed)
    with pytest.raises(swar.KernelProbeError, match="SWAR wavefront.*score"):
        swar.swar_ok()
    assert swar._SWAR_OK is None
