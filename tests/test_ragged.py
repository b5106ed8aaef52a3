"""Round-10 ragged window packing: byte-identical consensus vs the
padded path, across the {padded, ragged} x {scatter, matmul} grid.

The ragged packer buckets windows by their OWN power-of-two lane width
and greedy-fills groups against a fixed lane arena (the cudabatch
batch-fill design) instead of padding every window to the global bucket
maxima; the int8-matmul vote path replaces the f32 one-hot matmul +
packed insertion scatter. Both are on by default, so this suite is the
tier-1 gate for their joint contract: per-window consensus must be
**byte-identical** on every combination (windows are independent and the
vote accumulation is exact integer arithmetic at any grouping), across
randomized mixed window lengths, strand mixes, F-mode short reads,
dummy-quality reads and empty/singleton windows — wired as a fail-fast
shard in ci/cpu/test.sh (and re-run under RACON_TPU_SANITIZE=1 there).

Economy: every engine here uses ``band=128`` and window lengths 60-300
(the 60/150 bp windows land in the L=256 ragged bucket, the 300 bp ones
in L=512 — two buckets, small Lq), so the whole grid shares a handful of
compile geometries; parity is a per-window bytes property, independent
of the band, so nothing is lost vs the production 512 band.
"""

import numpy as np
import pytest

from racon_tpu.core.window import Window, WindowType

BASES = np.frombuffer(b"ACGT", np.uint8)
TEST_BAND = 128


def _engine(ragged, matmul, max_depth=200, rounds=4, num_batches=1):
    from racon_tpu.core.backends import CpuPoaConsensus
    from racon_tpu.ops.poa import TpuPoaConsensus

    return TpuPoaConsensus(
        3, -5, -4, fallback=CpuPoaConsensus(3, -5, -4),
        max_depth=max_depth, band=TEST_BAND, rounds=rounds,
        num_batches=num_batches, use_ragged=ragged,
        use_matmul_votes=matmul)


def _mixed_windows(rng, n_w=18, with_quality=True, type_=WindowType.TGS):
    """Randomized mixed workload: window lengths spanning two ragged
    buckets (60..300 bp), depths 0..12 (empty, singleton and passthrough
    windows included), mixed real/dummy qualities."""
    lengths = [60, 150, 300]
    windows = []
    for wi in range(n_w):
        wl = lengths[int(rng.integers(0, len(lengths)))]
        truth = BASES[rng.integers(0, 4, wl)]
        bb = truth.copy()
        flips = rng.random(wl) < 0.1
        bb[flips] = BASES[rng.integers(0, 4, int(flips.sum()))]
        win = Window(0, wi, type_, bb.tobytes(), b"!" * wl)
        depth = int(rng.integers(0, 13)) if wi % 7 else wi % 3  # 0/1/2 mix
        for _ in range(depth):
            layer = truth.copy()
            flips = rng.random(wl) < 0.08
            layer[flips] = BASES[rng.integers(0, 4, int(flips.sum()))]
            layer = np.delete(layer, rng.integers(0, len(layer), 4))
            layer = np.insert(layer, rng.integers(0, len(layer), 4),
                              BASES[rng.integers(0, 4, 4)])
            qual = (bytes(33 + int(x) for x in
                          rng.integers(5, 50, len(layer)))
                    if with_quality and wi % 3 else None)
            win.add_layer(layer.tobytes(), qual, 0, wl - 1)
        windows.append(win)
    return windows


def _run_grid(windows, **eng_kw):
    """Run all four path combinations on the same windows; return
    {(ragged, matmul): (flags, [consensus bytes])}."""
    out = {}
    for ragged in (True, False):
        for matmul in (True, False):
            eng = _engine(ragged, matmul, **eng_kw)
            flags = eng.run(windows, trim=True)
            out[(ragged, matmul)] = (flags,
                                     [w.consensus for w in windows])
    return out


@pytest.mark.parametrize("seed", range(2))
def test_ragged_grid_parity_randomized(seed):
    rng = np.random.default_rng(100 + seed)
    windows = _mixed_windows(rng, with_quality=bool(seed % 2))
    grid = _run_grid(windows)
    ref_flags, ref_cons = grid[(False, False)]  # the r05 configuration
    assert any(ref_flags)
    for key, (flags, cons) in grid.items():
        assert flags == ref_flags, key
        assert cons == ref_cons, key


def test_ragged_parity_f_mode_short_reads():
    """F-mode (fragment correction) windows: short backbones/layers, the
    NGS window type — the shapes that land in the smallest ragged
    bucket and pack the most windows per group."""
    rng = np.random.default_rng(321)
    windows = _mixed_windows(rng, n_w=24, type_=WindowType.NGS)
    eng_r = _engine(True, True)
    flags_r = eng_r.run(windows, trim=True)
    cons_r = [w.consensus for w in windows]
    flags_p = _engine(False, False).run(windows, trim=True)
    assert flags_r == flags_p
    assert cons_r == [w.consensus for w in windows]


def test_ragged_stream_feed_batches_match_single_feed():
    """Polisher.run() feeds the stream session in producer-sized ranges;
    the grouping must not change any window's bytes vs one monolithic
    feed (and vs the padded path)."""
    rng = np.random.default_rng(7)
    windows = _mixed_windows(rng, n_w=21)

    eng = _engine(True, True)
    sess = eng.stream(trim=True)
    assert sess is not None
    for a in range(0, len(windows), 7):
        sess.feed(windows[a:a + 7])
    flags_stream = sess.finish()
    cons_stream = [w.consensus for w in windows]

    flags_pad = _engine(False, True).run(windows, trim=True)
    assert flags_stream == flags_pad
    assert cons_stream == [w.consensus for w in windows]


def test_ragged_strand_mix_via_polisher_store():
    """Columnar-store windows (the production path: layers are (offset,
    len) views into the read pool, strands mixed) through ragged vs
    padded — exercises the vectorized store gather packing, not just
    the hand-built add_layer path."""
    from tests.test_columnar_init import (build_with, make_polisher,
                                          random_state)

    sequences, nt, overlaps = random_state(5, 100)
    assert any(o.strand for o in overlaps)          # strand mix present
    assert any(not o.strand for o in overlaps)
    p = build_with(make_polisher(100), sequences, nt, overlaps,
                   legacy=False)
    windows = p.windows
    assert any(w.layer_view[0] is not None for w in windows)
    flags_r = _engine(True, True).run(windows, trim=True)
    cons_r = [w.consensus for w in windows]
    flags_p = _engine(False, False).run(windows, trim=True)
    assert flags_r == flags_p
    assert cons_r == [w.consensus for w in windows]


def test_ragged_reject_parity_oversized_layers():
    """The reject SET is part of the byte-identity contract: a window
    whose layers exceed the padded path's pair buffer (Lq from the
    batch-global backbone maximum) goes to the CPU fallback there — the
    ragged packer must NOT quietly polish it on device in a bigger
    bucket, or the two paths diverge on exactly the stress shapes
    ``tests/test_scale_stress.py`` asserts on."""
    rng = np.random.default_rng(55)
    windows = _mixed_windows(rng, n_w=8)
    # one window with layers far past Lq_pad = L_pad + band (~640 for
    # this 300 bp batch at band=128): device reject on the padded path
    wl = 150
    truth = BASES[rng.integers(0, 4, wl)]
    win = Window(0, len(windows), WindowType.TGS, truth.tobytes(),
                 b"!" * wl)
    for _ in range(4):
        layer = np.insert(truth.copy(), rng.integers(0, wl, 800),
                          BASES[rng.integers(0, 4, 800)])
        win.add_layer(layer.tobytes(), None, 0, wl - 1)
    windows.append(win)

    er, ep = _engine(True, True), _engine(False, False)
    flags_r = er.run(windows, trim=True)
    cons_r = [w.consensus for w in windows]
    assert er.stats["fallback_windows"] >= 1     # the oversized window
    flags_p = ep.run(windows, trim=True)
    assert ep.stats["fallback_windows"] >= 1
    assert flags_r == flags_p
    assert cons_r == [w.consensus for w in windows]


def test_ragged_occupancy_telemetry():
    """The round-10 occupancy counters must account real lanes: both
    paths report occupied <= total, a sane efficiency/pad split and a
    windows-per-group mean >= 1."""
    rng = np.random.default_rng(13)
    # short windows only: the padded path still pads each pair row to
    # the global bucket width
    windows = []
    for wi in range(16):
        wl = 80
        truth = BASES[rng.integers(0, 4, wl)]
        win = Window(0, wi, WindowType.TGS, truth.tobytes(), b"!" * wl)
        for _ in range(6):
            layer = truth.copy()
            flips = rng.random(wl) < 0.05
            layer[flips] = BASES[rng.integers(0, 4, int(flips.sum()))]
            win.add_layer(layer.tobytes(), None, 0, wl - 1)
        windows.append(win)

    er = _engine(True, True)
    ep = _engine(False, True)
    er.run(windows, trim=True)
    ep.run(windows, trim=True)
    pr, pp = er.pack_metrics(), ep.pack_metrics()
    assert pr["groups"] >= 1 and pp["groups"] >= 1
    assert 0 < pr["pack_efficiency"] <= 1
    assert pr["windows_per_group"] >= 1
    assert abs(pr["pack_efficiency"] + pr["pad_fraction"] - 1) < 1e-6
    # both paths bucket these 80 bp windows at L=256, so efficiencies
    # tie; the ragged win is MORE PAIRS PER GROUP on mixed-size batches
    # (covered by the parity tests) — here just require no regression
    assert pr["pack_efficiency"] >= pp["pack_efficiency"] - 1e-6
    st = er.stats
    assert st["lanes_occupied"] <= st["lanes_total"]
    assert st["lanes_occupied"] > 0


def test_dropped_layers_warns_once_per_run(capsys):
    """scale_stats.dropped_layers was 4943 at r05 with no warning; the
    engine now emits ONE summary line per run through
    utils.logger.warn."""
    rng = np.random.default_rng(3)
    windows = _mixed_windows(rng, n_w=6)
    eng = _engine(True, True, max_depth=3)  # force depth-cap drops
    eng.run(windows, trim=True)
    err = capsys.readouterr().err
    assert eng.stats["dropped_layers"] > 0
    lines = [ln for ln in err.splitlines()
             if "layer alignments dropped" in ln]
    assert len(lines) == 1
    assert "dropped_layers" in lines[0]
