"""Stress-shaped scale probe correctness (VERDICT r4 #6): on a window
set that exercises the device engine's reject contract — w=500-regime
lengths (80% exactly 500 bp, 20% shorter tails), depths 3..400,
oversized layers, a low-identity slice — the
telemetry must actually fire, and every window the device REJECTS must
come out byte-identical to a CPU-engine-only polish of the same window
(the reject path routes through the same fallback engine; reference
analog: ``src/cuda/cudabatch.cpp:135-156`` rejects re-polished on spoa).
"""

import numpy as np
import pytest

from racon_tpu import flags as racon_flags
from racon_tpu.core.window import Window, WindowType

RUN_SLOW = racon_flags.get_bool("RACON_TPU_SLOW")


def build_stress_windows(mbp: float, seed: int = 17):
    """Stress-shaped window set in the real w=500 regime (the windower
    emits <=500 bp windows: mostly exactly 500, plus shorter contig
    tails): depths 3..400 (the 200 voting cap and
    the <3-layer passthrough both fire), an oversized-layer slice
    (layers past the pair buffer -> device reject -> CPU fallback) and
    a low-identity slice, so the reject/fallback telemetry is non-zero."""
    rng = np.random.default_rng(seed)
    bases = np.frombuffer(b"ACGT", dtype=np.uint8)
    windows = []
    covered = 0
    wi = 0
    while covered < mbp * 1e6:
        # ~80% full 500 bp windows, ~20% shorter tails
        wl = 500 if rng.random() < 0.8 else int(rng.integers(150, 500))
        covered += wl
        kind = wi % 50
        if kind == 47:       # passthrough: fewer than 3 sequences
            depth = 1
        elif kind == 48:     # beyond the 200-layer voting cap
            depth = int(rng.integers(250, 400))
        elif kind == 49:     # oversized layers: device reject -> CPU
            depth = 6
        else:
            depth = int(rng.integers(3, 60))
        truth = bases[rng.integers(0, 4, wl)]
        bb = truth.copy()
        flips = rng.random(wl) < 0.10
        bb[flips] = bases[rng.integers(0, 4, int(flips.sum()))]
        win = Window(0, wi, WindowType.TGS, bb.tobytes(), b"!" * wl)
        err = 0.30 if kind == 46 else 0.08   # one low-identity slice
        nindel = max(2, wl // 40)
        for _ in range(depth):
            layer = truth.copy()
            flips = rng.random(wl) < err
            layer[flips] = bases[rng.integers(0, 4, int(flips.sum()))]
            layer = np.delete(layer, rng.integers(0, len(layer), nindel))
            # kind 49 blows past the pair buffer Lq = L + band ~ 1024
            # for every window length: deterministic device rejects
            # (mild enough that the CPU fallback's O(len^2) POA doesn't
            # dominate the probe)
            ins_n = nindel if kind != 49 else 1200
            layer = np.insert(layer, rng.integers(0, len(layer), ins_n),
                              bases[rng.integers(0, 4, ins_n)])
            win.add_layer(layer.tobytes(), b"9" * len(layer), 0, wl - 1)
        windows.append(win)
        wi += 1
    return windows


@pytest.mark.skipif(not RUN_SLOW, reason="set RACON_TPU_SLOW=1")
def test_stress_scale_rejects_match_cpu_only():
    from racon_tpu.core.backends import CpuPoaConsensus
    from racon_tpu.ops.poa import TpuPoaConsensus

    windows = build_stress_windows(0.1)
    assert len(windows) >= 100  # all stress kinds present (period 50)
    eng = TpuPoaConsensus(3, -5, -4,
                          fallback=CpuPoaConsensus(3, -5, -4, 8),
                          num_batches=2)
    flags = eng.run(windows, trim=True)
    # the reject contract fires on this workload
    assert eng.stats["fallback_windows"] > 0, eng.stats
    assert eng.stats["dropped_layers"] > 0, eng.stats
    assert eng.stats["passthrough"] > 0, eng.stats
    assert eng.stats["device_windows"] > len(windows) // 2, eng.stats
    assert all(len(w.consensus) > 0 for w in windows)

    # CPU-engine-only polish of the same (deterministically rebuilt) set
    cpu_windows = build_stress_windows(0.1)
    cpu = CpuPoaConsensus(3, -5, -4, 8)
    cpu.run(cpu_windows, trim=True)

    # kind-49 windows carry layers far beyond the device pair buffer —
    # deterministic rejects, so their output must equal the CPU-only run
    n_checked = 0
    for i, (w, cw) in enumerate(zip(windows, cpu_windows)):
        if i % 50 == 49:
            assert w.consensus == cw.consensus, i
            n_checked += 1
    assert n_checked >= 2
    # kind-47 windows (<3 sequences) pass through as their backbone
    for i, w in enumerate(windows):
        if i % 50 == 47:
            assert w.consensus == w.sequences[0], i
    assert sum(flags) > len(windows) // 2
