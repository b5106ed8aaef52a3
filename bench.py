#!/usr/bin/env python
"""Benchmark: POA consensus throughput (windows/sec) on the λ-phage set.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.
``value`` is the TPU consensus engine's warm windows/sec over the real
λ-phage polishing workload (1 contig of 47.5 kbp → 96 windows of w=500 at
~30x);
``vs_baseline`` is the speedup over the CPU spoa-equivalent engine on the
same windows (the reference's own accelerated-vs-CPU framing — it publishes
no absolute numbers, BASELINE.md). Extra diagnostic fields ride along in
the same JSON object. Progress goes to stderr.
"""

from __future__ import annotations

import json
import sys
import time

DATA = "/root/reference/test/data"


def log(msg):
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def build_windows():
    """Parse λ-phage and build the window set (SAM input carries CIGARs, so
    no alignment is needed here; the aligner is benched separately)."""
    from racon_tpu.core.polisher import create_polisher

    p = create_polisher(
        f"{DATA}/sample_reads.fastq.gz", f"{DATA}/sample_overlaps.sam.gz",
        f"{DATA}/sample_layout.fasta.gz", num_threads=8)
    p.initialize()
    return p.windows


def bench_consensus(windows):
    from racon_tpu.core.backends import CpuPoaConsensus
    from racon_tpu.ops.poa import TpuPoaConsensus

    cpu = CpuPoaConsensus(3, -5, -4, num_threads=8)
    tpu = TpuPoaConsensus(3, -5, -4, fallback=cpu)

    log("TPU consensus: cold run (compiles)...")
    t0 = time.perf_counter()
    tpu.run(windows, trim=True)
    cold = time.perf_counter() - t0
    log(f"cold: {cold:.2f}s, stats={tpu.stats}")

    # best-of-2 warm runs; min is the standard noise-free estimator
    # (ROADMAP S1 replaces this with medians over repeated readings)
    warm = float("inf")
    for r in range(2):
        tpu.stats = {k: 0 for k in tpu.stats}  # stats = one warm run
        t0 = time.perf_counter()
        tpu.run(windows, trim=True)
        warm = min(warm, time.perf_counter() - t0)
    log(f"warm (best of 2): {warm:.2f}s")

    # matmul vote path: insertion fold overflow is structurally
    # impossible (the r05 96-window run recorded 265 events); the
    # RACON_TPU_MATMUL_VOTES=0 A/B leg may legitimately overflow
    if tpu.use_matmul_votes:
        assert tpu.stats["ins_overflow"] == 0, tpu.stats

    log("CPU consensus baseline...")
    t0 = time.perf_counter()
    cpu.run(windows, trim=True)
    cpu_t = time.perf_counter() - t0
    log(f"cpu: {cpu_t:.2f}s")
    stats = dict(tpu.stats)
    stats["pack"] = tpu.pack_metrics()
    # per-window fold-overflow attribution (round 19): empty on the
    # matmul path (overflow is structurally impossible there); on the
    # scatter path it names the offending window ids instead of the
    # old opaque event total
    stats["ins_overflow_by_window"] = {
        str(k): v for k, v in
        getattr(tpu, "ins_overflow_by_window", {}).items()}
    return cold, warm, cpu_t, stats


def bench_aligner():
    """Device aligner vs the 8-thread host Myers aligner on the same
    synthetic ONT-like batch (15% divergence, read lengths 2-8 kbp,
    2048 pairs — the aligner is a batch engine; real polishing runs
    stream 10^4-10^6 overlaps, so the batch must be large enough to
    amortize the device-dispatch latency the way production runs do)."""
    import numpy as np
    from racon_tpu.core.backends import NativeAligner
    from racon_tpu.ops.nw import TpuAligner

    rng = np.random.default_rng(11)
    bases = np.frombuffer(b"ACGT", dtype=np.uint8)
    pairs = []
    for k in range(2048):
        # a 1-in-32 slice of short ~40%-divergence pairs exercises the
        # band-escape -> escalation cascade the rejects contract exists
        # for (band_escalated lands in the stats below) without routing
        # work into the widest buckets
        hot = k % 32 == 0
        ln = int(rng.integers(500, 900)) if hot else int(
            rng.integers(2000, 8000))
        t = bases[rng.integers(0, 4, ln)]
        q = t.copy()
        flips = rng.random(ln) < 0.15
        q[flips] = bases[rng.integers(0, 4, int(flips.sum()))]
        if hot:
            # structural rearrangement: moving the first ~ln/2 bases to
            # the end forces an off-diagonal path wander ~ln/2 wide with
            # a tiny length difference, deterministically escaping the
            # initial bucket's band — the escalate (and for the longest
            # pairs host-fallback) legs of the reject cascade run
            cut = len(q) // 2
            q = np.concatenate([q[cut:], q[:cut]])
        pairs.append((q.tobytes(), t.tobytes()))

    # pipeline depth 2 (the reference tunes --cudaaligner-batches the
    # same way) so packing/transfer of chunk k+1 overlaps compute of k.
    # The headline measures the PRODUCTION surface — breaking_points_batch
    # (find_overlap_breaking_points role): the walk stays on device and
    # only ~8 bytes per window boundary cross the host link; CIGAR mode
    # (align_batch) is timed separately for the host-agreement check.
    metas = [(k * 17 % 1000, k * 13 % 500) for k in range(len(pairs))]
    aligner = TpuAligner(num_batches=4)
    log("TPU aligner (breaking-points mode): cold run (compiles)...")
    t0 = time.perf_counter()
    aligner.breaking_points_batch(pairs, metas, 500)
    cold = time.perf_counter() - t0
    log(f"cold: {cold:.2f}s, stats={aligner.stats}")
    log("TPU aligner: warm runs...")
    warm = float("inf")
    for r in range(2):
        aligner.stats = {k: 0 for k in aligner.stats}  # one warm run
        t0 = time.perf_counter()
        bps = aligner.breaking_points_batch(pairs, metas, 500)
        warm = min(warm, time.perf_counter() - t0)
    bases_aligned = sum(len(q) for q, _ in pairs)
    log(f"warm (best of 2): {warm:.2f}s ({len(pairs) / warm:.1f} pairs/s)")
    assert sum(1 for b in bps if len(b)) > 0.9 * len(pairs)

    log("TPU aligner (CIGAR mode) for the host-agreement check...")
    t0 = time.perf_counter()
    cigars = aligner.align_batch(pairs)
    cigar_warm = time.perf_counter() - t0
    log(f"cigar mode: {cigar_warm:.2f}s")
    assert all(cigars)

    log("host aligner (Myers bit-parallel, 8 threads) on the same pairs...")
    host = NativeAligner(num_threads=8)
    t0 = time.perf_counter()
    host_cigars = host.align_batch(pairs)
    host_t = time.perf_counter() - t0
    agree = sum(a == b for a, b in zip(cigars, host_cigars)) / len(pairs)
    log(f"host: {host_t:.2f}s ({len(pairs) / host_t:.1f} pairs/s, "
        f"agreement {agree:.3f})")

    # packed-vs-int32 A/B: the same breaking-points workload through the
    # int32-lane kernels (use_swar=False). The packed path is bit-exact,
    # so the only difference is wavefront-step wall-clock — the SWAR
    # speedup is visible on any backend (int16 lanes double the VPU/AVX
    # lane density).
    log("TPU aligner (int32 lanes) for the packed-vs-int32 comparison...")
    al32 = TpuAligner(num_batches=4, use_swar=False)
    al32.breaking_points_batch(pairs, metas, 500)  # cold (compiles)
    warm32 = float("inf")
    for r in range(2):
        t0 = time.perf_counter()
        al32.breaking_points_batch(pairs, metas, 500)
        warm32 = min(warm32, time.perf_counter() - t0)
    log(f"int32 warm (best of 2): {warm32:.2f}s "
        f"(packed speedup {warm32 / warm:.2f}x)")

    # round-17 A/B grid: {bucketed, ragged} x {fixed-band, ladder} on
    # the same pairs, with the ladder seeded from the span-asymmetry
    # error estimate the overlap filter would provide. Breaking points
    # must be byte-identical on every leg (the accept gate is an
    # optimality certificate at every rung — see ops/nw._AlignStream);
    # the recorded numbers are warm wall plus the honest work metric
    # (wavefront_work = B x steps x band summed over every dispatched
    # chunk) and the pad fraction that motivated the rework.
    errs = [1.0 - min(len(q), len(t)) / max(len(q), len(t))
            for q, t in pairs]

    def align_ab(label, ragged, ladder):
        eng = TpuAligner(num_batches=4, use_ragged=ragged,
                         use_ladder=ladder)
        eng.breaking_points_batch(pairs, metas, 500, errors=errs)  # cold
        eng.stats = {k: 0 for k in eng.stats}
        t0 = time.perf_counter()
        got = eng.breaking_points_batch(pairs, metas, 500, errors=errs)
        dt = time.perf_counter() - t0
        assert all(np.array_equal(a, b) for a, b in zip(got, bps)), \
            f"breaking points diverged on {label}"
        log(f"aligner A/B ({label}): {dt:.2f}s "
            f"work={eng.stats['wavefront_work']} "
            f"pack={eng.pack_metrics()}")
        return dt, dict(eng.stats), eng.pack_metrics()

    t_bf, s_bf, p_bf = align_ab("bucketed+fixed-band, the r16 path",
                                False, False)
    t_bl, s_bl, p_bl = align_ab("bucketed+ladder", False, True)
    t_rf, s_rf, p_rf = align_ab("ragged+fixed-band", True, False)
    t_rl, s_rl, p_rl = align_ab("ragged+ladder, the default", True, True)

    # banded DP cell-updates/s: each wavefront step updates band/2 lanes
    # per pair; approximate with the bucket each pair landed in
    cells = 0
    for q, t in pairs:
        bi = aligner._bucket_index(len(q), len(t))
        max_len, band = aligner.buckets[bi]
        cells += (len(q) + len(t)) * (band // 2)
    gcups = cells / warm / 1e9
    return {
        "aligner_pairs_per_sec": round(len(pairs) / warm, 2),
        "aligner_bases_per_sec": round(bases_aligned / warm, 1),
        "aligner_cold_s": round(cold, 3),
        "aligner_warm_s": round(warm, 3),
        "aligner_warm_int32_s": round(warm32, 3),
        "aligner_swar_speedup": round(warm32 / warm, 3),
        "aligner_cigar_mode_s": round(cigar_warm, 3),
        "aligner_host8_s": round(host_t, 3),
        "aligner_vs_host8": round(host_t / warm, 3),
        "aligner_host_agreement": round(agree, 4),
        "aligner_banded_gcups": round(gcups, 2),
        "aligner_banded_gcups_int32": round(cells / warm32 / 1e9, 2),
        # the round-17 occupancy grid (byte-identical on every leg):
        # ragged speedup at fixed band, ladder work reduction at fixed
        # packing, and the default-path occupancy
        "align_ragged_speedup": round(t_bf / t_rf, 3),
        "align_ladder_speedup": round(t_bf / t_bl, 3),
        "align_ladder_step_reduction": round(
            1.0 - s_bl["wavefront_work"] / max(1, s_bf["wavefront_work"]),
            4),
        "align_work_reduction": round(
            1.0 - s_rl["wavefront_work"] / max(1, s_bf["wavefront_work"]),
            4),
        "align_pad_fraction": p_rl["align_pad_fraction"],
        "align_pad_fraction_bucketed_fixed": p_bf["align_pad_fraction"],
        "align_ab_wall_s": {"bucketed_fixed": round(t_bf, 3),
                            "bucketed_ladder": round(t_bl, 3),
                            "ragged_fixed": round(t_rf, 3),
                            "ragged_ladder": round(t_rl, 3)},
        "aligner_stats": dict(aligner.stats),
    }


def build_stress_windows(mbp: float, seed: int = 17):
    """Stress-shaped window set (VERDICT r4 #6) in the real w=500
    regime (the windower emits <=500 bp windows: mostly exactly 500,
    plus shorter contig tails): depths 3..400 (the 200 voting cap and
    the <3-layer passthrough both fire), an oversized-layer slice
    (layers past the pair buffer -> device reject -> CPU fallback) and
    a low-identity slice — so the scale number is earned on a workload
    where the reject/fallback telemetry is non-zero, not on uniform
    best-case windows."""
    import numpy as np
    from racon_tpu.core.window import Window, WindowType

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


def bench_scale():
    """Scaling probe, on by default (RACON_TPU_BENCH_SCALE overrides the
    size in Mbp; 0 disables): consensus throughput on a STRESS-shaped
    synthetic window set (mixed lengths/depths, rejects firing — see
    :func:`build_stress_windows`), with a measured CPU-engine baseline
    on the same windows for an apples-to-apples ``scale_vs_cpu``."""
    from racon_tpu import flags as racon_flags

    mbp = racon_flags.get_float("RACON_TPU_BENCH_SCALE")
    if not mbp:
        return {}
    from racon_tpu.core.backends import CpuPoaConsensus
    from racon_tpu.ops.poa import TpuPoaConsensus

    windows = build_stress_windows(mbp)
    n_windows = len(windows)
    cpu = CpuPoaConsensus(3, -5, -4, 8)
    # default engine: ragged packing + int8-matmul votes (round 10)
    tpu = TpuPoaConsensus(3, -5, -4, fallback=cpu, num_batches=4)
    log(f"scale probe: {n_windows} stress windows ({mbp} Mbp), cold...")
    t0 = time.perf_counter()
    tpu.run(windows, trim=True)
    cold = time.perf_counter() - t0
    log(f"scale cold: {cold:.2f}s")
    # best-of-2 warm runs (like the λ probe): a single sample is noise
    warm = float("inf")
    for _ in range(2):
        tpu.stats = {k: 0 for k in tpu.stats}  # stats = one warm run
        t0 = time.perf_counter()
        tpu.run(windows, trim=True)
        warm = min(warm, time.perf_counter() - t0)
    out_ref = [w.consensus for w in windows]
    out_bytes = sum(len(c) for c in out_ref)
    # the stress shapes must actually exercise the reject contract (the
    # stress kinds recur every 50 windows, so tiny override sizes may
    # legitimately not contain them)
    if n_windows >= 100:
        assert tpu.stats["fallback_windows"] > 0, tpu.stats
        assert tpu.stats["passthrough"] > 0, tpu.stats
        # silent-layer-loss guard (round 10): the depth-cap component of
        # dropped_layers is deterministic from the window set, so the
        # counter must cover at least it — a regression that stops
        # counting (or stops feeding the per-run warn line) fails here
        # instead of silently at assembly scale
        expected_drops = sum(max(0, w.layer_count - tpu.max_depth)
                             for w in windows)
        assert expected_drops > 0, "stress set lost its deep windows"
        assert tpu.stats["dropped_layers"] >= expected_drops, (
            tpu.stats["dropped_layers"], expected_drops)
    # the matmul vote path has no insertion fold cap: overflow events
    # are structurally impossible (265 of them at r05); the
    # RACON_TPU_MATMUL_VOTES=0 A/B leg may legitimately overflow
    if tpu.use_matmul_votes:
        assert tpu.stats["ins_overflow"] == 0, tpu.stats
    pack = tpu.pack_metrics()
    log(f"scale pack: {pack}")

    # A/B grid vs the r05 configuration ({padded, ragged} x {scatter,
    # matmul}): same windows, byte-identical consensus on every path —
    # the speedup is recorded at fixed output bytes, not prose
    def ab(label, ragged, mm, warm_runs=1):
        eng = TpuPoaConsensus(3, -5, -4, fallback=cpu, num_batches=4,
                              use_ragged=ragged, use_matmul_votes=mm)
        log(f"scale A/B ({label}): cold...")
        eng.run(windows, trim=True)  # cold (compiles)
        best = float("inf")
        for _ in range(warm_runs):
            t0 = time.perf_counter()
            eng.run(windows, trim=True)
            best = min(best, time.perf_counter() - t0)
        outs = [w.consensus for w in windows]
        assert outs == out_ref, f"consensus diverged on {label}"
        log(f"scale A/B ({label}): {best:.2f}s ({mbp / best:.3f} Mbp/s), "
            f"output byte-identical")
        return best

    warm_ps = ab("padded+scatter, the r05 path", False, False,
                 warm_runs=2)
    warm_pm = ab("padded+matmul", False, True)
    warm_rs = ab("ragged+scatter", True, False)
    # packed-vs-int32 A/B on the same windows (bit-exact outputs, so
    # the delta is pure wavefront wall-clock)
    log("scale probe (int32 lanes) for the packed comparison...")
    tpu32 = TpuPoaConsensus(3, -5, -4, fallback=cpu, num_batches=4,
                            use_swar=False)
    tpu32.run(windows, trim=True)  # cold (compiles)
    t0 = time.perf_counter()
    tpu32.run(windows, trim=True)
    warm32 = time.perf_counter() - t0
    log(f"scale int32 warm: {warm32:.2f}s "
        f"(packed speedup {warm32 / warm:.2f}x)")
    log("scale CPU baseline on the same windows...")
    t0 = time.perf_counter()
    cpu.run(windows, trim=True)
    cpu_t = time.perf_counter() - t0
    log(f"scale cpu: {cpu_t:.2f}s ({mbp / cpu_t:.3f} Mbp/s)")
    log(f"scale warm: {warm:.2f}s ({n_windows / warm:.1f} windows/s, "
        f"{mbp / warm:.3f} Mbp/s, {warm_ps / warm:.2f}x over "
        f"padded+scatter)")
    return {
        "scale_mbp": mbp,
        "scale_windows": n_windows,
        "scale_windows_per_sec": round(n_windows / warm, 2),
        "scale_mbp_per_sec": round(mbp / warm, 4),
        # fixed-output-bytes proof: every A/B leg above asserted its
        # consensus byte-identical to the default path's
        "scale_out_bytes": out_bytes,
        # the r05 configuration and the single-axis legs (BENCH_r06 A/B)
        "scale_mbp_per_sec_padded_scatter": round(mbp / warm_ps, 4),
        "scale_ragged_matmul_speedup": round(warm_ps / warm, 3),
        "scale_padded_matmul_s": round(warm_pm, 3),
        "scale_ragged_scatter_s": round(warm_rs, 3),
        "scale_int32_s": round(warm32, 3),
        "consensus_swar_speedup": round(warm32 / warm, 3),
        "scale_cpu_s": round(cpu_t, 3),
        "scale_cpu_mbp_per_sec": round(mbp / cpu_t, 4),
        "scale_vs_cpu": round(cpu_t / warm, 3),
        # real pair-arena occupancy (occupied/total lanes, mean windows
        # per group) — replaces the coarse consensus_vpu_util_est, which
        # modeled VPU busy-ness from wavefront steps and could not see
        # padding waste (the 0.018 headline at r05 was ~98% padding)
        "scale_pack": pack,
        "scale_stats": dict(tpu.stats),
    }


def bench_pipeline():
    """FULL-pipeline benchmark at assembly scale (VERDICT r4 #1), on by
    default: parse -> device align/breaking-points -> window -> device
    consensus -> stitch on a >=10 Mbp simulated ONT assembly (reads at
    30x + exact PAF overlaps + a ~10%-error draft; tools/simulate.py),
    through the exact create_polisher/initialize/polish surface the CLI
    drives. A 1 Mbp slice runs the identical pipeline on the CPU engines
    for a measured per-Mbp baseline. Quality gate: the polished draft
    must land much closer to the truth than the input draft (checked on
    a 100 kbp prefix with the native Myers distance).
    RACON_TPU_BENCH_PIPELINE overrides the size in Mbp; 0 disables."""
    import os
    import sys
    import tempfile
    import time as _time

    from racon_tpu import flags as racon_flags

    mbp = racon_flags.get_float("RACON_TPU_BENCH_PIPELINE")
    if not mbp:
        return {}
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tools"))
    from simulate import simulate
    from racon_tpu.core.polisher import create_polisher
    from racon_tpu import native

    def run_once(mbp_run, seed, backend, batches, fused=False):
        t0 = _time.perf_counter()
        reads, paf, contigs, truths = simulate(mbp_run, seed=seed)
        gen_s = _time.perf_counter() - t0
        with tempfile.TemporaryDirectory() as td:
            rp = os.path.join(td, "reads.fastq")
            pp = os.path.join(td, "ovl.paf")
            cp = os.path.join(td, "draft.fasta")
            for path, blob in ((rp, reads), (pp, paf), (cp, contigs)):
                with open(path, "wb") as f:
                    f.write(blob)
            # run boundary: each bench leg reports its own registry
            # numbers (retrace below), not the previous leg's
            from racon_tpu.obs import metrics as obs_metrics
            from racon_tpu.obs import trace as obs_trace
            obs_metrics.clear_run()
            # arm the span timers (no ring buffers) so the init
            # breakdown's dispatch-vs-fetch split is measured, not 0
            obs_trace.activate(tracing=False)
            t0 = _time.perf_counter()
            p = create_polisher(rp, pp, cp, num_threads=8,
                                aligner_backend=backend,
                                consensus_backend=backend,
                                aligner_batches=batches,
                                consensus_batches=batches)
            if fused:
                # pipelined surface: window build streams into consensus
                polished = p.run(drop_unpolished_sequences=True)
                init_s = polish_s = 0.0
                total_s = _time.perf_counter() - t0
            else:
                p.initialize()
                init_s = _time.perf_counter() - t0
                t0 = _time.perf_counter()
                polished = p.polish(drop_unpolished_sequences=True)
                polish_s = _time.perf_counter() - t0
                total_s = init_s + polish_s
        stats = {}
        for eng in (p.aligner, p.consensus):
            for k, v in getattr(eng, "stats", {}).items():
                stats[k] = stats.get(k, 0) + v
        # per-phase jit-compile churn (PhaseRetraceBudget publishes the
        # deltas to the obs metrics registry whether or not the
        # sanitizer is armed — bench reads the one registry like the
        # heartbeat and the run report do)
        from racon_tpu.obs import metrics as obs_metrics
        retrace = obs_metrics.group("retrace.")
        # resident-dataflow accounting (round 19): bytes fetched vs
        # host round-trips avoided, host-fallback pairs, device-lane
        # consensus groups — all zeros with RACON_TPU_RESIDENT off
        dataflow = obs_metrics.dataflow_summary()
        # quality gate on a truth-prefix slice (coordinates drift with
        # indels, so compare a bounded prefix with the full Myers NW)
        probe = min(100_000, len(truths[0]))
        pol0 = next((s.data for s in polished
                     if s.name.startswith(b"contig_0")), b"")
        draft0 = contigs.split(b"\n", 1)[1].split(b"\n", 1)[0]
        err_after = native.edit_distance(pol0[:probe], truths[0][:probe])
        err_before = native.edit_distance(draft0[:probe],
                                          truths[0][:probe])
        return dict(gen_s=gen_s, init_s=init_s, polish_s=polish_s,
                    total_s=total_s, stats=stats, timings=dict(p.timings),
                    align_stats=dict(getattr(p.aligner, "stats", {})),
                    align_pack=(p.aligner.pack_metrics()
                                if hasattr(p.aligner, "pack_metrics")
                                else {}),
                    retrace=retrace, dataflow=dataflow,
                    err_after=err_after,
                    err_before=err_before, probe=probe,
                    n_polished=len(polished), pol0=pol0)

    log(f"pipeline bench: {mbp} Mbp TPU full pipeline...")
    tpu = run_once(mbp, seed=23, backend="tpu", batches=4)
    log(f"pipeline tpu: init {tpu['init_s']:.1f}s + polish "
        f"{tpu['polish_s']:.1f}s = {tpu['total_s']:.1f}s "
        f"({mbp / tpu['total_s']:.3f} Mbp/s), stats={tpu['stats']}, "
        f"init breakdown={tpu['timings']}")
    # fused A/B (RACON_TPU_BENCH_FUSED=0 disables): the same workload
    # through run() — init->polish pipelined; polished bytes must be
    # IDENTICAL to the split surface (scale-sized bit-parity check)
    fused_metrics = {}
    if racon_flags.get_bool("RACON_TPU_BENCH_FUSED"):
        log(f"pipeline bench: {mbp} Mbp TPU fused (pipelined) run...")
        fused = run_once(mbp, seed=23, backend="tpu", batches=4,
                         fused=True)
        assert fused["pol0"] == tpu["pol0"], \
            "fused run() diverged from initialize()+polish()"
        log(f"pipeline fused: {fused['total_s']:.1f}s "
            f"({mbp / fused['total_s']:.3f} Mbp/s, split was "
            f"{tpu['total_s']:.1f}s)")
        fused_metrics = {
            "pipeline_fused_total_s": round(fused["total_s"], 2),
            "pipeline_fused_mbp_per_sec": round(mbp / fused["total_s"], 4),
            "pipeline_fused_vs_split": round(
                tpu["total_s"] / fused["total_s"], 3),
        }
    # round-17 aligner A/B: the same pipeline with the ragged align
    # stream and band ladder DISABLED (the r16 aligner path), at fixed
    # output bytes — records the acceptance metric: total banded
    # wavefront work (B x steps x band summed over every dispatched
    # chunk and rung) must drop vs the fixed-band bucketed path, with
    # the pad fraction reported alongside
    align_ab_metrics = {}
    log(f"pipeline bench: {mbp} Mbp fixed-band bucketed aligner A/B...")
    os.environ["RACON_TPU_ALIGN_RAGGED"] = "0"
    os.environ["RACON_TPU_BAND_LADDER"] = "0"
    try:
        fixed = run_once(mbp, seed=23, backend="tpu", batches=4)
    finally:
        os.environ.pop("RACON_TPU_ALIGN_RAGGED", None)
        os.environ.pop("RACON_TPU_BAND_LADDER", None)
    assert fixed["pol0"] == tpu["pol0"], \
        "fixed-band bucketed aligner A/B diverged from the default path"
    work_fixed = max(1, fixed["align_stats"].get("wavefront_work", 0))
    work_def = tpu["align_stats"].get("wavefront_work", 0)
    align_ab_metrics = {
        "pipeline_align_work": work_def,
        "pipeline_align_work_fixed": work_fixed,
        "pipeline_align_work_reduction": round(
            1.0 - work_def / work_fixed, 4),
        "pipeline_align_pad_fraction":
            tpu["align_pack"].get("align_pad_fraction", 0.0),
        "pipeline_align_pad_fraction_fixed":
            fixed["align_pack"].get("align_pad_fraction", 0.0),
        "pipeline_align_ab_total_s": round(fixed["total_s"], 2),
    }
    log(f"pipeline align A/B: work {work_fixed} -> {work_def} "
        f"({align_ab_metrics['pipeline_align_work_reduction']:.1%} "
        f"reduction), output byte-identical")

    # round-19 resident-dataflow A/B (RACON_TPU_BENCH_RESIDENT=0
    # disables): the same workload with RACON_TPU_RESIDENT=1 — breaking
    # points stay on device, window assignment + layer rows derive on
    # device, and the consensus engine gathers its qpw lanes from the
    # device-resident pool. Polished bytes must be IDENTICAL to the
    # host path (the resident path's contract is byte-parity, not
    # approximation); the recorded numbers are the collapsed init
    # breakdown (align_fetch_s / bp_decode_s / build_windows_s vs the
    # new window_derive_s) plus the dataflow bytes ledger.
    resident_metrics = {}
    if racon_flags.get_bool("RACON_TPU_BENCH_RESIDENT"):
        log(f"pipeline bench: {mbp} Mbp resident-dataflow A/B...")
        os.environ["RACON_TPU_RESIDENT"] = "1"
        try:
            res = run_once(mbp, seed=23, backend="tpu", batches=4)
        finally:
            os.environ.pop("RACON_TPU_RESIDENT", None)
        assert res["pol0"] == tpu["pol0"], \
            "resident dataflow diverged from the host align→consensus path"
        if racon_flags.get_bool("RACON_TPU_BENCH_FUSED") and fused_metrics:
            assert res["pol0"] == fused["pol0"], \
                "resident dataflow diverged from the fused run() output"
        df = res["dataflow"]
        tm = res["timings"]
        host_tm = tpu["timings"]
        collapsed = (host_tm.get("align_fetch_s", 0.0)
                     + host_tm.get("bp_decode_s", 0.0)
                     + host_tm.get("build_windows_s", 0.0))
        resident_now = (tm.get("align_fetch_s", 0.0)
                        + tm.get("bp_decode_s", 0.0)
                        + tm.get("build_windows_s", 0.0)
                        + tm.get("window_derive_s", 0.0))
        resident_metrics = {
            "pipeline_resident_total_s": round(res["total_s"], 2),
            "pipeline_resident_mbp_per_sec": round(
                mbp / res["total_s"], 4),
            "pipeline_resident_vs_host": round(
                tpu["total_s"] / res["total_s"], 3),
            "pipeline_resident_init_breakdown": tm,
            # the handoff cost the tentpole attacks, host vs resident
            "pipeline_resident_handoff_host_s": round(collapsed, 3),
            "pipeline_resident_handoff_s": round(resident_now, 3),
            "pipeline_resident_dataflow": df,
        }
        log(f"pipeline resident: {res['total_s']:.1f}s "
            f"({mbp / res['total_s']:.3f} Mbp/s, host was "
            f"{tpu['total_s']:.1f}s), handoff {collapsed:.2f}s -> "
            f"{resident_now:.2f}s, fetched {df['bytes_fetched']} B, "
            f"avoided {df['bytes_avoided']} B, output byte-identical")

    cpu_mbp = min(1.0, mbp)
    log(f"pipeline bench: {cpu_mbp} Mbp CPU-engine baseline...")
    cpu = run_once(cpu_mbp, seed=29, backend="cpu", batches=1)
    log(f"pipeline cpu: {cpu['total_s']:.1f}s "
        f"({cpu_mbp / cpu['total_s']:.3f} Mbp/s)")
    assert cpu["err_after"] * 3 < cpu["err_before"], cpu
    assert tpu["err_after"] * 3 < tpu["err_before"], tpu
    tput = mbp / tpu["total_s"]
    cput = cpu_mbp / cpu["total_s"]
    return {
        "pipeline_mbp": mbp,
        "pipeline_total_s": round(tpu["total_s"], 2),
        "pipeline_init_s": round(tpu["init_s"], 2),
        "pipeline_polish_s": round(tpu["polish_s"], 2),
        # init-phase attribution (parse_s, align_s, bp_decode_s,
        # layer_append_s, build_windows_s, pipeline_overlap_saved_s) so
        # BENCH rounds can pin future init regressions to a phase — the
        # layer_append_s entry is the slice-and-append cost the "move
        # layer storage columnar" ROADMAP call will be decided from
        "pipeline_init_breakdown": tpu["timings"],
        "pipeline_retrace": tpu["retrace"],
        "pipeline_mbp_per_sec": round(tput, 4),
        **fused_metrics,
        **align_ab_metrics,
        **resident_metrics,
        "pipeline_cpu_mbp": cpu_mbp,
        "pipeline_cpu_total_s": round(cpu["total_s"], 2),
        "pipeline_cpu_mbp_per_sec": round(cput, 4),
        "pipeline_vs_cpu": round(tput / cput, 3),
        "pipeline_err_per_100k_before": tpu["err_before"],
        "pipeline_err_per_100k_after": tpu["err_after"],
        "pipeline_stats": tpu["stats"],
    }


def bench_overlap():
    """First-party overlapper benchmark (round 20): seed+match+chain a
    RACON_TPU_BENCH_OVERLAP-Mbp (default 1) simulated assembly through
    ``--overlaps auto``'s own path and report overlapper Mbp/s plus the
    seed/chain lane occupancies and the candidate-pair funnel. Quality
    gate: an auto-fed polish leg must land within noise of the
    PAF-fed leg's edit distance to truth (and far below the draft's),
    and the emitted auto PAF must be byte-identical across reruns.
    0 disables."""
    import os
    import sys
    import tempfile
    import time as _time

    from racon_tpu import flags as racon_flags

    mbp = racon_flags.get_float("RACON_TPU_BENCH_OVERLAP")
    if not mbp:
        return {}
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tools"))
    from simulate import simulate
    from racon_tpu import native
    from racon_tpu.core.polisher import create_polisher
    from racon_tpu.exec.index import write_auto_paf
    from racon_tpu.obs import metrics as obs_metrics
    from racon_tpu.obs import trace as obs_trace

    log(f"overlap bench: {mbp} Mbp first-party overlapper...")
    reads, paf, contigs, truths = simulate(mbp, seed=37)
    out = {}
    with tempfile.TemporaryDirectory() as td:
        rp = os.path.join(td, "reads.fastq")
        pp = os.path.join(td, "ovl.paf")
        cp = os.path.join(td, "draft.fasta")
        for path, blob in ((rp, reads), (pp, paf), (cp, contigs)):
            with open(path, "wb") as f:
                f.write(blob)

        # ---- overlapper-only throughput leg (parse -> seed -> match
        # -> chain -> PAF serialize, the sharded auto path verbatim)
        obs_metrics.clear_run()
        obs_trace.activate(tracing=False)
        t0 = _time.perf_counter()
        write_auto_paf(rp, cp, os.path.join(td, "auto1.paf"))
        dt = _time.perf_counter() - t0
        g = obs_metrics.group("overlap.")
        in_mbp = (sum(len(s) for s in reads.split(b"\n")[1::4])
                  + sum(len(t) for t in truths)) / 1e6
        seed_occ = (g.get("seed_lanes_occupied", 0)
                    / max(1, g.get("seed_lanes_total", 1)))
        chain_occ = (g.get("chain_lanes_occupied", 0)
                     / max(1, g.get("chain_lanes_total", 1)))
        log(f"overlapper: {in_mbp:.2f} Mbp in {dt:.2f}s = "
            f"{in_mbp / dt:.3f} Mbp/s; {g.get('minimizers', 0)} "
            f"minimizers, {g.get('candidate_pairs', 0)} candidate "
            f"pairs, {g.get('chains_kept', 0)} chains kept "
            f"({g.get('chains_dropped', 0)} dropped, "
            f"{g.get('freq_capped_buckets', 0)} hot buckets capped); "
            f"occupancy seed {seed_occ:.3f} chain {chain_occ:.3f}")
        # rerun byte-identity (the acceptance determinism contract);
        # the rerun also serves the target table from the fingerprint
        # cache — the warm-serve accounting the grid below extends
        hits_before = obs_metrics.counter("overlap.cache_hits")
        write_auto_paf(rp, cp, os.path.join(td, "auto2.paf"))
        with open(os.path.join(td, "auto1.paf"), "rb") as f1, \
                open(os.path.join(td, "auto2.paf"), "rb") as f2:
            b1, b2 = f1.read(), f2.read()
        assert b1 == b2, "auto PAF not byte-identical across reruns"
        assert len(b1) > 0, "auto overlapper emitted no overlaps"

        # ---- A/B grid (round 21): {device join, host join} x {ragged
        # stream, phase barrier} at fixed output bytes — every leg warm
        # (auto1 paid the compiles) and byte-identical to the default
        # leg, so the timing deltas are scheduling, not output
        grid = {}
        for leg, env in (
                ("device_stream", {}),
                ("host_join", {"RACON_TPU_OVERLAP_DEVICE_JOIN": "0"}),
                ("barrier", {"RACON_TPU_OVERLAP_RAGGED": "0"}),
                ("host_barrier", {"RACON_TPU_OVERLAP_DEVICE_JOIN": "0",
                                  "RACON_TPU_OVERLAP_RAGGED": "0"})):
            saved = {kk: os.environ.get(kk) for kk in env}
            os.environ.update(env)
            try:
                t0 = _time.perf_counter()
                write_auto_paf(rp, cp, os.path.join(td, leg + ".paf"))
                grid[leg] = _time.perf_counter() - t0
            finally:
                for kk, vv in saved.items():
                    if vv is None:
                        os.environ.pop(kk, None)
                    else:
                        os.environ[kk] = vv
            with open(os.path.join(td, leg + ".paf"), "rb") as f:
                assert f.read() == b1, f"{leg} leg PAF diverged"
        cache_hits_warm = (obs_metrics.counter("overlap.cache_hits")
                           - hits_before)
        join_speedup = grid["host_join"] / max(1e-9,
                                               grid["device_stream"])
        stream_saved = grid["barrier"] - grid["device_stream"]
        log(f"overlap A/B: device+stream {grid['device_stream']:.2f}s, "
            f"host join {grid['host_join']:.2f}s "
            f"(join speedup {join_speedup:.2f}x), barrier "
            f"{grid['barrier']:.2f}s (stream saved {stream_saved:.2f}s),"
            f" host+barrier {grid['host_barrier']:.2f}s; "
            f"{cache_hits_warm} warm target-table cache hits")

        # ---- auto-vs-PAF polish legs (same quality probe as
        # bench_pipeline: bounded truth-prefix Myers distance)
        def polish_leg(ovl):
            obs_metrics.clear_run()
            obs_trace.activate(tracing=False)
            t0 = _time.perf_counter()
            p = create_polisher(rp, ovl, cp, num_threads=8)
            polished = p.run(drop_unpolished_sequences=True)
            leg_s = _time.perf_counter() - t0
            probe = min(100_000, len(truths[0]))
            pol0 = next((s.data for s in polished
                         if s.name.startswith(b"contig_0")), b"")
            return (native.edit_distance(pol0[:probe], truths[0][:probe]),
                    leg_s, probe)

        err_auto, auto_s, probe = polish_leg("auto")
        err_paf, paf_s, _ = polish_leg(pp)
        draft0 = contigs.split(b"\n", 1)[1].split(b"\n", 1)[0]
        err_before = native.edit_distance(draft0[:probe],
                                          truths[0][:probe])
        log(f"polish quality (err/{probe // 1000}k to truth): draft "
            f"{err_before} -> PAF-fed {err_paf} vs auto-fed {err_auto} "
            f"(auto leg {auto_s:.1f}s, PAF leg {paf_s:.1f}s)")
        assert err_auto < 0.2 * err_before, \
            "auto-fed polish did not substantially improve the draft"
        assert err_auto <= err_paf * 1.3 + 20, \
            "auto-fed polish quality outside noise of the PAF-fed leg"

        out = {
            "overlap_mbp": round(in_mbp, 3),
            "overlap_mbp_per_sec": round(in_mbp / dt, 4),
            "overlap_minimizers": int(g.get("minimizers", 0)),
            "overlap_candidate_pairs": int(g.get("candidate_pairs", 0)),
            "overlap_chains_kept": int(g.get("chains_kept", 0)),
            "overlap_chains_dropped": int(g.get("chains_dropped", 0)),
            "overlap_freq_capped": int(g.get("freq_capped_buckets", 0)),
            "overlap_seed_occupancy": round(seed_occ, 4),
            "overlap_chain_occupancy": round(chain_occ, 4),
            "overlap_rerun_identical": True,
            "overlap_grid_identical": True,
            "overlap_device_stream_s": round(grid["device_stream"], 3),
            "overlap_host_join_s": round(grid["host_join"], 3),
            "overlap_barrier_s": round(grid["barrier"], 3),
            "overlap_host_barrier_s": round(grid["host_barrier"], 3),
            "overlap_join_speedup": round(join_speedup, 3),
            "overlap_stream_saved_s": round(stream_saved, 3),
            "overlap_cache_hits_warm": int(cache_hits_warm),
            "overlap_err_per_100k_before": err_before,
            "overlap_err_per_100k_paf": err_paf,
            "overlap_err_per_100k_auto": err_auto,
            "overlap_auto_leg_s": round(auto_s, 2),
            "overlap_paf_leg_s": round(paf_s, 2),
        }
    return out


def bench_shards():
    """Streaming shard-runner scaling entry (the ROADMAP ">=100 Mbp
    demonstration"): run a RACON_TPU_BENCH_SHARDS-sized (default 100)
    Mbp simulated assembly through ``racon_tpu.exec.ShardRunner`` under
    a --max-ram-style budget and record the scaling curve — Mbp/s per
    shard, init/polish breakdown, retrace counters, peak RSS vs budget —
    plus a 1 Mbp CPU-engine baseline. A smaller invariance probe first
    asserts ``--shards 4`` output is byte-identical to the single-shot
    FASTA (the subsystem's concluding contract). 0 disables."""
    import io
    import os
    import subprocess
    import tempfile

    from racon_tpu import flags as racon_flags

    mbp = racon_flags.get_float("RACON_TPU_BENCH_SHARDS")
    if not mbp:
        return {}
    from racon_tpu.core.polisher import create_polisher
    from racon_tpu.exec import ShardRunner
    from racon_tpu.exec.heartbeat import peak_rss_bytes

    sim_py = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "tools", "simulate.py")

    def gen(mbp_run, seed, td):
        # throwaway subprocess: a 100 Mbp set materializes several GB
        # while generating, which must not land in THIS process's
        # ru_maxrss — that is the number the budget check reports on
        subprocess.run([sys.executable, sim_py, str(mbp_run), td,
                        "--seed", str(seed)], check=True)
        return {k: os.path.join(td, v) for k, v in
                (("reads", "reads.fastq"), ("overlaps", "ovl.paf"),
                 ("draft", "draft.fasta"))}

    def run_sharded(paths, work, **kw):
        runner = ShardRunner(
            paths["reads"], paths["overlaps"], paths["draft"],
            num_threads=8, aligner_backend="tpu", consensus_backend="tpu",
            aligner_batches=4, consensus_batches=4, work_dir=work,
            keep_work_dir=False, **kw)
        buf = io.BytesIO()
        summary = runner.run(buf)
        return buf.getvalue(), summary

    def run_single(paths, backend="tpu", batches=4):
        p = create_polisher(
            paths["reads"], paths["overlaps"], paths["draft"],
            num_threads=8, aligner_backend=backend,
            consensus_backend=backend, aligner_batches=batches,
            consensus_batches=batches)
        polished = p.run(True)
        return b"".join(b">" + s.name + b"\n" + s.data + b"\n"
                        for s in polished)

    out = {}
    inv_mbp = min(4.0, mbp)
    with tempfile.TemporaryDirectory() as td:
        gen_paths = gen(inv_mbp, 41, td)
        log(f"shard bench: invariance probe at {inv_mbp} Mbp "
            f"(single-shot vs --shards 4)...")
        t0 = time.perf_counter()
        want = run_single(gen_paths)
        single_s = time.perf_counter() - t0
        got, _ = run_sharded(gen_paths, os.path.join(td, "work"),
                             n_shards=4)
        assert got == want, \
            "--shards 4 output diverged from the single-shot FASTA"
        log(f"shard bench: invariance OK (single-shot {single_s:.1f}s)")
        out.update(shard_invariance_mbp=inv_mbp,
                   shard_invariance="byte-identical")

    with tempfile.TemporaryDirectory() as td:
        log(f"shard bench: generating {mbp} Mbp workload (subprocess)...")
        gen_paths = gen(mbp, 43, td)
        data_bytes = sum(os.path.getsize(p) for p in gen_paths.values())
        base = peak_rss_bytes()
        budget = base + max(int(0.6 * data_bytes), 2 << 30)
        log(f"shard bench: {mbp} Mbp streaming run, --max-ram "
            f"{budget >> 20} MB (base RSS {base >> 20} MB)...")
        t0 = time.perf_counter()
        blob, summary = run_sharded(gen_paths, os.path.join(td, "work"),
                                    max_ram_bytes=budget)
        wall = time.perf_counter() - t0
        peak = peak_rss_bytes()
        log(f"shard bench: {summary['n_shards']} shards in {wall:.1f}s "
            f"({mbp / wall:.4f} Mbp/s), peak RSS {peak >> 20} MB "
            f"(budget {budget >> 20} MB), "
            f"{len(blob) / 1e6:.0f} MB polished FASTA")
        assert blob.count(b">") > 0
        curve = [{
            "shard": e["id"], "status": e["status"],
            "engine": e.get("engine"), "mbp": e.get("mbp"),
            "wall_s": e.get("wall_s"),
            "mbp_per_sec": (round(e["mbp"] / e["wall_s"], 4)
                            if e.get("wall_s") else None),
            "init_breakdown": e.get("timings"),
            "retrace": e.get("retrace"),
            "peak_rss_mb": e.get("peak_rss_mb"),
        } for e in summary["shards"]]
        out.update(
            shard_mbp=mbp, shard_count=summary["n_shards"],
            shard_total_s=round(wall, 2),
            shard_mbp_per_sec=round(mbp / wall, 4),
            shard_peak_rss_mb=peak >> 20,
            shard_budget_mb=budget >> 20,
            shard_under_budget=bool(peak <= budget),
            shard_curve=curve,
            shard_quarantined=summary["quarantined"])

    with tempfile.TemporaryDirectory() as td:
        cpu_mbp = min(1.0, mbp)
        gen_paths = gen(cpu_mbp, 47, td)
        log(f"shard bench: {cpu_mbp} Mbp CPU-engine baseline...")
        t0 = time.perf_counter()
        run_single(gen_paths, backend="cpu", batches=1)
        cpu_s = time.perf_counter() - t0
        log(f"shard bench: cpu {cpu_s:.1f}s "
            f"({cpu_mbp / cpu_s:.4f} Mbp/s)")
        out.update(
            shard_cpu_mbp=cpu_mbp,
            shard_cpu_mbp_per_sec=round(cpu_mbp / cpu_s, 4),
            shard_vs_cpu=round(out["shard_mbp_per_sec"]
                               / (cpu_mbp / cpu_s), 3))
    return out


def bench_multichip():
    """Mbp/s-vs-chips scaling curve through the in-process chip
    scheduler (ROADMAP item 2; the MULTICHIP_r06 artifact shape): polish
    a RACON_TPU_BENCH_MULTICHIP-sized simulated assembly once per chip
    count through the real CLI (``--chips k`` routes through the shard
    runner's chip-worker pool), with a byte-identity assert of the
    1-chip vs all-chip outputs. Each point runs in a subprocess — chip
    visibility is process-level JAX state — sharing one persistent
    compile cache so later points start warm. On a single-device host
    point k provisions a k-virtual-device CPU mesh (capped at 4): the
    schedule, leases and merge still execute end-to-end, but
    wall-clock is NOT a hardware number (``multichip_devices`` records
    which regime ran — only real-chip curves belong in a
    BENCH/MULTICHIP record of merit). 0 disables."""
    import os
    import subprocess
    import tempfile

    from racon_tpu import flags as racon_flags

    mbp = racon_flags.get_float("RACON_TPU_BENCH_MULTICHIP")
    if not mbp:
        return {}
    import jax

    n_real = len(jax.local_devices())
    fake = n_real == 1
    # virtual mesh: cap at 4 chips — the point is exercising the
    # scheduler end-to-end, and every fake chip pays a real per-device
    # CPU compile for zero measurement value
    n_chips = 4 if fake else n_real
    points = sorted({1, 2, n_chips} - {0})
    sim_py = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "tools", "simulate.py")
    out = {}
    with tempfile.TemporaryDirectory() as td:
        log(f"multichip bench: generating {mbp} Mbp workload...")
        subprocess.run([sys.executable, sim_py, str(mbp), td,
                        "--seed", "53"], check=True)
        paths = [os.path.join(td, n)
                 for n in ("reads.fastq", "ovl.paf", "draft.fasta")]
        cache = os.path.join(td, "xla_cache")
        curve = []
        blobs = {}
        for k in points:
            env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=cache)
            if fake:
                # provision exactly k virtual devices per point: the
                # 1-chip reference must BE one chip (no 8-way mesh),
                # and point k must not idle 8-k fake devices' compiles
                env["JAX_PLATFORMS"] = "cpu"
                env["XLA_FLAGS"] = (
                    env.get("XLA_FLAGS", "")
                    + f" --xla_force_host_platform_device_count={k}"
                ).strip()
            out_path = os.path.join(td, f"out_{k}.fasta")
            log(f"multichip bench: --chips {k} "
                + (f"({k} virtual CPU devices)..." if fake
                   else "(hardware)..."))
            t0 = time.perf_counter()
            with open(out_path, "wb") as f:
                subprocess.run(
                    [sys.executable, "-m", "racon_tpu", "-t", "4",
                     "-c", "1", "--tpualigner-batches", "1",
                     "--chips", str(k)] + paths,
                    stdout=f, check=True, env=env)
            wall = time.perf_counter() - t0
            with open(out_path, "rb") as f:
                blobs[k] = f.read()
            assert blobs[k].count(b">") > 0
            curve.append({"chips": k, "wall_s": round(wall, 2),
                          "mbp_per_sec": round(mbp / wall, 4)})
            log(f"multichip bench: --chips {k}: {wall:.1f}s "
                f"({mbp / wall:.4f} Mbp/s)")
        assert blobs[points[0]] == blobs[points[-1]], \
            "all-chip output diverged from the 1-chip output"
        out.update(
            multichip_mbp=mbp,
            multichip_devices=(f"virtual-cpu-{n_chips}" if fake
                               else f"hardware-{n_chips}"),
            multichip_curve=curve,
            multichip_identity="byte-identical")
    return out


def bench_service():
    """Resident polishing service (round 14, ROADMAP item 3): p50/p95
    job latency across ``RACON_TPU_BENCH_SERVICE_JOBS`` (default 100)
    sequential submissions of a ``RACON_TPU_BENCH_SERVICE``-Mbp
    (default 5) polish job to ONE resident ``racon --serve`` server,
    with a cold one-shot CLI baseline for the speedup claim and a
    byte-identity assert against it.  The acceptance metric:
    ``service_compile_fraction`` — the p50 of per-job measured XLA
    compile seconds over job wall, from job #2 on — must be < 0.1
    (latency dominated by compute, not compile).  0 disables.

    Recovery leg (round 16): the same warm loop re-runs against a
    ``--serve-dir`` server to measure the journal's warm-path
    overhead (asserted < 5% p50 regression), then the server is
    SIGKILLed with an unfetched job spooled and restarted to measure
    restart-to-first-result recovery time — the BENCH_r06 crash-safety
    numbers."""
    import os
    import statistics
    import subprocess
    import tempfile

    from racon_tpu import flags as racon_flags

    mbp = racon_flags.get_float("RACON_TPU_BENCH_SERVICE")
    if not mbp:
        return {}
    n_jobs = max(2, racon_flags.get_int("RACON_TPU_BENCH_SERVICE_JOBS"))
    from racon_tpu.serve.client import ServiceClient

    sim_py = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "tools", "simulate.py")
    out = {}
    with tempfile.TemporaryDirectory(dir="/tmp") as td:
        log(f"service bench: generating {mbp} Mbp workload...")
        subprocess.run([sys.executable, sim_py, str(mbp), td,
                        "--seed", "59"], check=True)
        reads, paf, draft = (os.path.join(td, n) for n in
                             ("reads.fastq", "ovl.paf", "draft.fasta"))
        cache = os.path.join(td, "xla_cache")
        env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=cache)

        # cold baseline: a fresh one-shot process pays the full compile
        log("service bench: cold one-shot CLI baseline...")
        t0 = time.perf_counter()
        want = subprocess.run(
            [sys.executable, "-m", "racon_tpu", "-t", "4", "-c", "1",
             "--tpualigner-batches", "1", reads, paf, draft],
            stdout=subprocess.PIPE, check=True, env=env).stdout
        cold_s = time.perf_counter() - t0
        log(f"service bench: cold one-shot {cold_s:.1f}s")

        sock = os.path.join(td, "racon.sock")
        log(f"service bench: starting resident server "
            f"({n_jobs} sequential submissions)...")
        server = subprocess.Popen(
            [sys.executable, "-m", "racon_tpu", "--serve", sock,
             "-t", "4", "-c", "1", "--tpualigner-batches", "1"],
            env=env, stderr=subprocess.DEVNULL)
        try:
            deadline = time.monotonic() + 300
            while not os.path.exists(sock):
                if time.monotonic() > deadline or \
                        server.poll() is not None:
                    raise RuntimeError("resident server did not start")
                time.sleep(0.2)
            lat, frac = [], []
            compiles_after_warm = 0
            spec = {"sequences": reads, "overlaps": paf,
                    "target_sequences": draft, "threads": 4}
            for k in range(n_jobs):
                t0 = time.perf_counter()
                with ServiceClient(sock, timeout_s=3600) as c:
                    job = c.submit(spec)
                    assert job.get("ok"), job
                    header, payload = c.result(job["job"],
                                               timeout_s=3600)
                wall = time.perf_counter() - t0
                assert header.get("ok"), header
                assert payload == want, \
                    f"job {k} diverged from the one-shot CLI output"
                lat.append(wall)
                frac.append(header.get("compile_s", 0.0)
                            / max(header.get("wall_s", wall), 1e-9))
                if k >= 1:
                    # the server seals its warm path when job #1
                    # completes: from job #2 on, the attributed
                    # post-warm compile count must be exactly zero —
                    # the warm-path claim, now measured, not inferred
                    compiles_after_warm += int(
                        header.get("compiles_after_warm", 0))
                if k in (0, 1) or (k + 1) % 20 == 0:
                    log(f"service bench: job {k + 1}/{n_jobs} "
                        f"{wall:.2f}s (compile "
                        f"{header.get('compile_s', 0.0):.2f}s, "
                        f"post-warm compiles "
                        f"{header.get('compiles_after_warm', 0)})")
            with ServiceClient(sock, timeout_s=60) as c:
                c.shutdown()
            server.wait(timeout=120)
        finally:
            if server.poll() is None:
                server.kill()
                server.wait()
        warm_lat = sorted(lat[1:])  # job #1 pays any residual compile
        p50 = statistics.median(warm_lat)
        p95 = warm_lat[min(len(warm_lat) - 1,
                           int(0.95 * len(warm_lat)))]
        compile_fraction = statistics.median(frac[1:])
        log(f"service bench: p50 {p50:.2f}s p95 {p95:.2f}s "
            f"(cold one-shot {cold_s:.1f}s, "
            f"compile fraction {compile_fraction:.4f})")
        assert compile_fraction < 0.1, (
            f"warm jobs are still compile-dominated "
            f"(service_compile_fraction={compile_fraction:.3f})")
        assert compiles_after_warm == 0, (
            f"{compiles_after_warm} XLA compile(s) attributed to "
            f"repeat-shape jobs after the warm-path seal — the "
            f"server's warm-path claim is broken (see the "
            f"compiles_after_warm headers / the job reports' "
            f"`compiles` section for the offending signatures)")
        out.update(
            service_mbp=mbp, service_jobs=n_jobs,
            service_p50_s=round(p50, 3),
            service_p95_s=round(p95, 3),
            service_first_job_s=round(lat[0], 3),
            service_compile_fraction=round(compile_fraction, 4),
            service_compiles_after_warm=compiles_after_warm,
            service_cold_oneshot_s=round(cold_s, 2),
            service_speedup_vs_cold=round(cold_s / p50, 2),
            service_identity="byte-identical")

        # ---- recovery leg (round 16): journal overhead + restart time
        serve_dir = os.path.join(td, "serve_dir")
        jn = min(n_jobs, 20)
        log(f"service bench: recovery leg — {jn} jobs against a "
            f"--serve-dir journaled server...")
        server = subprocess.Popen(
            [sys.executable, "-m", "racon_tpu", "--serve", sock,
             "--serve-dir", serve_dir,
             "-t", "4", "-c", "1", "--tpualigner-batches", "1"],
            env=env, stderr=subprocess.DEVNULL)
        unfetched_job = None
        try:
            deadline = time.monotonic() + 300
            while not os.path.exists(sock):
                if time.monotonic() > deadline or \
                        server.poll() is not None:
                    raise RuntimeError(
                        "journaled resident server did not start")
                time.sleep(0.2)
            jlat = []
            for k in range(jn):
                t0 = time.perf_counter()
                with ServiceClient(sock, timeout_s=3600) as c:
                    job = c.submit(spec)
                    assert job.get("ok"), job
                    header, payload = c.result(job["job"],
                                               timeout_s=3600)
                jlat.append(time.perf_counter() - t0)
                assert header.get("ok") and payload == want
            # one more job, completed but NOT fetched: the restart must
            # serve it from the spool without re-polishing
            with ServiceClient(sock, timeout_s=3600) as c:
                job = c.submit(spec)
                assert job.get("ok"), job
                unfetched_job = job["job"]
                st = c.status(unfetched_job)
                poll_deadline = time.monotonic() + 3600
                while st.get("state") not in ("done", "failed"):
                    assert time.monotonic() < poll_deadline
                    time.sleep(0.5)
                    with ServiceClient(sock, timeout_s=60) as c2:
                        st = c2.status(unfetched_job)
                assert st.get("state") == "done", st
        finally:
            server.kill()  # SIGKILL: the crash the journal exists for
            server.wait()
        p50_journal = statistics.median(sorted(jlat[1:]))
        overhead = (p50_journal - p50) / p50 if p50 else 0.0
        log(f"service bench: journaled warm p50 {p50_journal:.2f}s "
            f"(overhead {overhead * 100:+.1f}% vs {p50:.2f}s)")
        # the durability tax on the warm path must stay noise-level
        # (<5%, with a small absolute floor for sub-second jobs)
        assert p50_journal <= p50 * 1.05 + 0.05, (
            f"journal overhead {overhead * 100:.1f}% exceeds the 5% "
            f"warm-path budget (p50 {p50:.3f}s -> {p50_journal:.3f}s)")

        log("service bench: restarting from the serve-dir "
            "(recovery time to first result)...")
        # SIGKILL leaves the socket FILE behind (only a clean shutdown
        # unlinks it): drop it so the wait below genuinely measures
        # the restarted server's bind, not client connect-retries
        # against a stale path
        try:
            os.unlink(sock)
        except FileNotFoundError:
            pass
        t_restart = time.perf_counter()
        server = subprocess.Popen(
            [sys.executable, "-m", "racon_tpu", "--serve", sock,
             "--serve-dir", serve_dir,
             "-t", "4", "-c", "1", "--tpualigner-batches", "1"],
            env=env, stderr=subprocess.DEVNULL)
        try:
            deadline = time.monotonic() + 600
            while not os.path.exists(sock):
                if time.monotonic() > deadline or \
                        server.poll() is not None:
                    raise RuntimeError(
                        "restarted resident server did not start")
                time.sleep(0.1)
            with ServiceClient(sock, timeout_s=3600) as c:
                header, payload = c.result(unfetched_job,
                                           timeout_s=3600)
            recovery_s = time.perf_counter() - t_restart
            assert header.get("ok"), header
            assert payload == want, \
                "recovered result diverged from the one-shot CLI"
            with ServiceClient(sock, timeout_s=60) as c:
                c.shutdown()
            server.wait(timeout=120)
        finally:
            if server.poll() is None:
                server.kill()
                server.wait()
        log(f"service bench: restart-to-first-result "
            f"{recovery_s:.2f}s (spool-served, zero re-polish)")
        out.update(
            service_journal_p50_s=round(p50_journal, 3),
            service_journal_overhead_pct=round(overhead * 100, 2),
            service_recovery_s=round(recovery_s, 3),
            service_recovery_identity="byte-identical")
    return out


def bench_fleet():
    """Fleet serving (round 23): a 3-host fleet (three ``--serve
    --fleet-dir`` subprocesses) behind one ``--gateway``, driven with
    mixed-tenant open-loop load (``alpha:3`` vs ``beta:1`` under
    ``RACON_TPU_FLEET_TENANTS``).  Reports per-tenant
    ``fleet_<tenant>_p50_s``/``p95_s``, the isolation ratio (alpha's
    p95 under beta contention over alpha's solo p50 — the weighted-
    fair claim), and migration-to-first-result after a member SIGKILL
    (the lease-break re-placement path).  Every result — including
    the post-kill migrated ones — must be byte-identical to the
    one-shot CLI run.  ``RACON_TPU_BENCH_FLEET=0`` disables."""
    import os
    import socket as socket_mod
    import statistics
    import subprocess
    import tempfile
    import threading

    from racon_tpu import flags as racon_flags

    mbp = racon_flags.get_float("RACON_TPU_BENCH_FLEET")
    if not mbp:
        return {}
    per_tenant = max(2,
                     racon_flags.get_int("RACON_TPU_BENCH_FLEET_JOBS"))
    from racon_tpu.serve.client import ServiceClient

    sim_py = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "tools", "simulate.py")
    out = {}
    with tempfile.TemporaryDirectory(dir="/tmp") as td:
        log(f"fleet bench: generating {mbp} Mbp workload...")
        subprocess.run([sys.executable, sim_py, str(mbp), td,
                        "--seed", "61"], check=True)
        reads, paf, draft = (os.path.join(td, n) for n in
                             ("reads.fastq", "ovl.paf", "draft.fasta"))
        cache = os.path.join(td, "xla_cache")
        env = dict(os.environ,
                   JAX_COMPILATION_CACHE_DIR=cache,
                   RACON_TPU_FLEET_HOST_TTL_S="2.0",
                   RACON_TPU_FLEET_POLL_S="0.05",
                   RACON_TPU_FLEET_TENANTS="alpha:3,beta:1")
        log("fleet bench: one-shot CLI baseline (the byte-identity "
            "reference)...")
        want = subprocess.run(
            [sys.executable, "-m", "racon_tpu", "-t", "2", "-c", "1",
             "--tpualigner-batches", "1", reads, paf, draft],
            stdout=subprocess.PIPE, check=True, env=env).stdout

        fleet_dir = os.path.join(td, "fleet")
        hosts = []
        gateway = None
        spec = {"sequences": reads, "overlaps": paf,
                "target_sequences": draft, "threads": 2}
        try:
            for i in range(3):
                sock = os.path.join(td, f"host{i}.sock")
                hosts.append((sock, subprocess.Popen(
                    [sys.executable, "-m", "racon_tpu",
                     "--serve", sock, "--fleet-dir", fleet_dir,
                     "-t", "2", "-c", "1", "--tpualigner-batches",
                     "1"],
                    env=env, stderr=subprocess.DEVNULL)))
            # a pre-probed free port: the gateway needs a concrete
            # HOST:PORT on its command line
            probe = socket_mod.socket()
            probe.bind(("127.0.0.1", 0))
            port = probe.getsockname()[1]
            probe.close()
            addr = f"127.0.0.1:{port}"
            gateway = subprocess.Popen(
                [sys.executable, "-m", "racon_tpu",
                 "--gateway", addr, "--fleet-dir", fleet_dir],
                env=env, stderr=subprocess.DEVNULL)
            deadline = time.monotonic() + 300
            while True:
                if time.monotonic() > deadline or \
                        gateway.poll() is not None:
                    raise RuntimeError("fleet did not come up")
                try:
                    with ServiceClient(addr, timeout_s=10,
                                       retries=0) as c:
                        if c.ping().get("hosts", {}).get("alive",
                                                         0) >= 3:
                            break
                except (OSError, ConnectionError):
                    pass
                time.sleep(0.2)
            log(f"fleet bench: 3 hosts registered behind {addr}")

            def run_jobs(tenant, n, walls, leg, priority=0):
                def one(idx):
                    t0 = time.perf_counter()
                    with ServiceClient(addr, timeout_s=3600) as c:
                        job = c.submit(
                            dict(spec, tenant=tenant,
                                 priority=priority),
                            key=f"bench-{leg}-{tenant}-{idx}")
                        assert job.get("ok"), job
                        header, payload = c.result(job["job"],
                                                   timeout_s=3600)
                    assert header.get("ok"), header
                    assert payload == want, (
                        f"{leg}/{tenant} job {idx} diverged from the "
                        f"one-shot CLI output")
                    walls[idx] = time.perf_counter() - t0
                threads = [threading.Thread(target=one, args=(i,))
                           for i in range(n)]
                for t in threads:
                    t.start()
                return threads

            def pctl(walls, q):
                w = sorted(walls)
                return w[min(len(w) - 1, int(q * len(w)))]

            # solo leg: alpha alone on an idle fleet (the isolation
            # denominator) — also warms every host's engine pool so
            # the mixed leg measures scheduling, not compiles
            n_solo = min(per_tenant, 6)
            log(f"fleet bench: solo leg ({n_solo} alpha jobs, idle "
                f"fleet)...")
            solo = [0.0] * n_solo
            for t in run_jobs("alpha", n_solo, solo, "solo"):
                t.join()
            solo_p50 = statistics.median(solo)

            # mixed leg: both tenants flood the gateway open-loop
            log(f"fleet bench: mixed leg ({per_tenant} alpha + "
                f"{per_tenant} beta open-loop jobs)...")
            alpha = [0.0] * per_tenant
            beta = [0.0] * per_tenant
            pending = run_jobs("alpha", per_tenant, alpha, "mixed") \
                + run_jobs("beta", per_tenant, beta, "mixed")
            for t in pending:
                t.join()
            isolation = pctl(alpha, 0.95) / max(solo_p50, 1e-9)
            log(f"fleet bench: alpha p50 {statistics.median(alpha):.2f}s "
                f"p95 {pctl(alpha, 0.95):.2f}s, beta p50 "
                f"{statistics.median(beta):.2f}s p95 "
                f"{pctl(beta, 0.95):.2f}s (solo p50 {solo_p50:.2f}s, "
                f"isolation x{isolation:.2f})")

            # migration leg: SIGKILL a member with jobs in flight —
            # the gateway breaks its leases and re-places on survivors
            log("fleet bench: migration leg (SIGKILL one host under "
                "load)...")
            mig = [0.0] * 6
            pending = run_jobs("alpha", 6, mig, "mig")
            time.sleep(max(0.5, solo_p50 / 2))
            t_kill = time.perf_counter()
            hosts[0][1].kill()
            for t in pending:
                t.join()
            migration_s = time.perf_counter() - t_kill
            with ServiceClient(addr, timeout_s=60) as c:
                migrated = int(c.stats().get("migrated", 0))
            log(f"fleet bench: all 6 in-flight jobs done "
                f"{migration_s:.2f}s after the kill "
                f"({migrated} migrated), byte-identical")

            with ServiceClient(addr, timeout_s=60) as c:
                c.shutdown()
            gateway.wait(timeout=120)
        finally:
            if gateway is not None and gateway.poll() is None:
                gateway.kill()
                gateway.wait()
            for _, proc in hosts:
                if proc.poll() is None:
                    proc.kill()
                    proc.wait()
        out.update(
            fleet_mbp=mbp, fleet_jobs_per_tenant=per_tenant,
            fleet_hosts=3,
            fleet_solo_p50_s=round(solo_p50, 3),
            fleet_alpha_p50_s=round(statistics.median(alpha), 3),
            fleet_alpha_p95_s=round(pctl(alpha, 0.95), 3),
            fleet_beta_p50_s=round(statistics.median(beta), 3),
            fleet_beta_p95_s=round(pctl(beta, 0.95), 3),
            fleet_isolation_ratio=round(isolation, 2),
            fleet_migration_s=round(migration_s, 3),
            fleet_migrated_jobs=migrated,
            fleet_identity="byte-identical")
    return out


def bench_parse():
    """Ingest throughput (VERDICT r3: parse must stay <10% of wall at
    >=100 Mbp inputs): ~100 MB of concatenated λ-phage FASTQ and ~100 MB
    of concatenated real PAF through the native parsers. Gzipped inputs
    bottom out at zlib's serial inflate (~40 MB/s — the reference's
    vendored bioparser shares that floor), so the probes measure the
    parsers themselves on plain bytes."""
    import gzip
    import os
    import tempfile

    from racon_tpu.io.parsers import parse_fastq, parse_paf

    out = {}
    for label, src, parser, suffix in (
            ("parse_mb_per_sec", f"{DATA}/sample_reads.fastq.gz",
             parse_fastq, ".fastq"),
            ("parse_paf_mb_per_sec", f"{DATA}/sample_ava_overlaps.paf.gz",
             parse_paf, ".paf")):
        raw = gzip.open(src).read()
        n = max(1, 100_000_000 // len(raw))
        with tempfile.NamedTemporaryFile(suffix=suffix, delete=False) as f:
            for _ in range(n):
                f.write(raw)
            path = f.name
        try:
            size = os.path.getsize(path)
            t0 = time.perf_counter()
            records = list(parser(path))
            dt = time.perf_counter() - t0
        finally:
            os.unlink(path)
        rate = size / dt / 1e6
        log(f"parse {suffix}: {len(records)} records, {size / 1e6:.0f} MB "
            f"in {dt:.2f}s = {rate:.0f} MB/s")
        out[label] = round(rate, 1)
    return out


def main():
    import jax
    log(f"jax {jax.__version__}, devices: {jax.devices()}")

    log("building λ-phage windows...")
    t0 = time.perf_counter()
    windows = build_windows()
    log(f"{len(windows)} windows in {time.perf_counter() - t0:.2f}s")

    cold, warm, cpu_t, stats = bench_consensus(windows)
    aligner_metrics = bench_aligner()
    scale_metrics = bench_scale()
    pipeline_metrics = bench_pipeline()
    overlap_metrics = bench_overlap()
    shard_metrics = bench_shards()
    multichip_metrics = bench_multichip()
    service_metrics = bench_service()
    fleet_metrics = bench_fleet()
    parse_metrics = bench_parse()

    total_bases = sum(len(w.sequences[0]) for w in windows)
    result = {
        "metric": "poa_windows_per_sec",
        "value": round(len(windows) / warm, 2),
        "unit": "windows/s",
        "vs_baseline": round(cpu_t / warm, 3),
        "n_windows": len(windows),
        "mbp_polished_per_sec": round(total_bases / warm / 1e6, 4),
        "tpu_warm_s": round(warm, 3),
        "tpu_cold_s": round(cold, 3),
        "cpu_s": round(cpu_t, 3),
        "consensus_stats": stats,
        **aligner_metrics,
        **scale_metrics,  # scale_mbp_per_sec + pack occupancy + A/B grid
        **pipeline_metrics,  # full-pipeline Mbp/s + CPU baseline
        **overlap_metrics,  # first-party overlapper Mbp/s + quality A/B
        **shard_metrics,  # streaming shard-runner scaling curve
        **multichip_metrics,  # Mbp/s-vs-chips curve + identity assert
        **service_metrics,  # resident-service p50/p95 + compile fraction
        **fleet_metrics,  # per-tenant p50/p95 + isolation + migration
        **parse_metrics,
        "device": str(jax.devices()[0]),
    }
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
