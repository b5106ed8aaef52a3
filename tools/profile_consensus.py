#!/usr/bin/env python
"""Stage-level timing of the device consensus round on the real chip.

Decomposes one ``refine_round`` into its stages and times each with
``block_until_ready`` (best of N), so perf work attacks measured hot spots
instead of guesses. Also times the whole round and the full engine run for
cross-checking, and sweeps the Pallas pair-block caps when asked.

Usage:
    python tools/profile_consensus.py [--scale MBP] [--fwd-p N] [--walk-p N]
                                      [--rounds N] [--xla]
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

DATA = "/root/reference/test/data"


def timeit_pipelined(dispatch, k=10, n=2):
    """Device time per call: dispatch ``k`` back-to-back (async), block
    once, divide — the host<->device sync latency amortizes away,
    leaving the true per-call device time."""
    import jax
    jax.block_until_ready(dispatch())  # compile / warm
    best = float("inf")
    for _ in range(n):
        t0 = time.perf_counter()
        out = None
        for _ in range(k):
            out = dispatch()
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / k)
    return best


def build_lambda_windows():
    from racon_tpu.core.polisher import create_polisher
    p = create_polisher(
        f"{DATA}/sample_reads.fastq.gz", f"{DATA}/sample_overlaps.sam.gz",
        f"{DATA}/sample_layout.fasta.gz", num_threads=8)
    p.initialize()
    return p.windows


def build_scale_windows(mbp):
    import numpy as np
    from racon_tpu.core.window import Window, WindowType
    rng = np.random.default_rng(17)
    bases = np.frombuffer(b"ACGT", dtype=np.uint8)
    n_windows = int(mbp * 1e6) // 500
    windows = []
    for wi in range(n_windows):
        truth = bases[rng.integers(0, 4, 500)]
        bb = truth.copy()
        flips = rng.random(500) < 0.10
        bb[flips] = bases[rng.integers(0, 4, int(flips.sum()))]
        win = Window(0, wi, WindowType.TGS, bb.tobytes(), b"!" * 500)
        for _ in range(30):
            layer = truth.copy()
            flips = rng.random(500) < 0.08
            layer[flips] = bases[rng.integers(0, 4, int(flips.sum()))]
            layer = np.delete(layer, rng.integers(0, len(layer), 12))
            ins_at = rng.integers(0, len(layer), 12)
            layer = np.insert(layer, ins_at,
                              bases[rng.integers(0, 4, 12)])
            win.add_layer(layer.tobytes(), b"9" * len(layer), 0, 499)
        windows.append(win)
    return windows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.0)
    ap.add_argument("--fwd-p", type=int, default=0)
    ap.add_argument("--walk-p", type=int, default=0)
    ap.add_argument("--rounds", type=int, default=6)
    ap.add_argument("--xla", action="store_true")
    args = ap.parse_args()
    if not args.scale and not os.path.isdir(DATA):
        sys.exit(f"profile_consensus: the λ-phage window set needs the "
                 f"reference's test data at {DATA}, which is not there; "
                 f"use --scale MBP for simulated windows")

    from racon_tpu.ops import pallas_nw
    if args.fwd_p:
        pallas_nw.FWD_P_CAP = args.fwd_p
    if args.walk_p:
        pallas_nw.WALK_P_CAP = args.walk_p

    import jax
    import jax.numpy as jnp
    import numpy as np
    from racon_tpu.ops import poa as poa_mod
    from racon_tpu.ops.poa import (
        GROW, K_INS, CH, DEL, Q_PAD, T_PAD, TpuPoaConsensus, _Work,
        _consensus_kernel, _accumulate_votes, _vote_from_ops, refine_round)
    from racon_tpu.core.backends import CpuPoaConsensus

    print(f"devices: {jax.devices()}  fwd_p={pallas_nw.FWD_P_CAP} "
          f"walk_p={pallas_nw.WALK_P_CAP}", flush=True)

    windows = (build_scale_windows(args.scale) if args.scale
               else build_lambda_windows())
    print(f"{len(windows)} windows", flush=True)

    eng = TpuPoaConsensus(3, -5, -4, fallback=CpuPoaConsensus(3, -5, -4, 8),
                          rounds=args.rounds)

    # replicate run()'s sizing
    works = [(i, _Work(w, eng.max_depth, eng.stats))
             for i, w in enumerate(windows) if len(w.sequences) >= 3]
    live = [(i, w) for i, w in works if len(w.layers) >= 2]
    max_bb = max(len(w.backbone) for _, w in live)
    L = max(256, -(-max_bb // 256) * 256)
    Lq = L + eng.band
    Lb = min(L + GROW, Lq)
    live = [(i, w) for i, w in live
            if all(len(s) <= Lq for s, _, _, _ in w.layers)
            and len(w.backbone) <= Lb]
    max_nm = max(len(s) + min((e - b + 1) + 64, Lb)
                 for _, w in live for s, _, b, e in w.layers)
    steps = -(-min(-(-max_nm // 128) * 128, 2 * Lq) // 128) * 128
    # one group only (profile a single launch)
    from racon_tpu.ops.poa import MAX_GROUP_PAIRS
    total_pairs = sum(len(w.layers) for _, w in live)
    if total_pairs > MAX_GROUP_PAIRS:
        acc = []
        s = 0
        for i, w in live:
            if s + len(w.layers) > MAX_GROUP_PAIRS:
                break
            acc.append((i, w))
            s += len(w.layers)
        live = acc
        total_pairs = s
    launch = eng._launch_group(live, Lq, Lb)
    n_, qcodes, qweights, win_of, real = launch["static"]
    (bg, ed, bcodes, bweights, blen, covs, ever, frozen, conv,
     dropped) = launch["state"]
    nWp = launch["nWp"]
    B = qcodes.shape[0]
    print(f"pairs={total_pairs} B={B} Lq={Lq} Lb={Lb} steps={steps} "
          f"nWp={nWp} band={eng.band}", flush=True)

    use_pallas = (not args.xla) and pallas_nw.pallas_ok()
    print(f"use_pallas={use_pallas}", flush=True)

    band = eng.band
    c = band // 2
    width = c + Lq + band
    m_ = ed - bg + 1

    @jax.jit
    def build_rows(n, qcodes, bg, ed, bcodes):
        m = ed - bg + 1
        core = jnp.where((Lq - 1 - jnp.arange(Lq, dtype=jnp.int32))[None, :]
                         < n[:, None],
                         jnp.flip(qcodes, axis=1), jnp.uint8(Q_PAD))
        qrp = jnp.concatenate(
            [jnp.full((B, c), Q_PAD, jnp.uint8), core,
             jnp.full((B, band), Q_PAD, jnp.uint8)], axis=1)
        cols = jnp.arange(width, dtype=jnp.int32)[None, :] - c
        bbrow = jnp.take(bcodes, win_of, axis=0)
        y = jnp.pad(bbrow, ((0, 0), (c, width - c - Lb)))
        for k in range((Lb - 1).bit_length()):
            y = jnp.where(((bg[:, None] >> k) & 1).astype(bool),
                          jnp.roll(y, -(1 << k), axis=1), y)
        tp = jnp.where((cols >= 0) & (cols < m[:, None]), y,
                       jnp.uint8(T_PAD))
        return qrp, tp

    qrp, tp = jax.block_until_ready(build_rows(n_, qcodes, bg, ed, bcodes))
    t_rows = timeit_pipelined(lambda: build_rows(n_, qcodes, bg, ed, bcodes))
    print(f"rows:      {t_rows * 1e3:8.2f} ms", flush=True)

    if use_pallas:
        from racon_tpu.ops.pallas_nw import pallas_nw_fwd, pallas_walk_vote
        fwd = lambda: pallas_nw_fwd(qrp, tp, n_, m_, max_len=Lq, band=band,
                                    steps=steps)
        packed, score = jax.block_until_ready(fwd())
        t_fwd = timeit_pipelined(fwd)
        print(f"fwd:       {t_fwd * 1e3:8.2f} ms", flush=True)

        wv = lambda: pallas_walk_vote(packed, n_, m_, bg, qcodes, qweights,
                                      band=band, L=Lb, K=K_INS, CH=CH,
                                      DEL=DEL)
        idx, w8, fi, fj = jax.block_until_ready(wv())
        t_walk = timeit_pipelined(wv)
        print(f"walk+vote: {t_walk * 1e3:8.2f} ms", flush=True)

        okp = (fi == 0) & (fj == 0) & (score < (band // 2))
        sc = jax.jit(lambda idx, w8, okp, win_of: _accumulate_votes(
            idx, w8.astype(jnp.int32), okp, win_of, m_, bg, n_, score,
            n_windows=nWp, L=Lb, K=K_INS, band=band))
        t_scatter = timeit_pipelined(lambda: sc(idx, w8, okp, win_of))
        print(f"accum:     {t_scatter * 1e3:8.2f} ms", flush=True)
        weighted, unweighted, _, _ = sc(idx, w8, okp, win_of)
    else:
        from racon_tpu.ops.nw import _nw_wavefront_kernel, _walk_ops_kernel
        fwd = lambda: _nw_wavefront_kernel(qrp, tp, n_, m_, max_len=Lq,
                                           band=band, steps=steps)
        packed, score = jax.block_until_ready(fwd())
        t_fwd = timeit_pipelined(fwd)
        print(f"fwd:       {t_fwd * 1e3:8.2f} ms", flush=True)
        wk = lambda: _walk_ops_kernel(packed, n_, m_, band=band)
        ops, fi, fj = jax.block_until_ready(wk())
        t_walk = timeit_pipelined(wk)
        print(f"walk:      {t_walk * 1e3:8.2f} ms", flush=True)
        def vt():
            idx, wv, okp = _vote_from_ops(
                ops, fi, fj, score, n_, m_, qcodes, qweights, bg,
                max_len=Lq, band=band, L=Lb, K=K_INS)
            w_, u_, _, _ = _accumulate_votes(idx, wv, okp, win_of, m_, bg,
                                             n_, score, n_windows=nWp,
                                             L=Lb, K=K_INS, band=band)
            return w_, u_, okp
        weighted, unweighted, okp = jax.block_until_ready(vt())
        t_scatter = timeit_pipelined(vt)
        print(f"vote+accum:{t_scatter * 1e3:8.2f} ms", flush=True)

    ck = jax.jit(lambda w, u: _consensus_kernel(
        w, u, bcodes, bweights, blen,
        jnp.float32(eng.ins_theta), jnp.float32(eng.del_beta),
        L=Lb, K=K_INS))
    t_cons = timeit_pipelined(lambda: ck(weighted, unweighted))
    print(f"consensus: {t_cons * 1e3:8.2f} ms", flush=True)

    rr = lambda: refine_round(
        n_, qcodes, qweights, win_of, real, bg, ed, bcodes, bweights,
        blen, covs, ever, frozen, conv, dropped,
        jnp.float32(eng.ins_theta), jnp.float32(eng.del_beta),
        n_windows=nWp, max_len=Lq, band=band, Lb=Lb, K=K_INS,
        steps=steps, use_pallas=use_pallas)
    t_round = timeit_pipelined(rr)
    print(f"round:     {t_round * 1e3:8.2f} ms "
          f"(stages sum {1e3 * (t_rows + t_fwd + t_walk + t_scatter + t_cons):.2f})",
          flush=True)

    # whole-engine wall for cross-check
    t0 = time.perf_counter()
    eng.run(windows, trim=True)
    print(f"engine cold: {time.perf_counter() - t0:.2f}s", flush=True)
    t0 = time.perf_counter()
    eng.run(windows, trim=True)
    print(f"engine warm: {time.perf_counter() - t0:.2f}s  stats={eng.stats}",
          flush=True)


if __name__ == "__main__":
    main()
