"""Accelerator knob tests: -b (banded), -c N / --tpualigner-batches N
(batch counts = device pipeline depth + per-batch memory split).
Reference: src/main.cpp:111-126, cudapolisher.cpp:91,215-228."""

import numpy as np

from racon_tpu.cli import build_parser, _preprocess_argv
from racon_tpu.core.backends import make_aligner, make_consensus
from racon_tpu.core.window import Window, WindowType
from racon_tpu.ops.nw import TpuAligner
from racon_tpu.ops.poa import BAND, TpuPoaConsensus

from test_parallel import _random_pairs, _random_windows


def test_cli_optional_c_argument():
    args = build_parser().parse_args(_preprocess_argv(
        ["-c", "2", "a.fasta", "b.paf", "c.fasta"]))
    assert args.tpupoa_batches == 2
    args = build_parser().parse_args(_preprocess_argv(
        ["-c", "a.fasta", "b.paf", "c.fasta"]))
    assert args.tpupoa_batches == 1
    args = build_parser().parse_args(_preprocess_argv(
        ["a.fasta", "b.paf", "c.fasta"]))
    assert args.tpupoa_batches == 0


def test_banded_flag_halves_consensus_band():
    eng = make_consensus("tpu", 3, -5, -4, banded=True)
    assert eng.band == BAND // 2
    eng = make_consensus("tpu", 3, -5, -4, banded=False)
    assert eng.band == BAND


def test_batch_counts_reach_engines():
    aligner = make_aligner("tpu", 1, num_batches=4)
    assert aligner.num_batches == 4
    consensus = make_consensus("tpu", 3, -5, -4, num_batches=3)
    assert consensus.num_batches == 3


def test_aligner_batches_do_not_change_results():
    pairs = _random_pairs(50, seed=13)
    one = TpuAligner(buckets=((256, 128),), num_batches=1,
                     max_dirs_bytes=256 * 128 * 64)  # force several chunks
    three = TpuAligner(buckets=((256, 128),), num_batches=3,
                       max_dirs_bytes=256 * 128 * 64)
    assert one.align_batch(pairs) == three.align_batch(pairs)
    assert three.stats["device"] == len(pairs)


def test_consensus_batches_do_not_change_results():
    wins_a = _random_windows(11, seed=31)
    wins_b = _random_windows(11, seed=31)
    TpuPoaConsensus(3, -5, -4, band=64, rounds=2, num_batches=1).run(
        wins_a, True)
    eng = TpuPoaConsensus(3, -5, -4, band=64, rounds=2, num_batches=3)
    eng.run(wins_b, True)
    assert [w.consensus for w in wins_a] == [w.consensus for w in wins_b]
    assert eng.stats["device_windows"] == len(wins_b)


def test_banded_consensus_still_polishes():
    wins = _random_windows(6, seed=41)
    eng = TpuPoaConsensus(3, -5, -4, band=64, rounds=2)
    flags = eng.run(wins, True)
    assert all(flags)
    assert all(len(w.consensus) > 0 for w in wins)


def test_device_scores_map_to_emission_thresholds():
    """-g scales the device indel-emission thresholds (identity at the
    default -4, so goldens are untouched; the scale is capped so extreme
    -g degrades symmetrically, ADVICE r3); -m/-x/-g also reach the vote
    weights as the per-layer score multiplier (cudapoa consumes the
    scores directly, cudabatch.cpp:54-62 — score-weighted voting is the
    pileup engine's analog)."""
    from racon_tpu.ops.poa import TpuPoaConsensus

    default = TpuPoaConsensus(3, -5, -4)
    assert default.ins_theta == 0.25 and default.del_beta == 0.65
    assert default.scores == (3, -5, -4)

    strong_gap = TpuPoaConsensus(3, -5, -8)
    assert strong_gap.ins_theta == 0.5 and strong_gap.del_beta == 1.3

    extreme_gap = TpuPoaConsensus(3, -5, -20)
    assert extreme_gap.ins_theta == 0.95 and extreme_gap.del_beta == 2.5

    ref_e2e = TpuPoaConsensus(8, -6, -8)  # ci/gpu/cuda_test.sh:29 config
    assert ref_e2e.scores == (8, -6, -8)


def test_device_alpha_identity_at_defaults():
    """The score-weight alpha is exactly 64 (the q6 unit) for every layer
    at the reference default scores — weighted voting is bit-identical to
    unweighted there — and deviates for other score sets."""
    import jax.numpy as jnp
    import numpy as np

    from racon_tpu.ops.poa import CH, DEL, _accumulate_votes

    B, S, L, K, nW = 8, 128, 64, 4, 2
    rng = np.random.default_rng(5)
    # a tiny synthetic vote stream: 20 column votes + 2 ins votes per row
    idx = np.full((B, S), L * (1 + K) * CH, np.int32)
    for b in range(B):
        for t in range(20):
            ch = DEL if t % 7 == 0 else int(rng.integers(0, 4))
            idx[b, t] = (19 - t) * CH + ch
        idx[b, 20] = (L + 3 * K + 0) * CH + 1
        idx[b, 21] = (L + 3 * K + 1) * CH + 2
    w = np.where(idx < L * (1 + K) * CH, 9, 0).astype(np.int32)
    ok = np.ones(B, bool)
    win_of = np.zeros(B, np.int32)
    span_m = (np.sum(idx < L * CH, axis=1)).astype(np.int32)
    n = span_m + 2  # 2 ins steps consume query
    score = np.full(B, 5, np.int32)

    args = [jnp.asarray(a) for a in (idx, w, ok, win_of, span_m,
                                     np.zeros(B, np.int32), n, score)]
    w_def, u_def, _, _ = _accumulate_votes(
        *args, n_windows=nW, L=L, K=K, band=64, scores=(3, -5, -4))
    w_e2e, u_e2e, _, _ = _accumulate_votes(
        *args, n_windows=nW, L=L, K=K, band=64, scores=(8, -6, -8))
    # defaults: every weight is w * 64 exactly
    assert float(w_def.max()) > 0
    assert np.all(np.asarray(w_def) % 64 == 0)
    # counts are alpha-independent; weights shift under the e2e scores
    assert np.array_equal(np.asarray(u_def), np.asarray(u_e2e))
    assert not np.array_equal(np.asarray(w_def), np.asarray(w_e2e))


def test_tpu_backend_requires_native_core(monkeypatch):
    """``backend="tpu"`` hands its rejects to the native host engines;
    without the native core it raises instead of quietly taking the
    pure-Python engines (seconds per overlap) as the reject path."""
    import pytest

    from racon_tpu import native
    monkeypatch.setattr(native, "available", lambda: False)
    with pytest.raises(ValueError, match="native host core"):
        make_aligner("tpu", 1)
    with pytest.raises(ValueError, match="native host core"):
        make_consensus("tpu", 3, -5, -4)
    # the host backends keep their Python fallback
    assert type(make_aligner("auto", 1)).__name__ == "PythonAligner"
