"""Batched device aligner tests (run on the CPU XLA backend via conftest;
the same code path runs on TPU — see .claude/skills/verify/SKILL.md)."""

import random

import pytest

from racon_tpu.core.backends import NativeAligner, PythonAligner
from racon_tpu.models.nw import edit_distance
from racon_tpu.ops.nw import TpuAligner, BUCKETS
from tests.test_nw import cigar_cost, cigar_consumes


def mutate(rng, s, err):
    out = bytearray()
    for ch in s:
        r = rng.random()
        if r < err * 0.4:
            out.append(rng.choice(b"ACGT"))
        elif r < err * 0.7:
            pass
        elif r < err:
            out.extend([ch, rng.choice(b"ACGT")])
        else:
            out.append(ch)
    return bytes(out)


@pytest.fixture(scope="module")
def aligner():
    try:
        fb = NativeAligner(1)
    except RuntimeError:
        fb = PythonAligner()
    return TpuAligner(fallback=fb)


def test_device_alignments_optimal(aligner):
    rng = random.Random(11)
    pairs = []
    for L, err in [(60, 0.2), (200, 0.15), (900, 0.15), (2000, 0.12),
                   (300, 0.3), (500, 0.02), (100, 0.0)]:
        a = bytes(rng.choice(b"ACGT") for _ in range(L))
        pairs.append((mutate(rng, a, err), a))
    cigars = aligner.align_batch(pairs)
    for (q, t), cig in zip(pairs, cigars):
        assert cigar_consumes(cig) == (len(q), len(t))
        assert cigar_cost(cig, q, t) == edit_distance(q, t)


def test_length_mismatch_and_empty(aligner):
    rng = random.Random(12)
    a = bytes(rng.choice(b"ACGT") for _ in range(400))
    pairs = [(a, a[:200]), (a[:150], a), (b"", a[:30]), (a[:30], b"")]
    cigars = aligner.align_batch(pairs)
    for (q, t), cig in zip(pairs, cigars):
        assert cigar_consumes(cig) == (len(q), len(t))
    assert cigars[2] == "30D"
    assert cigars[3] == "30I"


def test_band_escalation_handles_high_divergence(aligner):
    rng = random.Random(13)
    a = bytes(rng.choice(b"ACGT") for _ in range(1500))
    b = mutate(rng, a, 0.45)  # extreme divergence forces band escalation
    (cig,) = aligner.align_batch([(b, a)])
    assert cigar_consumes(cig) == (len(b), len(a))
    assert cigar_cost(cig, b, a) == edit_distance(b, a)


def test_oversize_pair_falls_back(aligner):
    max_len = max(m for m, _ in BUCKETS)
    rng = random.Random(14)
    a = bytes(rng.choice(b"ACGT") for _ in range(max_len + 10))
    before = dict(aligner.stats)
    (cig,) = aligner.align_batch([(a, a)])
    assert cig == f"{len(a)}M"
    assert aligner.stats["fallback_length"] == before["fallback_length"] + 1


def test_breaking_points_match_cigar_walker():
    """Device breaking points (per-boundary tables computed from the
    device-resident op stream) must equal walking the device CIGAR with
    the shared oracle walker, for every pair, strand offset and window
    phase — including pairs with matchless windows (deletion crossings)."""
    import numpy as np

    from racon_tpu.core.overlap import breaking_points_from_cigar
    from racon_tpu.ops.nw import TpuAligner

    rng = np.random.default_rng(29)
    bases = np.frombuffer(b"ACGT", np.uint8)
    pairs, metas = [], []
    for k in range(24):
        ln = int(rng.integers(120, 240))
        t = bases[rng.integers(0, 4, ln)]
        q = t.copy()
        flips = rng.random(ln) < 0.12
        q[flips] = bases[rng.integers(0, 4, int(flips.sum()))]
        q = np.delete(q, rng.integers(0, len(q), 5))
        if k % 4 == 0:  # a long deletion -> a window with no matches
            cut = int(rng.integers(20, ln - 60))
            q = np.concatenate([q[:cut], q[cut + 45:]])
        pairs.append((q.tobytes(), t.tobytes()))
        metas.append((int(rng.integers(0, 1000)),    # global t_begin
                      int(rng.integers(0, 500))))    # global q_off
    w = 64

    from racon_tpu.core.backends import PythonAligner
    from racon_tpu.core.overlap import bp_array_to_pairs
    al = TpuAligner(buckets=((256, 128),), fallback=PythonAligner())
    bps = al.breaking_points_batch(pairs, metas, w)
    assert al.stats["fallback_length"] > 0  # deletion pairs exercise the
    cigars = al.align_batch(pairs)        # host-walker fallback path too
    for k, ((q, t), (t_begin, q_off)) in enumerate(zip(pairs, metas)):
        oracle = breaking_points_from_cigar(
            cigars[k], q_off, t_begin, t_begin + len(t), w)
        assert bps[k].dtype == np.int32 and bps[k].shape[1] == 4
        assert bp_array_to_pairs(bps[k]) == oracle, f"pair {k}"


# ------------------------------------------- kernel family by platform

def test_pallas_ok_is_false_off_the_tpu_without_a_mosaic_attempt(
        monkeypatch, capsys):
    """On the CPU platform the XLA kernels ARE the path: ``pallas_ok()``
    answers False from the platform alone — no Mosaic call is tried, so
    nothing is swallowed and no warning is printed."""
    from racon_tpu.obs import metrics
    from racon_tpu.ops import pallas_nw

    def no_mosaic(*a, **kw):
        raise AssertionError("a Mosaic kernel was attempted off the TPU")

    monkeypatch.setattr(pallas_nw, "_PALLAS_OK", None)
    monkeypatch.setattr(pallas_nw, "_PALLAS_SWAR_OK", None)
    monkeypatch.setattr(pallas_nw, "pallas_nw_fwd", no_mosaic)
    monkeypatch.setattr(pallas_nw, "pallas_walk_ops", no_mosaic)
    monkeypatch.setattr(pallas_nw, "pallas_walk_vote", no_mosaic)
    before = dict(metrics.group("swallowed."))
    assert pallas_nw.pallas_ok() is False
    assert pallas_nw.pallas_swar_ok() is False
    assert dict(metrics.group("swallowed.")) == before
    assert "swallowed" not in capsys.readouterr().err


def test_pallas_probe_failure_is_a_hard_error_on_the_tpu(monkeypatch):
    """On the TPU a probe that raises or mismatches fails the run — it
    is never a logged downgrade to the XLA kernels, and the memo stays
    unset so nothing later reads a quiet False."""
    import jax
    from racon_tpu.ops import pallas_nw, swar

    monkeypatch.setattr(pallas_nw, "_PALLAS_OK", None)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    # the CPU backend refuses the Mosaic call itself: that must surface
    with pytest.raises(Exception, match="[Ii]nterpret mode|Mosaic|TPU"):
        pallas_nw.pallas_ok()
    assert pallas_nw._PALLAS_OK is None

    def mismatch():
        swar.probe_equal("pallas_nw_fwd", "dirs", [[0, 1], [2, 3]],
                         [[0, 1], [2, 7]])

    monkeypatch.setattr(pallas_nw, "_probe_pallas", mismatch)
    with pytest.raises(swar.KernelProbeError,
                       match=r"pallas_nw_fwd.*'dirs'.*\(1, 1\).*3.*7"):
        pallas_nw.pallas_ok()
    assert pallas_nw._PALLAS_OK is None


def test_engine_takes_the_platforms_kernels_without_a_downgrade_path():
    """The per-shape Pallas->XLA downgrade bookkeeping is gone: an engine
    asks ``_use_pallas`` (the platform's answer) and keeps no failed-
    shape memo or fallback stat."""
    a = TpuAligner(fallback=NativeAligner(1))
    assert a._use_pallas((256, 128, 512, 8)) is False
    assert not any("pallas" in k for k in a.stats)
    assert not [n for n in dir(a) if "pallas_fail" in n
                or "note_pallas" in n]
