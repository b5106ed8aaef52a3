"""``correct`` comes out false for each fault the checks are there to
catch, on a recorded report doctored to show it."""

import copy
import json
import os

import pytest

from bench_paths import DATA

from harness import checks, peaks


@pytest.fixture()
def report():
    """The recorded CPU report, dressed as a chip job's: every dispatch
    on the Mosaic kernels, nothing compiled."""
    with open(os.path.join(DATA, "run_report.json")) as fh:
        rep = json.load(fh)
    c = rep["metrics"]["counters"]
    c["aligner.pallas_chunks"] = c["align.chunks"]
    c["consensus.pallas_groups"] = c["consensus.groups"]
    rep["compiles"].update(count=0, total_s=0.0, post_warm=0)
    return rep


def _failed(rows):
    return [r["check"] for r in rows if not r["ok"]]


def test_a_sound_window_job_passes(report):
    assert _failed(checks.report_rows(report, 85, 41, "w0", True)) == []


def test_a_dispatch_that_left_the_mosaic_kernels_fails(report):
    bad = copy.deepcopy(report)
    bad["metrics"]["counters"]["aligner.pallas_chunks"] -= 1
    assert _failed(checks.report_rows(bad, 85, 41, "w0", True)) == \
        ["w0.aligner_chunks_off_mosaic"]
    bad = copy.deepcopy(report)
    bad["metrics"]["counters"]["consensus.pallas_groups"] = 0
    assert _failed(checks.report_rows(bad, 85, 41, "w0", True)) == \
        ["w0.consensus_groups_off_mosaic"]


def test_a_compile_in_the_window_fails_but_not_in_the_warmup(report):
    bad = copy.deepcopy(report)
    bad["compiles"]["count"] = 1
    assert _failed(checks.report_rows(bad, 85, 41, "w0", True)) == \
        ["w0.compiles"]
    assert _failed(checks.report_rows(bad, 85, 41, "warmup", False)) == []
    bad["compiles"]["post_warm"] = 1
    assert "warmup.post_warm_compiles" in _failed(
        checks.report_rows(bad, 85, 41, "warmup", False))


def test_swallowed_exceptions_and_host_rejects_fail(report):
    bad = copy.deepcopy(report)
    bad["swallowed"] = {"ops: something": 1}
    assert _failed(checks.report_rows(bad, 85, 41, "w0", True)) == \
        ["w0.swallowed"]
    bad = copy.deepcopy(report)
    bad["metrics"]["counters"]["aligner.fallback_band"] = 2   # of 85: 2.4 %
    assert _failed(checks.report_rows(bad, 85, 41, "w0", True)) == \
        ["w0.host_pair_share"]
    bad = copy.deepcopy(report)
    bad["metrics"]["counters"]["consensus.fallback_windows"] = 1  # of 41
    assert _failed(checks.report_rows(bad, 85, 41, "w0", True)) == \
        ["w0.host_window_share"]
    bad = copy.deepcopy(report)
    bad["metrics"]["counters"]["align.chunks"] = 0
    bad["metrics"]["counters"]["aligner.pallas_chunks"] = 0
    assert "w0.device_dispatches" in _failed(
        checks.report_rows(bad, 85, 41, "w0", True))


def test_the_residual_gates():
    def failed(distance, reference=1100, contigs=1, ppm_limit=250):
        return _failed(checks.residual_rows(distance, reference, contigs,
                                            2_000_000, ppm_limit))
    # 600 edits less 100 for the contig's ends = 250 ppm of 2 Mbp
    assert failed(600) == []
    assert failed(601) == ["residual_ppm_after_allowance"]
    # three contigs: 300 edits of allowance
    assert failed(800, contigs=3) == []
    # no worse than the host path (plus the allowance), whatever the ppm
    assert failed(350, reference=240, ppm_limit=1000) == \
        ["residual_distance_vs_reference"]
    assert failed(340, reference=240, ppm_limit=1000) == []
    # a FASTA too far from the truth to measure has failed both
    assert failed(None) == ["residual_ppm_after_allowance",
                            "residual_distance_vs_reference"]


def test_peaks_table_knows_the_v5e_and_refuses_the_rest():
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("TPU v9 imaginary")
