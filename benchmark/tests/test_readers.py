"""The reader kinds against a recorded run report
(``data/run_report.json``: the 0.02 Mbp CPU rehearsal job)."""

import json
import os

import pytest

from bench_paths import BENCH, DATA

from harness import readers


@pytest.fixture(scope="module")
def report():
    with open(os.path.join(DATA, "run_report.json")) as fh:
        return json.load(fh)


def _metric(name):
    with open(os.path.join(BENCH, "metrics", name + ".json")) as fh:
        return json.load(fh)


def test_span_sum_adds_the_leaf_spans(report):
    timers = report["metrics"]["timers"]
    ctx = {"traced": report}
    assert readers.read_metric(_metric("parse_s"), ctx) == pytest.approx(
        timers["parse.reads"] + timers["parse.overlaps"]
        + timers["parse.targets"])
    # poa.stage_b did not run in that job: the spans that did are summed
    assert "poa.stage_b" not in timers
    assert readers.read_metric(_metric("poa_fetch_s"), ctx) == \
        pytest.approx(timers["poa.fetch"])
    assert readers.read_metric(_metric("align_feed_s"), ctx) == \
        pytest.approx(timers["align.dispatch"] + timers["align.fetch"])


def test_counter_ratio(report):
    c = report["metrics"]["counters"]
    assert readers.read_metric(_metric("align_lane_fill"),
                               {"traced": report}) == pytest.approx(
        100 * c["align.lanes_occupied"] / c["align.lanes_total"])


def test_report_value_of_the_warmup_job_and_the_window_median(report):
    ctx = {"warmup": report, "traced": None,
           "window": [{"wall_s": 30.0}, {"wall_s": 26.0}, {"wall_s": 27.0}]}
    assert readers.read_metric(_metric("compile_programs"), ctx) == \
        report["compiles"]["count"]
    assert readers.read_metric(_metric("compile_s"), ctx) == \
        report["compiles"]["total_s"]
    assert readers.read_metric(_metric("job_s"), ctx) == 27.0


def test_family_time_sums_the_matching_programs():
    ctx = {"modules": {"jit__pallas_align_chain": 5.0, "jit__build_rows_packed2": 1.5,
                       "jit__refine_loop_packed": 3.0, "jit_other": 9.0}}
    assert readers.read_metric(_metric("align_device_s"), ctx) == 6.5
    assert readers.read_metric(_metric("consensus_device_s"), ctx) == 3.0


def test_run_value_reads_what_the_harness_measured():
    assert readers.read_metric(_metric("residual_ppm"),
                               {"run": {"residual_ppm": 156.5}}) == 156.5
    assert readers.read_metric(_metric("residual_ppm"), {"run": {}}) is None


def test_a_reader_with_nothing_to_read_returns_nothing(report):
    empty = {"warmup": None, "traced": None, "window": [], "modules": None}
    for name in ("parse_s", "align_lane_fill", "compile_s", "job_s",
                 "align_device_s"):
        assert readers.read_metric(_metric(name), empty) is None
    assert readers.read_metric(
        {"reader": "report-span-sum", "spans": ["no.such.span"]},
        {"traced": report}) is None
    assert readers.read_metric(
        {"reader": "xplane-family-time", "patterns": ["nothing"]},
        {"modules": {"jit_f": 1.0}}) is None


def test_an_unknown_reader_kind_is_an_error():
    with pytest.raises(ValueError, match="unknown reader"):
        readers.read_metric({"name": "x", "reader": "made-up"}, {})
