"""Whole runs of ``benchmark/run.py`` on the CPU, at the tests' tiny
cells (minutes each, so marked slow): the plumbing from the command line
to the result line, the chips-4 path on four virtual CPU devices, and a
run whose timed path is broken underneath.

No CPU run may end ``correct: true`` or put a number under a metric's
name: its readings go under ``rehearsal_readings``.
"""

import json
import os
import subprocess
import sys
import time

import pytest

from bench_paths import ROOT, TINY_BENCHMARK

pytestmark = pytest.mark.slow

RUN = [sys.executable, os.path.join(ROOT, "benchmark", "run.py")]


def _run(args, devices=1):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}"}
    proc = subprocess.run([*RUN, *args], cwd=ROOT, env=env, text=True,
                          capture_output=True, timeout=3000)
    lines = proc.stdout.strip().splitlines()
    return proc, lines


def test_without_a_tpu_the_command_fails_and_prints_no_result():
    proc, lines = _run(["--workload", "bact2m-paf30x", "--seed", "1",
                        "--seconds", "1", "--trace", "0"])
    assert proc.returncode == 3
    assert lines == []
    assert "no TPU" in proc.stderr


def test_a_cell_that_asks_for_more_chips_than_there_are_fails():
    proc, lines = _run(["--workload", "tiny-chips4", "--seed", "1",
                        "--seconds", "1", "--trace", "0", "--rehearse",
                        "--benchmark-json", TINY_BENCHMARK], devices=2)
    assert proc.returncode == 3 and lines == []
    assert "4 chips" in proc.stderr


@pytest.mark.parametrize("trace", [0, 1])
def test_cpu_rehearsal_ends_not_correct_with_the_device_named_cpu(trace):
    proc, lines = _run(["--workload", "tiny", "--seed", str(2**31 + 11),
                        "--seconds", "1", "--trace", str(trace),
                        "--rehearse", "--benchmark-json", TINY_BENCHMARK])
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(lines[-1])
    assert result["correct"] is False
    assert result["device"]["platform"] == "cpu"
    assert result["metrics"] == {}
    assert result["attempted"] == 1
    # no device number on a CPU: nothing read from a trace
    assert "busy_s" not in result["device"]
    assert "breakdown" not in result
    readings = result["rehearsal_readings"]
    if trace:
        assert {"job_s", "residual_ppm", "parse_s", "align_feed_s",
                "align_lane_fill", "build_s", "poa_pack_s", "poa_fetch_s",
                "compile_s", "compile_programs"} <= set(readings)
        assert 0 < readings["residual_ppm"]["value"] < 20000
        assert "align_device_s" not in readings
        assert "consensus_device_s" not in readings
    else:
        # no rate: on a CPU no job passes the kernel-family check, and a
        # rate is taken over the jobs that completed soundly
        assert set(readings) == {"host_peak_rss_gb", "setup_s"}
        assert result["failed"] == 1
    # every number compared stands beside its limit, before the result
    rows = [json.loads(line) for line in lines[:-1]]
    assert all(set(r) == {"check", "value", "limit", "ok"} for r in rows)
    failed = {r["check"] for r in rows if not r["ok"]}
    assert failed == set(result["failed_checks"])
    # what fails on a CPU is the platform and the kernel family, not the
    # work: same bytes, no compile in the window, the residual gate holds
    assert "platform" in failed
    assert "w0.aligner_chunks_off_mosaic" in failed
    assert not failed & {"w0.fasta_differs_from_warmup", "w0.compiles",
                         "w0.exit_code", "residual_ppm_after_allowance",
                         "residual_distance_vs_reference"}


def test_chips_4_path_on_four_virtual_cpu_devices():
    proc, lines = _run(["--workload", "tiny-chips4", "--seed", "77",
                        "--seconds", "1", "--trace", "0", "--rehearse",
                        "--benchmark-json", TINY_BENCHMARK], devices=4)
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(lines[-1])
    assert result["correct"] is False
    assert result["device"]["count"] == 4
    rows = {json.loads(line)["check"]: json.loads(line)
            for line in lines[:-1]}
    assert rows["w0.exit_code"]["ok"]
    assert rows["w0.fasta_differs_from_warmup"]["ok"]
    assert rows["residual_distance_vs_reference"]["ok"]


def test_a_broken_timed_path_comes_out_not_correct(tmp_path, monkeypatch):
    """Skip the look for a chip and drive the rest of a run with a base
    altered where a window job's answer is produced: the byte-identity
    check sees it and the job counts as failed."""
    from harness import cell as harness_cell
    from harness import spec

    def broken(flags, inputs, work_dir, tag):
        job = harness_cell.run_job(flags, inputs, work_dir, tag)
        if tag != "warmup":
            with open(job["fasta"], "r+b") as fh:
                fh.seek(5000)
                base = fh.read(1)
                fh.seek(5000)
                fh.write(b"A" if base != b"A" else b"C")
        return job

    tiny = spec.load_cell("tiny", TINY_BENCHMARK)
    result = harness_cell.run_cell(tiny, 5, 1.0, 0, time.perf_counter(),
                                   require_tpu=False, job_runner=broken)
    assert result["correct"] is False
    assert "w0.fasta_differs_from_warmup" in result["failed_checks"]
    assert result["failed"] == 1
    # no job completed soundly, so no rate is made up
    assert "polish_mbp_per_s" not in result["rehearsal_readings"]


def test_the_control_comes_out_not_correct_at_test_size():
    """``benchmark/control.py`` at the tiny cell: the program given
    every second overlap (the control whose chip readings set the
    limits, PERF.md section 2) fails the residual row that the sound
    run passes. At 0.02 Mbp the contig's two ends weigh on every
    distance, so the tiny configuration states a limit of its own."""
    from harness import checks, spec
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "control.py"),
         "--workload", "tiny", "--seeds", "6", "--control",
         "keep-overlaps=0.5", "--rehearse", "--benchmark-json",
         TINY_BENCHMARK],
        cwd=ROOT, env=env, text=True, capture_output=True, timeout=3000)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(line) for line in proc.stdout.strip().splitlines()]
    reading, summary = lines[0], lines[-1]
    assert reading["seed"] == 6 and summary["seeds"] == 1
    limit = spec.load_cell("tiny", TINY_BENCHMARK).config[
        "residual_ppm_limit"]

    def failed(tag):
        rows = checks.residual_rows(
            reading[tag]["distance"], reading["reference"]["distance"], 1,
            20000, limit)
        return [r["check"] for r in rows if not r["ok"]]

    assert failed("sound") == []
    assert "residual_ppm_after_allowance" in failed("keep-overlaps=0.5")
    assert summary["sound_ppm_max"] <= limit < \
        summary["control_ppm_min"]["keep-overlaps=0.5"]
