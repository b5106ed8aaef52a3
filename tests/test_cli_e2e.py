"""Byte-exact end-to-end CLI golden + observability contract.

The analog of the reference's golden-output CI run
(``ci/gpu/cuda_test.sh:29-42``, which byte-diffs polished stdout against a
recorded ``golden-output.txt``): run the ``racon`` CLI on the λ-phage set
and byte-compare stdout against ``tests/data/golden_lambda_fastq_paf.fasta``
(recorded with the CPU path at ``-t 8``; catches tag/format/stitch
regressions that scalar edit-distance goldens miss).

Also asserts the observability contract: 20-bin progress bars during
overlap alignment and consensus, and the total wall-time line
(``src/polisher.cpp:475-481,534-543``, ``src/cuda/cudapolisher.cpp:21-24``).
"""

import pathlib
import subprocess
import sys

import pytest

GOLDEN = pathlib.Path(__file__).parent / "data" / "golden_lambda_fastq_paf.fasta"


def run_cli(data_dir, *extra_args):
    """Canonical λ-phage CLI invocation (+ optional extra flags) — the
    single definition every e2e test shares."""
    proc = subprocess.run(
        [sys.executable, "-m", "racon_tpu", "-t", "8", *extra_args,
         str(data_dir / "sample_reads.fastq.gz"),
         str(data_dir / "sample_overlaps.paf.gz"),
         str(data_dir / "sample_layout.fasta.gz")],
        capture_output=True, timeout=600,
        cwd=str(pathlib.Path(__file__).parent.parent))
    assert proc.returncode == 0, proc.stderr.decode()[-2000:]
    return proc


@pytest.fixture(scope="module")
def cli_run(data_dir):
    return run_cli(data_dir)


def test_cli_stdout_byte_exact(cli_run):
    assert cli_run.stdout == GOLDEN.read_bytes()


def test_cli_progress_bars(cli_run):
    err = cli_run.stderr.decode()
    assert ("[racon_tpu::Polisher::initialize] aligning overlaps "
            "[====================>] 100%") in err
    assert ("[racon_tpu::Polisher::polish] generating consensus "
            "[====================>] 100%") in err
    # intermediate bins are emitted too (20-bin contract, not one jump)
    assert "] 50%" in err


def test_cli_total_line(cli_run):
    assert "[racon_tpu::Polisher::] total =" in cli_run.stderr.decode()


def test_cli_tpualigner_byte_exact(data_dir):
    """Real-data golden through the device aligner path: the PAF input
    carries no CIGARs, so ``--tpualigner-batches`` routes every breaking-
    point alignment through the batched device aligner (XLA kernels on the
    CPU test mesh; the Pallas kernels are bit-identical by probe) — stdout
    must match the recorded CPU-path golden byte for byte."""
    proc = run_cli(data_dir, "--tpualigner-batches", "1")
    assert proc.stdout == GOLDEN.read_bytes()


def test_cli_profile_flag(data_dir, tmp_path):
    """--profile wraps the run in a jax.profiler trace (the nvprof-hooks
    analog): the run must still produce the golden bytes and leave a
    trace directory behind."""
    prof_dir = tmp_path / "trace"
    proc = run_cli(data_dir, "--profile", str(prof_dir))
    assert proc.stdout == GOLDEN.read_bytes()
    assert prof_dir.exists() and any(prof_dir.iterdir())


# ------------------------------------------------------- chip_smoke.py

REPO = pathlib.Path(__file__).parent.parent


def _run_chip_smoke(tmp_path, *args, timeout=120):
    import json
    import os
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py"), "--out",
         str(tmp_path / "work"), *args],
        capture_output=True, text=True, timeout=timeout, env=env,
        cwd=str(REPO))
    lines = [json.loads(ln) for ln in proc.stdout.splitlines()]
    return proc, lines


def test_chip_smoke_without_a_chip_fails_at_the_device_phase(tmp_path):
    """As the driver runs it (no arguments) on a machine with no chip:
    non-zero exit, last line ``"ok": false`` with the CPU device, and
    nothing after the ``device`` phase ran (no 2 Mbp polish on a CPU)."""
    proc, lines = _run_chip_smoke(tmp_path)
    assert proc.returncode != 0
    assert [ln.get("phase") for ln in lines] == ["device", None]
    assert lines[0]["ok"] is False and lines[0]["platform"] == "cpu"
    assert lines[-1] == {"ok": False, "device": {
        "platform": "cpu", "kind": lines[0]["kind"], "count": 1}}
    assert not (tmp_path / "work" / "inputs").exists()


@pytest.mark.slow
def test_chip_smoke_cpu_rehearsal(tmp_path):
    """``--mbp 0.02`` on the CPU rehearses every phase: the chip-only
    checks (platform, probes) are recorded as failed, everything else
    really runs and passes, and the run still ends ``"ok": false``
    (about 4 minutes on 8 cores, hence slow)."""
    proc, lines = _run_chip_smoke(tmp_path, "--mbp", "0.02", timeout=1500)
    assert proc.returncode != 0, proc.stderr[-2000:]
    by_phase = {ln["phase"]: ln for ln in lines[:-1]}
    assert list(by_phase) == ["device", "native", "probes", "polish",
                              "checks", "second_run"], proc.stderr[-2000:]
    assert by_phase["device"]["platform"] == "cpu"
    assert not by_phase["device"]["ok"] and not by_phase["probes"]["ok"]
    assert by_phase["probes"]["swar_ok"] is True
    assert by_phase["probes"]["pallas_ok"] is False
    for name in ("native", "polish", "checks", "second_run"):
        assert by_phase[name]["ok"], by_phase[name]
    checks = by_phase["checks"]
    assert checks["polished_distance"] < checks["polished_bound"]
    assert checks["swallowed"] == {}
    assert checks["aligner_pallas_chunks"] == 0     # XLA twins on CPU
    assert by_phase["second_run"]["byte_identical"]
    assert by_phase["second_run"]["new_compiles"] == 0
    assert lines[-1]["ok"] is False
    assert lines[-1]["device"]["platform"] == "cpu"
    assert "device backend: platform=cpu" in proc.stderr
