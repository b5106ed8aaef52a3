"""A whole run of ``benchmark/run.py`` on the CPU at the tests' tiny
stand-in for ``bact2m-auto30x`` (minutes, so marked slow): the job runs
with ``--overlaps auto``, never reads the PAF the harness hands it, and
every per-layer metric the auto cell adds finds something to read
(all but the one that needs a device trace).
"""

import json
import os

import pytest

from bench_paths import DATA
from test_rehearsal import _run

pytestmark = pytest.mark.slow

TINY_AUTO_BENCHMARK = os.path.join(DATA, "BENCHMARK.tiny-auto.json")


def test_cpu_rehearsal_of_the_auto_cell_reads_every_new_metric():
    proc, lines = _run(["--workload", "tiny-auto", "--seed",
                        str(2**31 + 34), "--seconds", "1", "--trace", "1",
                        "--rehearse", "--benchmark-json",
                        TINY_AUTO_BENCHMARK])
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(lines[-1])
    assert result["correct"] is False and result["metrics"] == {}
    readings = result["rehearsal_readings"]
    assert {"overlap_s", "overlap_lane_fill", "overlap_query_kept_pct",
            "idle_overlap_s", "align_feed_s",
            "align_lane_fill", "job_s", "residual_ppm"} <= set(readings)
    # a device metric has no CPU reading
    assert "overlap_device_s" not in readings
    assert 90 <= readings["overlap_query_kept_pct"]["value"] <= 100
    rows = {json.loads(line)["check"]: json.loads(line)
            for line in lines[:-1]}
    # the work is sound: same bytes, nothing compiled in the window,
    # and the computed overlaps polish to within the gate of the host
    # path on the exact PAF
    for check in ("w0.exit_code", "w0.fasta_differs_from_warmup",
                  "w0.compiles", "w0.post_warm_compiles",
                  "residual_ppm_after_allowance",
                  "residual_distance_vs_reference"):
        assert rows[check]["ok"], rows[check]
    # the idle_* metrics of the cell sum to the ledger's idle seconds
    idle = sum(v["value"] for k, v in readings.items()
               if k.startswith("idle_"))
    assert abs(idle - readings["device_idle_host_s"]["value"]) < 1e-4
