"""A whole run of ``benchmark/run.py`` on the CPU at the tests' tiny
stand-in for ``bact-sr150-50x`` (minutes, so marked slow): 150-base
reads at 50x through the cell's own flags, every window over the depth
cap, and every per-layer metric the short-read cell adds finds
something to read (all but those that need a device trace).
"""

import json
import os

import pytest

from bench_paths import DATA
from test_rehearsal import _run

pytestmark = pytest.mark.slow

TINY_SR_BENCHMARK = os.path.join(DATA, "BENCHMARK.tiny-sr.json")


def test_cpu_rehearsal_of_the_short_read_cell_reads_every_new_metric():
    proc, lines = _run(["--workload", "tiny-sr", "--seed",
                        str(2**31 + 37), "--seconds", "1", "--trace", "1",
                        "--rehearse", "--benchmark-json",
                        TINY_SR_BENCHMARK])
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(lines[-1])
    assert result["correct"] is False and result["metrics"] == {}
    readings = {k: v["value"]
                for k, v in result["rehearsal_readings"].items()}
    assert {"consensus_lane_fill", "consensus_layers_dropped_pct",
            "consensus_windows_capped_pct", "align_feed_s",
            "align_lane_fill", "align_host_s", "align_wait_s",
            "align_pack_ahead_pct", "idle_align_feed_s", "job_s",
            "residual_ppm"} <= set(readings)
    # device metrics have no CPU reading
    assert not {"align_device_s", "align_rows_device_s",
                "consensus_device_s"} & set(readings)
    # 150-base rows in 1,024-lane blocks; 213 layers offered a window,
    # 200 kept: the cell's shape at a hundredth of its size
    assert 5 < readings["consensus_lane_fill"] < 15
    assert 2 < readings["consensus_layers_dropped_pct"] < 12
    assert readings["consensus_windows_capped_pct"] > 50
    rows = {json.loads(line)["check"]: json.loads(line)
            for line in lines[:-1]}
    # the work is sound: same bytes, nothing compiled in the window,
    # no pair and no window on the host, and the device path within
    # the gate of the host path
    for check in ("w0.exit_code", "w0.fasta_differs_from_warmup",
                  "w0.compiles", "w0.post_warm_compiles",
                  "w0.host_pair_share", "w0.host_window_share",
                  "residual_ppm_after_allowance",
                  "residual_distance_vs_reference"):
        assert rows[check]["ok"], rows[check]
    assert rows["w0.host_pair_share"]["value"] == 0
    idle = sum(v for k, v in readings.items() if k.startswith("idle_"))
    assert abs(idle - readings["device_idle_host_s"]) < 1e-4
