"""A whole run of ``benchmark/run.py`` on the CPU at the tests' tiny
stand-in for ``frag2m-shards4-paf30x`` (minutes, so marked slow): every
job goes through the shard runner (``--shards 4`` over seven contigs),
the harness's checks hold over four shards' summed counters, and every
per-layer metric the cell adds finds something to read.
"""

import json
import os

import pytest

from bench_paths import DATA
from test_rehearsal import _run

pytestmark = pytest.mark.slow

TINY_SPLIT_BENCHMARK = os.path.join(DATA, "BENCHMARK.tiny-split.json")


def test_cpu_rehearsal_of_the_split_cell_reads_every_new_metric():
    proc, lines = _run(["--workload", "tiny-split", "--seed",
                        str(2**31 + 43), "--seconds", "1", "--trace", "1",
                        "--rehearse", "--benchmark-json",
                        TINY_SPLIT_BENCHMARK])
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(lines[-1])
    assert result["correct"] is False and result["metrics"] == {}
    readings = result["rehearsal_readings"]
    assert {"shard_index_s", "shard_extract_s", "shard_commit_s",
            "idle_exec_s", "shard_boundary_idle_s", "shard_on_device_pct",
            "align_feed_s", "align_lane_fill", "align_pack_ahead_pct",
            "consensus_lane_fill", "parse_s", "job_s", "compile_programs",
            "residual_ppm"} <= set(readings)
    assert readings["shard_on_device_pct"]["value"] == 100
    # the boundaries are part of the idle, and the runner's own spans
    # take some of it
    assert 0 < readings["shard_boundary_idle_s"]["value"] \
        < readings["device_idle_host_s"]["value"]
    assert readings["idle_exec_s"]["value"] > 0
    rows = {json.loads(line)["check"]: json.loads(line)
            for line in lines[:-1]}
    # sound over four shards: the report's compiles, swallowed and
    # counters sections are the whole job's (the runner's own run
    # boundary takes nothing of them), same bytes as the warm-up job,
    # nothing compiled in the window
    for check in ("warmup.swallowed", "warmup.device_dispatches",
                  "w0.exit_code", "w0.fasta_differs_from_warmup",
                  "w0.device_dispatches", "w0.compiles",
                  "w0.post_warm_compiles", "w0.host_pair_share",
                  "w0.host_window_share", "residual_ppm_after_allowance"):
        assert rows[check]["ok"], rows[check]
    # the sharded device job against the ONE-SHOT host path: compared
    assert rows["residual_distance_vs_reference"]["value"] is not None
    # the idle_* metrics of the cell, idle_exec_s among them, sum to the
    # ledger's idle seconds
    idle = sum(v["value"] for k, v in readings.items()
               if k.startswith("idle_"))
    assert abs(idle - readings["device_idle_host_s"]["value"]) < 1e-4
