"""A whole run of ``benchmark/run.py`` on the CPU at the tests' tiny
stand-in for ``bact1m-auto30x-r2`` (minutes, so marked slow): every job
is two rounds in one process (``--overlaps auto --rounds 2``), the
harness's checks hold over two rounds' summed counters, and every
per-layer metric the rounds cell adds finds something to read.
"""

import json
import os

import pytest

from bench_paths import DATA
from test_rehearsal import _run

pytestmark = pytest.mark.slow

TINY_ROUNDS_BENCHMARK = os.path.join(DATA, "BENCHMARK.tiny-rounds.json")


def test_cpu_rehearsal_of_the_rounds_cell_reads_every_new_metric():
    proc, lines = _run(["--workload", "tiny-rounds", "--seed",
                        str(2**31 + 41), "--seconds", "1", "--trace", "1",
                        "--rehearse", "--benchmark-json",
                        TINY_ROUNDS_BENCHMARK])
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(lines[-1])
    assert result["correct"] is False and result["metrics"] == {}
    readings = result["rehearsal_readings"]
    assert {"round_first_s", "round_last_s", "round_handoff_s",
            "round_read_reuse_pct", "overlap_s", "overlap_query_kept_pct",
            "overlap_handoff_s", "align_feed_s", "align_lane_fill",
            "consensus_lane_fill", "job_s",
            "residual_ppm"} <= set(readings)
    assert readings["round_read_reuse_pct"]["value"] == 100
    assert 90 <= readings["overlap_query_kept_pct"]["value"] <= 100
    # the rounds and the hand-off between them lie inside the job, and
    # are most of it (what is left: the probes' line, the FASTA, the
    # report)
    inside = sum(readings[k]["value"] for k in (
        "round_first_s", "round_last_s", "round_handoff_s"))
    assert 0.9 * readings["job_s"]["value"] <= inside \
        <= readings["job_s"]["value"] + 1e-3    # wall_s has 3 decimals
    rows = {json.loads(line)["check"]: json.loads(line)
            for line in lines[:-1]}
    # sound over two rounds: same bytes as the warm-up job, nothing
    # compiled in the window (round 2's programs are the warm-up job's
    # round 2's), host rejects under 2 % of ONE round's pairs, and the
    # residual inside the stand-in's limit (at 0.02 Mbp the residual is
    # the contig's two ends, which every round trims a little further:
    # the stand-in's limit is set for two rounds of that)
    for check in ("w0.exit_code", "w0.fasta_differs_from_warmup",
                  "w0.compiles", "w0.post_warm_compiles",
                  "w0.host_pair_share", "w0.host_window_share",
                  "residual_ppm_after_allowance"):
        assert rows[check]["ok"], rows[check]
    # two device rounds against ONE host round on the exact PAF plus
    # the allowance: compared, whatever it says at this size
    assert rows["residual_distance_vs_reference"]["value"] is not None
    # the idle_* metrics of the cell still sum to the ledger's idle
    # seconds: `round` and `round.handoff` take none of it
    idle = sum(v["value"] for k, v in readings.items()
               if k.startswith("idle_"))
    assert abs(idle - readings["device_idle_host_s"]["value"]) < 1e-4
