"""The comparisons that decide ``correct``. Each returns rows
``{"check", "value", "limit", "ok"}``; the harness prints every row, so
each number compared stands beside its limit in every run.

The run-report checks are ``chip_smoke.py``'s ``check_report`` (proven on
the chip in PR 22), cut into rows. The residual is held twice: against
the truth itself, by the limit the configuration states (the one a
degraded computation fails), and against the host path's residual on the
same inputs (``reference.py``).
"""

from __future__ import annotations

import json

from .distance import read_fasta

# racon's accelerator->CPU reject path is a contract, not a place to hide
# a device path that rejects everything (chip_smoke.py's bound)
MAX_HOST_FRACTION = 0.02
# a contig's two low-coverage ends, which both paths polish badly and
# differently (chip_smoke.py's allowance)
CONTIG_END_ALLOWANCE = 100
# the device path may leave this share more edits than the host path on
# the same inputs. 0: on every seed read on the chip the device path left
# under a third of the host path's edits (PERF.md section 2), so "no
# worse than the host path" is the smallest round figure every seed meets
RESIDUAL_MARGIN = 0.0


def row(check: str, value, limit, ok: bool) -> dict:
    return {"check": check, "value": value, "limit": limit, "ok": bool(ok)}


def workload_size(inputs: dict, window: int) -> tuple:
    """(overlap pairs, consensus windows) of an input set."""
    with open(inputs["overlaps"], "rb") as fh:
        n_pairs = sum(1 for _ in fh)
    n_windows = sum(-(-len(seq) // window)
                    for _, seq in read_fasta(inputs["draft"]))
    return n_pairs, n_windows


def report_rows(rep: dict, n_pairs: int, n_windows: int, tag: str,
                in_window: bool) -> list:
    """One job's run report against the guarantees: nothing swallowed,
    every device dispatch on the Mosaic kernels, host rejects bounded,
    and (for a window job) nothing compiled."""
    c = rep["metrics"]["counters"]
    # the runtime Pallas->XLA downgrade is gone from the program: its
    # stat must be absent from the whole report
    mentions = json.dumps(rep).count("pallas_fallback")
    rows = [row(f"{tag}.swallowed", len(rep["swallowed"]), 0,
                not rep["swallowed"]),
            row(f"{tag}.pallas_fallback_mentions", mentions, 0,
                mentions == 0)]
    chunks = c.get("align.chunks", 0)
    groups = c.get("consensus.groups", 0)
    rows.append(row(f"{tag}.device_dispatches", min(chunks, groups),
                    ">=1", chunks > 0 and groups > 0))
    rows.append(row(f"{tag}.aligner_chunks_off_mosaic",
                    chunks - c.get("aligner.pallas_chunks", 0), 0,
                    c.get("aligner.pallas_chunks", 0) == chunks))
    rows.append(row(f"{tag}.consensus_groups_off_mosaic",
                    max(0, groups - c.get("consensus.pallas_groups", 0)), 0,
                    c.get("consensus.pallas_groups", 0) >= groups))
    host_pairs = (c.get("aligner.fallback_band", 0)
                  + c.get("aligner.fallback_length", 0)
                  + c.get("dataflow.fallback_pairs", 0))
    rows.append(row(f"{tag}.host_pair_share", host_pairs / max(1, n_pairs),
                    MAX_HOST_FRACTION,
                    host_pairs <= MAX_HOST_FRACTION * n_pairs))
    host_windows = c.get("consensus.fallback_windows", 0)
    rows.append(row(f"{tag}.host_window_share",
                    host_windows / max(1, n_windows), MAX_HOST_FRACTION,
                    host_windows <= MAX_HOST_FRACTION * n_windows))
    comp = rep["compiles"]
    if in_window:
        rows.append(row(f"{tag}.compiles", comp["count"], 0,
                        comp["count"] == 0))
    rows.append(row(f"{tag}.post_warm_compiles", comp["post_warm"], 0,
                    comp["post_warm"] == 0))
    return rows


def residual_rows(device_distance, reference_distance: int,
                  n_contigs: int, truth_bases: int,
                  ppm_limit: float) -> list:
    """The polished distance to the truth, less the contig-end
    allowance, against the configuration's own limit in edits per
    million truth bases, and against the host-path reference's distance.
    ``device_distance`` is ``None`` when the FASTA was too far from the
    truth to measure (``distance.TooFar``): both rows fail."""
    allowance = CONTIG_END_ALLOWANCE * n_contigs
    ref_limit = reference_distance * (1 + RESIDUAL_MARGIN) + allowance
    if device_distance is None:
        return [row("residual_ppm_after_allowance", None, ppm_limit, False),
                row("residual_distance_vs_reference", None, ref_limit,
                    False)]
    ppm = 1e6 * max(0, device_distance - allowance) / truth_bases
    return [row("residual_ppm_after_allowance", ppm, ppm_limit,
                ppm <= ppm_limit),
            row("residual_distance_vs_reference", device_distance,
                ref_limit, device_distance <= ref_limit)]
