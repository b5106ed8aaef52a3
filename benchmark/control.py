#!/usr/bin/env python3
"""Read the numbers the residual gate is set from, over several seeds in
one process::

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3 --control=-b --control keep-overlaps=0.5

For each seed: the cell's inputs, one job as the cell runs it (the sound
reading), one job per control, and the host-path reference. A control is
the program made cheaper the way a later PR might be tempted to:
``--control=-b`` adds flags (``-b`` halves the consensus band);
``--control keep-overlaps=0.5`` gives the job that share of the
overlaps, evenly thinned — what a depth cap, or a part of the batch left
out, does to the answer. Prints one JSON line per seed with the three
distances to the truth and the run-report checks a window job must pass;
the last line sums up: the largest sound and the smallest control reading of the residual
in edits per million truth bases after the per-contig allowance — the
configuration's ``residual_ppm_limit`` must lie between them — and the
largest sound reading of ``distance / reference distance``, which
``RESIDUAL_MARGIN`` in ``harness/checks.py`` must cover.

Not part of a benchmark run. It needs the TPU like ``run.py`` does
(``--rehearse`` lets it run on the CPU at a tests-only cell) and costs
two jobs and a reference per seed after one compile.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmark"))


def thin_overlaps(path: str, share: float, out_path: str) -> str:
    """Every line of ``path`` whose running ``share`` crosses a whole
    number: an even thinning that keeps ``share`` of the lines."""
    with open(path, "rb") as src, open(out_path, "wb") as dst:
        for i, line in enumerate(src):
            if int((i + 1) * share) > int(i * share):
                dst.write(line)
    return out_path


def measured_distance(fasta: str, truth: str) -> tuple:
    """(distance, note): a damaged FASTA defeats the cut that suits a
    polished one, so cut finer and allow more before giving up."""
    from harness import distance
    try:
        return distance.total_distance(fasta, truth)[0], None
    except distance.TooFar as coarse:
        try:
            return (distance.total_distance(fasta, truth, seg=4096,
                                            max_d=4096)[0],
                    f"finer cut after: {coarse}")
        except distance.TooFar as fine:
            return None, str(fine)


def read_seed(cell, seed: int, controls: list, work_dir: str) -> dict:
    from harness import cell as hc
    from harness import checks, reference
    inputs = hc.generate_inputs(cell, seed, work_dir)
    n_pairs, n_windows = checks.workload_size(
        inputs, cell.config["window_length"])
    n_contigs = len(cell.traffic["contig_sizes"])
    out = {"seed": seed, "pairs": n_pairs, "windows": n_windows}
    jobs = [("sound", cell.job_flags(), inputs)]
    for control in controls:
        if control.startswith("keep-overlaps="):
            thinned = thin_overlaps(
                inputs["overlaps"], float(control.split("=", 1)[1]),
                os.path.join(work_dir, "thinned.paf"))
            jobs.append((control, cell.job_flags(),
                         {**inputs, "overlaps": thinned}))
        else:
            jobs.append((control, [*cell.job_flags(), *control.split()],
                         inputs))
    for tag, flags, given in jobs:
        job = hc.run_job(flags, given, work_dir, "job")
        if job["rc"] != 0:
            out[tag] = {"rc": job["rc"]}
            continue
        dist, dist_note = measured_distance(job["fasta"], inputs["truth"])
        rows = checks.report_rows(job["report"], n_pairs, n_windows, tag,
                                  in_window=False)
        out[tag] = {"distance": dist, "distance_note": dist_note,
                    "wall_s": job["wall_s"],
                    "compiles": job["report"]["compiles"]["count"],
                    "failed_checks": [r["check"] for r in rows
                                      if not r["ok"]]}
    ref = reference.reference_record(cell, seed, inputs, work_dir)
    out["reference"] = {"distance": ref["distance"], "wall_s": ref["wall_s"]}
    allowance = checks.CONTIG_END_ALLOWANCE * n_contigs
    for tag, _, _ in jobs:
        dist = out[tag].get("distance")
        if dist is not None:
            out[tag]["ppm_after_allowance"] = \
                1e6 * max(0, dist - allowance) / ref["truth_bases"]
            out[tag]["vs_reference"] = (dist - allowance) / ref["distance"]
    shutil.rmtree(os.path.dirname(inputs["reads"]), ignore_errors=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--control", action="append", required=True,
                    help="flags to add, or keep-overlaps=<share>")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--benchmark-json", default="")
    args = ap.parse_args(argv)

    from harness import cell as hc
    from harness import spec
    cell = spec.load_cell(args.workload, args.benchmark_json)
    try:
        device = hc.find_device(cell.chips, require_tpu=not args.rehearse)
    except hc.NoDevice as e:
        print(f"control: {e}", file=sys.stderr)
        return hc.EXIT_NO_DEVICE
    hc.build_native()
    work_dir = tempfile.mkdtemp(prefix="racon-control-")
    readings = []
    try:
        for seed in (int(s) for s in args.seeds.split(",")):
            readings.append(read_seed(cell, seed, args.control, work_dir))
            print(json.dumps(readings[-1]), flush=True)
    finally:
        shutil.rmtree(work_dir, ignore_errors=True)

    def column(tag: str, key: str) -> list:
        return [r[tag][key] for r in readings if key in r[tag]]

    summary = {"workload": cell.name, "device": device,
               "seeds": len(readings),
               "sound_ppm_max": max(column("sound", "ppm_after_allowance"),
                                    default=None),
               "sound_vs_reference_max": max(column("sound", "vs_reference"),
                                             default=None),
               # None: the control gave no number on some seed
               "control_ppm_min": {
                   c: min(column(c, "ppm_after_allowance"))
                   if len(column(c, "ppm_after_allowance")) == len(readings)
                   else None for c in args.control}}
    keep = os.path.join(ROOT, "chiprun_out", "benchmark")
    os.makedirs(keep, exist_ok=True)
    with open(os.path.join(keep, f"control.{cell.name}.json"), "a") as fh:
        fh.write(json.dumps({"summary": summary, "readings": readings})
                 + "\n")
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
