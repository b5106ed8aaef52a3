#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that racon-tpu still starts on the chip.

One process polishes a simulated 2 Mbp bacterial assembly (30x reads,
PAF given, contig mode, racon's default scoring) through the normal
entry point, ``racon_tpu.cli.main`` — which is all ``python -m
racon_tpu`` does — with the device aligner and the device consensus::

    python -m racon_tpu -t 8 -c 1 --tpualigner-batches 1 \\
        reads.fastq ovl.paf draft.fasta

and fails loudly if any part of that did not happen on the chip. Each
phase prints one JSON line as it finishes (``device``, ``native``,
``probes``, ``polish``, ``checks``, ``second_run``); the LAST line of
stdout is ``{"ok": true, "device": {"platform": "tpu", "kind": ...,
"count": 1}}`` on success and ``{"ok": false, ...}`` otherwise, and the
exit code is 0 only on success. No run without a TPU ends in
``"ok": true``: at the default size it stops after the ``device``
phase.

Options::

    --mbp X     genome size (default 2.0). With a size given, a run
                WITHOUT a TPU is a rehearsal: the checks only a chip can
                meet (platform, probes) are recorded as failed and the
                remaining phases still run (``--mbp 0.02`` takes a few
                minutes on the CPU); it still ends ``"ok": false``.
    --chips 4   the four-chip leg and its one-chip comparison, and no
                other phase: the same genome as four contigs, polished
                once with ``--chips 4`` and once with ``--chips 1`` in
                this one process; fails unless four TPU devices are
                visible, the two FASTAs are byte-identical, the quality
                check holds and the run report shows work on each chip.
    --seed N    simulator seed (default 11).
    --out DIR   work directory (default ./chip_smoke_out): the simulated
                inputs (116 MB of FASTQ at 2 Mbp) and the FASTAs land
                there. The small artifacts — these phase lines and the
                run reports — are also kept under
                ./chiprun_out/chip_smoke/, which the chip tool brings
                back.

One process per chip: nothing here starts a child that needs the
device, and the wall times printed are those of a smoke run, not of a
benchmark.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import shutil
import sys
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))
# a polished assembly must close at least 95% of the draft's distance to
# the truth (0.2 Mbp reference point: 16006 -> ~315, i.e. 2%), give or
# take its contigs' low-coverage ends, which the tiny rehearsal sizes
# feel (0.02 Mbp: 1603 -> 81, of which about 50 sit at the two ends)
MAX_POLISHED_FRACTION = 0.05
CONTIG_END_ALLOWANCE = 100
# racon's accelerator->CPU reject path is a contract, not a place to
# hide a device path that rejects everything
MAX_HOST_FRACTION = 0.02


class SmokeFailure(Exception):
    """A phase's check failed; the run is over."""


KEEP_DIR = os.path.join(REPO, "chiprun_out", "chip_smoke")


def emit(obj: dict) -> None:
    line = json.dumps(obj)
    print(line, flush=True)
    with open(os.path.join(KEEP_DIR, "phases.jsonl"), "a") as fh:
        fh.write(line + "\n")


class Phase:
    """Times one phase and prints its JSON line; an exception inside
    leaves with ``"ok": false`` on that line and propagates."""

    def __init__(self, name: str):
        self.name = name
        self.out: dict = {}

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self.out

    def __exit__(self, etype, exc, tb):
        failed = self.out.pop("_failed", False) or etype is not None
        line = {"phase": self.name, "ok": not failed}
        line.update(self.out)
        if etype is not None:
            line["error"] = f"{etype.__name__}: {exc}"
        line["seconds"] = round(time.perf_counter() - self.t0, 3)
        emit(line)
        return False


@contextlib.contextmanager
def stdout_to(path: str):
    """Redirect file descriptor 1 to ``path`` (the CLI writes its FASTA
    to ``sys.stdout.buffer``; redirecting the descriptor catches every
    writer, native code included)."""
    sys.stdout.flush()
    saved = os.dup(1)
    fd = os.open(path, os.O_WRONLY | os.O_CREAT | os.O_TRUNC, 0o644)
    os.dup2(fd, 1)
    os.close(fd)
    try:
        yield
    finally:
        sys.stdout.flush()
        os.dup2(saved, 1)
        os.close(saved)


def read_fasta(path: str) -> list:
    """[(name, sequence bytes)] of a one-line-per-record FASTA."""
    with open(path, "rb") as fh:
        lines = fh.read().split(b"\n")
    return [(lines[i][1:], lines[i + 1])
            for i in range(0, len(lines) - 1, 2) if lines[i][:1] == b">"]


def anchored_distance(a: bytes, b: bytes, seg: int = 65536, k: int = 24,
                      slack: int = 4096) -> list:
    """Cut two long, related sequences into pieces at exact shared
    ``k``-mers and return the ``(a piece, b piece)`` list whose edit
    distances sum to the sequences' distance — the unbanded native
    distance is quadratic (minutes at 2 Mbp), the pieces take
    milliseconds each. Anchors are unique exact matches within
    ``slack`` of where the running offset expects them, so they lie on
    the optimal path; sequences under ``2 * seg`` stay one piece (the
    exact distance). Unrelated sequences find no anchor and fail."""
    pieces = []
    ia = ib = 0
    while len(b) - ib > 2 * seg:
        pb = ib + seg
        while True:
            want = ia + (pb - ib)
            lo, hi = max(ia, want - slack), want + slack + k
            pa = a.find(b[pb:pb + k], lo, hi)
            if pa >= 0 and a.find(b[pb:pb + k], pa + 1, hi) < 0:
                break
            pb += k
            if pb > ib + 2 * seg:
                raise SmokeFailure(
                    f"no shared {k}-mer within {seg} bases of offset "
                    f"{ib}: the sequences are not near-identical")
        pieces.append((a[ia:pa], b[ib:pb]))
        ia, ib = pa, pb
    pieces.append((a[ia:], b[ib:]))
    return pieces


def total_distance(a_path: str, truth_path: str) -> int:
    """Sum over contigs of the edit distance to the truth
    (``native.edit_distance`` on bytes; contigs pair up by position:
    the CLI keeps the draft's order)."""
    from concurrent.futures import ThreadPoolExecutor

    from racon_tpu import native
    a, truth = read_fasta(a_path), read_fasta(truth_path)
    if len(a) != len(truth):
        raise SmokeFailure(f"{a_path}: {len(a)} contigs, the truth has "
                           f"{len(truth)}")
    pieces = [p for (_, x), (_, t) in zip(a, truth)
              for p in anchored_distance(x, t)]
    # ctypes releases the GIL, so the pieces run in parallel
    with ThreadPoolExecutor(max_workers=8) as pool:
        return sum(pool.map(lambda p: native.edit_distance(*p), pieces))


def run_cli(inputs: dict, out_dir: str, tag: str, extra=()) -> dict:
    """One in-process CLI run; returns exit code, paths, wall seconds
    and the parsed run report."""
    from racon_tpu import cli
    fasta = os.path.join(out_dir, f"polished_{tag}.fasta")
    report = os.path.join(out_dir, f"run_report_{tag}.json")
    argv = ["-t", "8", "-c", "1", "--tpualigner-batches", "1",
            *extra, "--run-report", report,
            inputs["reads"], inputs["overlaps"], inputs["draft"]]
    t0 = time.perf_counter()
    with stdout_to(fasta):
        rc = cli.main(argv)
    wall = time.perf_counter() - t0
    if rc != 0:
        raise SmokeFailure(f"racon_tpu.cli.main({argv}) exited {rc}")
    with open(report, "r", encoding="utf-8") as fh:
        rep = json.load(fh)
    shutil.copy(report, KEEP_DIR)
    return {"argv": argv, "fasta": fasta, "report": rep,
            "wall_s": round(wall, 3)}


def check_report(rep: dict, n_pairs: int, n_windows: int, out: dict,
                 on_tpu: bool) -> list:
    """The run-report checks shared by the one-chip and four-chip legs;
    returns the list of failures (empty = pass) and fills ``out``."""
    c = rep["metrics"]["counters"]
    bad = []
    out["swallowed"] = rep["swallowed"]
    if rep["swallowed"]:
        bad.append(f"swallowed exceptions: {rep['swallowed']}")
    # kernel families: every aligner chunk and consensus dispatch on
    # the Mosaic kernels; the runtime Pallas->XLA downgrade is gone, so
    # its stat must be absent from the whole report
    out["aligner_chunks"] = c.get("align.chunks", 0)
    out["aligner_pallas_chunks"] = c.get("aligner.pallas_chunks", 0)
    out["consensus_groups"] = c.get("consensus.groups", 0)
    out["consensus_pallas_dispatches"] = c.get("consensus.pallas_groups", 0)
    if "pallas_fallback" in json.dumps(rep):
        bad.append("the report mentions pallas_fallback")
    if not out["aligner_chunks"] or not out["consensus_groups"]:
        bad.append("a device engine dispatched nothing")
    if on_tpu and (out["aligner_pallas_chunks"] != out["aligner_chunks"]
                   or out["consensus_pallas_dispatches"]
                   < out["consensus_groups"]):
        bad.append("some device dispatches did not run the Mosaic "
                   "kernels")
    # host rejects, counted and bounded
    rejects = {k: c.get(k, 0) for k in (
        "aligner.fallback_band", "aligner.fallback_length",
        "consensus.fallback_windows", "consensus.dropped_layers")}
    out["host_rejects"] = rejects
    out["pairs"], out["windows"] = n_pairs, n_windows
    host_pairs = (rejects["aligner.fallback_band"]
                  + rejects["aligner.fallback_length"])
    if host_pairs > MAX_HOST_FRACTION * n_pairs:
        bad.append(f"{host_pairs} of {n_pairs} pairs went to the host "
                   f"aligner (> {MAX_HOST_FRACTION:.0%})")
    if rejects["consensus.fallback_windows"] > \
            MAX_HOST_FRACTION * n_windows:
        bad.append(f"{rejects['consensus.fallback_windows']} of "
                   f"{n_windows} windows went to the host consensus "
                   f"(> {MAX_HOST_FRACTION:.0%})")
    comp = rep["compiles"]
    out["compiles"] = {k: comp[k] for k in (
        "count", "total_s", "post_warm", "wall_s", "eager_programs",
        "unused_s", "miss_s")}
    if comp["post_warm"]:
        bad.append(f"{comp['post_warm']} post-warm compiles")
    return bad


def check_quality(fasta: str, inputs: dict, out: dict) -> list:
    draft_d = total_distance(inputs["draft"], inputs["truth"])
    polished_d = total_distance(fasta, inputs["truth"])
    bound = (MAX_POLISHED_FRACTION * draft_d
             + CONTIG_END_ALLOWANCE * len(read_fasta(inputs["truth"])))
    out.update(draft_distance=draft_d, polished_distance=polished_d,
               polished_bound=round(bound, 1))
    if polished_d >= bound:
        return [f"polished distance {polished_d} is not below {bound:.0f} "
                f"({MAX_POLISHED_FRACTION:.0%} of the draft's {draft_d} + "
                f"{CONTIG_END_ALLOWANCE} per contig)"]
    return []


def workload_size(inputs: dict, window: int = 500) -> tuple:
    with open(inputs["overlaps"], "rb") as fh:
        n_pairs = sum(1 for _ in fh)
    n_windows = sum(-(-len(seq) // window)
                    for _, seq in read_fasta(inputs["draft"]))
    return n_pairs, n_windows


def peak_device_bytes() -> dict:
    import jax
    peaks = {}
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks[str(d.id)] = int(stats["peak_bytes_in_use"])
    return peaks


def phase_device(device: dict, want_chips: int, rehearsal: bool) -> bool:
    """Phase 1. Returns True when the platform is a TPU."""
    with Phase("device") as out:
        import jax
        devs = jax.devices()
        device.update(platform=devs[0].platform,
                      kind=devs[0].device_kind, count=len(devs))
        out.update(device, jax=jax.__version__)
        on_tpu = device["platform"] == "tpu"
        if not on_tpu:
            out["_failed"] = True
            out["error"] = ("no TPU: JAX reports platform "
                            f"{device['platform']!r}")
            if not rehearsal:
                raise SmokeFailure(out["error"])
        if len(devs) < want_chips:
            raise SmokeFailure(f"--chips {want_chips} needs "
                               f"{want_chips} devices, JAX reports "
                               f"{len(devs)}")
    return on_tpu


def phase_native() -> None:
    """Phase 2: the host core rebuilt from the committed sources — never
    a .so that came with the copy (built -march=native on another CPU)."""
    with Phase("native") as out:
        from racon_tpu import native
        path = native.build(force=True)
        out.update(built=os.path.relpath(str(path), REPO),
                   parser_extension=native.load_ext() is not None)
        if not native.available():
            raise SmokeFailure("native core built but did not load")
        if not out["parser_extension"]:
            raise SmokeFailure("the native parser extension did not "
                               "build or load")
        if native.edit_distance(b"ACGT", b"AGT") != 1:
            raise SmokeFailure("native core loaded but miscomputes")


def phase_probes(on_tpu: bool) -> None:
    """Phase 3: all three kernel-family probes True on the chip (each
    raises on the TPU if its kernel is refused or mismatches)."""
    with Phase("probes") as out:
        from racon_tpu.ops.pallas_nw import pallas_ok, pallas_swar_ok
        from racon_tpu.ops.swar import swar_ok
        out.update(pallas_ok=pallas_ok(), pallas_swar_ok=pallas_swar_ok(),
                   swar_ok=swar_ok())
        if not (out["pallas_ok"] and out["pallas_swar_ok"]
                and out["swar_ok"]):
            out["_failed"] = True
            if on_tpu:
                raise SmokeFailure(f"a probe is False on the chip: {out}")


def simulate_inputs(mbp: float, seed: int, out_dir: str,
                    n_contigs: int) -> dict:
    sys.path.insert(0, os.path.join(REPO, "tools"))
    from simulate import write_inputs
    return write_inputs(mbp, os.path.join(out_dir, "inputs"), seed=seed,
                        n_contigs=n_contigs)


def one_chip(args, on_tpu: bool) -> None:
    phase_native()
    phase_probes(on_tpu)
    with Phase("polish") as out:
        t0 = time.perf_counter()
        inputs = simulate_inputs(args.mbp, args.seed, args.out, 1)
        out["simulate_s"] = round(time.perf_counter() - t0, 3)
        first = run_cli(inputs, args.out, "1")
        n_pairs, n_windows = workload_size(inputs)
        out.update(mbp=args.mbp, seed=args.seed, pairs=n_pairs,
                   windows=n_windows, argv=first["argv"], exit_code=0,
                   wall_s=first["wall_s"],
                   note="smoke run, not a benchmark")
    with Phase("checks") as out:
        import jax
        bad = check_quality(first["fasta"], inputs, out)
        bad += check_report(first["report"], n_pairs, n_windows, out,
                            on_tpu)
        out["compile_cache_dir"] = jax.config.jax_compilation_cache_dir
        out["peak_bytes_in_use"] = peak_device_bytes() or "not reported"
        if bad:
            raise SmokeFailure("; ".join(bad))
    with Phase("second_run") as out:
        second = run_cli(inputs, args.out, "2")
        with open(first["fasta"], "rb") as a, \
                open(second["fasta"], "rb") as b:
            same = a.read() == b.read()
        comp = second["report"]["compiles"]
        out.update(byte_identical=same, new_compiles=comp["count"],
                   first_wall_s=first["wall_s"],
                   second_wall_s=second["wall_s"],
                   note="smoke run, not a benchmark")
        if not same:
            raise SmokeFailure("second run's FASTA differs from the "
                               "first")
        if comp["count"]:
            names = sorted({f"{r['program']} [{r['geometry'] or r['fn']}]"
                            for r in comp["programs"]})
            raise SmokeFailure(
                f"second run compiled {comp['count']} programs "
                f"({comp['wall_s']} s as wall, {comp['eager_programs']} "
                f"of them eager helpers): {names}")


def four_chips(args, on_tpu: bool) -> None:
    """The --chips N leg and its one-chip comparison, nothing else."""
    n = args.chips
    with Phase("multichip") as out:
        inputs = simulate_inputs(args.mbp, args.seed, args.out, n)
        n_pairs, n_windows = workload_size(inputs)
        multi = run_cli(inputs, args.out, f"chips{n}",
                        extra=("--chips", str(n)))
        single = run_cli(inputs, args.out, "chips1",
                         extra=("--chips", "1"))
        out.update(mbp=args.mbp, contigs=n, pairs=n_pairs,
                   windows=n_windows,
                   wall_s={f"chips{n}": multi["wall_s"],
                           "chips1": single["wall_s"]},
                   note="smoke run, not a benchmark")
        with open(multi["fasta"], "rb") as a, \
                open(single["fasta"], "rb") as b:
            out["byte_identical"] = a.read() == b.read()
        bad = [] if out["byte_identical"] else [
            f"--chips {n} and --chips 1 FASTAs differ"]
        bad += check_quality(multi["fasta"], inputs, out)
        rows = multi["report"]["devices"]
        out["devices"] = rows
        worked = [k for k, row in rows.items()
                  if k != "mesh" and row.get("shards", 0) > 0]
        if len(worked) < n:
            bad.append(f"only {sorted(worked)} of {n} chips show work "
                       f"in the run report")
        chip_out: dict = {}
        bad += check_report(multi["report"], n_pairs, n_windows,
                            chip_out, on_tpu)
        out["report_checks"] = chip_out
        peaks = peak_device_bytes()
        out["peak_bytes_in_use"] = peaks or "not reported"
        # the physical evidence that arrays landed on every chip
        idle = [d for d, peak in peaks.items() if peak < (32 << 20)]
        if idle:
            bad.append(f"devices {idle} never held 32 MiB: the work "
                       f"did not reach them")
        if bad:
            raise SmokeFailure("; ".join(bad))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--mbp", type=float, default=None)
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--chips", type=int, default=1)
    ap.add_argument("--out", default=os.path.join(REPO, "chip_smoke_out"))
    args = ap.parse_args(argv)
    rehearsal = args.mbp is not None
    if args.mbp is None:
        args.mbp = 2.0
    device = {"platform": None, "kind": None, "count": 0}
    ok = False
    os.makedirs(KEEP_DIR, exist_ok=True)
    open(os.path.join(KEEP_DIR, "phases.jsonl"), "w").close()
    try:
        os.makedirs(args.out, exist_ok=True)
        sys.path.insert(0, REPO)
        on_tpu = phase_device(device, args.chips, rehearsal)
        if args.chips > 1:
            four_chips(args, on_tpu)
        else:
            one_chip(args, on_tpu)
        ok = on_tpu
    except BaseException as e:  # every failure ends in the last line
        traceback.print_exc()
        sys.stdout.flush()
        if not isinstance(e, Exception):
            emit({"ok": False, "device": device})
            raise
    emit({"ok": ok, "device": device})
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
