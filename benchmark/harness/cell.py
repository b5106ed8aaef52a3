"""One run of one cell: set-up, the measured window of whole polishing
jobs, the checks, and the result line.

A job is what a user types — ``racon <flags> --run-report R reads.fastq
ovl.paf draft.fasta > polished.fasta`` — run in this process through
``racon_tpu.cli.main`` (one process holds the chip; no child touches
JAX on it).

Set-up (``setup_s``, from process start to the window's start): reach
the device, build the native core if the checkout has none, generate
the inputs from the seed in a numpy-only child, run the kernel probes,
and polish the cell's own inputs once — that warm-up job compiles
exactly the programs the window will use, or loads them from the
persistent cache, which stays where the program puts it
(``JAX_COMPILATION_CACHE_DIR`` if set, else ``<checkout>/.xla_cache``).

Window: whole jobs back to back on the same inputs (``run_window``).
With ``--trace 1`` the first of them runs inside the benchmark's own
``jax.profiler`` bracket (host and Python tracers off).

After the window, outside it and outside ``setup_s``: the host-path
reference of this seed (``reference.py``), the distance to the truth,
the checks, the result line.
"""

from __future__ import annotations

import contextlib
import hashlib
import json
import os
import resource
import shutil
import subprocess
import sys
import tempfile
import time

from . import checks, distance, readers, reference, xplane
from .simulate import input_paths
from .spec import BENCH_DIR, ROOT, Cell

EXIT_NO_DEVICE = 3


class NoDevice(Exception):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def note(phase: str, **fields) -> None:
    """Progress goes to stderr: stdout carries only the compared numbers
    and the result line, so a run that dies leaves no line that could be
    read as a result."""
    print(json.dumps({"phase": phase, **fields}), file=sys.stderr,
          flush=True)


@contextlib.contextmanager
def stdout_to(path: str):
    """Redirect file descriptor 1 to ``path`` (the CLI writes its FASTA
    to ``sys.stdout.buffer``; redirecting the descriptor catches every
    writer, native code included). Copied from ``chip_smoke.py``."""
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


def run_job(flags: list, inputs: dict, work_dir: str, tag: str) -> dict:
    """One in-process CLI job: ``{"rc", "wall_s", "fasta", "report"}``
    (``report`` is ``None`` when the job wrote none)."""
    from racon_tpu import cli
    fasta = os.path.join(work_dir, f"polished_{tag}.fasta")
    report = os.path.join(work_dir, f"run_report_{tag}.json")
    argv = [*flags, "--run-report", report,
            inputs["reads"], inputs["overlaps"], inputs["draft"]]
    t0 = time.perf_counter()
    with stdout_to(fasta):
        rc = cli.main(argv)
    wall = time.perf_counter() - t0
    rep = None
    if os.path.exists(report):
        with open(report, "r", encoding="utf-8") as fh:
            rep = json.load(fh)
    return {"rc": rc, "wall_s": wall, "fasta": fasta, "report": rep}


def run_window(seconds: float, run_one, clock=time.perf_counter) -> list:
    """Run whole jobs back to back: ``run_one(i)`` runs the i-th and
    returns it (a dict with ``wall_s``). At least one always runs; a
    further one starts only if the previous job's wall time still fits
    in what is left of ``seconds``."""
    opened = clock()
    jobs = []
    while True:
        job = run_one(len(jobs))
        jobs.append(job)
        if job["wall_s"] > seconds - (clock() - opened):
            return jobs


def file_digest(path: str) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as fh:
        for block in iter(lambda: fh.read(1 << 20), b""):
            h.update(block)
    return h.hexdigest()


def find_device(chips: int, require_tpu: bool) -> dict:
    import jax
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if require_tpu and device["platform"] != "tpu":
        raise NoDevice(f"no TPU: JAX reports platform "
                       f"{device['platform']!r}")
    if len(devs) < chips:
        raise NoDevice(f"the cell asks for {chips} chips, JAX reports "
                       f"{len(devs)}")
    return device


def memory_peak_bytes() -> int:
    """The fullest chip's ``peak_bytes_in_use`` as JAX reports it (0
    where the backend reports none). It leaves out program temporaries
    (PERF.md, Open questions)."""
    import jax
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in jax.local_devices()]
    return max(peaks, default=0)


def build_native() -> None:
    from racon_tpu import native
    native.build()
    if not native.available():
        raise RuntimeError("the native core built but did not load")
    if native.load_ext() is None:
        raise RuntimeError("the native parser extension did not build "
                           "or load")


def run_probes() -> dict:
    from racon_tpu.ops.pallas_nw import pallas_ok, pallas_swar_ok
    from racon_tpu.ops.swar import swar_ok
    return {"pallas_ok": bool(pallas_ok()),
            "pallas_swar_ok": bool(pallas_swar_ok()),
            "swar_ok": bool(swar_ok())}


def generate_inputs(cell: Cell, seed: int, work_dir: str) -> dict:
    """The simulator in a throwaway child that imports numpy only: its
    arrays are not in this process's peak RSS."""
    sim = os.path.join(BENCH_DIR, "harness", "simulate.py")
    out_dir = os.path.join(work_dir, "inputs")
    subprocess.run([sys.executable, sim, cell.traffic_path, str(seed),
                    out_dir], check=True)
    return input_paths(out_dir)


@contextlib.contextmanager
def profiler_bracket(trace_dir: str):
    """The benchmark's own device trace around one job: host and Python
    tracers off and no HLO protos, so the file holds device events
    only."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 0
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def keep_dir(cell: Cell, seed: int, trace: int) -> str:
    """Small artifacts of the run (reports, the result line, the trace's
    reduction) for whoever reads the run afterwards; ``chiprun_out/`` is
    what the chip tool brings back, and git ignores it."""
    path = os.path.join(ROOT, "chiprun_out", "benchmark",
                        f"{cell.name}.{seed}.t{trace}")
    os.makedirs(path, exist_ok=True)
    return path


def run_cell(cell: Cell, seed: int, seconds: float, trace: int, t0: float,
             require_tpu: bool = True, job_runner=run_job) -> dict:
    """Returns the result line's object. ``t0`` is the process's start
    on ``time.perf_counter``. ``job_runner`` stands in for ``run_job``
    in the tests that break the timed path."""
    import racon_tpu.cli  # noqa: F401  (a checkout without the program fails here)
    device = find_device(cell.chips, require_tpu)
    note("device", **device)
    work_dir = tempfile.mkdtemp(prefix="racon-bench-")
    try:
        return _run(cell, seed, seconds, trace, t0, device, work_dir,
                    job_runner)
    finally:
        shutil.rmtree(work_dir, ignore_errors=True)


def _set_up(cell: Cell, seed: int, work_dir: str, job_runner) -> dict:
    """Everything before the window; what the later phases need of it."""
    t = time.perf_counter()
    build_native()
    note("native", seconds=time.perf_counter() - t)
    t = time.perf_counter()
    inputs = generate_inputs(cell, seed, work_dir)
    n_pairs, n_windows = checks.workload_size(
        inputs, cell.config["window_length"])
    draft_bases = sum(len(s) for _, s in distance.read_fasta(inputs["draft"]))
    note("inputs", seconds=time.perf_counter() - t, pairs=n_pairs,
         windows=n_windows, draft_bases=draft_bases)
    t = time.perf_counter()
    probes = run_probes()
    note("probes", seconds=time.perf_counter() - t, **probes)
    warm = job_runner(cell.job_flags(), inputs, work_dir, "warmup")
    note("warmup", rc=warm["rc"], wall_s=warm["wall_s"],
         compiles=(warm["report"] or {}).get("compiles", {}).get("count"))
    if warm["rc"] != 0 or warm["report"] is None:
        raise RuntimeError(f"the warm-up job exited {warm['rc']}")
    warm["digest"] = file_digest(warm["fasta"])
    return {"inputs": inputs, "pairs": n_pairs, "windows": n_windows,
            "draft_bases": draft_bases, "probes": probes, "warm": warm}


def _judge(cell: Cell, seed: int, device: dict, setup: dict, jobs: list,
           work_dir: str) -> dict:
    """The rows that decide ``correct``, the jobs that completed soundly,
    and the distances; runs the host-path reference if this seed has no
    record yet."""
    warm, inputs = setup["warm"], setup["inputs"]
    sizes = (setup["pairs"], setup["windows"])
    rows = [checks.row("platform", device["platform"], "tpu",
                       device["platform"] == "tpu")]
    rows += [checks.row(f"probe.{k}", v, True, v)
             for k, v in setup["probes"].items()]
    rows += checks.report_rows(warm["report"], *sizes, "warmup",
                               in_window=False)
    done = []
    for i, job in enumerate(jobs):
        job_rows = [checks.row(f"w{i}.exit_code", job["rc"], 0,
                               job["rc"] == 0)]
        if job["rc"] == 0 and job["report"] is not None:
            same = job["digest"] == warm["digest"]
            job_rows.append(checks.row(f"w{i}.fasta_differs_from_warmup",
                                       int(not same), 0, same))
            job_rows += checks.report_rows(job["report"], *sizes, f"w{i}",
                                           in_window=True)
        if all(r["ok"] for r in job_rows):
            done.append(job)
        rows += job_rows
    truth_bases = sum(cell.traffic["contig_sizes"])
    try:
        # the timed path's answer: the first window job's FASTA (the
        # others are held byte-identical to the warm-up job's above)
        device_distance = distance.total_distance(
            jobs[0]["fasta"], inputs["truth"])[0]
    except distance.TooFar as e:
        note("distance", error=str(e))
        device_distance = None
    ref = reference.reference_record(cell, seed, inputs, work_dir)
    note("reference", **ref)
    rows += checks.residual_rows(device_distance, ref["distance"],
                                 len(cell.traffic["contig_sizes"]),
                                 truth_bases,
                                 cell.config["residual_ppm_limit"])
    return {"rows": rows, "done": done, "reference": ref,
            "device_distance": device_distance, "truth_bases": truth_bases}


def _reduce_trace(trace_dir: str, chips: int, keep: str) -> dict:
    """The traced job's device trace, reduced (``{}`` if it holds no
    device operation); what was read is kept beside the run."""
    if not os.path.isdir(trace_dir):
        return {}
    path = xplane.find_xplane(trace_dir)
    events = xplane.extract(path)
    reduced = xplane.reduce_events(events, chips)
    with open(os.path.join(keep, "xplane_reduced.json"), "w") as fh:
        json.dump({"bytes": os.path.getsize(path), "reduced": reduced},
                  fh, indent=1)
    with open(os.path.join(keep, "trace_events.json"), "w") as fh:
        json.dump(xplane.short_names(events), fh)
    return reduced


def _run(cell, seed, seconds, trace, t0, device, work_dir, job_runner) -> dict:
    on_tpu = device["platform"] == "tpu"
    setup = _set_up(cell, seed, work_dir, job_runner)
    trace_dir = os.path.join(work_dir, "trace")

    def run_one(i: int) -> dict:
        bracket = (profiler_bracket(trace_dir) if trace and i == 0
                   else contextlib.nullcontext())
        with bracket:
            job = job_runner(cell.job_flags(), setup["inputs"], work_dir,
                             f"w{i}")
        job["digest"] = file_digest(job["fasta"])
        note("job", i=i, rc=job["rc"], wall_s=job["wall_s"])
        return job

    setup_s = time.perf_counter() - t0
    jobs = run_window(seconds, run_one)
    # the process's lifetime peak: the chip machine does not let a
    # process restart its high-water mark (/proc/self/clear_refs), so
    # set-up's compiler memory is in it (PERF.md section 2)
    host_peak_rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
    dev = {**device, "memory_peak_bytes": memory_peak_bytes()}

    verdict = _judge(cell, seed, device, setup, jobs, work_dir)
    rows, done = verdict["rows"], verdict["done"]
    for r in rows:
        print(json.dumps(r), flush=True)

    values = {"setup_s": setup_s, "host_peak_rss_gb": host_peak_rss / 1e9}
    if done:
        values["polish_mbp_per_s"] = (len(done) * setup["draft_bases"] / 1e6
                                      / sum(j["wall_s"] for j in done))
    if verdict["device_distance"] is not None:
        values["residual_ppm"] = (1e6 * verdict["device_distance"]
                                  / verdict["truth_bases"])
    result = {"correct": all(r["ok"] for r in rows), "attempted": len(jobs),
              "failed": len(jobs) - len(done)}
    keep = keep_dir(cell, seed, trace)
    breakdown = None
    if trace:
        reduced = _reduce_trace(trace_dir, cell.chips, keep)
        if on_tpu and not reduced:
            raise RuntimeError("the traced job left no device operation "
                               "in the trace")
        if reduced:
            dev.update(busy_s=reduced["busy_s"], window_s=jobs[0]["wall_s"],
                       busy_s_per_device=reduced["busy_s_per_device"])
            breakdown = {"device_ops": reduced["device_ops"],
                         "idle_gaps": reduced["idle_gaps"]}
        ctx = {"warmup": setup["warm"]["report"],
               "traced": jobs[0]["report"],
               "window": [j["report"] for j in jobs if j["report"]],
               "modules": reduced.get("modules"), "run": values}
        entries = [(e, readers.read_metric(m, ctx)) for e, m in cell.per_layer]
    else:
        entries = [(e, values.get(e["name"])) for e in cell.end_to_end]
    metrics = {e["name"]: {"value": v, "unit": e["unit"]}
               for e, v in entries if v is not None}
    # a CPU rehearsal's readings prove the plumbing; none of them may
    # stand under a metric's name
    result["metrics"] = metrics if on_tpu else {}
    if not on_tpu:
        result["rehearsal_readings"] = metrics
    result["device"] = dev
    if breakdown:
        result["breakdown"] = breakdown
    result["failed_checks"] = [r["check"] for r in rows if not r["ok"]]
    for tag, job in (("warmup", setup["warm"]), ("w0", jobs[0])):
        if job["report"] is not None:
            with open(os.path.join(keep, f"run_report_{tag}.json"), "w") as fh:
                json.dump(job["report"], fh)
    with open(os.path.join(keep, "result.json"), "w") as fh:
        json.dump({"result": result, "checks": rows,
                   "reference": verdict["reference"],
                   "job_walls": [j["wall_s"] for j in jobs],
                   "warmup_wall_s": setup["warm"]["wall_s"]}, fh, indent=1)
    return result
