"""`racon` command-line interface.

Same contract as the reference CLI (``src/main.cpp:22-222``): positional
``<sequences> <overlaps> <target sequences>``, identical option names and
defaults, FASTA written to stdout as ``>{name}{tags}\\n{data}``. The
accelerator knobs mirror the reference's CUDA flags with TPU naming:
``--tpupoa-batches`` (= ``-c/--cudapoa-batches``), ``--tpu-banded-alignment``
(= ``-b``), ``--tpualigner-batches`` (= ``--cudaaligner-batches``).
"""

from __future__ import annotations

import argparse
import os
import sys
import time

from . import __version__, flags, obs
from .core.polisher import PolisherType, create_polisher
from .core.readset import ReadSet
from .io import parsers
from .obs import metrics


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="racon",
        description="consensus module for raw de novo DNA assembly of long "
                    "uncorrected reads (TPU-native implementation)")
    # positionals are optional ONLY because --serve runs without them;
    # every polishing mode (one-shot, sharded, --submit) still requires
    # all three — enforced in main() with the reference's error text
    p.add_argument("sequences", nargs="?", default=None,
                   help="FASTA/FASTQ file (may be gzipped) with "
                        "sequences used for correction")
    p.add_argument("overlaps", nargs="?", default=None,
                   help="MHAP/PAF/SAM file (may be gzipped) with "
                        "overlaps between sequences and targets, or the "
                        "literal 'auto' to compute overlaps in-process "
                        "with the first-party minimizer-chain overlapper "
                        "(no external mapper needed; --overlaps auto "
                        "says the same with a file named here)")
    p.add_argument("target_sequences", nargs="?", default=None,
                   help="FASTA/FASTQ file (may be "
                        "gzipped) with targets to correct")
    p.add_argument("--overlaps", dest="overlaps_mode",
                   choices=("file", "auto"), default="file",
                   help="where the overlaps come from: 'file' (default) "
                        "follows the positional overlaps argument; "
                        "'auto' computes them in-process from the "
                        "sequences and the targets with the first-party "
                        "overlapper, exactly as the positional literal "
                        "'auto' does, and never opens the file named "
                        "there")
    p.add_argument("--rounds", type=int, default=1, metavar="N",
                   help="polish N times in this one process and print "
                        "the last round's FASTA: round k+1 takes round "
                        "k's contigs as its targets (in memory, named as "
                        "a parser would name them from the FASTA) and "
                        "the same reads, parsed and seeded once — byte "
                        "for byte the output of N runs chained through "
                        "files, -u or its absence applied in every "
                        "round. N > 1 needs --overlaps auto (overlaps "
                        "from a file describe one draft) and the "
                        "one-shot path: it refuses -f, --chips and the "
                        "shard runner's options, --submit and --serve")
    p.add_argument("-u", "--include-unpolished", action="store_true",
                   help="output unpolished target sequences")
    p.add_argument("-f", "--fragment-correction", action="store_true",
                   help="perform fragment correction instead of contig "
                        "polishing (overlaps file should contain dual/self "
                        "overlaps!)")
    p.add_argument("-w", "--window-length", type=int, default=500,
                   help="size of window on which POA is performed")
    p.add_argument("-q", "--quality-threshold", type=float, default=10.0,
                   help="threshold for average base quality of windows used "
                        "in POA")
    p.add_argument("-e", "--error-threshold", type=float, default=0.3,
                   help="maximum allowed error rate used for filtering "
                        "overlaps")
    p.add_argument("--no-trimming", action="store_true",
                   help="disables consensus trimming at window ends")
    from .ops.poa import DEFAULT_GAP, DEFAULT_MATCH, DEFAULT_MISMATCH
    p.add_argument("-m", "--match", type=int, default=DEFAULT_MATCH,
                   help="score for matching bases")
    p.add_argument("-x", "--mismatch", type=int, default=DEFAULT_MISMATCH,
                   help="score for mismatching bases")
    p.add_argument("-g", "--gap", type=int, default=DEFAULT_GAP,
                   help="gap penalty (must be negative)")
    p.add_argument("-t", "--threads", type=int, default=1,
                   help="number of threads")
    p.add_argument("--version", action="version", version=__version__)
    # TPU acceleration knobs (reference analog: -c/-b/--cudaaligner-batches)
    p.add_argument("-c", "--tpupoa-batches", type=int, nargs="?", const=1,
                   default=0,
                   help="number of batches for TPU accelerated polishing")
    p.add_argument("-b", "--tpu-banded-alignment", action="store_true",
                   help="use banding approximation for alignment on the TPU")
    p.add_argument("--tpualigner-batches", type=int, default=0,
                   help="number of batches for TPU accelerated alignment")
    p.add_argument("--chips", type=int, default=0, metavar="N",
                   help="drive N local accelerator chips from this one "
                        "process: the streaming shard runner spawns one "
                        "in-process chip worker per device, each with "
                        "its own pinned engines, all draining one shard "
                        "manifest through the lease protocol (implies "
                        "the shard runner; default: every local device "
                        "when a device backend is in use on multi-chip "
                        "hardware, 1 otherwise; more chips than are "
                        "present is an error; RACON_TPU_CHIPS is the "
                        "env equivalent)")
    p.add_argument("--compile-cache", metavar="DIR", default=None,
                   help="persistent XLA compilation cache directory: "
                        "kernels compiled once are reloaded by every "
                        "later run/process, so warm starts skip the "
                        "tens-of-seconds cold compile (default "
                        "<checkout>/.xla_cache; JAX's own "
                        "JAX_COMPILATION_CACHE_DIR, when set, places "
                        "the cache instead and this option yields to "
                        "it; RACON_TPU_NO_COMPILE_CACHE=1 disables)")
    p.add_argument("--profile", metavar="DIR", default=None,
                   help="write a jax.profiler trace of the polishing run "
                        "to DIR (view with TensorBoard / xprof; the TPU "
                        "analog of the reference's nvprof hooks)")
    # observability (racon_tpu.obs): pipeline span traces + run reports
    p.add_argument("--trace", metavar="FILE", default=None,
                   help="write a Chrome trace-event JSON of the run's "
                        "pipeline spans (parse/align/decode/build/"
                        "consensus/stitch, queue waits, per-shard "
                        "tracks) to FILE — load it in Perfetto; also "
                        "emits run_report.json next to FILE unless "
                        "--run-report names one (RACON_TPU_TRACE is the "
                        "env equivalent; output bytes are identical "
                        "with tracing on)")
    p.add_argument("--run-report", metavar="FILE", default=None,
                   help="write the schema-versioned machine-readable "
                        "run report (per-phase wall clock, dispatch-vs-"
                        "fetch split, pack occupancy, retrace/queue "
                        "metrics, per-shard rows) to FILE "
                        "(RACON_TPU_RUN_REPORT is the env equivalent)")
    # streaming shard runner (racon_tpu.exec): bounded-memory runs with
    # checkpoint/resume; output stays byte-identical to a single-shot run
    p.add_argument("--shards", type=int, default=0, metavar="N",
                   help="polish through the streaming shard runner with "
                        "N memory-bounded shards of target contigs")
    p.add_argument("--max-ram", default=None, metavar="SIZE",
                   help="shard the run to keep peak RSS under SIZE "
                        "(plain number = MB; K/M/G/T suffixes accepted); "
                        "implies the streaming shard runner")
    p.add_argument("--resume", action="store_true",
                   help="resume an interrupted shard run: completed "
                        "shards are skipped via the checkpoint manifest, "
                        "only the interrupted one re-runs")
    p.add_argument("--shard-dir", default=None, metavar="DIR",
                   help="work directory for shard inputs, part files and "
                        "the checkpoint manifest (default: a directory "
                        "derived from the input paths and parameters, "
                        "removed after a fully successful run; an "
                        "explicit DIR is kept)")
    p.add_argument("--workers", type=int, default=1, metavar="N",
                   help="drain the shard manifest with N cooperating "
                        "worker processes (this one plus N-1 spawned "
                        "secondaries): workers claim shards via O_EXCL "
                        "lease files with heartbeats, a dead worker's "
                        "lease expires and its shard is reclaimed, and "
                        "output stays byte-identical to a single-shot "
                        "run; independently launched racon processes "
                        "sharing one --shard-dir cooperate the same "
                        "way (implies the streaming shard runner; "
                        "with --serve it instead sizes the resident "
                        "worker-slot pool)")
    # resident polishing service (racon_tpu.serve): one warm engine
    # pool amortizes the cold XLA compile across every job it ever runs
    p.add_argument("--serve", metavar="SOCK", default=None,
                   help="run as a resident polishing service on the "
                        "unix socket SOCK (no positional inputs): a "
                        "warm per-chip engine pool executes submitted "
                        "jobs through the normal pipeline, so a job's "
                        "latency is compute, not the one-shot cold "
                        "compile; -m/-x/-g/-b fix the resident engine "
                        "profile, --serve-budget bounds the in-flight "
                        "job footprint (see README 'Polishing as a "
                        "service')")
    p.add_argument("--submit", metavar="SOCK", default=None,
                   help="submit this invocation as a job to the "
                        "resident service listening on SOCK and stream "
                        "the polished FASTA to stdout — byte-identical "
                        "to running the same command one-shot")
    p.add_argument("--serve-budget", metavar="SIZE", default=None,
                   help="admission budget for --serve: the summed "
                        "resident-footprint estimate of running jobs "
                        "stays under SIZE (plain number = MB; K/M/G/T "
                        "suffixes; default RACON_TPU_SERVE_BUDGET)")
    p.add_argument("--serve-dir", metavar="DIR", default=None,
                   help="durable directory for --serve (crash-safe "
                        "serving): every job lifecycle transition is "
                        "journaled (append-only, fsync'd) and results "
                        "spool to CRC-verified files, so a server "
                        "killed mid-batch restarts from the same DIR "
                        "with no lost or duplicated work — completed "
                        "jobs serve from the spool, queued/running "
                        "jobs re-run down the crash ladder "
                        "(RACON_TPU_SERVE_DIR is the env equivalent; "
                        "unset = in-memory only)")
    # fleet serving (racon_tpu.fleet): a TCP gateway places jobs
    # across registered --serve hosts under per-job leases
    p.add_argument("--gateway", metavar="HOST:PORT", default=None,
                   help="run the fleet gateway: a TCP front door "
                        "speaking the serve protocol verbatim that "
                        "journals every accepted job durably (same "
                        "machinery as --serve-dir) before "
                        "acknowledging, schedules tenants "
                        "weighted-fair (RACON_TPU_FLEET_TENANTS), and "
                        "places jobs across the hosts registered in "
                        "--fleet-dir under per-job leases — a host "
                        "dead past RACON_TPU_FLEET_HOST_TTL_S has its "
                        "jobs re-placed on survivors (see README "
                        "'Fleet serving')")
    p.add_argument("--fleet-dir", metavar="DIR", default=None,
                   help="fleet membership + durable gateway state "
                        "directory: with --serve the host registers a "
                        "heartbeat beacon under DIR/hosts/ so the "
                        "gateway can place work on it; with --gateway "
                        "it holds the fleet journal, result spool, "
                        "and per-job leases")
    p.add_argument("--tenant", metavar="NAME", default=None,
                   help="tenant to submit under (--submit only): the "
                        "gateway schedules tenants weighted-fair and "
                        "enforces per-tenant cost budgets "
                        "(RACON_TPU_FLEET_TENANTS); unset = 'default'")
    p.add_argument("--priority", metavar="N", type=int, default=None,
                   help="job priority for --submit (higher first "
                        "within a tenant; default 0): at the gateway "
                        "a high-priority job may preempt a running "
                        "lower-priority one, draining it back to the "
                        "queue at a ladder boundary — never killing "
                        "it mid-window")
    # internal: a spawned cooperating worker — adopts the primary's
    # manifest, claims/polishes shards, emits no merged FASTA
    p.add_argument("--exec-secondary", action="store_true",
                   help=argparse.SUPPRESS)
    return p


def _preprocess_argv(argv):
    """Make ``-c`` consume a following token only when it is an integer,
    matching the reference's getopt optional-argument handling
    (``src/main.cpp:111-123``) without argparse's greedy ``nargs='?'``."""
    out = []
    i = 0
    while i < len(argv):
        tok = argv[i]
        if tok in ("-c", "--tpupoa-batches"):
            nxt = argv[i + 1] if i + 1 < len(argv) else None
            if nxt is not None and not nxt.startswith("-"):
                try:
                    int(nxt)
                except ValueError:
                    out.append(f"--tpupoa-batches=1")
                    i += 1
                    continue
        out.append(tok)
        i += 1
    return out


def _obs_paths(args):
    """(trace_path, report_path) from the CLI flags merged with their
    env-flag equivalents; ``--trace`` without ``--run-report`` defaults
    the report next to the trace file (one switch yields the whole
    observability artifact set)."""
    trace_path = args.trace or flags.get_str("RACON_TPU_TRACE") or None
    report_path = (args.run_report
                   or flags.get_str("RACON_TPU_RUN_REPORT") or None)
    if trace_path and report_path is None:
        report_path = os.path.join(
            os.path.dirname(os.path.abspath(trace_path)),
            "run_report.json")
    return trace_path, report_path


def _finish_obs(trace_path, report_path, kind, argv, t_start, t0,
                phases=None, shards=None) -> None:
    """Export the requested observability artifacts (also called on the
    error paths: a trace of a crashed run is exactly the data needed to
    debug it). The trace exports FIRST so its ring-overflow gauge
    (``trace.dropped_events``) lands in the report's snapshot."""
    from .obs import report as obs_report
    if trace_path:
        obs.trace.export(trace_path)
    if report_path:
        rep = obs_report.build_report(
            kind, argv=argv, started_unix=t_start,
            wall_s=time.perf_counter() - t0, phases=phases,
            shards=shards)
        obs_report.write_report(report_path, rep)


def _secondary_argv(argv, n: int):
    """Child argv for the N-1 spawned cooperating workers: the original
    command line minus the ``--workers`` spawn directive (a child must
    not spawn grandchildren) plus the internal secondary marker."""
    child = []
    skip = False
    for tok in argv:
        if skip:
            skip = False
            continue
        if tok == "--workers":
            skip = True
            continue
        if tok.startswith("--workers="):
            continue
        child.append(tok)
    child.append("--exec-secondary")
    return [child] * n


def _announce_device(args) -> None:
    """One stderr line when a device backend is selected: where the
    kernels will really run (platform, device kind, device count) and
    which kernel family (Pallas on = Mosaic kernels, off = their XLA
    twins). ``pallas_ok()`` runs the TPU's bit-exactness probe here, up
    front, so a broken kernel fails the run before any work starts."""
    if args.tpualigner_batches <= 0 and args.tpupoa_batches <= 0:
        return
    import jax

    from .ops.pallas_nw import pallas_ok
    devs = jax.devices()
    print(f"[racon_tpu] device backend: platform={devs[0].platform} "
          f"kind={devs[0].device_kind!r} devices={len(devs)} "
          f"pallas={'on' if pallas_ok() else 'off'}", file=sys.stderr)


def _refuse_rounds(parser, args) -> None:
    """``--rounds N > 1`` is a loop over the one-shot path with the
    overlaps computed per round; every option that leaves that path is
    refused by name (none silently ignored)."""
    if args.rounds < 1:
        parser.error(f"--rounds must be >= 1 (got {args.rounds})")
    if args.rounds == 1:
        return
    sharded = [opt for opt, on in (
        ("--chips", args.chips), ("--shards", args.shards),
        ("--max-ram", args.max_ram), ("--resume", args.resume),
        ("--shard-dir", args.shard_dir), ("--workers", args.workers > 1),
        ("--exec-secondary", args.exec_secondary),
        ("RACON_TPU_CHIPS", flags.get_int("RACON_TPU_CHIPS") > 0)) if on]
    for bad, why in (
            (args.serve and "--serve", "a resident server runs jobs, not "
             "rounds: submit one-round jobs"),
            (args.gateway and "--gateway", "a gateway runs no job itself"),
            (args.submit and "--submit", "the service takes one-round "
             "jobs"),
            (args.fragment_correction and "-f", "fragment correction "
             "has no draft to hand to a next round"),
            (", ".join(sharded), "the shard runner polishes one round"),
            (args.overlaps
             and parsers.overlaps_mode(args.overlaps) != "auto"
             and "overlaps from a file", "they describe one draft: a "
             "later round needs --overlaps auto")):
        if bad:
            parser.error(f"--rounds {args.rounds} cannot be combined with "
                         f"{bad} ({why})")


def _sum_phases(phases: dict, timings: dict) -> None:
    """A job's ``phases`` are its rounds' timings summed, as its
    counters and span timers are."""
    for key, value in timings.items():
        phases[key] = phases.get(key, 0.0) + value


def _compiles_so_far() -> int:
    return metrics.counter((metrics.get_scope() or "")
                           + "compile.backend_total")


def _round_begin() -> tuple:
    return time.perf_counter_ns(), _compiles_so_far()


def _round_end(k: int, mark: tuple, polisher) -> None:
    """Round ``k`` is over: its row of the report's ``rounds`` section
    (gauges ``rounds.<column>.<k>``) and its span. A follow-up round
    begins where its targets and reads were indexed; what lies between
    the round before's last stitch and that instant is ``round.handoff``
    (both spans back-dated, so neither is ever the open span a compile
    or a submission is charged to)."""
    t0_ns, compiles0 = mark
    t1_ns = time.perf_counter_ns()
    begun_ns = t0_ns
    if polisher.handoff_end_ns is not None:
        begun_ns = polisher.handoff_end_ns
        obs.trace.record("round.handoff", t0_ns, begun_ns)
        metrics.set_gauge(f"rounds.handoff_s.{k}", (begun_ns - t0_ns) * 1e-9)
        metrics.inc("rounds.followups")
    obs.trace.record("round", begun_ns, t1_ns)
    metrics.inc("rounds.completed")
    metrics.set_gauge(f"rounds.wall_s.{k}", (t1_ns - begun_ns) * 1e-9)
    metrics.set_gauge(f"rounds.compiles.{k}",
                      _compiles_so_far() - compiles0)
    metrics.set_gauge(f"rounds.overlaps_kept.{k}", polisher.overlaps_kept)


def _run_sharded(args, argv, trace_path, report_path, t_start, t0) -> int:
    """Route through the streaming shard runner (racon_tpu.exec)."""
    import subprocess

    from .exec import ShardRunner, parse_ram

    workers = max(1, args.workers)
    secondary = bool(args.exec_secondary)
    children = []
    try:
        runner = ShardRunner(
            args.sequences, args.overlaps, args.target_sequences,
            type_=PolisherType.F if args.fragment_correction
            else PolisherType.C,
            window_length=args.window_length,
            quality_threshold=args.quality_threshold,
            error_threshold=args.error_threshold,
            trim=not args.no_trimming,
            match=args.match, mismatch=args.mismatch, gap=args.gap,
            num_threads=args.threads,
            aligner_backend="tpu" if args.tpualigner_batches > 0 else "auto",
            consensus_backend="tpu" if args.tpupoa_batches > 0 else "auto",
            aligner_batches=max(1, args.tpualigner_batches),
            consensus_batches=max(1, args.tpupoa_batches),
            banded=args.tpu_banded_alignment,
            include_unpolished=args.include_unpolished,
            n_shards=args.shards,
            max_ram_bytes=parse_ram(args.max_ram) if args.max_ram else 0,
            resume=args.resume, work_dir=args.shard_dir,
            secondary=secondary, defer_cleanup=workers > 1,
            chips=args.chips)
        if workers > 1 and not secondary:
            # the secondaries poll for the manifest this process is
            # about to publish, then start claiming shards; their
            # merged-FASTA stream stays empty by construction
            for child_argv in _secondary_argv(argv, workers - 1):
                children.append(subprocess.Popen(
                    [sys.executable, "-m", "racon_tpu"] + child_argv,
                    stdout=subprocess.DEVNULL))
        if secondary:
            with open(os.devnull, "wb") as sink:
                runner.run(sink, begun=True)
        else:
            runner.run(sys.stdout.buffer, begun=True)
    except (ValueError, RuntimeError, OSError) as e:
        print(f"[racon::] error: {e}", file=sys.stderr)
        for proc in children:
            proc.terminate()
        _finish_obs(trace_path, report_path, "exec", argv, t_start, t0)
        return 1
    for proc in children:
        # all shards were terminal before our run() returned, so the
        # secondaries are draining their last poll; reap them before
        # the work-dir cleanup pulls the manifest out from under them.
        # A wedged secondary must not fail an already-successful run
        # (the merged FASTA is on stdout): kill it and move on.
        try:
            proc.wait(timeout=120)
        except subprocess.TimeoutExpired:
            print("[racon::] warning: a secondary worker did not exit "
                  "after the run completed — killing it",
                  file=sys.stderr)
            proc.kill()
            proc.wait()
    if workers > 1 and not secondary:
        runner.cleanup_work_dir()
    _finish_obs(trace_path, report_path, "exec", argv, t_start, t0,
                shards=runner.summary.get("shards"))
    return 0


def main(argv=None) -> int:
    if argv is None:
        argv = sys.argv[1:]
    parser = build_parser()
    args = parser.parse_args(_preprocess_argv(list(argv)))
    if args.chips < 0:
        parser.error(f"--chips must be >= 0 (got {args.chips}); "
                     f"0 means automatic")
    if args.overlaps_mode == "auto" and args.overlaps:
        # one way to decide the mode: from here on every path (one-shot,
        # shard runner, planner, --submit) sees the positional sentinel
        args.overlaps = parsers.AUTO_OVERLAPS
    _refuse_rounds(parser, args)

    trace_path, report_path = _obs_paths(args)
    obs.begin(trace_path, report_path)
    t_start = time.time()
    t0 = time.perf_counter()

    if args.compile_cache:
        # re-point the persistent XLA cache before anything compiles
        # (the import-time default already armed it; an explicit DIR
        # wins over the in-checkout default, and yields to
        # JAX_COMPILATION_CACHE_DIR)
        from . import ops
        ops.configure_compile_cache(args.compile_cache)

    if args.serve_dir and not args.serve:
        parser.error("--serve-dir only makes sense with --serve "
                     "(the shard runner's checkpoint directory is "
                     "--shard-dir)")
    if args.fleet_dir and not (args.serve or args.gateway):
        parser.error("--fleet-dir only makes sense with --serve (to "
                     "register the host) or --gateway (to hold the "
                     "fleet journal and host registry)")
    if args.gateway:
        if args.serve or args.submit:
            parser.error("--gateway is mutually exclusive with "
                         "--serve and --submit")
        if args.sequences or args.overlaps or args.target_sequences:
            parser.error("--gateway takes no positional inputs (jobs "
                         "submit theirs over the socket)")
        if not args.fleet_dir:
            parser.error("--gateway requires --fleet-dir (the fleet "
                         "journal, host registry, and leases live "
                         "there)")
        from .fleet.gateway import Gateway
        try:
            gateway = Gateway(args.gateway, args.fleet_dir)
            return gateway.serve_forever()
        except KeyboardInterrupt:
            gateway.shutdown()
            return 0
        except (ValueError, RuntimeError, OSError) as e:
            print(f"[racon_tpu::fleet] error: {e}", file=sys.stderr)
            return 1
    if args.serve:
        if args.sequences or args.overlaps or args.target_sequences:
            parser.error("--serve takes no positional inputs (jobs "
                         "submit theirs over the socket)")
        if args.submit:
            parser.error("--serve and --submit are mutually exclusive")
        from .exec import parse_ram
        from .serve.service import PolishServer
        _announce_device(args)
        server = PolishServer(
            args.serve,
            match=args.match, mismatch=args.mismatch, gap=args.gap,
            banded=args.tpu_banded_alignment,
            num_threads=args.threads,
            aligner_backend="tpu" if args.tpualigner_batches > 0
            else "auto",
            consensus_backend="tpu" if args.tpupoa_batches > 0
            else "auto",
            aligner_batches=max(1, args.tpualigner_batches),
            consensus_batches=max(1, args.tpupoa_batches),
            chips=args.chips,
            # --workers N in serve mode = N worker slots on the pool
            # (the chaos soak's "2-slot server"; chips still win when
            # more chips than workers are present)
            workers=args.workers if args.workers > 1 else 0,
            budget_bytes=parse_ram(args.serve_budget)
            if args.serve_budget else 0,
            serve_dir=args.serve_dir,
            fleet_dir=args.fleet_dir)
        try:
            return server.serve_forever()
        except KeyboardInterrupt:
            server.shutdown()
            return 0
        except (ValueError, RuntimeError, OSError) as e:
            print(f"[racon_tpu::serve] error: {e}", file=sys.stderr)
            return 1

    # every polishing mode (one-shot, sharded, --submit) needs the
    # input triple — only --serve runs without it
    if not (args.sequences and args.overlaps and args.target_sequences):
        parser.error("the following arguments are required: sequences, "
                     "overlaps, target_sequences")

    if args.submit:
        from .serve import client as serve_client
        try:
            return serve_client.submit_and_stream(
                args.submit, serve_client.spec_from_args(args),
                sys.stdout.buffer, report_path=report_path)
        except (ValueError, RuntimeError, OSError) as e:
            print(f"[racon_tpu::serve] error: {e}", file=sys.stderr)
            return 1

    _announce_device(args)

    # RACON_TPU_CHIPS is documented as the --chips env equivalent, so
    # it must also route the run into the shard runner (where the chip
    # scheduler lives) — not just tune it once something else did
    if args.shards or args.max_ram or args.resume or args.shard_dir \
            or args.workers > 1 or args.exec_secondary or args.chips \
            or flags.get_int("RACON_TPU_CHIPS") > 0:
        return _run_sharded(args, list(argv), trace_path, report_path,
                            t_start, t0)

    import contextlib
    if args.profile:
        import jax
        trace = jax.profiler.trace(args.profile)
    else:
        trace = contextlib.nullcontext()
    # the rounds of the job, one polisher each. One round is the
    # one-shot path itself; N > 1 share the reads (parsed and seeded
    # once: core/readset.py) and the engines, and round k's contigs
    # are round k+1's targets
    reads = ReadSet(args.sequences) if args.rounds > 1 else None
    phases: dict = {}
    polisher = polished = None
    with trace:
        for k in range(1, args.rounds + 1):
            mark = _round_begin()
            try:
                polisher = create_polisher(
                    args.sequences, args.overlaps, args.target_sequences,
                    PolisherType.F if args.fragment_correction
                    else PolisherType.C,
                    window_length=args.window_length,
                    quality_threshold=args.quality_threshold,
                    error_threshold=args.error_threshold,
                    trim=not args.no_trimming,
                    match=args.match, mismatch=args.mismatch, gap=args.gap,
                    num_threads=args.threads,
                    aligner_backend="tpu" if args.tpualigner_batches > 0
                    else "auto",
                    consensus_backend="tpu" if args.tpupoa_batches > 0
                    else "auto",
                    aligner_batches=max(1, args.tpualigner_batches),
                    consensus_batches=max(1, args.tpupoa_batches),
                    banded=args.tpu_banded_alignment,
                    **({} if reads is None else dict(
                        reads=reads, targets=polished,
                        final=k == args.rounds,
                        aligner=polisher and polisher.aligner,
                        consensus=polisher and polisher.consensus)))
            except (ValueError, ImportError) as e:
                print(f"[racon::createPolisher] error: {e}",
                      file=sys.stderr)
                _finish_obs(trace_path, report_path, "cli", list(argv),
                            t_start, t0, phases=phases)
                return 1

            try:
                # fused surface: window build and consensus pipelined
                # through a bounded queue (sequential fallback at -t 1)
                # — output is byte-identical to initialize() + polish()
                polished = polisher.run(not args.include_unpolished)
            except (ValueError, RuntimeError, OSError) as e:
                print(f"[racon::] error: {e}", file=sys.stderr)
                _sum_phases(phases, polisher.timings)
                _finish_obs(trace_path, report_path, "cli", list(argv),
                            t_start, t0, phases=phases)
                return 1
            _sum_phases(phases, polisher.timings)
            _round_end(k, mark, polisher)

    out = sys.stdout.buffer
    for seq in polished:
        out.write(b">" + seq.name + b"\n" + seq.data + b"\n")
    out.flush()
    _finish_obs(trace_path, report_path, "cli", list(argv), t_start, t0,
                phases=phases)
    return 0


if __name__ == "__main__":
    sys.exit(main())
