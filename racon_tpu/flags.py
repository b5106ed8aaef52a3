"""Central registry of every ``RACON_TPU_*`` environment flag.

This module is the **single sanctioned reader** of ``RACON_TPU_*``
environment variables: every flag the package (and its tests)
consults is declared here with a type, default and one-line doc, and all
call sites go through :func:`raw` / :func:`get_bool` / :func:`get_int` /
:func:`get_float` / :func:`get_str`.  The ``graftlint`` rule
``env-flag-registry`` (``tools/analysis``) enforces the monopoly: a
direct ``os.environ`` read of a ``RACON_TPU_*`` key anywhere else in the
repo is a lint error, and reading an undeclared name through this module
raises at runtime.  The README "Environment flags" table is generated
from this registry (``python -m racon_tpu.flags``), so docs cannot drift
from the code.

Deliberately dependency-free (no jax, no numpy): ``tests/conftest.py``
consults flags before the JAX backend may initialize.

Boolean semantics are uniform: unset/empty/``0``/``false``/``no``/``off``
mean **false**, anything else means **true**.  (This makes
``RACON_TPU_NO_COMPILE_CACHE=0`` a no-op, where the pre-registry ad-hoc
read treated any set value as true — the sane reading wins.)
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Dict, Iterable

_FALSE = ("", "0", "false", "no", "off")


@dataclass(frozen=True)
class Flag:
    """One declared environment flag: its default (as the env string the
    getters parse), a kind tag for the README table, and a one-line doc."""

    name: str
    default: str
    kind: str  # "bool" | "int" | "float" | "str" | "path"
    help: str


def _declare(flags: Iterable[Flag]) -> Dict[str, Flag]:
    reg: Dict[str, Flag] = {}
    for f in flags:
        if not f.name.startswith("RACON_TPU_"):
            raise ValueError(f"flag {f.name!r} outside the RACON_TPU_ "
                             f"namespace")
        if not f.help:
            raise ValueError(f"flag {f.name!r} declared without a doc line")
        if f.name in reg:
            raise ValueError(f"flag {f.name!r} declared twice")
        reg[f.name] = f
    return reg


REGISTRY: Dict[str, Flag] = _declare([
    # ------------------------------------------------------------- kernels
    Flag("RACON_TPU_SWAR", "1", "bool",
         "Packed SWAR kernels (int16x2 score lanes, 2-bit bases); set 0 "
         "to force the int32 path for A/B measurement."),
    # ------------------------------------------------------- compile cache
    Flag("RACON_TPU_NO_COMPILE_CACHE", "0", "bool",
         "Set to disable the persistent XLA compilation cache (its "
         "directory is JAX's own JAX_COMPILATION_CACHE_DIR, else "
         "--compile-cache, else <checkout>/.xla_cache)."),
    # ------------------------------------------------------- observability
    Flag("RACON_TPU_TRACE", "", "path",
         "Write a Chrome trace-event JSON of the run's pipeline spans "
         "(parse/align/decode/build/consensus/stitch, queue waits, "
         "per-shard tracks) to this file — load it in Perfetto or "
         "chrome://tracing; equivalent to the CLI --trace flag."),
    Flag("RACON_TPU_RUN_REPORT", "", "path",
         "Write the schema-versioned machine-readable run_report.json "
         "(per-phase wall clock, dispatch-vs-fetch split, pack "
         "occupancy, retrace and queue-stall metrics, the device_time "
         "occupancy ledger, per-shard rows) "
         "to this file; equivalent to the CLI --run-report flag."),
    # ----------------------------------------------------------- sanitizer
    Flag("RACON_TPU_SANITIZE", "0", "bool",
         "Runtime sanitizer: int32 shadow execution of sampled SWAR "
         "chunks, kernel-output canaries, a jit-retrace budget per "
         "pipeline phase, and the pipelined-polish queue watchdog."),
    Flag("RACON_TPU_SANITIZE_SAMPLE", "8", "int",
         "Shadow-execute every Nth SWAR chunk under the sanitizer "
         "(1 = every chunk; the first chunk of a run is always checked)."),
    Flag("RACON_TPU_SANITIZE_WATCHDOG_S", "120", "float",
         "Pipelined-polish queue watchdog timeout in seconds: with the "
         "sanitizer on, a producer/consumer stall longer than this dumps "
         "every thread's stack to stderr."),
    Flag("RACON_TPU_SANITIZE_RETRACE_BUDGET", "64", "int",
         "Maximum new jit compilations the sanitizer tolerates per "
         "pipeline phase before flagging a silent-recompile regression."),
    Flag("RACON_TPU_NATIVE_SANITIZE", "0", "bool",
         "Build the native C++ core with ASan/UBSan "
         "(-fsanitize=address,undefined) into a separate shared object; "
         "loading it requires the ASan runtime preloaded (see "
         "ci/checks/native_sanitize.sh)."),
    # -------------------------------------------------- streaming shard runs
    Flag("RACON_TPU_HEARTBEAT_S", "30", "float",
         "Streaming shard runner heartbeat interval in seconds (0 "
         "disables the periodic line; per-shard completion lines always "
         "print)."),
    Flag("RACON_TPU_EXEC_FAULT_SHARD", "", "str",
         "Test hook: inject a device-engine fault before polishing the "
         "named shard ('2' faults shard 2's first attempt, exercising "
         "the CPU retry; '2*' faults every attempt, exercising "
         "quarantine)."),
    Flag("RACON_TPU_EXEC_SLEEP_S", "0", "float",
         "Test hook: sleep this many seconds before polishing every "
         "shard after the first (lets kill/resume tests land a SIGKILL "
         "mid-run deterministically)."),
    Flag("RACON_TPU_CHIPS", "", "int",
         "In-process chip workers for the streaming shard runner "
         "(equivalent to the CLI --chips flag): each local device gets "
         "its own pinned engine pair draining manifest shards through "
         "the lease protocol. Unset/0 = automatic (every local device "
         "when a device backend is requested); 1 forces the legacy "
         "single-chip path."),
    # ------------------------------------------------- fault tolerance
    Flag("RACON_TPU_FAULTS", "", "str",
         "Seeded site-addressed fault injection: "
         "'site:kind[@N][*][%P],...' — sites consensus.dispatch / "
         "align.dispatch / align.fetch / part.write / manifest.write / "
         "worker.kill / "
         "exec.polish / serve.polish / serve.journal / serve.socket / "
         "serve.slot / server.kill; kinds io, enospc, oom, err, "
         "stall, kill; @N arms on the Nth hit, '*' keeps firing, %P "
         "fires with seeded probability P (see racon_tpu/faults.py)."),
    Flag("RACON_TPU_FAULTS_SEED", "0", "int",
         "Seed for probabilistic (%P) fault-injection draws, so a "
         "chaos run replays deterministically."),
    Flag("RACON_TPU_WORKER", "", "str",
         "Worker identity recorded in shard leases, manifest entries "
         "and heartbeat lines (default: hostname:pid)."),
    Flag("RACON_TPU_EXEC_LEASE_TTL_S", "30", "float",
         "Shard lease time-to-live in seconds: a worker that stops "
         "refreshing its lease mtime for longer than this is presumed "
         "dead and another worker may break the lease and reclaim the "
         "shard."),
    Flag("RACON_TPU_EXEC_POLL_S", "1", "float",
         "Idle wait between shard-claim passes when every remaining "
         "shard is leased by another worker."),
    Flag("RACON_TPU_EXEC_RETRIES", "3", "int",
         "Degradation-ladder budget for transient-io faults: retries "
         "with exponential backoff on the same engine tier before the "
         "shard moves down the ladder."),
    Flag("RACON_TPU_EXEC_BACKOFF_S", "0.5", "float",
         "Base of the transient-fault exponential backoff (doubled "
         "per retry, deterministic jitter added; see the ladder in "
         "racon_tpu/exec/runner.py)."),
    # --------------------------------------------- resident polishing service
    Flag("RACON_TPU_SERVE_WARM_SHAPES", "500:131072:8192:8", "str",
         "Expected-shape profile the resident service (racon --serve) "
         "warm-compiles at startup, so job #1 is already warm: "
         "comma-separated 'window_length:pairs:windows[:contigs]' "
         "entries fed to the consensus engine's warmup_async on every "
         "pool worker (empty disables the startup warm-up; jobs still "
         "warm their own geometry on admission)."),
    Flag("RACON_TPU_SERVE_BUDGET", "8G", "str",
         "Resident service admission budget: the summed resident-"
         "footprint estimate (the exec planner's cost model) of "
         "running jobs is kept under this size, and a single job "
         "estimated over it is rejected with the reason instead of "
         "OOMing the server (plain number = MB; K/M/G/T suffixes; "
         "the CLI --serve-budget flag overrides)."),
    Flag("RACON_TPU_SERVE_QUEUE", "64", "int",
         "Maximum queued (admitted, not yet running) jobs the "
         "resident service holds before rejecting submissions with "
         "'queue full'."),
    Flag("RACON_TPU_SERVE_DIR", "", "path",
         "Durable serve directory (equivalent to the CLI --serve-dir "
         "flag): the append-only fsync'd job journal and the "
         "CRC-verified result spool live here, so a server killed "
         "mid-batch restarts with no lost or duplicated work — "
         "completed jobs serve from the spool, queued/running jobs "
         "re-admit down the crash ladder (empty = in-memory only)."),
    Flag("RACON_TPU_SERVE_DRAIN_S", "600", "float",
         "Bound on the graceful-drain wait (SIGTERM or the protocol's "
         "shutdown mode=drain): the server stops admission and "
         "finishes queued + in-flight jobs, but exits anyway after "
         "this many seconds (0 = wait forever)."),
    Flag("RACON_TPU_CLIENT_RETRIES", "5", "int",
         "Bounded retry budget for ServiceClient / racon --submit: "
         "failed connects and connections lost mid-job reconnect this "
         "many times with exponential backoff, resubmitting under the "
         "same idempotency key so a server restart never duplicates "
         "compute."),
    Flag("RACON_TPU_CLIENT_BACKOFF_S", "0.25", "float",
         "Base of the client reconnect exponential backoff (doubled "
         "per attempt, deterministic CRC32 jitter added — the shared "
         "faults.backoff_s formula the exec ladder uses)."),
    # ------------------------------------------------------- fleet serving
    Flag("RACON_TPU_FLEET_TENANTS", "", "str",
         "Fleet tenant configuration for the gateway (racon --gateway): "
         "comma-separated 'name:weight:budget' entries — weight is the "
         "stride-scheduling share (higher drains faster), budget bounds "
         "the tenant's summed in-flight cost estimate (plain number = "
         "MB; K/M/G/T suffixes; 0 or empty = unbounded).  Unknown "
         "tenants get weight 1 and no budget; empty = every tenant "
         "equal."),
    Flag("RACON_TPU_FLEET_HOST_TTL_S", "10", "float",
         "Member-host heartbeat time-to-live in seconds: a serve host "
         "whose registry heartbeat file (under --fleet-dir) goes "
         "unrefreshed for longer than this is declared dead, its job "
         "leases are broken and its queued/running jobs are re-placed "
         "on surviving hosts."),
    Flag("RACON_TPU_FLEET_POLL_S", "0.2", "float",
         "Gateway placement-loop poll interval in seconds: how often "
         "the fleet scheduler re-scans tenant queues, host heartbeats "
         "and in-flight job status between placement events."),
    # ------------------------------------------------ first-party overlapper
    Flag("RACON_TPU_OVERLAP_K", "15", "int",
         "Overlapper minimizer k-mer length (4..16; canonical codes "
         "live in uint32)."),
    Flag("RACON_TPU_OVERLAP_W", "5", "int",
         "Overlapper minimizer window: each run of w consecutive "
         "k-mers contributes its leftmost minimum-hash k-mer."),
    Flag("RACON_TPU_OVERLAP_MAX_OCC", "64", "int",
         "Overlapper seed frequency cap: hash buckets whose total "
         "occurrence count (reads + targets) exceeds this drop whole "
         "before matching (counted in the run report's overlap "
         "section, never silent)."),
    Flag("RACON_TPU_OVERLAP_MIN_SEEDS", "4", "int",
         "Minimum chained seeds for an overlapper candidate pair to "
         "emit an overlap row (pairs and chains below it count as "
         "chains_dropped)."),
    Flag("RACON_TPU_OVERLAP_DEVICE_JOIN", "1", "bool",
         "Device-resident seed join: sort both minimizer tables once "
         "on device and run the read-to-target searchsorted join + "
         "counted frequency capping as jit'd kernels (byte-identical "
         "to the host join; set 0 to force the numpy match_seeds "
         "oracle for A/B measurement)."),
    Flag("RACON_TPU_OVERLAP_RAGGED", "1", "bool",
         "Ragged overlap occupancy: candidate pairs fill the fixed "
         "lane arena of their seed-count class, chunks planned once, "
         "with double-buffered dispatch/fetch (_ChainStream), and "
         "chained overlap rows stream into the align session as whole "
         "query groups per fetched chunk instead of phase-barriering "
         "(byte-identical either way; set 0 to force the bucketed "
         "barrier path for A/B measurement)."),
    # --------------------------------------------------------------- tests
    Flag("RACON_TPU_SLOW", "0", "bool",
         "Enable the slow (tier-2) test set."),
    Flag("RACON_TPU_TEST_REAL", "0", "bool",
         "Run tests on the real accelerator instead of forcing the "
         "8-virtual-device CPU mesh."),
])


def _flag(name: str) -> Flag:
    try:
        return REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"environment flag {name!r} is not declared in "
            f"racon_tpu/flags.py — add it to REGISTRY with a doc line "
            f"(the env-flag-registry lint rule enforces this)") from None


def raw(name: str) -> str:
    """The single sanctioned ``RACON_TPU_*`` environment read: the raw
    string value of a **declared** flag (registry default when unset)."""
    f = _flag(name)
    return os.environ.get(name, f.default)


def get_bool(name: str) -> bool:
    return raw(name).strip().lower() not in _FALSE


def get_int(name: str) -> int:
    """Numeric semantics: unset -> registry default; set-but-empty -> 0
    (the shell-script way to disable, preserved from the pre-registry
    ad-hoc reads)."""
    v = raw(name).strip()
    return int(v) if v else 0


def get_float(name: str) -> float:
    """See :func:`get_int` for the set-but-empty -> 0 contract."""
    v = raw(name).strip()
    return float(v) if v else 0.0


def get_str(name: str) -> str:
    return raw(name)


def sanitize_enabled() -> bool:
    """The runtime-sanitizer master switch (shared shorthand)."""
    return get_bool("RACON_TPU_SANITIZE")


# ------------------------------------------------------- README generation

_TABLE_HEADER = "## Environment flags"
_TABLE_NOTE = ("<!-- generated by `python -m racon_tpu.flags` from "
               "racon_tpu/flags.py — do not edit by hand -->")


def readme_table() -> str:
    """The README "Environment flags" section, generated from the
    registry (one row per flag, declaration order)."""
    lines = [_TABLE_HEADER, "", _TABLE_NOTE, "",
             "| Flag | Type | Default | Effect |",
             "| --- | --- | --- | --- |"]
    for f in REGISTRY.values():
        default = f.default if f.default != "" else "(unset)"
        lines.append(f"| `{f.name}` | {f.kind} | `{default}` | {f.help} |")
    return "\n".join(lines) + "\n"


def check_readme(path: str) -> bool:
    """True when ``path`` contains the current generated table verbatim
    (the lint shard runs this so the README cannot drift)."""
    try:
        with open(path, "r", encoding="utf-8") as fh:
            return readme_table() in fh.read()
    except OSError:
        return False


def _main(argv) -> int:
    if argv and argv[0] == "--check-readme":
        if check_readme(argv[1] if len(argv) > 1 else "README.md"):
            return 0
        import sys
        print("README environment-flags table is stale — regenerate with "
              "`python -m racon_tpu.flags` and paste the output",
              file=sys.stderr)
        return 1
    print(readme_table(), end="")
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(_main(sys.argv[1:]))
