"""PolishServer: the long-lived polishing daemon (``racon --serve``).

Every one-shot ``racon`` invocation pays the cold XLA compile
(``setup_s`` in ``PERF.md``) for kernels whose warm dispatch is
sub-second — fatal for heavy traffic of small jobs (one user's plasmid
or amplicon panel).  The reference amortizes exactly this cost by
reusing its cudapoa/cudaaligner batch objects across fills (SURVEY
§L3); this server is the TPU analog at process granularity: ONE
resident process keeps a warm engine pool alive and executes submitted
polish jobs through the existing :meth:`Polisher.run` pipeline with
those engines injected, so a job's latency is compute, not compile.

Architecture (every piece is an existing subsystem, re-hosted):

- **Warm engine pool** — one :class:`racon_tpu.exec.runner._ChipWorker`
  per local chip (the round-13 slot type; the server passes itself as
  the duck-typed engine profile), each slot owning a device-pinned
  aligner/consensus pair plus a CPU-retry pair.  Engines are built
  eagerly at startup and *never* discarded: jit caches, SWAR probes and
  warm-up compiles survive across every job the server ever runs, and
  ``configure_compile_cache`` persists the executables across server
  restarts.
- **Shape canonicalization** — jobs land on already-compiled
  executables because the ragged consensus stream buckets windows by
  power-of-two lane width against a fixed arena (round 10): two jobs
  with the same polishing parameters share executables regardless of
  their input sizes.  At startup the pool warm-compiles the expected
  profile (``RACON_TPU_SERVE_WARM_SHAPES``) so job #1 is already warm,
  and every admitted job's own geometry is handed to ``warmup_async``
  (shape-deduped) so a genuinely new geometry starts compiling while
  the job waits in queue.
- **Admission control** — the exec planner's resident-footprint cost
  model (:func:`racon_tpu.exec.planner.estimate_job_cost`) gates
  submissions: a job estimated over the budget, a full queue, or a
  parameter set the resident engines cannot serve (the score/banding
  profile is baked into the compiled kernels) is *rejected with the
  reason* — never silently queued into an OOM.  Workers start a job
  only while the summed estimate of running jobs fits the budget.
- **Degradation ladder** — a failed job attempt walks the round-12
  per-class ladder (transient-io backoff → device-OOM backpressure via
  ``reduce_capacity`` → CPU engines → fail-with-reason); the server
  survives every rung — a job dying must never take the warm pool (and
  every queued job behind it) down with it.
- **Per-job observability** — each job runs under its own metric scope
  (``job.<id>.*``, :func:`racon_tpu.obs.metrics.set_scope`), gets its
  own schema-validated ``run_report`` (kind ``"job"``) returned
  alongside the result, and real XLA compile seconds are attributed
  per job via a ``jax.monitoring`` duration listener — the
  ``service_compile_fraction`` number the ROADMAP item is scored on.
- **Crash safety** (round 16, ``--serve-dir``) — every lifecycle
  transition is journaled durably (:mod:`racon_tpu.serve.journal`),
  results spool to CRC-verified files instead of RAM, a restart from
  the same serve-dir replays the journal (completed jobs serve from
  the spool, queued/running jobs re-admit down the round-12 crash
  ladder, client idempotency keys dedupe resubmissions), worker slots
  are *supervised* (a dead/wedged slot thread fails its job down the
  per-job ladder and is restarted with fresh engines; repeated deaths
  quarantine the slot and shrink advertised capacity), and
  ``SIGTERM``/``shutdown {"mode": "drain"}`` stops admission, finishes
  in-flight jobs and flushes the journal before exit.  The run-report
  schema grew a ``recovery`` section (v5) carrying the journal
  replay/compaction and slot-supervision counters.
"""

from __future__ import annotations

import os
import signal as signal_mod
import socket
import sys
import threading
import time
from typing import Dict, List, Optional, Tuple

from .. import contracts, faults, flags, sanitize
from ..core.polisher import PolisherType, create_polisher
from ..exec import heartbeat as hb
from ..exec import lease as lease_mod
from ..exec.planner import cached_job_cost, input_cost_bytes, parse_ram
from ..exec.runner import _ChipWorker
from ..io import parsers
from ..obs import compilewatch, metrics, report as obs_report
from ..parallel.topology import ChipSlot
from ..utils.logger import log_swallowed, warn
from . import protocol
from .journal import JobJournal

# job states — the JOB_MACHINE of racon_tpu/contracts.py; the
# state-transition lint rule checks every `job.state = ...` write (and
# its lexical equality guard, when present) against the declared edges
QUEUED = contracts.JOB_QUEUED
RUNNING = contracts.JOB_RUNNING
DONE = contracts.JOB_DONE
FAILED = contracts.JOB_FAILED
CANCELLED = contracts.JOB_CANCELLED

_TERMINAL = (DONE, FAILED, CANCELLED)

# default client-side wait bound for a blocking result request
DEFAULT_RESULT_TIMEOUT_S = 3600.0

# the per-job crash ladder (server death / slot death both count):
# crash 1 -> re-run on the primary engines (could have been unlucky),
# crash 2 -> re-run on the CPU engines, crash 3 -> fail-with-reason —
# the round-12 degradation shape, never an infinite redo loop
_MAX_JOB_CRASHES = 3
# slot supervision: consecutive deaths before a slot is quarantined
# instead of restarted (advertised capacity shrinks with it)
_SLOT_QUARANTINE_DEATHS = 3
_SUPERVISE_POLL_S = 0.5


def _eprint(msg: str) -> None:
    print(f"[racon_tpu::serve] {msg}", file=sys.stderr, flush=True)


# Compile attribution (round 18): the serve-only jax.monitoring
# listener of round 14 is absorbed into the process-wide
# racon_tpu.obs.compilewatch — same ``compile.jax_s`` scoped-timer
# semantics (fired on a job's worker thread, the time lands in THAT
# job's metric scope: the measured numerator of
# ``service_compile_fraction``), plus per-compile attribution to
# (function, shape signature, phase, scope) and the warm-path seal the
# sanitized serve assert reads (``sanitize.check_post_warm_compiles``).


def parse_warm_shapes(raw: str) -> List[Tuple[int, int, int, int]]:
    """Parse ``RACON_TPU_SERVE_WARM_SHAPES``: comma-separated
    ``window_length:pairs:windows[:contigs]`` entries.  A malformed
    entry fails loudly (an operator typo must not silently serve
    cold)."""
    out: List[Tuple[int, int, int, int]] = []
    for entry in raw.split(","):
        entry = entry.strip()
        if not entry:
            continue
        parts = entry.split(":")
        if len(parts) not in (3, 4):
            raise ValueError(
                f"RACON_TPU_SERVE_WARM_SHAPES entry {entry!r} is not "
                f"window_length:pairs:windows[:contigs]")
        vals = [int(p) for p in parts]
        if any(v <= 0 for v in vals):
            raise ValueError(
                f"RACON_TPU_SERVE_WARM_SHAPES entry {entry!r} has a "
                f"non-positive field")
        out.append((vals[0], vals[1], vals[2],
                    vals[3] if len(vals) == 4 else 1))
    return out


class Job:
    """One submitted polish job: spec, admission cost, lifecycle state,
    ladder attempts, result payload and the per-job run report."""

    def __init__(self, job_id: str, spec: dict, cost: int):
        self.id = job_id
        self.spec = spec
        self.cost = cost
        self.state = QUEUED
        self.error: Optional[str] = None
        self.engine: Optional[str] = None
        self.attempts: List[dict] = []
        self.result: Optional[bytes] = None
        self.result_bytes = 0          # recorded before retention drop
        self.collected = False
        self.phases: Dict[str, float] = {}
        self.report: Optional[dict] = None
        self.worker: Optional[str] = None
        # fleet routing hints (round 23): recorded for stats/status —
        # a plain serve host still schedules FIFO; the gateway is the
        # layer that turns these into weighted-fair + preemption
        self.tenant = str(spec.get("tenant", "default"))
        self.priority = int(spec.get("priority", 0))
        # cooperative preemption: set by the `preempt` op on a RUNNING
        # job; honored at the next ladder-attempt boundary (a polish
        # dispatch is never interrupted mid-flight)
        self.preempt = threading.Event()
        self.submitted_unix = time.time()
        self.started_at: Optional[float] = None
        self.wall_s = 0.0
        self.compile_s = 0.0
        # compiles attributed to this job AFTER the server sealed its
        # warm path (round 18) — 0 on the warm-path claim, reported in
        # the result header and asserted by bench_service
        self.compiles_after_warm = 0
        # the warm-path assert only judges jobs that STARTED after the
        # seal: a job already compiling when the first job completed
        # must not be failed retroactively (concurrent submissions)
        self.post_warm_eligible = False
        # True when admission warm-up queued NEW shapes for this job's
        # estimated geometry — a declared geometry expansion, exempt
        # from the warm-path assert (see _warm_job_geometry)
        self.warmup_declared = False
        self.done = threading.Event()
        # crash-safe serving (round 16): the client's idempotency key,
        # the spooled-result coordinates (name + CRC the fetch path
        # verifies), how many `running` journal records exist for this
        # job, and how many times it died with its executor (server
        # crash or slot death) — the ladder input
        self.key: Optional[str] = None
        self.spool: Optional[str] = None
        self.crc32 = 0
        self.journal_runs = 0
        self.crash_count = 0
        self.recovered = False
        # answered FAILED in RAM by a hard stop, but still journaled
        # `submitted` on disk: the final compaction must keep it live
        # so the restarted server runs it
        self.shutdown_orphan = False

    def row(self) -> dict:
        """The protocol's status view of this job."""
        out = {"job": self.id, "state": self.state,
               "cost_bytes": self.cost,
               "tenant": self.tenant, "priority": self.priority,
               "submitted_unix": round(self.submitted_unix, 3)}
        if self.worker:
            out["worker"] = self.worker
        if self.engine:
            out["engine"] = self.engine
        if self.attempts:
            out["attempts"] = self.attempts
        if self.state in _TERMINAL:
            out["wall_s"] = round(self.wall_s, 3)
            out["compile_s"] = round(self.compile_s, 3)
            out["compiles_after_warm"] = self.compiles_after_warm
            out["bytes"] = self.result_bytes
        elif self.started_at is not None:
            out["wall_s"] = round(time.perf_counter() - self.started_at,
                                  3)
        if self.error:
            out["error"] = self.error
        return out


class PolishServer:
    """The resident polishing service (see the module docstring).

    The server object doubles as the duck-typed **engine profile**
    :class:`racon_tpu.exec.runner._ChipWorker` consumes — the
    attributes below named like :class:`ShardRunner`'s are that
    contract, and they are also the *service profile* admission checks
    jobs against: scores and banding are baked into the resident
    compiled kernels, so a job requesting different ones cannot be
    served warm and is rejected with that reason."""

    def __init__(self, socket_path: str, *,
                 match: int = 3, mismatch: int = -5, gap: int = -4,
                 banded: bool = False, num_threads: int = 1,
                 aligner_backend: str = "auto",
                 consensus_backend: str = "auto",
                 aligner_batches: int = 1, consensus_batches: int = 1,
                 chips: int = 0, workers: int = 0,
                 budget_bytes: int = 0, max_queue: int = 0,
                 autostart: bool = True,
                 serve_dir: Optional[str] = None,
                 fleet_dir: Optional[str] = None):
        self.socket_path = os.path.abspath(socket_path)
        self.match, self.mismatch, self.gap = match, mismatch, gap
        self.banded = banded
        self.num_threads = num_threads
        self.aligner_backend = aligner_backend
        self.consensus_backend = consensus_backend
        self.aligner_batches = aligner_batches
        self.consensus_batches = consensus_batches
        self.chips_requested = chips
        self.workers_requested = workers
        self.worker = lease_mod.worker_identity()
        self.budget_bytes = budget_bytes or parse_ram(
            flags.get_str("RACON_TPU_SERVE_BUDGET"))
        self.max_queue = max_queue or max(
            1, flags.get_int("RACON_TPU_SERVE_QUEUE"))
        self.autostart = autostart

        self._slots: Optional[List[_ChipWorker]] = None
        # first slot-pool resolution is raced by connection handlers
        # (admission warm-up) against startup (_warm_pool)
        self._slots_lock = sanitize.named_lock("serve.slots")
        # the scheduler state lock (queue, counts, footprint); under
        # RACON_TPU_SANITIZE=1 both feed the lock-order witness
        self._lock = sanitize.named_lock("serve.state")
        self._cond = threading.Condition(self._lock)
        self._queue: List[Job] = []            # admitted, not yet running
        self._jobs: Dict[str, Job] = {}
        # terminal jobs retained for status/result queries, oldest
        # first; bounded so a server that has run 100k jobs holds 100k
        # of nothing (payloads go after one fetch, scoped metrics at
        # job end, and whole records past this horizon)
        self._retired: List[str] = []
        self.max_retained_jobs = 1024
        self._next_id = 0
        self._running_cost = 0
        self._counts = {"submitted": 0, "rejected": 0, "done": 0,
                        "failed": 0, "cancelled": 0}
        self._stop = threading.Event()
        self._listener: Optional[socket.socket] = None
        self._threads: List[threading.Thread] = []
        self._conn_threads: List[threading.Thread] = []
        self._t0 = time.perf_counter()
        self.started = threading.Event()       # listener bound + warm kick
        # crash-safe serving (round 16): the durable job journal +
        # result spool (None = the pre-round-16 in-memory service),
        # the idempotency-key index, the drain flag, and the slot-
        # supervision state (per-ordinal thread/death bookkeeping)
        serve_dir = serve_dir or \
            flags.get_str("RACON_TPU_SERVE_DIR").strip() or None
        self.serve_dir = os.path.abspath(serve_dir) if serve_dir else None
        self._journal: Optional[JobJournal] = \
            JobJournal(self.serve_dir) if self.serve_dir else None
        self._by_key: Dict[str, str] = {}
        self._draining = False
        self._slot_threads: Dict[int, threading.Thread] = {}
        self._slot_deaths: Dict[int, int] = {}
        self._quarantined: set = set()
        self._supervisor: Optional[threading.Thread] = None
        # fleet membership (round 23): a --fleet-dir host advertises
        # itself to the gateway with a heartbeat beacon file; a beacon
        # gone stale past RACON_TPU_FLEET_HOST_TTL_S is how the
        # gateway declares this host dead and migrates its jobs
        self.fleet_dir = os.path.abspath(fleet_dir) if fleet_dir \
            else None
        self._beacon = None

    # ------------------------------------------------------- engine pool

    def _chip_slots(self) -> List[_ChipWorker]:
        """The warm executor pool: one slot per local chip (mirrors the
        shard runner's auto-engagement — explicit ``--chips`` /
        ``RACON_TPU_CHIPS`` wins, else every local device when a device
        backend runs on real multi-chip hardware), topped up to
        ``workers`` unpinned slots when more concurrency than chips was
        asked for (each slot owns its OWN engine pair — engines hold
        per-run state and are never shared across concurrent jobs)."""
        if self._slots is not None:
            return self._slots
        # double-checked under its own lock: a connection handler's
        # admission warm-up and the startup warm pool can both trigger
        # the first resolution — two pools would split the warm jit
        # caches and double every engine's device footprint
        with self._slots_lock:
            if self._slots is not None:
                return self._slots
            n = 1
            explicit = self.chips_requested > 0 \
                or flags.get_int("RACON_TPU_CHIPS") > 0
            if explicit:
                from ..parallel import topology
                n = topology.resolve_chips(self.chips_requested)
            elif "tpu" in (self.aligner_backend, self.consensus_backend):
                from ..parallel import topology
                devs = topology.local_devices()
                if len(devs) > 1 and \
                        getattr(devs[0], "platform", "cpu") != "cpu":
                    n = len(devs)
            if n <= 1:
                slots = [_ChipWorker(self, ChipSlot(0, None),
                                     pinned=False)]
            else:
                from ..parallel import topology
                topo = topology.Topology(n)
                slots = [_ChipWorker(self, s, pinned=True)
                         for s in topo.slots]
            for k in range(len(slots), max(1, self.workers_requested)):
                extra = _ChipWorker(self, ChipSlot(k, None),
                                    pinned=False)
                extra.worker = f"{self.worker}#w{k}"
                slots.append(extra)
            self._slots = slots
        return slots

    def _warm_pool(self) -> None:
        """Build every slot's engines NOW (resident = the pool exists
        before the first job) and kick the expected-shape warm-up
        profile so job #1 dispatches into a hot jit cache."""
        raw = flags.get_str("RACON_TPU_SERVE_WARM_SHAPES")
        shapes = parse_warm_shapes(raw) if raw.strip() else []
        for w in self._chip_slots():
            aligner, consensus = w.get_engines(cpu=False)
            warm = getattr(consensus, "warmup_async", None)
            awarm = getattr(aligner, "warmup_async", None)
            for (wl, pairs, wins, contigs) in shapes:
                if warm is not None:
                    warm(wl, pairs, wins, est_contigs=contigs)
                if awarm is not None:
                    # align-chunk geometry (round 17): overlap spans run
                    # read-length scale, not window scale — ~8 windows
                    # per ONT-era read is the profile's implied ratio;
                    # a wrong estimate only wastes a background compile
                    awarm(8 * wl, max(1, pairs // 8), window_length=wl)
        _eprint(f"engine pool: {len(self._chip_slots())} worker(s), "
                f"budget {self.budget_bytes >> 20} MB, "
                f"{len(shapes)} warm shape profile(s)")

    def _warm_job_geometry(self, spec: dict) -> bool:
        """Hand an admitted job's own (estimated) geometry to every
        slot's warm-up — shape-deduped in the engine, so a repeat
        geometry (the service's common case) is free and a genuinely
        new one starts compiling while the job waits in queue.
        Returns True when any engine queued NEW warm-up shapes: the
        job declared a geometry expansion, and the warm-path assert
        must not judge it (its dispatch legitimately races its own
        warm-up thread for the compile)."""
        wl = spec["window_length"]
        read_bases = max(1, input_cost_bytes(spec["sequences"]) // 2)
        target_bases = max(
            1, input_cost_bytes(spec["target_sequences"]) // 2)
        est_pairs = max(1, read_bases // wl)
        est_windows = max(1, target_bases // wl)
        queued_new = False
        for w in self._chip_slots():
            if w.engines is None:
                continue
            warm = getattr(w.engines[1], "warmup_async", None)
            if warm is not None:
                queued_new |= warm(
                    wl, est_pairs, est_windows,
                    est_contigs=max(1, min(est_windows, 8))) is not None
            awarm = getattr(w.engines[0], "warmup_async", None)
            if awarm is not None:
                # align-stream geometry (round 17): see _warm_pool —
                # shape-deduped in the engine, so repeats are free
                queued_new |= awarm(8 * wl, max(1, est_pairs // 8),
                                    window_length=wl) is not None
        if parsers.is_auto_overlaps(spec["overlaps"]):
            # --overlaps auto job: the overlapper's seed + chain-arena
            # kernels are process-global (module jit caches, not
            # per-slot engines) — warm them once with the job's implied
            # read geometry (the ~8-windows-per-read profile above),
            # shape-deduped inside each module so repeats are free
            from ..ops import chain as chain_ops
            from ..ops import overlap_seed
            est_len = 8 * wl
            est_reads = max(1, read_bases // est_len)
            k = max(4, min(16, flags.get_int("RACON_TPU_OVERLAP_K")))
            queued_new |= overlap_seed.warmup_async(
                est_len, est_reads) is not None
            queued_new |= chain_ops.warmup_async(
                max(1, est_len // 8), est_reads, k=k) is not None
        return queued_new

    # --------------------------------------------------------- admission

    def _admit(self, raw_spec: dict, key: Optional[str] = None) \
            -> Tuple[Optional[Job], Optional[str], bool]:
        """Admission control: validate the spec, check it against the
        resident engine profile, estimate its footprint with the exec
        planner's cost model, and bound queue depth + total footprint.
        Returns ``(job, None, existing)`` or ``(None, rejection
        reason, False)`` — the reject-with-reason contract that
        replaces a silent OOM.  ``key`` is the client's idempotency
        key: a resubmission of an already-journaled spec returns the
        EXISTING job (``existing=True``) instead of duplicating
        compute — the contract that makes client reconnect-and-refetch
        across a server restart safe."""
        if key:
            with self._lock:
                jid = self._by_key.get(key)
                prior = self._jobs.get(jid) if jid else None
            # a FAILED prior is retryable — a fresh submission under
            # the same key admits a new attempt; queued/running/done
            # work is never duplicated
            if prior is not None and prior.state != FAILED:
                return prior, None, True
        if self._draining:
            return None, (
                "server is draining (SIGTERM / shutdown mode=drain): "
                "admission is stopped — resubmit to the restarted "
                "server (your idempotency key keeps it safe)"), False
        if self._quarantined and self.healthy_workers() == 0:
            return None, (
                "every worker slot is quarantined after repeated "
                "deaths — the server has no healthy capacity left; "
                "restart it (a --serve-dir server recovers its queue "
                "on restart)"), False
        spec, err = protocol.normalize_spec(raw_spec)
        if err is not None:
            return None, err, False
        for pkey in protocol.SPEC_PATHS:
            if pkey == "overlaps" \
                    and parsers.is_auto_overlaps(spec[pkey]):
                # first-party overlapper: the job is self-contained
                # (reads + target, no overlaps upload)
                continue
            spec[pkey] = os.path.abspath(spec[pkey])
            if not os.path.isfile(spec[pkey]):
                return None, f"input not found: {spec[pkey]}", False
        for path, kind in ((spec["sequences"], "sequences"),
                           (spec["target_sequences"], "target")):
            if parsers.sequence_parser_for(path) is None:
                return None, (f"{kind} file {path} has an unsupported "
                              f"format extension"), False
        if not parsers.is_auto_overlaps(spec["overlaps"]) \
                and parsers.overlap_parser_for(spec["overlaps"]) is None:
            return None, (f"overlaps file {spec['overlaps']} has an "
                          f"unsupported format extension"), False
        profile = (self.match, self.mismatch, self.gap, self.banded)
        requested = (spec["match"], spec["mismatch"], spec["gap"],
                     spec["banded"])
        if requested != profile:
            return None, (
                f"engine profile mismatch: the resident engines are "
                f"compiled for (match, mismatch, gap, banded) = "
                f"{profile}, the job asked for {requested} — submit to "
                f"a server started with those scores, or restart this "
                f"one with them"), False
        # content-fingerprint cached (round 23): a fleet gateway and a
        # member host pricing the same inputs stat them once, not twice
        cost = cached_job_cost(spec["sequences"], spec["overlaps"],
                               spec["target_sequences"])
        if cost > self.budget_bytes:
            return None, (
                f"job footprint estimate {cost >> 20} MB exceeds the "
                f"service budget {self.budget_bytes >> 20} MB "
                f"(--serve-budget / RACON_TPU_SERVE_BUDGET) — run it "
                f"one-shot through the streaming shard runner "
                f"(--max-ram) instead"), False
        with self._cond:
            if len(self._queue) >= self.max_queue:
                return None, (
                    f"queue full ({self.max_queue} jobs waiting; "
                    f"RACON_TPU_SERVE_QUEUE raises the bound)"), False
            if key and key in self._by_key:
                # a racing duplicate landed between the fast-path check
                # and here: the first submission wins, same contract
                prior = self._jobs.get(self._by_key[key])
                if prior is not None and prior.state != FAILED:
                    return prior, None, True
            self._next_id += 1
            job = Job(f"j{self._next_id}", spec, cost)
            job.key = key or None
            # registered (and key-indexed) BEFORE it is runnable, so a
            # duplicate submit dedupes while we journal below
            self._jobs[job.id] = job
            if job.key:
                self._by_key[job.key] = job.id
        if self._journal is not None:
            # the write-ahead half of admission: the `submitted` record
            # must be durable BEFORE the job can run (a `running`
            # record must never precede its `submitted`); a journal
            # that cannot record the job means the job is not admitted
            try:
                self._journal.append({
                    "rec": "submitted", "job": job.id, "key": job.key,
                    "cost": cost, "unix": round(job.submitted_unix, 3),
                    "spec": spec})
            # graftlint: disable=swallowed-exception (the failure IS the reply: it becomes the client's rejection reason)
            except Exception as e:
                # the job stays registered but FAILED (not popped): a
                # racing duplicate submission under the same key may
                # already have been answered with this id, and an id
                # the server acknowledged must keep resolving.  A
                # FAILED prior is retryable, so the key is reusable.
                with self._cond:
                    job.state = FAILED
                    job.error = (f"job journal write failed "
                                 f"({type(e).__name__}: {e})")
                    self._counts["failed"] = \
                        self._counts.get("failed", 0) + 1
                    self._retired.append(job.id)
                    job.done.set()
                return None, (f"job journal write failed "
                              f"({type(e).__name__}: {e}) — the "
                              f"serve-dir is not accepting durable "
                              f"admissions"), False
        with self._cond:
            self._queue.append(job)
            self._counts["submitted"] += 1
            self._cond.notify_all()
        # outside the lock: warm-up geometry derivation stats files.
        # A job whose estimate queued NEW warm-up shapes declared a
        # geometry expansion — the warm-path assert must not judge it
        # (it races its own admission warm-up thread for the compile)
        job.warmup_declared = self._warm_job_geometry(spec)
        return job, None, False

    # ------------------------------------------------------ job execution

    def _next_job(self, worker: _ChipWorker) -> Optional[Job]:
        """Block until the HEAD of the queue fits the in-flight
        footprint budget (or the server stops).  Strict FIFO: a big
        job waiting for footprint is never overtaken by later small
        ones — overtaking would keep the footprint pinned high and
        starve it indefinitely.  Progress is guaranteed: admission
        rejected anything bigger than the whole budget, so the head
        always fits once enough running jobs drain (at the latest,
        when the pool is idle)."""
        with self._cond:
            while True:
                if self._stop.is_set():
                    return None
                if self._queue:
                    job = self._queue[0]
                    if job.cost + self._running_cost \
                            <= self.budget_bytes \
                            or self._running_cost == 0:
                        self._queue.pop(0)
                        job.state = RUNNING
                        job.worker = worker.worker
                        job.post_warm_eligible = (
                            compilewatch.sealed() is not None
                            and not job.warmup_declared)
                        job.started_at = time.perf_counter()
                        self._running_cost += job.cost
                        # supervision handle: if this slot's thread
                        # dies, the supervisor finds the orphaned job
                        # here and walks it down the crash ladder
                        worker.current_job = job
                        return job
                self._cond.wait(0.2)

    def _worker_loop(self, worker: _ChipWorker) -> None:
        while True:
            job = self._next_job(worker)
            if job is None:
                return
            # slot-supervision chaos site: an injected fault HERE is
            # OUTSIDE the per-job ladder and kills the slot thread
            # itself — exactly the death the supervisor must detect,
            # requeue the job from, and restart the slot after
            faults.check("serve.slot")
            try:
                self._run_job(worker, job)
            except Exception as e:
                # a fault OUTSIDE the per-attempt ladder (a report-build
                # bug, say) must fail the job, never the worker — the
                # warm pool outliving every job is the whole service
                job.state = FAILED
                job.error = f"internal error: {type(e).__name__}: {e}"
                warn(f"job {job.id} worker fault past the ladder: {e}")
            finally:
                with self._cond:
                    self._running_cost -= job.cost
                    worker.current_job = None
                    self._counts[job.state] = \
                        self._counts.get(job.state, 0) + 1
                    self._retired.append(job.id)
                    while len(self._retired) > self.max_retained_jobs:
                        old = self._jobs.pop(self._retired.pop(0),
                                             None)
                        if old is not None:
                            old.result = None  # drop a never-fetched blob
                    self._cond.notify_all()
                self._journal_terminal(job)
                job.done.set()
            self._maybe_compact()
            _eprint(f"job {job.id} {job.state} in {job.wall_s:.2f}s "
                    f"(engine={job.engine or '-'}, "
                    f"compile {job.compile_s:.2f}s, "
                    f"{job.result_bytes} B) on {worker.worker}")

    def _polish(self, job: Job, worker: _ChipWorker,
                cpu: bool) -> bytes:
        """One polish attempt with the worker's resident engines
        injected — the job's whole latency is :meth:`Polisher.run`."""
        spec = job.spec
        aligner, consensus = worker.get_engines(cpu)
        p = create_polisher(
            spec["sequences"], spec["overlaps"],
            spec["target_sequences"],
            PolisherType.F if spec["fragment_correction"]
            else PolisherType.C,
            window_length=spec["window_length"],
            quality_threshold=spec["quality_threshold"],
            error_threshold=spec["error_threshold"],
            trim=not spec["no_trimming"],
            match=spec["match"], mismatch=spec["mismatch"],
            gap=spec["gap"], num_threads=spec["threads"],
            aligner=aligner, consensus=consensus)
        polished = p.run(not spec["include_unpolished"])
        job.phases = dict(p.timings)
        return b"".join(b">" + s.name + b"\n" + s.data + b"\n"
                        for s in polished)

    def _run_job(self, worker: _ChipWorker, job: Job) -> None:
        """Execute one job under its own metric scope, walking the
        round-12 degradation ladder on failure — the server survives
        every rung, and the ladder record rides in the job's status,
        result and report."""
        if self._journal is not None:
            # write-ahead: the incarnation is journaled BEFORE any
            # compute, so a crash from here on leaves a countable
            # `running` record — the crash ladder's input on replay
            job.journal_runs += 1
            self._journal.append({"rec": "running", "job": job.id,
                                  "worker": worker.worker,
                                  "run": job.journal_runs})
        # kill-restart chaos site: a SIGKILL here leaves this job
        # journaled `running` with no terminal record — exactly the
        # state restart recovery must re-admit
        faults.check("server.kill")
        scope = metrics.job_scope(job.id)
        metrics.set_scope(scope)
        t_start = time.time()
        t0 = time.perf_counter()
        max_retries = max(0, flags.get_int("RACON_TPU_EXEC_RETRIES"))
        transient_used = 0
        # a job that already died with its executor re-enters the
        # ladder where it left off: the second crash lands it on the
        # CPU engines (a device/engine fault may be what killed it)
        tier_cpu = job.crash_count >= 2
        blob: Optional[bytes] = None
        try:
            for attempt_no in range(64):  # ladder is finite
                if job.preempt.is_set():
                    # cooperative preemption (round 23): honored only
                    # at ladder-attempt boundaries — a polish dispatch
                    # is never interrupted, so a first attempt that
                    # succeeds outruns its own preemption (completion
                    # wins; drain, never kill)
                    job.attempts.append({
                        "n": attempt_no, "engine": "-",
                        "class": "preempt", "action": "drain"})
                    break
                try:
                    faults.check("serve.polish", attempt=attempt_no)
                    blob = self._polish(job, worker, cpu=tier_cpu)
                    break
                except Exception as e:
                    cls = faults.classify(e)
                    metrics.inc(f"faults.{cls}")
                    err = f"{type(e).__name__}: {e}"
                    att = {"n": attempt_no,
                           "engine": "cpu" if tier_cpu else "primary",
                           "class": cls, "error": err}
                    job.attempts.append(att)
                    if cls == faults.CLASS_TRANSIENT and \
                            transient_used < max_retries:
                        backoff = faults.backoff_s(
                            max(0.0, flags.get_float(
                                "RACON_TPU_EXEC_BACKOFF_S")),
                            transient_used,
                            f"{job.id}:{transient_used}")
                        att["action"] = "retry-backoff"
                        att["backoff_s"] = round(backoff, 3)
                        transient_used += 1
                        warn(f"job {job.id} transient fault ({err}) — "
                             f"retry {transient_used}/{max_retries} in "
                             f"{backoff:.2f}s")
                        time.sleep(backoff)
                    elif cls == faults.CLASS_OOM and not tier_cpu and \
                            worker.reduce_capacity():
                        att["action"] = "reduce-capacity"
                        # the halved arenas dispatch NEW geometries by
                        # design: this job leaves the warm-path claim
                        # (the ladder contract is that it survives),
                        # and the seal re-opens so the shrunk engine's
                        # re-warm compiles land in the warmed set
                        # instead of failing every subsequent sanitized
                        # job — the next completed job re-seals
                        job.post_warm_eligible = False
                        compilewatch.unseal()
                        warn(f"job {job.id} device OOM ({err}) — "
                             f"halved worker {worker.worker}'s "
                             f"consensus arena/group capacity, "
                             f"re-dispatching on the device "
                             f"(warm-path seal re-opened)")
                    elif not tier_cpu:
                        tier_cpu = True
                        att["action"] = "cpu-retry"
                        # off the warm path by definition: the failed
                        # device attempt may have compiled, but the
                        # ladder contract says this job completes on
                        # the CPU engines — it is not judged by the
                        # warm-path assert (its story is in `attempts`)
                        job.post_warm_eligible = False
                        warn(f"job {job.id} attempt failed ({err}) — "
                             f"retrying on the CPU engines")
                    else:
                        att["action"] = "fail"
                        job.error = "; ".join(
                            a["error"] for a in job.attempts)
                        break
            job.wall_s = time.perf_counter() - t0
            job.compile_s = metrics.timer_s(scope + "compile.jax_s")
            # warm-path claim (round 18): compiles attributed to this
            # job's scope after the server sealed warm-up are counted
            # into the result header; under RACON_TPU_SANITIZE=1 they
            # FAIL the job with the offending (function, signature)
            # named next to the nearest warmed one.  Only jobs that
            # STARTED after the seal are judged — a concurrent job
            # already compiling when job #1 completed is not failed
            # retroactively.
            if job.post_warm_eligible:
                try:
                    viol = sanitize.check_post_warm_compiles(scope)
                    job.compiles_after_warm = len(viol)
                except sanitize.CompileAfterWarmError as e:
                    job.compiles_after_warm = len(
                        compilewatch.post_warm(scope))
                    job.error = f"sanitized warm-path assert: {e}"
                    blob = None
            if blob is not None:
                if self._journal is not None:
                    # results spool to CRC-verified files, not RAM:
                    # the server's memory stays bounded by in-flight
                    # work and the payload survives a restart
                    job.spool, job.result_bytes, job.crc32 = \
                        self._journal.spool_write(job.id, blob)
                    job.result = None
                else:
                    job.result = blob
                    job.result_bytes = len(blob)
                job.engine = "cpu-retry" if tier_cpu else "primary"
                job.state = DONE
                # first completed job = warm-up complete: every shape
                # the startup profile, admission warm-ups and job #1
                # compiled is now the warmed set, and any later compile
                # of a never-seen (function, signature) is a warm-path
                # violation (warned + counted; a hard job failure under
                # RACON_TPU_SANITIZE=1)
                compilewatch.seal(f"serve warm path "
                                  f"(job {job.id} complete)")
            elif job.preempt.is_set():
                # drained at a ladder boundary: terminal here, but NOT
                # a failure — the fleet gateway requeues the job and
                # places it again under a fresh incarnation key
                job.state = CANCELLED
                job.error = job.error or (
                    "preempted: drained back to the queue at a "
                    "ladder boundary")
            else:
                job.state = FAILED
            # the per-job run report: built from THIS job's metric
            # scope, so concurrent jobs' numbers stay disjoint — the
            # machine-readable artifact returned alongside the result
            job.report = obs_report.build_report(
                "job", argv=[job.id, spec_summary(job.spec)],
                started_unix=t_start, wall_s=job.wall_s,
                phases=job.phases, scope=scope)
            # judged (or ladder-exempted) and reported: drop this
            # scope's violation records so the bounded global list
            # never fills up and quietly stops flagging later jobs
            compilewatch.clear_scope(scope)
        finally:
            metrics.set_scope(None)
            # the report snapshot above embeds everything the scope
            # held; retiring the registry entries NOW is what keeps a
            # server that runs 100k jobs from growing the metrics
            # dicts without bound (the heartbeat only reads RUNNING
            # jobs' scopes, so nothing still wants these)
            metrics.clear_job(job.id)

    # ----------------------------------------- journal lifecycle + recovery

    def _journal_terminal(self, job: Job) -> None:
        """Durably record a job's terminal transition.  A failed append
        here is logged, not raised: losing a ``done`` record only means
        the job re-runs (byte-identically) after a restart — safe,
        where a dead worker thread is not."""
        if self._journal is None or \
                job.state not in (DONE, FAILED, CANCELLED):
            return
        try:
            if job.state == DONE:
                self._journal.append({
                    "rec": "done", "job": job.id,
                    "bytes": job.result_bytes, "crc32": job.crc32,
                    "spool": job.spool,
                    "wall_s": round(job.wall_s, 3),
                    "engine": job.engine})
            elif job.state == CANCELLED:
                # a preempt-drained run: without this record a restart
                # would re-run a job the gateway already re-placed
                # elsewhere — a duplicate polish nobody collects
                self._journal.append({"rec": "cancelled",
                                      "job": job.id,
                                      "error": job.error or ""})
            else:
                self._journal.append({"rec": "failed", "job": job.id,
                                      "error": job.error or ""})
        except Exception as e:
            log_swallowed("serve: journal terminal record failed "
                          "(the job will re-run after a restart)", e)

    def _live_records_locked(self) -> List[dict]:
        """The live-jobs-only journal a compaction rewrites to: one
        ``submitted`` record, the job's ``running`` incarnations (the
        crash ladder's input must survive compaction), and the ``done``
        record for an uncollected payload.  Fully retired jobs —
        collected, failed, cancelled — drop out (their client already
        has the answer; a keyed resubmission simply runs fresh).
        Caller holds the scheduler lock; returns ``(records,
        live job ids)`` — the ids feed the orphan-spool sweep."""
        recs: List[dict] = []
        live: List[str] = []
        for job in self._jobs.values():
            if (job.state in (FAILED, CANCELLED)
                    and not job.shutdown_orphan) or \
                    (job.state == DONE and job.collected):
                continue
            live.append(job.id)
            recs.append({"rec": "submitted", "job": job.id,
                         "key": job.key, "cost": job.cost,
                         "unix": round(job.submitted_unix, 3),
                         "spec": job.spec})
            for k in range(job.journal_runs):
                recs.append({"rec": "running", "job": job.id,
                             "worker": job.worker, "run": k + 1})
            if job.state == DONE:
                recs.append({"rec": "done", "job": job.id,
                             "bytes": job.result_bytes,
                             "crc32": job.crc32, "spool": job.spool,
                             "wall_s": round(job.wall_s, 3),
                             "engine": job.engine})
        return recs, live

    def _compact(self) -> None:
        """Rewrite the journal to live jobs only (atomic tmp → fsync →
        rename) and sweep orphaned spool files — what keeps a
        long-lived server's serve-dir bounded."""
        j = self._journal
        if j is None:
            return
        # lock order journal -> state, matching every append site
        # (appends happen outside the scheduler lock); the round-15
        # lock-order witness checks this under RACON_TPU_SANITIZE=1.
        # Snapshot and rewrite happen under ONE journal-lock hold so a
        # concurrent append cannot slip between them and be dropped.
        with j.lock:
            with self._cond:
                recs, live = self._live_records_locked()
            # graftlint: disable=blocking-under-lock (snapshot+rewrite must be one atomic hold vs appends)
            j.rewrite_locked(recs)
        j.sweep_spool(live)

    def _maybe_compact(self) -> None:
        j = self._journal
        if j is not None and \
                j.appends_since_rewrite >= j.compact_every:
            self._compact()

    def _recover(self) -> None:
        """Restart recovery: replay the journal and pick every live job
        back up — completed jobs serve from the (CRC-verified) spool
        without re-polishing, queued/running jobs re-enter the queue in
        submission order walking the crash ladder, and terminal jobs
        answer status queries.  Runs before any worker starts."""
        if self._journal is None:
            return
        records = self._journal.replay()
        metrics.inc("serve.journal_replayed", len(records))
        by_job: Dict[str, List[dict]] = {}
        order: List[str] = []
        for rec in records:
            jid = rec.get("job")
            if not isinstance(jid, str):
                continue
            if jid not in by_job:
                order.append(jid)
            by_job.setdefault(jid, []).append(rec)
        max_id = 0
        n_live = n_spool = n_requeued = 0
        for jid in order:
            recs = by_job[jid]
            sub = next((r for r in recs
                        if r.get("rec") == "submitted"), None)
            if sub is None:
                continue  # unreadable head: nothing admissible remains
            if jid.startswith("j") and jid[1:].isdigit():
                max_id = max(max_id, int(jid[1:]))
            if any(r.get("rec") == "collected" for r in recs):
                continue  # fully retired; compaction fodder
            kinds = {r.get("rec"): r for r in recs}
            spec, err = protocol.normalize_spec(sub.get("spec") or {})
            if spec is None:
                warn(f"journal job {jid} has an unreadable spec "
                     f"({err}) — dropping it")
                continue
            job = Job(jid, spec, int(sub.get("cost", 0)))
            job.key = sub.get("key") or None
            job.recovered = True
            job.submitted_unix = float(sub.get("unix") or
                                       job.submitted_unix)
            job.journal_runs = sum(1 for r in recs
                                   if r.get("rec") == "running")
            n_live += 1
            if "cancelled" in kinds:
                job.state = CANCELLED
                job.error = "cancelled by client (before the restart)"
                self._register_recovered(job)
                continue
            if "failed" in kinds:
                job.state = FAILED
                job.error = str(kinds["failed"].get("error") or
                                "failed (before the restart)")
                self._register_recovered(job)
                continue
            done_rec = kinds.get("done")
            if done_rec is not None:
                blob = self._journal.spool_read(
                    jid, int(done_rec.get("bytes", -1)),
                    int(done_rec.get("crc32", 0)))
                if blob is not None:
                    # served from the spool: completed-at-crash work is
                    # NOT re-polished (the soak asserts zero duplicate
                    # running records for these)
                    job.state = DONE
                    job.spool = done_rec.get("spool") or \
                        self._journal.spool_name(jid)
                    job.result_bytes = int(done_rec.get("bytes", 0))
                    job.crc32 = int(done_rec.get("crc32", 0))
                    job.wall_s = float(done_rec.get("wall_s") or 0.0)
                    job.engine = done_rec.get("engine")
                    n_spool += 1
                    self._register_recovered(job)
                    continue
                # truncated/corrupt spool: the result is lost — requeue
                # the job instead of serving garbage (the round-12
                # part-verification rule)
                metrics.inc("serve.spool_corrupt")
                warn(f"job {jid}: result spool failed verification — "
                     f"re-queueing instead of serving a corrupt result")
            # queued or running at crash time: re-admit down the ladder
            job.crash_count = job.journal_runs
            for k in range(job.crash_count):
                job.attempts.append({
                    "n": k, "engine": "primary", "class": "crash",
                    "error": "server died while the job was running",
                    "action": ("fail" if k + 1 >= _MAX_JOB_CRASHES
                               else "requeue")})
            if job.crash_count >= _MAX_JOB_CRASHES:
                job.state = FAILED
                job.error = (f"the server crashed {job.crash_count} "
                             f"times while running this job — failing "
                             f"it down the ladder instead of an "
                             f"infinite redo loop")
                self._register_recovered(job)
                continue
            with self._cond:
                self._queue.append(job)
            n_requeued += 1
            self._register_recovered(job)
        with self._cond:
            self._next_id = max(self._next_id, max_id)
        metrics.inc("serve.recovered_jobs", n_live)
        metrics.inc("serve.requeued_jobs", n_requeued)
        metrics.inc("serve.spool_served", n_spool)
        if n_live:
            _eprint(f"recovery: {n_live} journaled job(s) restored "
                    f"({n_spool} served from the result spool, "
                    f"{n_requeued} re-queued) from {self.serve_dir}")
        # clean-startup compaction: the replayed history is rewritten
        # live-jobs-only, so crash-looped serve dirs stay bounded
        self._compact()

    def _register_recovered(self, job: Job) -> None:
        with self._cond:
            self._jobs[job.id] = job
            if job.key:
                self._by_key[job.key] = job.id
            self._counts["submitted"] += 1
            if job.state in _TERMINAL:
                self._counts[job.state] = \
                    self._counts.get(job.state, 0) + 1
                self._retired.append(job.id)
                job.done.set()
            self._cond.notify_all()

    # --------------------------------------------------- slot supervision

    def healthy_workers(self) -> int:
        """Advertised capacity: resolved slots minus quarantined ones
        (admission reads this — a server whose every slot died stops
        accepting instead of queueing into a black hole)."""
        with self._slots_lock:
            slots = self._slots or []
            return sum(1 for w in slots
                       if w.ordinal not in self._quarantined)

    def _supervise_loop(self) -> None:
        """Slot supervision: a worker thread that died outside the
        per-job ladder (device fault, unhandled exception, injected
        ``serve.slot`` chaos) is detected here; its job fails down the
        per-job crash ladder and the slot restarts with fresh engines.
        Repeated deaths quarantine the slot — capacity shrinks, the
        server survives."""
        while not self._stop.wait(_SUPERVISE_POLL_S):
            with self._slots_lock:
                slots = list(self._slots or [])
            for idx, slot in enumerate(slots):
                t = self._slot_threads.get(slot.ordinal)
                if t is None or t.is_alive() or self._stop.is_set():
                    continue
                if slot.ordinal in self._quarantined:
                    continue
                self._handle_slot_death(idx, slot)

    def _handle_slot_death(self, idx: int, slot: _ChipWorker) -> None:
        deaths = self._slot_deaths.get(slot.ordinal, 0) + 1
        self._slot_deaths[slot.ordinal] = deaths
        metrics.inc("slot.deaths")
        job = slot.current_job
        failed_job = None
        with self._cond:
            if job is not None and job.state == RUNNING:
                # the dying thread never reached its finally: the
                # footprint reservation and the job are both orphaned
                self._running_cost -= job.cost
                job.crash_count += 1
                att = {"n": len(job.attempts), "engine": "primary",
                       "class": "crash",
                       "error": f"worker slot {slot.worker} died while "
                                f"running this job"}
                job.attempts.append(att)
                if job.crash_count >= _MAX_JOB_CRASHES:
                    att["action"] = "fail"
                    job.state = FAILED
                    job.error = (f"executor died {job.crash_count} "
                                 f"times on this job — failing it "
                                 f"down the ladder")
                    self._counts["failed"] = \
                        self._counts.get("failed", 0) + 1
                    self._retired.append(job.id)
                    failed_job = job
                else:
                    att["action"] = "requeue"
                    job.state = QUEUED
                    job.worker = None
                    job.started_at = None
                    # head of the queue: it was already running
                    self._queue.insert(0, job)
                self._cond.notify_all()
            slot.current_job = None
        if failed_job is not None:
            self._journal_terminal(failed_job)
            failed_job.done.set()
        if deaths >= _SLOT_QUARANTINE_DEATHS:
            self._quarantined.add(slot.ordinal)
            metrics.inc("slot.quarantined")
            warn(f"worker slot {slot.worker} died {deaths} times — "
                 f"quarantining it (advertised capacity is now "
                 f"{self.healthy_workers()} worker(s))")
            if self.healthy_workers() == 0:
                warn("every worker slot is quarantined — failing "
                     "queued jobs and rejecting new submissions")
                with self._cond:
                    stranded = list(self._queue)
                    for queued in stranded:
                        queued.state = FAILED
                        queued.error = ("no healthy worker slots left "
                                        "(all quarantined)")
                        self._counts["failed"] = \
                            self._counts.get("failed", 0) + 1
                        self._retired.append(queued.id)
                        queued.done.set()
                    self._queue.clear()
                    self._cond.notify_all()
                # journal the failures (outside the lock): the clients
                # were TOLD failed — a restart must not resurrect and
                # re-run jobs nobody will ever fetch
                for queued in stranded:
                    self._journal_terminal(queued)
            return
        fresh = _ChipWorker(self, slot.slot, pinned=slot.device is not None)
        fresh.worker = slot.worker  # keep the identity stable
        with self._slots_lock:
            if self._slots is not None and idx < len(self._slots) \
                    and self._slots[idx] is slot:
                self._slots[idx] = fresh
            # drop the dead thread's registration NOW: until the
            # replacement registers, an absent mapping reads as
            # "not started yet" and the supervisor skips it (leaving
            # it would re-detect the same death next tick)
            self._slot_threads.pop(slot.ordinal, None)
        metrics.inc("slot.restarts")
        _eprint(f"slot {slot.worker} died (death {deaths}/"
                f"{_SLOT_QUARANTINE_DEATHS}) — restarting it with "
                f"fresh engines")
        self._spawn_worker(fresh)

    # ----------------------------------------------------------- protocol

    def _handle_conn(self, conn: socket.socket) -> None:
        rfile = conn.makefile("rb")
        try:
            while True:
                try:
                    msg = protocol.read_msg(rfile)
                except ValueError as e:
                    protocol.send_msg(conn, {"ok": False,
                                             "error": f"bad request: {e}"})
                    return
                if msg is None:
                    return
                try:
                    if not self._dispatch_op(conn, msg):
                        return
                except (ValueError, TypeError, KeyError) as e:
                    # a malformed FIELD (non-numeric timeout_s, an
                    # unhashable job id) is the client's fault: answer
                    # with the reason instead of letting the handler
                    # thread die and the socket close silently
                    protocol.send_msg(conn, {
                        "ok": False,
                        "error": f"bad request field: "
                                 f"{type(e).__name__}: {e}"})
        except OSError as e:
            # a client hanging up mid-response is its own business —
            # the server's job records stay intact either way
            log_swallowed("serve: client connection dropped", e)
        finally:
            rfile.close()
            conn.close()

    def _dispatch_op(self, conn, msg: dict) -> bool:
        """Handle one request; False ends the connection loop."""
        op = msg.get("op")
        if op == "ping":
            self._chip_slots()  # resolve before counting capacity
            protocol.send_msg(conn, {
                "ok": True, "server": self.worker,
                "uptime_s": round(time.perf_counter() - self._t0, 3),
                "profile": {"match": self.match,
                            "mismatch": self.mismatch, "gap": self.gap,
                            "banded": self.banded},
                "workers": self.healthy_workers(),
                "serve_dir": self.serve_dir,
                "draining": self._draining})
            return True
        if op == "submit":
            key = msg.get("key")
            if key is not None and not isinstance(key, str):
                protocol.send_msg(conn, {
                    "ok": False,
                    "error": "idempotency key must be a string"})
                return True
            job, reason, existing = self._admit(msg.get("spec", {}),
                                                key=key)
            if job is None:
                with self._lock:
                    self._counts["rejected"] += 1
                protocol.send_msg(conn, {"ok": False, "error": reason,
                                         "rejected": True})
                return True
            protocol.send_msg(conn, {"ok": True, "job": job.id,
                                     "state": job.state,
                                     "cost_bytes": job.cost,
                                     "existing": existing})
            return True
        if op in ("status", "result", "cancel", "preempt"):
            job = self._jobs.get(msg.get("job", ""))
            if job is None:
                protocol.send_msg(conn, {
                    "ok": False,
                    "error": f"unknown job {msg.get('job')!r}"})
                return True
            if op == "status":
                row = job.row()
                with self._lock:
                    if job in self._queue:
                        row["queue_position"] = self._queue.index(job)
                protocol.send_msg(conn, {"ok": True, **row})
                return True
            if op == "cancel":
                return self._op_cancel(conn, job)
            if op == "preempt":
                return self._op_preempt(conn, job)
            return self._op_result(conn, job, msg)
        if op == "stats":
            with self._lock:
                counts = dict(self._counts)
                depth = len(self._queue)
                running = self._running_cost
                tenants: Dict[str, int] = {}
                for queued_job in self._queue:
                    tenants[queued_job.tenant] = \
                        tenants.get(queued_job.tenant, 0) + 1
            out = {
                "ok": True, **counts, "queued": depth,
                "tenants": tenants,
                "running_cost_bytes": running,
                "budget_bytes": self.budget_bytes,
                "peak_rss_bytes": metrics.peak_rss_bytes(),
                "quarantined_slots": len(self._quarantined),
                "slots": {"healthy": self.healthy_workers(),
                          "quarantined": len(self._quarantined)},
                "slot_restarts": int(metrics.counter("slot.restarts"))}
            if self._journal is not None:
                out["serve_dir"] = self.serve_dir
                out["recovery"] = metrics.recovery_summary()
            protocol.send_msg(conn, out)
            return True
        if op == "shutdown":
            mode = msg.get("mode", "now")
            if mode not in ("now", "drain"):
                protocol.send_msg(conn, {
                    "ok": False,
                    "error": f"unknown shutdown mode {mode!r} "
                             f"(now | drain)"})
                return True
            if mode == "drain":
                # admission must be stopped BEFORE the reply lands: a
                # client that sees "draining" and immediately submits
                # must deterministically be rejected
                with self._lock:
                    self._draining = True
            protocol.send_msg(conn, {
                "ok": True,
                "state": "draining" if mode == "drain" else "stopping"})
            self.shutdown(mode=mode)
            return False
        protocol.send_msg(conn, {"ok": False,
                                 "error": f"unknown op {op!r}"})
        return True

    def _op_cancel(self, conn, job: Job) -> bool:
        cancelled = False
        with self._cond:
            if job in self._queue:
                self._queue.remove(job)
                job.state = CANCELLED
                job.error = "cancelled by client"
                self._counts["cancelled"] += 1
                self._retired.append(job.id)  # bounded-history horizon
                job.done.set()
                cancelled = True
        # reply OUTSIDE the scheduler lock (blocking-under-lock): a
        # client slow to drain its socket must not stall every worker
        # contending for the state lock
        if cancelled:
            if self._journal is not None:
                try:
                    self._journal.append({"rec": "cancelled",
                                          "job": job.id})
                except Exception as e:
                    log_swallowed(
                        "serve: journal cancel record failed (the job "
                        "would re-run after a restart)", e)
            protocol.send_msg(conn, {"ok": True, "job": job.id,
                                     "state": job.state})
            return True
        protocol.send_msg(conn, {
            "ok": False, "job": job.id, "state": job.state,
            "error": f"job {job.id} is not queued ({job.state}) — a "
                     f"running job cannot be safely interrupted "
                     f"mid-dispatch"})
        return True

    def _op_preempt(self, conn, job: Job) -> bool:
        """The fleet gateway's drain request (round 23): a QUEUED job
        is released immediately (``drained: true`` — it never ran); a
        RUNNING job gets its cooperative preempt flag and drains at
        the next ladder-attempt boundary or completes first
        (``drained: false`` — the gateway watches its status either
        way).  Never kills a dispatch mid-flight."""
        drained = False
        running = False
        with self._cond:
            if job in self._queue:
                self._queue.remove(job)
                job.state = CANCELLED
                job.error = "preempted by the fleet scheduler"
                self._counts["cancelled"] += 1
                self._retired.append(job.id)
                job.done.set()
                drained = True
            elif job.state == RUNNING:
                job.preempt.set()
                running = True
        # reply OUTSIDE the scheduler lock, like _op_cancel
        if drained:
            if self._journal is not None:
                try:
                    self._journal.append({"rec": "cancelled",
                                          "job": job.id})
                except Exception as e:
                    log_swallowed(
                        "serve: journal preempt record failed (the "
                        "job would re-run after a restart)", e)
            protocol.send_msg(conn, {"ok": True, "job": job.id,
                                     "state": job.state,
                                     "drained": True})
            return True
        if running:
            protocol.send_msg(conn, {
                "ok": True, "job": job.id, "state": job.state,
                "drained": False,
                "note": "running — drains at the next ladder "
                        "boundary or completes first"})
            return True
        protocol.send_msg(conn, {
            "ok": False, "job": job.id, "state": job.state,
            "error": f"job {job.id} is already terminal "
                     f"({job.state})"})
        return True

    def _op_result(self, conn, job: Job, msg: dict) -> bool:
        timeout = float(msg.get("timeout_s", DEFAULT_RESULT_TIMEOUT_S))
        if not job.done.wait(timeout):
            protocol.send_msg(conn, {
                "ok": False, "job": job.id, "state": job.state,
                "timeout": True,
                "error": f"job {job.id} not finished within "
                         f"{timeout:.0f}s (still {job.state})"})
            return True
        header = {"ok": job.state == DONE, **job.row(),
                  "report": job.report}
        if job.state != DONE:
            protocol.send_msg(conn, header)
            return True
        with self._lock:
            blob = job.result
            spool = job.spool if self._journal is not None else None
            collected = job.collected
        if blob is None and spool and not collected:
            # spooled result (--serve-dir): re-read and CRC-verify on
            # EVERY fetch — a disk that lied about fsync or flipped a
            # bit must re-queue the job, never stream garbage (the
            # round-12 part-verification rule)
            blob = self._journal.spool_read(job.id, job.result_bytes,
                                            job.crc32)
            if blob is None:
                with self._lock:
                    racing_collected = job.collected
                if not racing_collected:
                    self._requeue_corrupt_spool(job)
                    header.update(
                        ok=False, state=job.state,
                        error=f"job {job.id} result spool failed "
                              f"verification — the job was re-queued; "
                              f"retry the fetch")
                    protocol.send_msg(conn, header)
                    return True
                # a racing fetcher streamed + unlinked the spool while
                # we were between the snapshot and the read: the result
                # was DELIVERED, not lost — answer "collected", never
                # re-queue already-delivered work
        if blob is None:
            why = ("was already collected (payloads are retained for "
                   "one successful fetch)" if job.collected
                   else "was retired (the server keeps a bounded "
                        "terminal-job history)")
            header.update(ok=False,
                          error=f"job {job.id} result {why}")
            protocol.send_msg(conn, header)
            return True
        header["bytes"] = len(blob)
        protocol.send_msg(conn, header)
        conn.sendall(blob)
        if not msg.get("keep", False):
            # retention: the FASTA payload is the big allocation — one
            # SUCCESSFUL fetch per job keeps a long-lived server's
            # memory bounded by in-flight work, not by its history.
            # Dropped only AFTER sendall returned: a client that died
            # waiting must be able to reconnect and fetch (two racing
            # fetchers both succeed; the second drop is a no-op).
            with self._lock:
                newly = not job.collected
                job.result = None
                job.collected = True
            if newly and self._journal is not None:
                try:
                    self._journal.append({"rec": "collected",
                                          "job": job.id})
                except Exception as e:
                    log_swallowed(
                        "serve: journal collected record failed (the "
                        "result would be re-servable after a restart "
                        "— safe)", e)
                self._journal.spool_unlink(job.id)
                self._maybe_compact()
        return True

    def _requeue_corrupt_spool(self, job: Job) -> None:
        """A spooled result that fails verification is LOST work, not
        servable work: put the job back at the head of the queue (it
        re-polishes byte-identically) — mirroring the exec runner's
        corrupt-part re-queue."""
        with self._cond:
            if job.state != DONE or job.collected:
                return  # racing fetcher re-queued it / already served
            metrics.inc("serve.spool_corrupt")
            warn(f"job {job.id}: result spool corrupt at fetch time — "
                 f"re-queueing the job")
            job.state = QUEUED
            job.done.clear()
            job.result = None
            job.spool = None
            job.attempts.append({
                "n": len(job.attempts), "engine": "primary",
                "class": "spool-corrupt", "action": "requeue",
                "error": "result spool failed size/CRC verification"})
            # it is live again: pull it back off the retention horizon,
            # or 1024 later terminals would evict it mid-queue (and its
            # re-completion would double-append the horizon entry)
            try:
                self._retired.remove(job.id)
            except ValueError:
                pass
            self._queue.insert(0, job)
            self._cond.notify_all()

    # ---------------------------------------------------------- lifecycle

    def _heartbeat_loop(self, interval: float) -> None:
        """Per-job progress heartbeat: one line per tick naming every
        running job with its scope's pack/queue/retrace summaries —
        the shard heartbeat's fields, re-keyed per job."""
        while not self._stop.wait(interval):
            with self._lock:
                running = [j for j in self._jobs.values()
                           if j.state == RUNNING]
                depth = len(self._queue)
                counts = dict(self._counts)
            fields = []
            for j in running:
                scope = metrics.job_scope(j.id)
                dt = (time.perf_counter() - j.started_at
                      if j.started_at else 0.0)
                fields.append(
                    f"{j.id}@{hb.Heartbeat._short(j.worker or '?')}"
                    f" {dt:.1f}s pack[{hb.pack_summary_str(scope)}]"
                    f" queue[{hb.queue_summary_str(scope)}]"
                    f" retrace[{hb.retrace_summary(scope)}]")
            _eprint(f"heartbeat: {counts.get('done', 0)} done, "
                    f"{counts.get('failed', 0)} failed, "
                    f"{len(running)} running"
                    + (" (" + "; ".join(fields) + ")" if fields else "")
                    + f", {depth} queued, "
                    f"peak_rss={metrics.peak_rss_bytes() >> 20}MB")

    def _spawn_worker(self, w: _ChipWorker) -> None:
        t = threading.Thread(target=self._worker_loop, args=(w,),
                             name=f"racon-serve-{w.worker}",
                             daemon=True)
        t.start()
        # registered under the slots lock (startup and the supervisor
        # both spawn), and only AFTER start() — a registered-but-not-
        # started thread reads as dead and would trip the supervisor
        with self._slots_lock:
            self._threads.append(t)
            self._slot_threads[w.ordinal] = t

    def start_workers(self) -> None:
        """Spawn the pool's worker threads plus their supervisor
        (idempotent; split out so tests can exercise the queue
        deterministically before any worker drains it)."""
        if self._threads:
            return
        for w in self._chip_slots():
            self._spawn_worker(w)
        # graftlint: disable=lock-discipline (start_workers runs once, guarded by the _threads check, on the single startup path)
        self._supervisor = threading.Thread(
            target=self._supervise_loop,
            name="racon-serve-supervisor", daemon=True)
        self._supervisor.start()

    def _bind(self) -> socket.socket:
        path = self.socket_path
        if os.path.exists(path):
            import stat as stat_mod
            if not stat_mod.S_ISSOCK(os.stat(path).st_mode):
                # refuse, don't unlink: a typo'd --serve path must not
                # delete the operator's regular file
                raise RuntimeError(
                    f"{path} exists and is not a socket — refusing to "
                    f"replace it")
            # a previous server may have died without unlinking; only a
            # CONNECTABLE socket proves a live one
            probe = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
            probe.settimeout(1.0)
            try:
                probe.connect(path)
            except OSError as e:
                log_swallowed("serve: removing stale socket file", e)
                os.unlink(path)
            else:
                raise RuntimeError(
                    f"another server is already listening on {path}")
            finally:
                probe.close()
        listener = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        listener.bind(path)
        listener.listen(64)
        return listener

    def serve_forever(self) -> int:
        """Bind, warm the pool, accept until :meth:`shutdown`.  Returns
        an exit code (0 on a clean stop)."""
        compilewatch.arm()
        # a fresh server owns the process's warm-path state: re-open
        # the seal and drop stale attribution — events/counts AND the
        # registry's compile.* timers/counters, so a second in-process
        # server does not report a predecessor's total_s next to
        # count=0 (matters for in-process test servers sharing one
        # interpreter; production runs one server per process, where
        # this is a startup no-op)
        compilewatch.reset()
        metrics.clear("compile.")
        # span TIMERS must record for the life of the server: the
        # per-job dispatch/fetch split reads them through each job's
        # metric scope (ring-buffer tracing stays off — a long-lived
        # daemon's trace is unbounded by definition)
        from ..obs import trace
        trace.activate()
        # serve_forever runs on exactly ONE thread per server (the
        # process main thread in production, the single spawner thread
        # in tests) — its attribute writes below never race themselves
        # graftlint: disable=lock-discipline (serve_forever runs on exactly one thread per server instance)
        self._listener = self._bind()
        # restart recovery BEFORE any worker can drain the queue: the
        # journal's live jobs re-enter in submission order
        self._recover()
        self._warm_pool()
        if self.autostart:
            self.start_workers()
        # graceful drain on SIGTERM (the preemption signal): stop
        # admission, finish in-flight jobs, flush the journal, exit.
        # Only the process main thread may install handlers (in-process
        # test servers run serve_forever on a spawned thread).
        if threading.current_thread() is threading.main_thread():
            try:
                signal_mod.signal(
                    signal_mod.SIGTERM,
                    lambda *_: threading.Thread(
                        target=self.shutdown, kwargs={"mode": "drain"},
                        name="racon-serve-drain", daemon=True).start())
            except (ValueError, OSError) as e:
                log_swallowed("serve: SIGTERM drain handler "
                              "unavailable", e)
        interval = flags.get_float("RACON_TPU_HEARTBEAT_S")
        if interval > 0:
            t = threading.Thread(target=self._heartbeat_loop,
                                 args=(interval,),
                                 name="racon-serve-heartbeat",
                                 daemon=True)
            t.start()
        if self.fleet_dir:
            # registered AFTER the socket is bound: the beacon
            # advertises a listener the gateway can actually reach
            from ..fleet import registry as fleet_registry
            beacon = fleet_registry.HostBeacon(
                self.fleet_dir, socket_path=self.socket_path).start()
            # written once before the accept loop starts; shutdown
            # reads it only after _stop is set
            self._beacon = beacon  # graftlint: disable=lock-discipline (pre-accept-loop write)
            _eprint(f"fleet member {self._beacon.name} registered "
                    f"in {self.fleet_dir}")
        _eprint(f"listening on {self.socket_path} "
                f"(server {self.worker})")
        self.started.set()
        try:
            while not self._stop.is_set():
                try:
                    conn, _ = self._listener.accept()
                except OSError:
                    break  # listener closed by shutdown()
                t = threading.Thread(target=self._handle_conn,
                                     args=(conn,), daemon=True)
                t.start()
                self._conn_threads.append(t)
                # graftlint: disable=lock-discipline (serve_forever runs on exactly one thread per server instance)
                self._conn_threads = [c for c in self._conn_threads
                                      if c.is_alive()]
        finally:
            self.shutdown()
            for t in list(self._threads):
                t.join()
            if self._supervisor is not None:
                self._supervisor.join()
            self._finish_journal()
        _eprint(f"stopped ({self._counts['done']} done, "
                f"{self._counts['failed']} failed, "
                f"{self._counts['rejected']} rejected)")
        return 0

    def _finish_journal(self) -> None:
        """Final flush: one last live-jobs-only compaction (every
        worker has exited, so the snapshot is the run's terminal truth)
        and a clean close — the 'flushes the journal, then exits' leg
        of the drain contract."""
        if self._journal is None:
            return
        try:
            self._compact()
        except Exception as e:
            log_swallowed("serve: final journal compaction failed "
                          "(the un-compacted journal replays fine)", e)
        self._journal.close()

    def shutdown(self, mode: str = "now") -> None:
        """Stop the server (idempotent).  ``mode="now"``: stop
        admission and scheduling immediately — running jobs finish,
        queued jobs are answered FAILED in RAM but deliberately NOT
        journaled as failed, so a ``--serve-dir`` server recovers and
        runs them after restart.  ``mode="drain"``: stop admission,
        wait (bounded by ``RACON_TPU_SERVE_DRAIN_S``) for the queue
        AND the in-flight jobs to finish, then stop."""
        if mode == "drain" and not self._stop.is_set():
            with self._cond:
                self._draining = True
            _eprint("drain: admission stopped — finishing queued "
                    "and in-flight jobs")
            bound = flags.get_float("RACON_TPU_SERVE_DRAIN_S")
            deadline = (time.monotonic() + bound) if bound > 0 \
                else None
            drained = True
            with self._cond:
                while self._queue or any(
                        j.state == RUNNING
                        for j in self._jobs.values()):
                    if self._stop.is_set():
                        drained = False
                        break
                    if deadline is not None and \
                            time.monotonic() > deadline:
                        warn(f"drain: still busy after {bound:.0f}s "
                             f"(RACON_TPU_SERVE_DRAIN_S) — stopping "
                             f"anyway")
                        drained = False
                        break
                    self._cond.wait(0.2)
            if drained:
                _eprint("drain: all jobs finished")
        if self._stop.is_set():
            return
        self._stop.set()
        if self._beacon is not None:
            # deregister (clean goodbye): the gateway sees the beacon
            # withdrawn instead of waiting a TTL to declare us dead
            self._beacon.stop()
            self._beacon = None  # graftlint: disable=lock-discipline (_stop-gated shutdown)
        with self._cond:
            for job in self._queue:
                job.state = FAILED
                job.shutdown_orphan = self._journal is not None
                job.error = ("server shutdown before the job ran"
                             + (" — it is journaled and will recover "
                                "on restart from the same --serve-dir"
                                if self._journal is not None else ""))
                job.done.set()
            self._queue.clear()
            self._cond.notify_all()
        if self._listener is not None:
            try:
                # shutdown() BEFORE close(): a close alone does not
                # reliably wake a thread blocked in accept() on Linux —
                # the accept loop would outlive the server
                self._listener.shutdown(socket.SHUT_RDWR)
            except OSError as e:
                log_swallowed("serve: listener shutdown failed", e)
            try:
                self._listener.close()
            except OSError as e:
                log_swallowed("serve: listener close failed", e)
        try:
            os.unlink(self.socket_path)
        except FileNotFoundError:
            pass
        except OSError as e:
            log_swallowed("serve: socket unlink failed", e)


def spec_summary(spec: dict) -> str:
    """One-line human summary of a job spec (report argv, logs)."""
    return (f"{os.path.basename(spec['sequences'])} "
            f"{os.path.basename(spec['overlaps'])} "
            f"{os.path.basename(spec['target_sequences'])} "
            f"-w {spec['window_length']} -t {spec['threads']}"
            + (" -f" if spec["fragment_correction"] else "")
            + (" -u" if spec["include_unpolished"] else ""))
