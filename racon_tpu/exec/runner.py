"""ShardRunner: crash-safe, multi-worker streaming of a polishing run.

Round 9 made one process stream a run shard-by-shard with checkpoints;
round 12 makes the manifest a *coordination point*: N concurrent
runners (``--workers N``, or independently launched ``racon`` processes
pointed at the same ``--shard-dir`` — same host or hosts sharing the
directory) drain one manifest together.

- **Leases** (:mod:`.lease`): a worker claims a shard by creating its
  ``lease_NNNN.json`` with ``O_EXCL`` and keeps it alive by refreshing
  the file's mtime; a worker that dies stops heartbeating, its lease
  expires after ``RACON_TPU_EXEC_LEASE_TTL_S``, and another worker
  breaks the lease and reclaims the shard. Parts are written
  tmp->rename with worker-unique tmp names and shard output is
  deterministic, so kill-then-reclaim keeps the merged FASTA
  byte-identical (the chaos soak in ``tests/test_faults.py`` proves
  it under seeded SIGKILLs and injected faults).
- **Degradation ladder**: a failed shard attempt is classified
  (:func:`racon_tpu.faults.classify`) and degraded per class —
  ``transient-io`` retries the same engine under exponential backoff
  with deterministic jitter; ``device-oom`` applies memory
  backpressure (the consensus engine halves its pair-arena/group
  capacity and the shard re-dispatches on the device); only then come
  the CPU engines, and quarantine is the last rung. Every attempt is
  recorded in the shard's manifest entry and the run report's
  ``faults`` section.
- **Part durability**: each completed part records its byte size and
  CRC32; the pre-merge verification pass re-reads every part and
  re-queues a truncated/corrupt one instead of emitting a corrupt
  assembly.
- **Chip scheduler** (round 13): one invocation drives every local
  device. When a device backend is in use and the host has several
  chips (or ``--chips N`` asks for them), the runner spawns one
  in-process chip worker per device — each with its OWN
  aligner/consensus pair pinned via ``jax.default_device`` (so every
  chip runs the full single-device fast path: ragged packing,
  streaming sessions, SWAR) — and the workers drain the SAME manifest
  through the round-12 lease files, exactly like ``--workers``
  subprocesses or shared-FS workers: no new coordination code, chips
  and processes and hosts all interleave on one run. The plan carries
  an advisory LPT chip assignment (each worker drains its own shards
  first, then steals); a plan shard marked ``device = -1`` (one contig
  dominating the run) is instead mesh-sharded over ALL chips by the
  primary slot via the ``racon_tpu.parallel`` ``shard_map`` path.
  Device-OOM backpressure (``reduce_capacity``) acts on the failing
  worker's own engines — per *device*, not per process.

Completed parts finally merge back into target-file order, which makes
the output byte-identical to a single-shot run — the invariance proofs
live in ``tests/test_exec.py`` and ``tests/test_faults.py``.
"""

from __future__ import annotations

import hashlib
import json
import os
import shutil
import sys
import threading
import time
import zlib
from typing import Dict, List, Optional, Tuple

import numpy as np

from .. import faults, flags, obs, sanitize
from ..core.backends import make_aligner, make_consensus
from ..core.polisher import PolisherType, create_polisher
from ..io import parsers
from ..obs import metrics, report as obs_report
from ..utils.logger import warn
from . import heartbeat as hb
from . import lease as lease_mod
from . import manifest as mf
from .index import RunIndex, build_index, build_index_auto
from .planner import (MESH_DEVICE, ShardPlan, assign_devices,
                      plan_shards)

# verification/re-queue rounds before a persistently-corrupt part is a
# hard error (each round re-polishes the shard from scratch)
_MAX_VERIFY_ROUNDS = 3
# how long a secondary worker waits for the primary to publish the
# manifest before giving up
_SECONDARY_MANIFEST_WAIT_S = 120.0


def _eprint(msg: str) -> None:
    print(f"[racon_tpu::exec] {msg}", file=sys.stderr, flush=True)


def _plain_ext(path: str, candidates, default: str) -> str:
    """Output extension for extracted (always-uncompressed) spans."""
    base = path[:-3] if path.endswith(".gz") else path
    for ext in candidates:
        if not ext.endswith(".gz") and base.endswith(ext):
            return ext
    return default


def _terminal(entry: dict) -> bool:
    return entry.get("status") in (mf.DONE, mf.QUARANTINED)


class _ChipWorker:
    """One in-process executor slot of the chip scheduler: a worker
    identity (suffixed ``#chipK`` so leases/manifest rows attribute
    work per chip), an engine pair pinned to its local device, and —
    for slot 0 only — the mesh engines that run dominant-contig shards
    sharded over ALL chips. The legacy single-chip path is exactly one
    unpinned slot whose worker id is the profile's own.

    ``profile`` is duck-typed — anything carrying the engine recipe
    (``num_threads``, ``match``/``mismatch``/``gap``, ``banded``,
    ``aligner_backend``/``consensus_backend``, ``aligner_batches``/
    ``consensus_batches``), a ``worker`` identity string, and (for the
    mesh slot only) ``_chip_slots()``.  :class:`ShardRunner` passes
    itself; the resident polishing service (``racon_tpu.serve``) passes
    its ``PolishServer`` so one warm, chip-pinned engine pool serves
    both the shard drain loop and long-lived job execution."""

    def __init__(self, profile, slot, pinned: bool):
        self.profile = profile
        self.slot = slot                      # topology.ChipSlot
        self.ordinal = slot.ordinal
        self.device = slot.device if pinned else None
        self.worker = (f"{profile.worker}#{slot.key}" if pinned
                       else profile.worker)
        self.can_mesh = slot.ordinal == 0
        self.engines = None
        self.cpu_engines = None
        self.mesh_engines = None
        # serve-mode slot supervision: the job this slot is currently
        # executing (set by the scheduler under its lock, read by the
        # supervisor when the slot's thread dies so the job can fail
        # down the ladder instead of staying RUNNING forever)
        self.current_job = None

    def get_engines(self, cpu: bool, mesh: bool = False):
        # the engine caches below are deliberately lock-free: a slot is
        # drained by exactly one worker thread for its whole life (the
        # drain loop passes `worker=self`), and the serve pool builds
        # every slot's engines in _warm_pool BEFORE start_workers()
        # spawns a consumer — Thread.start() is the happens-before edge
        r = self.profile
        if cpu:
            if self.cpu_engines is None:
                # graftlint: disable=lock-discipline (one drain thread per slot; serve warms before workers start)
                self.cpu_engines = (
                    make_aligner("auto", r.num_threads),
                    make_consensus("auto", r.match, r.mismatch, r.gap,
                                   r.num_threads))
            return self.cpu_engines
        if mesh:
            # dominant-contig shards: batches mesh-shard over every
            # local chip via the parallel shard_map path (primary slot
            # only — one mesh run at a time by lease exclusion)
            if self.mesh_engines is None:
                from ..parallel import get_mesh
                # the RUN's chip set, not every visible device: a
                # --chips 2 run on an 8-chip host must not trample the
                # six excluded chips' HBM (nor inflate its own curve)
                mesh_obj = get_mesh(devices=[
                    w.device for w in r._chip_slots()])
                # graftlint: disable=lock-discipline (one drain thread per slot; serve warms before workers start)
                self.mesh_engines = (
                    make_aligner(r.aligner_backend, r.num_threads,
                                 num_batches=r.aligner_batches,
                                 mesh=mesh_obj),
                    make_consensus(r.consensus_backend, r.match,
                                   r.mismatch, r.gap, r.num_threads,
                                   num_batches=r.consensus_batches,
                                   banded=r.banded, mesh=mesh_obj))
            return self.mesh_engines
        if self.engines is None:
            # graftlint: disable=lock-discipline (one drain thread per slot; serve warms before workers start)
            self.engines = (
                make_aligner(r.aligner_backend, r.num_threads,
                             num_batches=r.aligner_batches,
                             device=self.device),
                make_consensus(r.consensus_backend, r.match,
                               r.mismatch, r.gap, r.num_threads,
                               num_batches=r.consensus_batches,
                               banded=r.banded, device=self.device))
        return self.engines

    def drain_warmups(self) -> None:
        """Wait for the warm-ups the slot's shards kicked on its device
        engines. A shard's polisher hands its engines on, warm-up and
        all (``create_polisher``'s ``final`` off), so the slot waits
        once, when it has no shard left — whichever shard was its last,
        and however that one ended (``drain_warmup``'s docstring names
        what a warm-up left running costs the process's next job)."""
        for engines in (self.engines, self.mesh_engines):
            drain = getattr(engines and engines[1], "drain_warmup", None)
            if drain is not None:
                drain()

    def reduce_capacity(self, mesh: bool = False) -> bool:
        """Memory backpressure for a device-oom fault, scoped to THIS
        worker's engines — per device, not per process: chip 3 OOMing
        must not shrink chip 0's arenas. False once the engines can
        shrink no further (or expose no knob — CPU engines)."""
        engines = self.mesh_engines if mesh else self.engines
        if engines is None:
            return False
        reduced = False
        for eng in engines:
            shrink = getattr(eng, "reduce_capacity", None)
            if shrink is not None and shrink():
                reduced = True
        return reduced


class ShardRunner:
    """Bounded-memory, checkpointed, lease-coordinated drive of the
    polishing pipeline."""

    def __init__(self, sequences: str, overlaps: str, target_sequences: str,
                 *, type_: PolisherType = PolisherType.C,
                 window_length: int = 500, quality_threshold: float = 10.0,
                 error_threshold: float = 0.3, trim: bool = True,
                 match: int = 3, mismatch: int = -5, gap: int = -4,
                 num_threads: int = 1, aligner_backend: str = "auto",
                 consensus_backend: str = "auto", aligner_batches: int = 1,
                 consensus_batches: int = 1, banded: bool = False,
                 include_unpolished: bool = False, n_shards: int = 0,
                 max_ram_bytes: int = 0, max_target_bytes: int = 0,
                 resume: bool = False, work_dir: Optional[str] = None,
                 keep_work_dir: Optional[bool] = None,
                 merge: bool = True, secondary: bool = False,
                 defer_cleanup: bool = False, chips: int = 0):
        self.sequences = os.path.abspath(sequences)
        # --overlaps auto: normalize to the sentinel (there is no file
        # to abspath); run() materializes the overlapper's PAF into the
        # work dir and repoints self.overlaps at it before indexing
        self.overlaps = (parsers.AUTO_OVERLAPS
                         if parsers.overlaps_mode(overlaps) == "auto"
                         else os.path.abspath(overlaps))
        self.target_sequences = os.path.abspath(target_sequences)
        self.type = type_
        self.window_length = window_length
        self.quality_threshold = quality_threshold
        self.error_threshold = error_threshold
        self.trim = trim
        self.match, self.mismatch, self.gap = match, mismatch, gap
        self.num_threads = num_threads
        self.aligner_backend = aligner_backend
        self.consensus_backend = consensus_backend
        self.aligner_batches = aligner_batches
        self.consensus_batches = consensus_batches
        self.banded = banded
        self.include_unpolished = include_unpolished
        self.n_shards = n_shards
        self.max_ram_bytes = max_ram_bytes
        self.max_target_bytes = max_target_bytes
        self.resume = resume
        # merge=False / secondary=True: a cooperating drain-only worker
        # (spawned by --workers, or launched by hand): it claims and
        # polishes shards but emits no merged FASTA, adopts the
        # primary's manifest instead of planning its own, and never
        # cleans the shared work dir
        self.merge = merge and not secondary
        self.secondary = secondary
        self.defer_cleanup = defer_cleanup
        self.worker = lease_mod.worker_identity()
        # an explicit work dir is the user's to keep (resume workflows);
        # a derived one is removed after a fully successful run.
        # Secondary workers never remove the shared directory.
        self.keep_work_dir = (keep_work_dir if keep_work_dir is not None
                              else (work_dir is not None or secondary))
        self.work_dir = os.path.abspath(work_dir or self.derive_work_dir())
        # in-process chip workers (round 13): 0 = automatic — every
        # local device when an accelerator backend is in use on real
        # hardware (the virtual CPU test mesh never auto-engages; pass
        # --chips/RACON_TPU_CHIPS to force it there); 1 pins the legacy
        # single-chip path
        self.chips_requested = chips
        self.index: Optional[RunIndex] = None
        self.plan: Optional[ShardPlan] = None
        self.summary: Dict = {}
        self.report: Dict = {}     # obs run report (also in work_dir)
        self._slots: Optional[List[_ChipWorker]] = None
        self._retry_quarantined: set = set()  # resume: claimable again
        self._initially_done: set = set()     # resume-skip bookkeeping
        self._announced: set = set()
        self._beat = None          # heartbeat (owns Mbp attribution)
        # shared-manifest discipline for concurrent chip workers: entry
        # mutations and snapshot serialization must not interleave.
        # named_lock: under RACON_TPU_SANITIZE=1 these feed the
        # lock-order witness (cycle = potential deadlock, reported at
        # process exit)
        self._mf_lock = sanitize.named_lock("exec.manifest")
        self._note_lock = sanitize.named_lock("exec.notes")
        # chip-pool unwind: any worker thread dying sets this so the
        # siblings stop polling (a dead primary's pending mesh shard
        # would otherwise never turn terminal and the pool would hang)
        self._abort = threading.Event()
        # shared state-file scan (multi-slot runs): N idle workers
        # re-reading the whole state directory every poll tick would
        # multiply the shared-FS metadata I/O round 12 bounded
        self._states_lock = sanitize.named_lock("exec.states")
        self._states_cache: Tuple[float, Dict[int, dict]] = (-1e9, {})

    # ------------------------------------------------------------ identity

    def derive_work_dir(self) -> str:
        """Deterministic default work dir: same inputs + parameters =>
        same directory, so ``--resume`` (and cooperating workers) need
        no extra bookkeeping."""
        h = hashlib.sha1()
        for part in (self.sequences, self.overlaps, self.target_sequences,
                     self.type.name, self.window_length,
                     self.quality_threshold, self.error_threshold,
                     self.trim, self.match, self.mismatch, self.gap,
                     self.include_unpolished):
            h.update(repr(part).encode())
        return os.path.join(os.getcwd(),
                            f"racon_exec_{h.hexdigest()[:12]}")

    def _params_fingerprint(self) -> dict:
        return {"type": self.type.name,
                "window_length": self.window_length,
                "quality_threshold": self.quality_threshold,
                "error_threshold": self.error_threshold,
                "trim": self.trim, "match": self.match,
                "mismatch": self.mismatch, "gap": self.gap,
                "include_unpolished": self.include_unpolished}

    # ---------------------------------------------------------- chip slots

    def _chip_slots(self) -> List["_ChipWorker"]:
        """This run's in-process executor slots (resolved once).

        One unpinned slot — the exact legacy path — unless the chip
        scheduler engages: an explicit request (``--chips`` /
        ``RACON_TPU_CHIPS``) always wins; otherwise a device backend on
        a real multi-chip host auto-engages every local device. The
        virtual CPU test mesh (``xla_force_host_platform_device_count``)
        never auto-engages — 8 fake devices on one CPU are a debugging
        surface, not 8x compute — and a ``--workers`` run never
        auto-engages on EITHER side (the spawned secondaries, or the
        primary that spawned them — it shares the host's chips with
        those secondaries already): the operator chose process-level
        parallelism, so chips x workers on one host must be an explicit
        choice."""
        if self._slots is not None:
            return self._slots
        n = 1
        explicit = self.chips_requested > 0 \
            or flags.get_int("RACON_TPU_CHIPS") > 0
        # defer_cleanup marks the primary of a --workers spawn (the CLI
        # defers the work-dir cleanup past the secondaries' exit)
        multi_process = self.secondary or self.defer_cleanup
        if explicit:
            from ..parallel import topology
            n = topology.resolve_chips(self.chips_requested)
        elif not multi_process and \
                "tpu" in (self.aligner_backend, self.consensus_backend):
            from ..parallel import topology
            devs = topology.local_devices()
            if len(devs) > 1 and \
                    getattr(devs[0], "platform", "cpu") != "cpu":
                n = len(devs)
        if n <= 1:
            from ..parallel.topology import ChipSlot
            if explicit:
                # an EXPLICIT --chips 1 means "use one chip": pin the
                # first local device so the every-visible-device
                # auto-mesh cannot engage — this is what makes the
                # 1-chip point of a scaling curve actually one chip
                from ..parallel import topology
                devs = topology.local_devices()
                # resolved on the main path (run() sizes the plan by
                # len(_chip_slots()) BEFORE _drain spawns any worker),
                # so the thread-time calls below only ever hit the
                # resolved fast path
                # graftlint: disable=lock-discipline (resolved on the main path before worker threads spawn)
                self._slots = [_ChipWorker(
                    self, ChipSlot(0, devs[0] if devs else None),
                    pinned=bool(devs))]
            else:
                self._slots = [_ChipWorker(self, ChipSlot(0, None),
                                           pinned=False)]
        else:
            from ..parallel import topology
            topo = topology.Topology(n)
            self._slots = [_ChipWorker(self, s, pinned=True)
                           for s in topo.slots]
            _eprint(f"chip scheduler: {len(self._slots)} in-process "
                    f"chip workers ({topo.describe()['device_kind']})")
        return self._slots

    # back-compat internals (tests poke the round-12 names): the
    # primary slot's engine pairs
    @property
    def _engines(self):
        slots = self._slots
        return slots[0].engines if slots else None

    @property
    def _cpu_engines(self):
        slots = self._slots
        return slots[0].cpu_engines if slots else None

    # ----------------------------------------------------------------- run

    def run(self, out, begun: bool = False) -> Dict:
        """Execute (or resume / join) the full sharded run, writing the
        merged polished FASTA to the binary stream ``out`` (primary
        workers only). Returns the summary dict (also kept as
        :attr:`summary`). ``begun``: the caller has marked the run
        boundary itself (``cli.main``'s ``obs.begin``), and what the
        job counted since — the kernel probes' compiles, anything
        swallowed — belongs to this job's report."""
        t0 = time.perf_counter()
        t_start = time.time()
        # run boundary: drop per-run metrics so a second in-process run
        # (bench_shards, tests, future service mode) reports its own
        # pack/queue/retrace numbers, then arm the span timers (ring
        # buffers stay off unless the CLI requested a trace): every
        # exec run persists a run report next to the manifest and its
        # dispatch-vs-fetch split must hold real seconds, not
        # schema-valid zeros
        if not begun:
            metrics.clear_run()
        obs.trace.activate()
        if parsers.is_auto_overlaps(self.overlaps):
            # first-party overlapper: materialize a deterministic PAF
            # in the work dir (reused on resume — same bytes, so the
            # path+size resume fingerprint holds) and index that file;
            # every downstream byte-span consumer works unchanged
            os.makedirs(self.work_dir, exist_ok=True)
            auto_paf = os.path.join(self.work_dir, "auto_overlaps.paf")
            _eprint(f"overlapping {os.path.basename(self.sequences)} "
                    f"(first-party overlapper, worker {self.worker})")
            with obs.span("exec.index"):
                self.index = build_index_auto(
                    self.sequences, self.target_sequences, auto_paf,
                    self.type, self.error_threshold)
            self.overlaps = auto_paf
            # overlap occupancy + cache telemetry (round 21): surface
            # the chain-arena fill and target-table reuse the run just
            # paid for, so a badly-packed or cache-cold overlap phase
            # is visible at the top of the log, not only in the report
            o_total = metrics.counter("overlap.lanes_total")
            if o_total:
                _eprint(
                    f"overlap pack: "
                    f"{metrics.counter('overlap.lanes_occupied') / o_total:.2f}eff "
                    f"({metrics.counter('overlap.chunks')} chunks), "
                    f"table cache "
                    f"{metrics.counter('overlap.cache_hits')}h/"
                    f"{metrics.counter('overlap.cache_misses')}m, "
                    f"{metrics.counter('overlap.join_bailouts')} "
                    f"join bailout(s), join took "
                    f"{metrics.counter('overlap.join_read_kept')} of "
                    f"{metrics.counter('overlap.join_read_entries')} "
                    f"read minimizers")
        else:
            _eprint(f"indexing {os.path.basename(self.overlaps)} / "
                    f"{os.path.basename(self.sequences)} "
                    f"(worker {self.worker})")
            with obs.span("exec.index"):
                self.index = build_index(self.sequences, self.overlaps,
                                         self.target_sequences,
                                         self.type,
                                         self.error_threshold)
        base_rss = hb.peak_rss_bytes()
        with obs.span("exec.plan"):
            self.plan = plan_shards(self.index, self.n_shards,
                                    self.max_ram_bytes,
                                    self.max_target_bytes,
                                    base_rss=base_rss,
                                    n_devices=len(self._chip_slots()))
        os.makedirs(self.work_dir, exist_ok=True)
        # a valid resume/adopted manifest carries the stored plan (a
        # --max-ram plan depends on the planning process's live RSS, so
        # this process could legitimately compute a different one —
        # re-running completed shards over that would defeat --resume,
        # and cooperating workers cutting parts by different plans
        # would corrupt the merge)
        manifest = self._load_or_init_manifest()
        n = self.plan.n_shards
        total_mbp = sum(t.bases for t in self.index.targets) / 1e6
        _eprint(f"plan: {len(self.index.targets)} contigs "
                f"({total_mbp:.2f} Mbp), {len(self.index.ov_start)} "
                f"overlaps -> {n} shards (mode={self.plan.mode})")
        beat = self._beat = hb.Heartbeat(n, worker=self.worker).start()
        try:
            # only a worker that will MERGE verifies parts: it is the
            # emitted assembly the CRC pass protects, and N workers
            # each re-reading the whole part set would multiply the
            # post-polish I/O for no additional safety
            for round_no in range(_MAX_VERIFY_ROUNDS):
                self._drain(manifest, beat)
                bad = self._verify_parts(manifest) if self.merge else []
                if not bad:
                    break
                for si in bad:
                    self._requeue_shard(si, manifest,
                                        "part verification failed")
            else:
                raise RuntimeError(
                    f"parts still failing verification after "
                    f"{_MAX_VERIFY_ROUNDS} re-polish rounds — refusing "
                    f"to emit a corrupt assembly")
            # one final fully-merged snapshot per worker: per-transition
            # saves fold in only the owned entry (O(shards^2) avoidance),
            # so the on-disk manifest converges to the all-states truth
            # here, where the run's terminal picture is what matters
            mf.merge_states(manifest,
                            mf.load_shard_states(self.work_dir))
            mf.save_manifest(self.work_dir, manifest)
            if self.merge:
                beat.update(phase="merging")
                with obs.span("exec.merge"):
                    self._merge_parts(manifest, out)
        finally:
            beat.stop()

        quarantined = [e for e in manifest["shards"]
                       if e["status"] == mf.QUARANTINED]
        for e in quarantined:
            warn(f"shard {e['id']} quarantined: {e.get('reason')}")
        mbp_done = sum(e.get("mbp", 0.0) for e in manifest["shards"]
                       if e["status"] == mf.DONE)
        wall = time.perf_counter() - t0
        self.summary = {
            "n_shards": n, "mode": self.plan.mode,
            "worker": self.worker,
            "chips": len(self._chip_slots()),
            "devices": metrics.device_summary(),
            "mbp_total": round(total_mbp, 4),
            "mbp_polished": round(mbp_done, 4),
            "wall_s": round(wall, 2),
            "mbp_per_sec": round(mbp_done / wall, 4) if wall else 0.0,
            "peak_rss_bytes": hb.peak_rss_bytes(),
            "base_rss_bytes": base_rss,
            "budget_bytes": self.plan.budget_bytes,
            "quarantined": [e["id"] for e in quarantined],
            "consensus_pack": metrics.pack_summary(),
            "faults": metrics.group("faults."),
            "lease": metrics.group("lease."),
            "shards": [dict(e) for e in manifest["shards"]],
        }
        # machine-readable run report next to the manifest (same durable
        # write protocol): the heartbeat and service-mode job
        # accounting are views over this artifact.
        # An explicit --shard-dir (or a quarantine) keeps it on disk; a
        # derived work dir takes it down with the rest of a fully
        # successful run — pass --run-report for a copy that survives.
        self.report = obs_report.build_report(
            "exec", started_unix=t_start, wall_s=wall,
            shards=manifest["shards"])
        mf.durable_write(os.path.join(self.work_dir, mf.REPORT_NAME),
                         json.dumps(self.report, indent=1).encode())
        if not self.defer_cleanup:
            self.cleanup_work_dir()
        return self.summary

    def cleanup_work_dir(self) -> None:
        """Remove a derived work dir after a fully successful run (an
        explicit/kept dir, a secondary worker, or a run with
        quarantined shards leaves it in place)."""
        if self.summary.get("quarantined") or self.keep_work_dir:
            return
        shutil.rmtree(self.work_dir, ignore_errors=True)

    # ------------------------------------------------------------ manifest

    def _load_or_init_manifest(self) -> dict:
        fingerprint = mf.input_fingerprint(
            (self.sequences, self.overlaps, self.target_sequences),
            self._params_fingerprint())
        manifest = None
        rejected = False
        if self.secondary:
            manifest = self._await_manifest(fingerprint)
            if not self._adopt_plan(manifest):
                raise RuntimeError(
                    "the published manifest's shard plan does not "
                    "cover this input — refusing to join it")
        elif self.resume:
            manifest = mf.load_manifest(self.work_dir)
            if manifest is not None and \
                    manifest["fingerprint"] != fingerprint:
                warn("manifest fingerprint does not match this run's "
                     "inputs/parameters — re-running every shard")
                manifest, rejected = None, True
            if manifest is not None and not self._adopt_plan(manifest):
                manifest, rejected = None, True
        if (not self.resume and not self.secondary) or rejected:
            self._clean_work_dir()
        if manifest is None:
            fresh = {
                "fingerprint": fingerprint,
                # "device" is the planner's ADVISORY chip assignment
                # (-1 = mesh over all chips); workers adopting the plan
                # re-derive it for their own local topology
                "shards": [{"id": si, "contigs": list(map(int, shard)),
                            "status": mf.PENDING,
                            "part": f"part_{si:04d}.fasta",
                            **({"device": self.plan.device_of(si)}
                               if self.plan.devices else {})}
                           for si, shard in enumerate(self.plan.shards)],
            }
            # atomic create-if-absent: of N concurrently-starting
            # workers exactly one publishes its plan; the losers adopt
            # the winner's (identical inputs, possibly different
            # --max-ram plan — the parts must all be cut by ONE plan)
            manifest = mf.create_manifest_if_absent(self.work_dir, fresh)
            if manifest is not fresh and not self._adopt_plan(manifest):
                raise RuntimeError(
                    "another worker published a manifest whose shard "
                    "plan does not cover this input — refusing to "
                    "join it")
        # overlay the authoritative per-shard state files (they win
        # over whatever snapshot the manifest holds)
        mf.merge_states(manifest, mf.load_shard_states(self.work_dir))
        for e in manifest["shards"]:
            if e["status"] == mf.DONE:
                # trusted for now; the pre-merge CRC verification pass
                # re-queues any part that is missing/truncated/corrupt
                self._initially_done.add(int(e["id"]))
            elif e["status"] == mf.QUARANTINED and \
                    (self.resume or self.secondary):
                # a new run gets to retry what a previous run gave up on
                self._retry_quarantined.add(int(e["id"]))
        return manifest

    def _await_manifest(self, fingerprint) -> dict:
        """Secondary workers adopt, never plan: poll until the primary
        has published a manifest for these inputs."""
        deadline = time.monotonic() + _SECONDARY_MANIFEST_WAIT_S
        while True:
            manifest = mf.load_manifest(self.work_dir)
            if manifest is not None and \
                    manifest["fingerprint"] == fingerprint:
                return manifest
            if time.monotonic() > deadline:
                raise RuntimeError(
                    f"secondary worker {self.worker}: no manifest for "
                    f"these inputs appeared in {self.work_dir} within "
                    f"{_SECONDARY_MANIFEST_WAIT_S:.0f}s")
            time.sleep(0.1)

    def _adopt_plan(self, manifest: dict) -> bool:
        """Adopt the stored shard plan (the one the parts were/will be
        cut by); False when it does not cover this input's contigs."""
        stored = [list(map(int, e["contigs"]))
                  for e in manifest["shards"]]
        if sorted(ci for s in stored for ci in s) == \
                list(range(len(self.index.targets))):
            self.plan.shards = stored
            # the chip assignment is process-local (another worker's
            # ordinals mean nothing here): re-derive it from the
            # adopted shard map against THIS process's topology
            self.plan.devices = assign_devices(
                stored, self.plan.contig_cost, len(self._chip_slots()))
            return True
        warn("manifest shard plan does not cover this input's "
             "contigs — re-running every shard")
        return False

    def _clean_work_dir(self) -> None:
        """Drop recognized artifacts of a previous run (fresh, non-resume
        runs must not trust stale parts) — including torn ``*.tmp.*``
        leftovers of crashed atomic writes and lock/lease tombstones,
        whose monotonic-ns names are never reused and would otherwise
        litter a crash-retried work dir forever. Refuses to clean while
        another worker holds a live lease: a plain (non ``--resume``)
        launch into a shard dir with a run in progress must not destroy
        its checkpoints."""
        for name in os.listdir(self.work_dir):
            if name.startswith(lease_mod.LEASE_PREFIX) \
                    and name.endswith(".json"):
                sid = name[len(lease_mod.LEASE_PREFIX):-len(".json")]
                if not sid.isdigit():
                    continue
                probe = lease_mod.try_claim(self.work_dir, int(sid),
                                            self.worker)
                if probe is None:
                    raise RuntimeError(
                        f"{self.work_dir} has a live shard lease "
                        f"({name}) — another worker is mid-run there. "
                        f"Pass --resume to cooperate with it, or pick "
                        f"a different --shard-dir.")
                probe.release()  # dead leftover: claimable, hence safe
        for name in os.listdir(self.work_dir):
            path = os.path.join(self.work_dir, name)
            if name in (mf.MANIFEST_NAME, mf.REPORT_NAME) \
                    or name.startswith(("part_", mf.STATE_PREFIX,
                                        lease_mod.LEASE_PREFIX,
                                        "plan.lock")) \
                    or ".tmp." in name:
                try:
                    os.unlink(path)
                except FileNotFoundError:
                    pass
            elif name.startswith("shard_") and os.path.isdir(path):
                shutil.rmtree(path, ignore_errors=True)

    def _save(self, entry: dict, manifest: dict) -> None:
        """Durably record one owned shard's state, then refresh the
        manifest snapshot. State files are authoritative and the
        snapshot is advisory, so only the OWNED entry is folded in here
        (it already sits in ``manifest["shards"]``); other workers'
        newer states were merged at the top of the drain pass and
        converge on their own transitions — re-reading every state file
        per write would be O(shards^2) metadata I/O on the shared
        filesystems multi-worker runs target."""
        with self._mf_lock:
            # fsync-under-lock is the POINT of this lock: the snapshot
            # serializes `manifest` while sibling chip workers mutate
            # entries in place (dumps during mutation raises), and
            # interleaved state/snapshot writes would invert the
            # state-then-snapshot crash ordering. Hold time is one
            # small JSON per shard transition.
            # graftlint: disable=blocking-under-lock (the lock exists to serialize these durable writes against entry mutation)
            mf.save_shard_state(self.work_dir, entry)
            # graftlint: disable=blocking-under-lock (same serialization: snapshot must not interleave with state writes)
            mf.save_manifest(self.work_dir, manifest)

    def _save_owned(self, entry: dict, manifest: dict, claim) -> None:
        """Terminal-state write under lease-ownership proof: a worker
        whose lease was broken (it stalled past the TTL and another
        worker reclaimed the shard) must NOT write — the reclaimer owns
        the state file now, and overwriting its ``done`` with our
        late ``quarantined`` would silently drop the shard from the
        merge. The part write that may have preceded this is harmless:
        both workers' parts are byte-identical by determinism."""
        if claim.lost.is_set() or not claim.heartbeat():
            metrics.inc("lease.stale_write_suppressed")
            warn(f"shard {entry['id']}: lease was broken while this "
                 f"worker ran — discarding its late "
                 f"{entry.get('status')} result (the reclaiming "
                 f"worker's state stands)")
            # reload the reclaimer's truth so our in-memory manifest
            # does not carry the suppressed result forward
            fresh = mf.load_shard_state(self.work_dir, int(entry["id"]))
            if fresh is not None:
                with self._mf_lock:
                    entry.clear()
                    entry.update(fresh)
            return
        self._save(entry, manifest)

    # ---------------------------------------------------------- drain loop

    def _drain(self, manifest: dict, beat) -> None:
        """Drain the manifest with every executor slot: the single-slot
        case runs the claim loop inline (the legacy path, byte for
        byte); with the chip scheduler engaged, one thread per chip
        worker runs the SAME loop — coordination is entirely the lease
        files, so in-process chips, ``--workers`` subprocesses and
        shared-FS workers interleave on one manifest with no extra
        protocol."""
        slots = self._chip_slots()
        if len(slots) == 1:
            self._drain_loop(slots[0], manifest, beat)
            return
        self._abort.clear()
        errors: List[BaseException] = []

        def body(worker: "_ChipWorker") -> None:
            try:
                self._drain_loop(worker, manifest, beat)
            # graftlint: disable=swallowed-exception (re-raised below after the join)
            except BaseException as e:
                errors.append(e)
                # unwind the pool: siblings must not keep polling for
                # shards only the dead worker could run (a mesh shard
                # of a dead primary never turns terminal)
                self._abort.set()

        threads = [threading.Thread(target=body, args=(w,),
                                    name=f"racon-{w.slot.key}",
                                    daemon=True)
                   for w in slots[1:]]
        for t in threads:
            t.start()
        body(slots[0])
        for t in threads:
            t.join()
        if errors:
            raise errors[0]

    def _shard_order(self, worker: "_ChipWorker") -> List[int]:
        """The order a slot walks the plan: mesh-marked shards first
        (primary slot only — they are the biggest by construction),
        then the slot's own assigned shards, then everyone else's
        (work stealing through the lease protocol keeps a fast chip
        from idling behind a slow one's backlog)."""
        n = self.plan.n_shards
        devs = self.plan.devices
        if not devs:
            return list(range(n))
        mesh = [si for si in range(n) if devs[si] == MESH_DEVICE]
        mine = [si for si in range(n) if devs[si] == worker.ordinal]
        rest = [si for si in range(n)
                if devs[si] != MESH_DEVICE and devs[si] != worker.ordinal]
        return (mesh if worker.can_mesh else []) + mine + rest

    def _drain_loop(self, worker: "_ChipWorker", manifest: dict,
                    beat) -> None:
        """Claim-and-run until every shard is terminal: each pass walks
        the plan (own shards first), claims what it can, and runs what
        it claims; when every remaining shard is leased by another live
        worker, poll — a lease whose worker died expires after the TTL
        and the next pass reclaims the shard."""
        poll_s = max(0.05, flags.get_float("RACON_TPU_EXEC_POLL_S"))
        multi = len(self._chip_slots()) > 1
        if multi:
            # mirror this thread's span timers under device.<ordinal>.*
            # so the run report gets per-chip dispatch/fetch seconds
            obs.trace.set_timer_prefix(f"device.{worker.ordinal}.")
        try:
            self._drain_loop_inner(worker, manifest, beat, poll_s,
                                   multi)
            with obs.span("exec.drain"):
                worker.drain_warmups()
        finally:
            if multi:
                obs.trace.set_timer_prefix(None)

    def _load_states(self, max_age_s: float) -> Dict[int, dict]:
        """State-file scan with a short shared cache: N concurrent chip
        workers polling the same directory would otherwise multiply the
        shared-FS metadata I/O N-fold for identical data. Staleness is
        bounded and safe — states only move toward terminal, so a stale
        snapshot can only delay (never fabricate) progress."""
        now = time.monotonic()
        with self._states_lock:
            ts, states = self._states_cache
            if now - ts <= max_age_s:
                return states
        states = mf.load_shard_states(self.work_dir)
        with self._states_lock:
            self._states_cache = (time.monotonic(), states)
        return states

    def _drain_loop_inner(self, worker: "_ChipWorker", manifest: dict,
                          beat, poll_s: float, multi: bool) -> None:
        order = self._shard_order(worker)
        devs = self.plan.devices
        cache_s = poll_s / 2 if multi else 0.0
        while True:
            if self._abort.is_set():
                return  # a sibling worker died; the pool is unwinding
            progressed = False
            waiting: List[int] = []
            states = self._load_states(cache_s)
            with self._mf_lock:
                mf.merge_states(manifest, states)
            for si in order:
                shard = self.plan.shards[si]
                use_mesh = bool(devs) and devs[si] == MESH_DEVICE
                if use_mesh and not worker.can_mesh:
                    continue  # the primary slot owns mesh shards
                entry = manifest["shards"][si]
                if _terminal(entry) and si not in self._retry_quarantined:
                    self._note_terminal(si, entry, beat)
                    continue
                claim = lease_mod.try_claim(self.work_dir, si,
                                            worker.worker)
                if claim is None:
                    waiting.append(si)
                    continue
                try:
                    # re-check under the lease: the previous owner may
                    # have finished between our state read and the claim
                    fresh = mf.load_shard_state(self.work_dir, si)
                    if fresh is not None:
                        with self._mf_lock:
                            manifest["shards"][si] = entry = dict(fresh)
                    if _terminal(entry) and \
                            si not in self._retry_quarantined:
                        self._note_terminal(si, entry, beat)
                        continue
                    self._retry_quarantined.discard(si)
                    if entry.get("status") == mf.RUNNING:
                        # stale-lease takeover of an abandoned shard
                        metrics.inc("lease.reclaimed")
                        entry["reclaimed"] = int(
                            entry.get("reclaimed", 0)) + 1
                        _eprint(f"reclaiming shard {si} abandoned by "
                                f"worker {entry.get('worker', '?')}")
                    beat.update(done=self._done_count(manifest),
                                phase="polishing")
                    if use_mesh and multi:
                        # a mesh shard's dispatch/fetch seconds belong
                        # to the report's "mesh" row, not to the chip
                        # whose thread happens to drive it
                        obs.trace.set_timer_prefix("device.mesh.")
                    try:
                        with obs.track(f"shard {si}"), \
                                obs.span("exec.shard", shard=si):
                            self._run_shard(si, shard, entry, manifest,
                                            beat, claim, worker,
                                            use_mesh)
                    finally:
                        if use_mesh and multi:
                            obs.trace.set_timer_prefix(
                                f"device.{worker.ordinal}.")
                finally:
                    claim.release()
                progressed = True
                self._note_terminal(si, entry, beat)
                beat.emit(f"shard {si} {entry['status']} "
                          f"engine={entry.get('engine', '-')}")
            if not waiting and self._done_all(manifest):
                return
            if not progressed:
                beat.update(phase=f"waiting on {len(waiting)} leased "
                                  f"shard(s)")
                time.sleep(poll_s)

    def _done_count(self, manifest: dict) -> int:
        return sum(_terminal(e) for e in manifest["shards"])

    def _done_all(self, manifest: dict) -> bool:
        # cached scan is sound here: states only move toward terminal,
        # so a (bounded-stale) all-terminal snapshot was already true
        states = self._load_states(
            0.05 if len(self._chip_slots()) > 1 else 0.0)
        with self._mf_lock:
            mf.merge_states(manifest, states)
            return all(_terminal(e) for e in manifest["shards"])

    def _my_worker_ids(self) -> set:
        return {w.worker for w in (self._slots or [])} | {self.worker}

    def _note_terminal(self, si: int, entry: dict, beat) -> None:
        with self._note_lock:
            if si in self._announced or not _terminal(entry):
                return
            self._announced.add(si)
            announced = len(self._announced)
        shard_mbp = sum(self.index.targets[ci].bases
                        for ci in self.plan.shards[si]) / 1e6
        if entry["status"] == mf.DONE:
            # per-worker attribution: the heartbeat owns the split so
            # concurrent chip workers' Mbp/s rates stay truthful
            beat.add_mbp(entry.get("worker"), shard_mbp)
        if si in self._initially_done and self.resume:
            _eprint(f"resume: skipping completed shard {si} "
                    f"({shard_mbp:.2f} Mbp)")
        elif entry.get("worker") not in (
                {None} | self._my_worker_ids()):
            _eprint(f"shard {si} {entry['status']} by worker "
                    f"{entry.get('worker')}")
        beat.update(done=announced)

    # ------------------------------------------------- verification/requeue

    def _verify_parts(self, manifest: dict) -> List[int]:
        """Re-read every done part against its recorded size and CRC32
        (the durability net of the part protocol: a torn rename cannot
        happen, but a disk that lied about fsync, a truncated copy or a
        flipped bit can). Returns the shard ids whose parts fail."""
        mf.merge_states(manifest, mf.load_shard_states(self.work_dir))
        bad: List[int] = []
        for entry in manifest["shards"]:
            if entry["status"] != mf.DONE:
                continue
            part = os.path.join(self.work_dir, entry["part"])
            try:
                crc = 0
                size = 0
                with open(part, "rb") as f:
                    for chunk in iter(lambda: f.read(1 << 20), b""):
                        crc = zlib.crc32(chunk, crc)
                        size += len(chunk)
                ok = (size == entry.get("bytes")
                      and crc == entry.get("crc32"))
            except OSError:
                ok = False
            if not ok:
                warn(f"part {entry['part']} failed verification "
                     f"(recorded {entry.get('bytes')}B "
                     f"crc32={entry.get('crc32')}) — re-queueing "
                     f"shard {entry['id']} instead of merging a "
                     f"corrupt assembly")
                metrics.inc("faults.part_corrupt")
                bad.append(int(entry["id"]))
        return bad

    def _requeue_shard(self, si: int, manifest: dict,
                       why: str) -> None:
        """Reset a shard to pending (under its lease, so concurrent
        verifiers cannot double-reset) and let the drain loop re-run
        it. The stale part file is deliberately left in place: the
        re-run atomically replaces it with identical bytes, and another
        worker concurrently mid-merge keeps reading its already-open
        (old-inode) copy — an unlink here would hand that merger a
        FileNotFoundError instead."""
        claim = lease_mod.try_claim(self.work_dir, si, self.worker)
        if claim is None:
            return  # another worker is already handling it
        try:
            was = manifest["shards"][si]
            entry = {"id": si,
                     "contigs": list(map(int, self.plan.shards[si])),
                     "status": mf.PENDING,
                     "part": f"part_{si:04d}.fasta",
                     "requeued": why}
            manifest["shards"][si] = entry
            self._save(entry, manifest)
            # a requeue moves a shard DONE -> PENDING, violating the
            # states-only-move-toward-terminal assumption the bounded-
            # staleness scan cache rests on: drop the cache so the next
            # drain pass sees the PENDING state, not a stale all-DONE
            # snapshot that would skip the re-polish
            with self._states_lock:
                self._states_cache = (-1e9, {})
            shard_mbp = sum(self.index.targets[ci].bases
                            for ci in self.plan.shards[si]) / 1e6
            if si in self._announced and was.get("status") == mf.DONE:
                if self._beat is not None:
                    # keep the heartbeat honest: the re-run will re-add
                    # it (retracted from the worker that claimed credit)
                    self._beat.add_mbp(was.get("worker"), -shard_mbp)
                if was.get("device") is not None and \
                        len(self._chip_slots()) > 1 and \
                        was.get("worker") in self._my_worker_ids():
                    # retract the report's per-device shard/Mbp credit
                    # too, or the re-run double-counts in the devices
                    # rows — but only credit THIS process granted: a
                    # resumed (or sibling-process) shard's counters
                    # were never incremented here, and retracting them
                    # would drive the devices rows negative
                    # (polish_s deliberately stays cumulative —
                    # it records real seconds spent, attempts included)
                    dev_key = ("mesh" if was["device"] == MESH_DEVICE
                               else str(was["device"]))
                    metrics.inc(f"device.{dev_key}.shards", -1)
                    metrics.inc(f"device.{dev_key}.mbp",
                                -round(shard_mbp, 4))
            self._announced.discard(si)
            self._initially_done.discard(si)
        finally:
            claim.release()

    # ------------------------------------------------------ shard execution

    def _backoff_s(self, si: int, k: int) -> float:
        """Exponential backoff with deterministic jitter keyed by
        (worker, shard, attempt) — the shared :func:`faults.backoff_s`
        formula (the service ladder and retrying client use it too)."""
        base = max(0.0, flags.get_float("RACON_TPU_EXEC_BACKOFF_S"))
        return faults.backoff_s(base, k, f"{self.worker}:{si}:{k}")

    def _run_shard(self, si: int, shard: List[int], entry: dict,
                   manifest: dict, beat, claim,
                   worker: Optional["_ChipWorker"] = None,
                   use_mesh: bool = False) -> None:
        worker = worker if worker is not None else self._chip_slots()[0]
        compiles0 = metrics.counter("compile.backend_total")
        sleep_s = flags.get_float("RACON_TPU_EXEC_SLEEP_S")
        if sleep_s > 0 and si > 0:
            time.sleep(sleep_s)  # test hook: widen the kill window
        with self._mf_lock:
            entry.update(status=mf.RUNNING, worker=worker.worker)
            # drop a previous incarnation's outcome fields (quarantine
            # reason, attempt ladder, part stats) so the record
            # describes THIS attempt's history only
            for stale in ("requeued", "reason", "attempts", "engine",
                          "bytes", "crc32"):
                entry.pop(stale, None)
        self._save(entry, manifest)
        # chaos-soak site: a SIGKILL here leaves the shard RUNNING with
        # a heartbeating-no-more lease — exactly the state another
        # worker must detect, break and reclaim
        faults.check("worker.kill")
        # per-shard attribution: the retrace gauges are process-wide, so
        # a shard that short-circuits (zero overlaps) must not inherit
        # the previous shard's compile churn as its own telemetry.
        # (Concurrent chip workers share the process-wide gauges, so
        # per-shard retrace rows are approximate under the scheduler —
        # the retrace_total.* counters stay exact.)
        metrics.clear("retrace.")
        t0 = time.perf_counter()

        part = os.path.join(self.work_dir, entry["part"])
        max_retries = max(0, flags.get_int("RACON_TPU_EXEC_RETRIES"))
        attempts: List[dict] = []
        transient_used = 0
        tier_cpu = False
        paths: Optional[Dict[str, str]] = None
        extract_s = 0.0
        timings: Dict = {}
        part_stat: Optional[Tuple[int, int]] = None  # (bytes, crc32)
        for attempt_no in range(64):  # ladder is finite by construction
            try:
                if paths is None:
                    t_ext = time.perf_counter()
                    with obs.span("exec.extract", shard=si):
                        paths = self._extract_shard(si, shard)
                    extract_s += time.perf_counter() - t_ext
                    metrics.inc("exec.extract_bytes", sum(
                        os.path.getsize(paths[k])
                        for k in ("targets", "reads", "overlaps")))
                faults.check("exec.polish", shard=si, attempt=attempt_no)
                records, timings = self._polish_shard(
                    paths, cpu=tier_cpu, worker=worker,
                    use_mesh=use_mesh)
                with obs.span("exec.commit", shard=si):
                    part_stat = self._write_part(part, records)
                break
            except Exception as e:
                cls = faults.classify(e)
                metrics.inc(f"faults.{cls}")
                err = f"{type(e).__name__}: {e}"
                att = {"n": attempt_no,
                       "engine": "cpu" if tier_cpu else "primary",
                       "class": cls, "error": err}
                attempts.append(att)
                if cls == faults.CLASS_TRANSIENT and \
                        transient_used < max_retries:
                    backoff = self._backoff_s(si, transient_used)
                    att["action"] = "retry-backoff"
                    att["backoff_s"] = round(backoff, 3)
                    transient_used += 1
                    metrics.add_time("exec.backoff_s", backoff)
                    warn(f"shard {si} transient fault ({err}) — "
                         f"retry {transient_used}/{max_retries} in "
                         f"{backoff:.2f}s")
                    if isinstance(e, OSError):
                        paths = None  # re-extract after an I/O fault
                    time.sleep(backoff)
                elif cls == faults.CLASS_OOM and not tier_cpu and \
                        worker.reduce_capacity(mesh=use_mesh):
                    att["action"] = "reduce-capacity"
                    warn(f"shard {si} device OOM ({err}) — halved "
                         f"worker {worker.worker}'s engine "
                         f"arena/group capacity (consensus pair arena "
                         f"+ align dirs budget), re-dispatching on "
                         f"the device")
                elif not tier_cpu:
                    tier_cpu = True
                    att["action"] = "cpu-retry"
                    warn(f"shard {si} attempt failed ({err}) — "
                         f"retrying on the CPU engines")
                else:
                    att["action"] = "quarantine"
                    warn(f"shard {si} CPU retry failed ({err}) — "
                         f"quarantining")
                    with self._mf_lock:
                        entry.update(
                            status=mf.QUARANTINED,
                            reason=self._reason(attempts),
                            attempts=attempts, worker=worker.worker,
                            wall_s=round(time.perf_counter() - t0, 2))
                    metrics.inc("exec.shards_retried")
                    self._save_owned(entry, manifest, claim)
                    self._drop_shard_inputs(paths)
                    return
        else:  # unreachable backstop: the ladder ends in break/return
            with self._mf_lock:
                entry.update(status=mf.QUARANTINED,
                             reason=self._reason(attempts),
                             attempts=attempts, worker=worker.worker,
                             wall_s=round(time.perf_counter() - t0, 2))
            self._save_owned(entry, manifest, claim)
            self._drop_shard_inputs(paths)
            return
        wall = round(time.perf_counter() - t0, 2)
        shard_mbp = round(sum(self.index.targets[ci].bases
                              for ci in shard) / 1e6, 4)
        with self._mf_lock:
            entry.update(
                status=mf.DONE,
                engine="cpu-retry" if tier_cpu else "primary",
                worker=worker.worker,
                bytes=part_stat[0], crc32=part_stat[1],
                mbp=shard_mbp,
                wall_s=wall,
                extract_s=round(extract_s, 2),
                timings=timings,
                retrace=metrics.group("retrace."),
                peak_rss_mb=hb.peak_rss_bytes() >> 20)
            if self.plan.devices:
                # the chip the shard actually ran on (-1 = mesh-sharded
                # over all chips); lands in the manifest + report row
                entry["device"] = (MESH_DEVICE if use_mesh
                                   else worker.ordinal)
            if attempts:
                # the per-attempt ladder record plus the round-9 summary
                # string every fault-path test and operator greps for
                entry["attempts"] = attempts
                entry["reason"] = self._reason(attempts)
        if len(self._chip_slots()) > 1:
            # per-chip telemetry: the report's "devices" rows and the
            # heartbeat's per-chip Mbp/s read these registry counters
            dev_key = "mesh" if use_mesh else str(worker.ordinal)
            metrics.inc(f"device.{dev_key}.shards")
            metrics.inc(f"device.{dev_key}.mbp", shard_mbp)
            metrics.add_time(f"device.{dev_key}.polish_s", wall)
        with obs.span("exec.commit", shard=si):
            self._save_owned(entry, manifest, claim)
        # what the report's ``shard_run`` section reads: shards done in
        # this process, those of them on the slot's device engines at
        # the first attempt (a shard that fell down the ladder still
        # ends DONE with the right bytes: here is where it shows), and
        # the first and the last done shard's wall and backend compiles
        wall_s = time.perf_counter() - t0
        compiles = metrics.counter("compile.backend_total") - compiles0
        with self._mf_lock:
            metrics.inc("exec.shards_done")
            if attempts:
                metrics.inc("exec.shards_retried")
            elif not tier_cpu:
                metrics.inc("exec.shards_primary")
            metrics.inc("exec.part_bytes", part_stat[0])
            if metrics.counter("exec.shards_done") == 1:
                metrics.set_gauge("exec.first_shard_wall_s", wall_s)
                metrics.set_gauge("exec.first_shard_compiles", compiles)
            metrics.set_gauge("exec.last_shard_wall_s", wall_s)
            metrics.set_gauge("exec.last_shard_compiles", compiles)
        self._drop_shard_inputs(paths)

    @staticmethod
    def _reason(attempts: List[dict]) -> str:
        parts = []
        for a in attempts:
            prefix = "cpu retry: " if a["engine"] == "cpu" else ""
            parts.append(prefix + a["error"])
        return "; ".join(parts)

    @staticmethod
    def _drop_shard_inputs(paths: Optional[Dict[str, str]]) -> None:
        if paths is not None:
            shutil.rmtree(os.path.dirname(paths["targets"]),
                          ignore_errors=True)

    def _write_part(self, part: str,
                    records: List[Tuple[bytes, bytes]]) -> Tuple[int, int]:
        """Durably write one part file (tmp + fsync + atomic rename,
        worker-unique tmp name) and return its (byte size, CRC32) for
        the manifest record the merge verifies against."""
        faults.check("part.write")
        # pid alone is NOT unique here: after an in-process lease break
        # (chip A stalls, chip B reclaims the shard) two slot threads of
        # one process can be in _write_part for the same part — the ns
        # suffix keeps their tmp files from tearing each other, exactly
        # like manifest.atomic_write's
        tmp = f"{part}.tmp.{os.getpid()}.{time.monotonic_ns()}"
        crc = 0
        size = 0
        with open(tmp, "wb") as f:
            for name, data in records:
                blob = b">" + name + b"\n" + data + b"\n"
                f.write(blob)
                crc = zlib.crc32(blob, crc)
                size += len(blob)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, part)
        mf.fsync_dir(self.work_dir)
        return size, crc

    def _polish_shard(self, paths: Dict[str, str], cpu: bool,
                      worker: Optional["_ChipWorker"] = None,
                      use_mesh: bool = False
                      ) -> Tuple[List[Tuple[bytes, bytes]], Dict]:
        if paths["n_overlaps"] == 0:
            return self._unpolished_records(paths), {}
        worker = worker if worker is not None else self._chip_slots()[0]
        aligner, consensus = worker.get_engines(cpu, mesh=use_mesh)
        # a shard is a round with another one behind it: it hands the
        # slot's engines on, warm-up and all (create_polisher's
        # ``final``), and the slot waits once, when it leaves the drain
        # loop (_ChipWorker.drain_warmups)
        p = create_polisher(
            paths["reads"], paths["overlaps"], paths["targets"],
            self.type, window_length=self.window_length,
            quality_threshold=self.quality_threshold,
            error_threshold=self.error_threshold, trim=self.trim,
            match=self.match, mismatch=self.mismatch, gap=self.gap,
            num_threads=self.num_threads, aligner=aligner,
            consensus=consensus, window_type=self.index.window_type,
            prefiltered_overlaps=True, evict_reads=True,
            stall_escalation=True, final=False)
        polished = p.run(not self.include_unpolished)
        return [(s.name, s.data) for s in polished], dict(p.timings)

    def _unpolished_records(self, paths) -> List[Tuple[bytes, bytes]]:
        """A shard whose contigs kept no overlaps at all: a single-shot
        run drops them unless ``-u``, where it emits the raw (uppercased)
        targets with zero-coverage tags — replicated here because a
        Polisher would refuse the empty overlap set."""
        if not self.include_unpolished:
            return []
        out = []
        tag_prefix = b"r" if self.type == PolisherType.F else b""
        for rec in parsers.sequence_parser_for(paths["targets"])(
                paths["targets"]):
            data = rec.data.upper()
            tags = tag_prefix + b" LN:i:%d RC:i:0 XC:f:%.6f" % (
                len(data), 0.0)
            out.append((rec.name + tags, data))
        return out

    # ----------------------------------------------------- shard extraction

    def _extract_shard(self, si: int, shard: List[int]) -> Dict[str, str]:
        """Write this shard's input triple from the original files by
        byte range (deterministic, so a retried/resumed/reclaimed shard
        sees the identical inputs)."""
        d = os.path.join(self.work_dir, f"shard_{si:04d}")
        os.makedirs(d, exist_ok=True)
        idx = self.index

        # the three shard-input files below are raw (no fsync/rename):
        # they are RE-DERIVABLE scratch — extraction is deterministic
        # byte ranges of the original inputs, each attempt rewrites the
        # files from offset 0 before the polish that reads them, and a
        # crash mid-extract just re-extracts on the retry/reclaim.
        # Durable artifacts (parts, states, manifest, report) all go
        # through the tmp+fsync+rename protocol.
        t_ext = _plain_ext(self.target_sequences,
                           parsers.SEQUENCE_EXTENSIONS, ".fasta")
        tgt_path = os.path.join(d, "targets" + t_ext)
        with open(tgt_path, "wb") as f:  # graftlint: disable=atomic-write-discipline (re-derivable scratch: deterministic re-extract on any retry)
            parsers.copy_byte_ranges(
                self.target_sequences,
                [(idx.targets[ci].start, idx.targets[ci].end)
                 for ci in shard], f)

        line_ids = np.concatenate(
            [idx.lines_of_contig(ci) for ci in shard]) \
            if shard else np.zeros(0, np.int64)
        line_ids = line_ids[np.argsort(idx.ov_start[line_ids],
                                       kind="stable")]
        read_ords = np.unique(idx.ov_read[line_ids])

        r_ext = _plain_ext(self.sequences, parsers.SEQUENCE_EXTENSIONS,
                           ".fasta")
        reads_path = os.path.join(d, "reads" + r_ext)
        with open(reads_path, "wb") as f:  # graftlint: disable=atomic-write-discipline (re-derivable scratch: deterministic re-extract on any retry)
            parsers.copy_byte_ranges(
                self.sequences,
                [(int(idx.read_spans[r, 0]), int(idx.read_spans[r, 1]))
                 for r in read_ords], f)

        ovl_path = os.path.join(d, "overlaps." + idx.overlap_fmt)
        ranges = [(int(idx.ov_start[i]), int(idx.ov_end[i]))
                  for i in line_ids]
        with open(ovl_path, "wb") as f:  # graftlint: disable=atomic-write-discipline (re-derivable scratch: deterministic re-extract on any retry)
            if idx.overlap_fmt == "mhap":
                # MHAP addresses records by file ordinal: rewrite the two
                # id columns to the shard-local 1-based positions
                read_pos = {int(r): k for k, r in enumerate(read_ords)}
                contig_pos = {ci: k for k, ci in enumerate(shard)}
                owners = [int(idx.ov_target[i]) for i in line_ids]
                reads = [int(idx.ov_read[i]) for i in line_ids]
                for blob, t_idx, r_ord in zip(
                        parsers.iter_byte_ranges(self.overlaps, ranges),
                        owners, reads):
                    fields = blob.split()
                    fields[0] = b"%d" % (read_pos[r_ord] + 1)
                    fields[1] = b"%d" % (contig_pos[t_idx] + 1)
                    f.write(b" ".join(fields) + b"\n")
            else:
                parsers.copy_byte_ranges(self.overlaps, ranges, f)

        return {"targets": tgt_path, "reads": reads_path,
                "overlaps": ovl_path, "n_overlaps": len(line_ids)}

    # ----------------------------------------------------------- part merge

    def _merge_parts(self, manifest: dict, out) -> None:
        """Concatenate part records back into target-file contig order
        (the LPT pack scatters contigs across shards; a single-shot run
        emits them in file order). Records stream through verbatim."""
        owner = self.plan.owner_of()
        readers: Dict[int, "_PartReader"] = {}
        tag = b"r" if self.type == PolisherType.F else b""
        try:
            for ci, target in enumerate(self.index.targets):
                si = owner[ci]
                entry = manifest["shards"][si]
                if entry["status"] != mf.DONE:
                    continue  # quarantined: nothing to emit
                if si not in readers:
                    readers[si] = _PartReader(
                        os.path.join(self.work_dir, entry["part"]))
                readers[si].emit_if(target.name + tag, out)
        finally:
            for r in readers.values():
                r.close()
        out.flush()


class _PartReader:
    """Sequential reader over one part file's 2-line FASTA records, with
    one-record lookahead (a dropped/unpolished contig leaves its slot
    empty — the pending record then belongs to a later contig)."""

    def __init__(self, path: str):
        self.f = open(path, "rb")
        self.pending: Optional[Tuple[bytes, bytes]] = None
        self._advance()

    def _advance(self) -> None:
        header = self.f.readline()
        if not header:
            self.pending = None
            return
        data = self.f.readline()
        token = header[1:].split(None, 1)[0]
        self.pending = (token, header + data)

    def emit_if(self, token: bytes, out) -> bool:
        if self.pending is not None and self.pending[0] == token:
            out.write(self.pending[1])
            self._advance()
            return True
        return False

    def close(self) -> None:
        self.f.close()
