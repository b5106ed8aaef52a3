"""The contract registry: every string-keyed coupling surface of the
pipeline, declared once.

Five growth rounds (resident serving, crash-safe journaling, multi-chip
exec, the auto overlapper) made the system's real coupling surface
*stringly typed*: run-report schema keys, ``metrics.*`` names,
``obs.span`` names, ``RACON_TPU_FAULTS`` site names and the
job/shard/lease lifecycle states are free-form strings agreed on by
convention across ``racon_tpu/{obs,exec,serve}`` and ``faults.py``.
This module is the ONE declaration of those conventions; the consumers
(:mod:`racon_tpu.obs.metrics`, :mod:`racon_tpu.obs.report`,
:mod:`racon_tpu.faults`, :mod:`racon_tpu.serve.journal`,
:mod:`racon_tpu.serve.service`, :mod:`racon_tpu.exec.manifest`) import
their literal sets from here, and the graftlint contract pass
(``tools/analysis/contracts.py``) statically checks every emission /
consumption site against the same declarations:

- **metric-registry** — every ``metrics.inc/set_gauge/add_time`` name
  parses under :data:`METRIC_NAME_RE` and is either a registered
  static name (:data:`METRICS`) or carries a registered dynamic prefix
  (:data:`DYNAMIC_METRIC_PREFIXES`);
- **span-registry** — ``obs.span`` names must be declared in
  :data:`SPANS` (a silent span rename orphans the report's
  dispatch-vs-fetch splits, which read the span timers by name);
- **fault-site-registry** — every :data:`FAULT_SITES` entry has a
  ``faults.check`` injection site AND a test that injects it;
- **schema-coherence** — every schema key has an emitter and every
  emitted key is schema-known (both directions,
  :func:`schema_keys`);
- **state-transition** — journal appends and manifest/job state writes
  encode declared machine edges (:data:`JOB_MACHINE`,
  :data:`SHARD_MACHINE`).

Adding a metric / span / fault site / schema key is a one-edit change
HERE plus the emitting code; the gate fails on either half alone, so
registry and reality cannot drift apart.  Stdlib-only and import-free
(no racon_tpu imports): loadable by ``flags``-level modules and by the
linter without pulling in a backend.

The runtime half (``RACON_TPU_SANITIZE=1``) is the process-exit
contract audit in :mod:`racon_tpu.sanitize`: registered-but-never-
emitted metrics and report keys whose backing metric never fired
(:data:`REPORT_BACKING`) are reported at exit.
"""

from __future__ import annotations

import re
from typing import Dict, FrozenSet, Iterable, Tuple

# ----------------------------------------------------------- metric names

# the metric-name grammar: lowercase dotted segments (a name is a path
# in the one process-wide registry; the report/heartbeat group-reads by
# "segment." prefixes, so a stray uppercase or separator breaks every
# aggregation silently)
METRIC_NAME_RE = re.compile(r"^[a-z][a-z0-9_]*(\.[a-z0-9_]+)*$")

# every statically-named counter/gauge/timer the pipeline publishes.
# Grouped by family; the graftlint metric-registry rule checks every
# literal `metrics.inc/set_gauge/add_time` name lands here.
METRICS: FrozenSet[str] = frozenset((
    # aligner wavefront arenas + dispatch accounting
    "align.chunks", "align.lanes_occupied", "align.lanes_total",
    "align.packed_ahead", "align.steps_wasted", "align.wavefront_work",
    "aligner.band_escalated", "aligner.capacity_scale",
    "aligner.fallback_band", "aligner.fallback_length",
    "aligner.ladder_narrow", "aligner.pallas_chunks",
    "aligner.swar_chunks", "aligner.swar_guard_int32",
    # window build: the read pool, and how much of it was ready before
    # the layer assembly had to wait for it
    "build.pool_bytes", "build.pool_bytes_ahead",
    # XLA compile attribution + JAX's persistent-cache lookups
    "compile.backend_total", "compile.cache_hits",
    "compile.cache_requests", "compile.jax_s",
    # consensus pair arenas
    "consensus.capacity_scale", "consensus.dropped_layers",
    "consensus.fallback_windows", "consensus.group_windows",
    "consensus.groups", "consensus.ins_overflow",
    "consensus.ins_overflow_windows", "consensus.lanes_occupied",
    "consensus.lanes_total", "consensus.pallas_groups",
    # the two-stage schedule: first-stage groups dispatched and those
    # of them sent for STAGE_A_ROUNDS with their survivors collected;
    # real windows of the stage-A groups fetched, and those of them
    # neither converged nor frozen (repacked, or continued in place)
    "consensus.first_stage_groups", "consensus.stage_a_groups",
    "consensus.stage_a_windows", "consensus.stage_a_survivors",
    # the depth cap: windows and layers offered to the packer (before
    # the cap; dropped_layers counts the layers past it) and windows
    # that lost at least one
    "consensus.layers", "consensus.windows", "consensus.windows_capped",
    # pair rows whose lanes came from a columnar store, and those of
    # them the native row copier wrote into the group's block
    "consensus.lane_rows", "consensus.lane_rows_copied",
    "consensus.swar_guard_int32",
    "consensus.sweep_truncated", "consensus.wavefront_steps",
    # exec ladder
    "exec.backoff_s",
    # the shard runner's job (the report's shard_run section): shards
    # done in this process, those of them on the slot's device engines
    # at the first attempt, shards that needed a second attempt (done
    # or quarantined), bytes of the parts written and of the shard
    # inputs cut out of the job's files, and (gauges) the first and
    # the last done shard's wall and backend compiles and, written by
    # obs/device_time.py, the device-idle seconds between one shard's
    # last device interval and the next one's first
    "exec.shards_done", "exec.shards_primary", "exec.shards_retried",
    "exec.part_bytes", "exec.extract_bytes", "exec.boundary_idle_s",
    "exec.first_shard_compiles", "exec.first_shard_wall_s",
    "exec.last_shard_compiles", "exec.last_shard_wall_s",
    # fault taxonomy + injection
    "faults.backpressure_halvings", "faults.injected.exec.polish",
    "faults.part_corrupt", "faults.stall_escalations",
    # fleet gateway + placement (gateway-process-lifetime, unscoped)
    "fleet.cost_cache_hits", "fleet.cost_cache_misses",
    "fleet.hosts_alive", "fleet.hosts_dead", "fleet.migrated",
    "fleet.placed", "fleet.preempted", "fleet.reject_requeued",
    "gateway.accepted", "gateway.rejected",
    # lease lifecycle
    "lease.claimed", "lease.expired", "lease.lost", "lease.reclaimed",
    "lease.stale_write_suppressed",
    # first-party overlapper
    "overlap.cache_hits", "overlap.cache_misses",
    "overlap.candidate_pairs", "overlap.chain_lanes_occupied",
    "overlap.chain_lanes_total",
    # the hand-off to the align feed: pairs handed to the chain stream
    # (candidate_pairs less the ones under min_seeds), the length of
    # every array its intake sorts to decide the launches (linear: a
    # visit a pair), and (gauge) the pairs launched when the first rows
    # left for the aligner
    "overlap.chain_pairs", "overlap.intake_visits",
    "overlap.first_emit_pairs",
    "overlap.chains_dropped",
    "overlap.chains_kept", "overlap.chunks",
    "overlap.freq_capped_buckets", "overlap.join_bailouts",
    # the seed join's host prefilter (ops/chain.py _present_reads): read
    # entries offered to the device joins of the job, and those of them
    # padded and uploaded (equal where the prefilter stood aside)
    "overlap.join_read_entries", "overlap.join_read_kept",
    "overlap.lanes_occupied", "overlap.lanes_total",
    "overlap.minimizers", "overlap.mode_auto",
    # reads offered to the overlapper / reads with a row after the filter
    "overlap.queries", "overlap.queries_kept",
    # the seeding stream (ops/overlap_seed.py _SeedStream): arenas
    # launched, and those of them packed before the arena in front had
    # been fetched (the pack-ahead engaged: all but the first of a
    # multi-arena build)
    "overlap.seed_arenas", "overlap.seed_arenas_ahead",
    "overlap.seed_lanes_occupied", "overlap.seed_lanes_total",
    "overlap.stream_feed", "overlap.stream_groups", "overlap.streamed",
    # the read set's window type (WindowType.value: 0 NGS, 1 TGS)
    "polisher.window_type",
    # bounded init->polish queue
    "queue.consumer_wait_s", "queue.depth", "queue.producer_wait_s",
    # the rounds of one job (cli.main's loop): rounds run, those of them
    # after the first, read-side seed tables built / taken from the
    # job's read set (ops/chain.py _read_table), read records parsed
    "rounds.completed", "rounds.followups", "rounds.read_tables_built",
    "rounds.read_tables_reused", "rounds.reads_parsed",
    # runtime sanitizer
    "sanitize.lock_order_cycles", "sanitize.contract_never_emitted",
    "sanitize.contract_defaulted_keys",
    # crash-safe serving (server-level, unscoped)
    "serve.journal_compactions", "serve.journal_records",
    "serve.journal_replayed", "serve.recovered_jobs",
    "serve.requeued_jobs", "serve.spool_corrupt", "serve.spool_served",
    # slot supervision (server-level, unscoped)
    "slot.deaths", "slot.quarantined", "slot.restarts",
    # tracing ring buffers
    "trace.dropped_events",
))

# dynamic name families: `f"<prefix>{suffix}"` emissions whose literal
# prefix must land here (the suffix is a runtime value — a chip
# ordinal, a phase, a fault class/site, a swallowed-exception context)
DYNAMIC_METRIC_PREFIXES: Tuple[str, ...] = (
    "align.pairs_by_bucket.",  # .<max_len>: pairs dispatched per bucket
    "device.",           # device.<ordinal>.shards/.mbp/.polish_s/...
    "faults.",           # faults.<class> taxonomy counts
    "faults.injected.",  # faults.injected.<site>
    "fleet.tenant.",     # fleet.tenant.<name>.placed/.queued/...
    "idle.",             # idle.<span>: device-idle seconds by host span
    "retrace.",          # retrace.<phase> per-phase deltas
    "retrace_total.",    # retrace_total.<phase> run accumulators
    # rounds.<column>.<k>: round k's row of the report's rounds section
    "rounds.compiles.", "rounds.handoff_s.", "rounds.overlaps_kept.",
    "rounds.wall_s.",
    "swallowed.",        # swallowed.<context>|<exc-type>
)

# thread-local job scoping (racon_tpu.obs.metrics.set_scope) prefixes
# every write with job.<id>. — a scope root, never a literal name
JOB_SCOPE_ROOT = "job."

# every name a run report / runner summary / heartbeat reads describes
# ONE run; span timers land keyed by the span name, hence the phase
# prefixes ("trace." covers the dropped-events gauge of the run's own
# ring buffers).  "serve." / "slot." / "sanitize." / "fleet." /
# "gateway." are deliberately absent: those are server/gateway/
# process-lifetime facts that must survive run boundaries.  "aligner."
# was the round-22 drift find: the family
# existed since round 17 but never matched "align." (no dot), so its
# counters leaked across back-to-back runs in one process.  A family
# prefix also clears its bare name (metrics.clear_run): the aggregate
# "align" / "consensus" span timers match no dotted prefix.  "idle." is
# the occupancy ledger's derived family (obs/device_time.py).
RUN_PREFIXES: Tuple[str, ...] = (
    "align.", "aligner.", "poa.", "consensus.", "queue.", "retrace.",
    "retrace_total.", "swallowed.", "trace.", "parse.", "overlap.",
    "transmute", "bp.", "build.", "stitch", "exec.", "faults.",
    "lease.", "device.", "compile.", "idle.", "polisher.",
    "round.", "rounds.",
)

# ------------------------------------------------------------- span names

# every obs.span name (span exits land in the metrics timers keyed by
# the span name — the report's dispatch-vs-fetch splits read these, so
# a renamed span silently zeroes a report column)
SPANS: FrozenSet[str] = frozenset((
    "align", "align.dispatch", "align.fetch",
    # leaves of align.dispatch / align.fetch (the parents stay whole)
    "align.pack", "align.put", "align.launch",
    "align.wait", "align.get", "align.decode",
    "bp.decode",
    "build.backbone", "build.store", "build.windows",
    # the half of the layer assembly that needs no breaking point
    # (beside the aligner, or inline), and the wait for it at the barrier
    "build.prepare", "build.prepare_wait",
    # the compile listener's back-dated stages (obs/compilewatch.py);
    # compile.retrieve: the persistent cache's read inside the backend
    "compile.backend", "compile.lower", "compile.retrieve",
    "compile.trace",
    "consensus", "consensus.feed", "consensus.finish", "consensus.run",
    # exec.commit: a shard's part written (tmp, fsync, rename) and
    # its terminal state saved (state file + manifest, fsync each);
    # exec.drain: a slot with no shard left waits for the warm-ups its
    # shards kicked on its engines
    "exec.commit", "exec.drain", "exec.extract", "exec.index",
    "exec.merge", "exec.plan", "exec.shard",
    "fleet.place", "gateway.admit",
    "overlap.chain", "overlap.chain.dispatch", "overlap.chain.fetch",
    # the streamed hand-off's host work inside `align` (timer-only):
    # classing + chunk planning, the completed groups' rows, and the
    # consumer's Overlap objects with their filter
    "overlap.chain.plan", "overlap.emit", "overlap.rows",
    "overlap.filter", "overlap.join.dispatch", "overlap.join.fetch",
    # the join's host prefilter inside overlap.match (timer-only)
    "overlap.join.prefilter",
    "overlap.match", "overlap.seed", "overlap.seed.dispatch",
    "overlap.seed.fetch",
    # the seeding stream's leaves (timer-only): an arena's host pack on
    # the feeding thread; its planes' way back and their compaction
    # into the table, on a worker of a multi-arena build
    "overlap.seed.pack", "overlap.seed.get", "overlap.seed.compact",
    "parse.overlaps", "parse.reads", "parse.targets",
    "poa.dispatch", "poa.fetch", "poa.pack", "poa.stage_b",
    # leaves of poa.pack / poa.fetch
    "poa.put", "poa.lanes", "poa.wait", "poa.get", "poa.decode",
    "queue.get", "queue.put",
    # one round of a job (from its targets and reads indexed to its
    # last stitch; a one-shot job is one round) and, between two
    # rounds, the hand-off: the contigs become targets, the name and
    # ordinal tables are keyed anew. Both back-dated (trace.record)
    "round", "round.handoff",
    "stitch", "transmute",
))

# leaves that time a stretch of their parent and take none of its device
# idle: the occupancy ledger (obs/device_time.py) reads through them, so
# the parent's ``idle.<span>`` timer, and every metric that sums it, is
# what it was before the leaf existed
TIMER_ONLY_SPANS: FrozenSet[str] = frozenset((
    "poa.lanes", "compile.retrieve",
    "overlap.chain.plan", "overlap.emit", "overlap.rows",
    "overlap.join.prefilter",
    "overlap.seed.pack", "overlap.seed.get", "overlap.seed.compact",
    # `round` lies over every span of its round: read through, the
    # idle under it goes where it went before rounds had a span, and
    # the hand-off's stays `unattributed` (idle_other_s lists it)
    "round", "round.handoff"))

# the shard runner's spans, which mark a slot's thread. A shard's
# pipeline feeds the device from threads born inside ``exec.shard``, so
# the idle such a thread is charged while it holds no span of its own
# (before its first parse: the stitch, the commit, the extract, the
# index) is cut by the spans of the slot thread that fed the same
# device: ``idle.exec.*`` (obs/device_time.py)
DRIVER_SPANS: FrozenSet[str] = frozenset((
    "exec.commit", "exec.drain", "exec.extract", "exec.index",
    "exec.merge", "exec.plan", "exec.shard"))

# ------------------------------------------------------------ fault sites

# the named RACON_TPU_FAULTS injection points (racon_tpu.faults.check
# call sites); the fault-site-registry rule requires each to have a
# check() site AND a test that injects "<site>:"
FAULT_SITES: Tuple[str, ...] = (
    "consensus.dispatch", "align.dispatch", "align.fetch",
    "part.write", "manifest.write", "worker.kill", "exec.polish",
    "serve.polish", "serve.journal", "serve.socket", "serve.slot",
    "server.kill", "fleet.place", "gateway.accept",
)

FAULT_KINDS: Tuple[str, ...] = ("io", "enospc", "oom", "err", "stall",
                                "kill")

FAULT_CLASSES: Tuple[str, ...] = ("transient-io", "device-oom", "stall",
                                  "deterministic-compute")

# -------------------------------------------------------- report schema

SCHEMA_VERSION = 16

# the oldest version validate_report still accepts, as itself: a stored
# v11 report is held to the v11 key sets
MIN_SCHEMA_VERSION = 11

REPORT_KINDS: Tuple[str, ...] = ("cli", "exec", "job")

OVERLAP_MODES: Tuple[str, ...] = ("auto", "paf")

# key -> schema version the key first appeared in.  The top-level
# sections (one dict per key below) plus the per-section key sets; a
# bump adds entries here and the schema-coherence rule fails until the
# emitter emits them (and vice versa: an emitter key absent here is a
# finding — both directions).
TOP_KEYS: Dict[str, int] = {
    "schema_version": 1, "kind": 1, "argv": 1, "started_unix": 1,
    "wall_s": 1, "phases": 1, "dispatch_fetch": 1, "pack": 1,
    "retrace": 1, "queue": 1, "swallowed": 1, "metrics": 1,
    "peak_rss_bytes": 1, "shards": 1,
    "faults": 2,
    "devices": 3,
    "recovery": 5,
    "compiles": 7,
    "dataflow": 8,
    "overlap": 9,
    "fleet": 11,
    "device_time": 12,
    "rounds": 14,
    "shard_run": 15,
}

SECTION_KEYS: Dict[str, Dict[str, int]] = {
    "dispatch_fetch": {
        "align_dispatch_s": 1, "align_fetch_s": 1,
        "consensus_pack_s": 1, "consensus_dispatch_s": 1,
        "consensus_fetch_s": 1,
        "compile_s": 4,
    },
    "queue": {"depth": 1, "producer_wait_s": 1, "consumer_wait_s": 1,
              "stall_s": 1},
    "pack": {
        "pack_efficiency": 1, "pad_fraction": 1, "windows_per_group": 1,
        "groups": 1,
        "align_pack_efficiency": 6, "align_pad_fraction": 6,
        "align_chunks": 6, "align_steps_wasted": 6,
    },
    "recovery": {
        "recovered_jobs": 5, "requeued_jobs": 5, "served_from_spool": 5,
        "spool_corrupt": 5, "journal_replayed": 5, "journal_records": 5,
        "journal_compactions": 5, "slot_restarts": 5,
        "slot_quarantined": 5,
    },
    "compiles": {"total_s": 7, "count": 7, "post_warm": 7, "sealed": 7,
                 "by_function": 7, "events": 7,
                 "programs": 13, "dropped": 13, "wall_s": 13,
                 "unused": 13, "unused_s": 13, "eager_programs": 13,
                 "miss_s": 13, "unrowed_s": 13},
    "dataflow": {
        "resident": 8, "bytes_fetched": 8, "bytes_avoided": 8,
        "fallback_pairs": 8, "resident_bailouts": 8,
        "lanes_device_groups": 8, "ins_overflow_windows": 8,
    },
    "overlap": {
        "mode": 9, "minimizers": 9, "candidate_pairs": 9,
        "freq_capped_buckets": 9, "chains_kept": 9, "chains_dropped": 9,
        "seed_dispatch_s": 9, "seed_fetch_s": 9, "chain_dispatch_s": 9,
        "chain_fetch_s": 9,
        "lanes_occupied": 10, "lanes_total": 10, "chunks": 10,
        "join_bailouts": 10, "cache_hits": 10, "cache_misses": 10,
        "join_dispatch_s": 10, "join_fetch_s": 10,
    },
    "fleet": {
        "jobs_accepted": 11, "jobs_rejected": 11, "jobs_placed": 11,
        "jobs_migrated": 11, "jobs_preempted": 11,
        "hosts_alive": 11, "hosts_dead": 11,
        "cost_cache_hits": 11, "cost_cache_misses": 11,
    },
    "device_time": {
        "window_s": 12, "busy_s": 12, "idle_s": 12, "head_idle_s": 12,
        "tail_idle_s": 12, "programs": 12, "by_program": 12,
        "idle_by": 12, "timeline": 12, "dropped": 12, "gaps": 12,
        "clock": 12, "devices": 12,
        "boundary_idle_s": 15,
    },
    "rounds": {
        "count": 14, "first_wall_s": 14, "last_wall_s": 14,
        "first_compiles": 14, "last_compiles": 14,
        "first_overlaps_kept": 14, "last_overlaps_kept": 14,
        "handoff_s": 14, "rows": 14,
    },
    "shard_run": {
        "count": 15, "primary": 15, "retried": 15,
        "first_wall_s": 15, "last_wall_s": 15,
        "first_compiles": 15, "last_compiles": 15,
        "boundary_idle_s": 15, "part_bytes": 15, "extract_bytes": 15,
    },
}

# schema keys REMOVED at a version (key -> (section, removed_in)): a
# stored report of an older version still holds them, a newer one must
# not, and the schema-coherence message says "retired in v<N>" instead
# of "unknown key". v13: the truncated event list and the roll-up by
# frame, superseded by compiles.programs. v16: the whole "dataflow"
# section (section "top": a key of the report itself), with the
# flag-gated device-resident path whose bytes it counted; its key set
# stays in SECTION_KEYS for the stored reports that hold it
REMOVED_KEYS: Dict[str, Tuple[str, int]] = {
    "by_function": ("compiles", 13), "events": ("compiles", 13),
    "dataflow": ("top", 16)}


def schema_keys(version: int = SCHEMA_VERSION) -> Dict[str, FrozenSet[str]]:
    """Per-section key sets as of ``version`` (section ``"top"`` is the
    report's top level).  ``schema_keys(9)`` answers "what did a v9
    report contain" — the registry twin of report.py's version-history
    comment block."""
    def retired(section: str, key: str) -> bool:
        where, since = REMOVED_KEYS.get(key, ("", 0))
        return where == section and since <= version

    out = {"top": frozenset(k for k, v in TOP_KEYS.items()
                            if v <= version and not retired("top", k))}
    for section, keys in SECTION_KEYS.items():
        # a section that left the report has no keys from then on
        if not retired("top", section):
            out[section] = frozenset(k for k, v in keys.items()
                                     if v <= version
                                     and not retired(section, k))
    return out


# which function emits each checked section (module rel path, function
# name) — the schema-coherence rule extracts the dict-literal keys the
# function returns and diffs them against SECTION_KEYS both ways.
# "top" and "dispatch_fetch" are assembled inline by build_report.
SECTION_EMITTERS: Dict[str, Tuple[str, str]] = {
    "top": ("racon_tpu/obs/report.py", "build_report"),
    "dispatch_fetch": ("racon_tpu/obs/report.py", "build_report"),
    "queue": ("racon_tpu/obs/metrics.py", "queue_summary"),
    "pack": ("racon_tpu/obs/metrics.py", "pack_summary"),
    "recovery": ("racon_tpu/obs/metrics.py", "recovery_summary"),
    "compiles": ("racon_tpu/obs/compilewatch.py", "summary"),
    "overlap": ("racon_tpu/obs/metrics.py", "overlap_summary"),
    "fleet": ("racon_tpu/obs/metrics.py", "fleet_summary"),
    "device_time": ("racon_tpu/obs/device_time.py", "account"),
    "rounds": ("racon_tpu/obs/metrics.py", "rounds_summary"),
    "shard_run": ("racon_tpu/obs/metrics.py", "shard_run_summary"),
}

# report key -> the metric whose emission backs it ("section.key" ->
# registry name).  The RACON_TPU_SANITIZE=1 exit audit uses this to
# tell a real zero (the metric fired and summed to 0) from a
# validator-default zero (the metric never fired at all — the section
# builder's .get() default filled the key).
REPORT_BACKING: Dict[str, str] = {
    "dispatch_fetch.align_dispatch_s": "align.dispatch",
    "dispatch_fetch.align_fetch_s": "align.fetch",
    "dispatch_fetch.consensus_pack_s": "poa.pack",
    "dispatch_fetch.consensus_dispatch_s": "poa.dispatch",
    "dispatch_fetch.consensus_fetch_s": "poa.fetch",
    "dispatch_fetch.compile_s": "compile.jax_s",
    "queue.depth": "queue.depth",
    "queue.producer_wait_s": "queue.producer_wait_s",
    "queue.consumer_wait_s": "queue.consumer_wait_s",
    "queue.stall_s": "queue.producer_wait_s",
    "pack.pack_efficiency": "consensus.lanes_occupied",
    "pack.pad_fraction": "consensus.lanes_total",
    "pack.windows_per_group": "consensus.group_windows",
    "pack.groups": "consensus.groups",
    "pack.align_pack_efficiency": "align.lanes_occupied",
    "pack.align_pad_fraction": "align.lanes_total",
    "pack.align_chunks": "align.chunks",
    "pack.align_steps_wasted": "align.steps_wasted",
    "recovery.recovered_jobs": "serve.recovered_jobs",
    "recovery.requeued_jobs": "serve.requeued_jobs",
    "recovery.served_from_spool": "serve.spool_served",
    "recovery.spool_corrupt": "serve.spool_corrupt",
    "recovery.journal_replayed": "serve.journal_replayed",
    "recovery.journal_records": "serve.journal_records",
    "recovery.journal_compactions": "serve.journal_compactions",
    "recovery.slot_restarts": "slot.restarts",
    "recovery.slot_quarantined": "slot.quarantined",
    "overlap.minimizers": "overlap.minimizers",
    "overlap.candidate_pairs": "overlap.candidate_pairs",
    "overlap.freq_capped_buckets": "overlap.freq_capped_buckets",
    "overlap.chains_kept": "overlap.chains_kept",
    "overlap.chains_dropped": "overlap.chains_dropped",
    "overlap.lanes_occupied": "overlap.lanes_occupied",
    "overlap.lanes_total": "overlap.lanes_total",
    "overlap.chunks": "overlap.chunks",
    "overlap.join_bailouts": "overlap.join_bailouts",
    "overlap.cache_hits": "overlap.cache_hits",
    "overlap.cache_misses": "overlap.cache_misses",
    "overlap.seed_dispatch_s": "overlap.seed.dispatch",
    "overlap.seed_fetch_s": "overlap.seed.fetch",
    "overlap.join_dispatch_s": "overlap.join.dispatch",
    "overlap.join_fetch_s": "overlap.join.fetch",
    "overlap.chain_dispatch_s": "overlap.chain.dispatch",
    "overlap.chain_fetch_s": "overlap.chain.fetch",
    "fleet.jobs_accepted": "gateway.accepted",
    "fleet.jobs_rejected": "gateway.rejected",
    "fleet.jobs_placed": "fleet.placed",
    "fleet.jobs_migrated": "fleet.migrated",
    "fleet.jobs_preempted": "fleet.preempted",
    "fleet.hosts_alive": "fleet.hosts_alive",
    "fleet.hosts_dead": "fleet.hosts_dead",
    "fleet.cost_cache_hits": "fleet.cost_cache_hits",
    "fleet.cost_cache_misses": "fleet.cost_cache_misses",
    "rounds.count": "rounds.completed",
    "shard_run.count": "exec.shards_done",
    "shard_run.primary": "exec.shards_primary",
    "shard_run.retried": "exec.shards_retried",
    "shard_run.part_bytes": "exec.part_bytes",
    "shard_run.extract_bytes": "exec.extract_bytes",
    "shard_run.boundary_idle_s": "exec.boundary_idle_s",
    "shard_run.first_wall_s": "exec.first_shard_wall_s",
    "shard_run.last_wall_s": "exec.last_shard_wall_s",
    "shard_run.first_compiles": "exec.first_shard_compiles",
    "shard_run.last_compiles": "exec.last_shard_compiles",
}

# -------------------------------------------------------- state machines


class StateMachine:
    """A declared lifecycle machine: states, directed edges, and the
    initial/terminal classification the consumers assert against.
    Frozen data, not behavior — the consumers keep their own logic and
    the lint/sanitize layers check writes against :meth:`has_edge`."""

    def __init__(self, name: str, states: Iterable[str],
                 edges: Iterable[Tuple[str, str]],
                 initial: Iterable[str]):
        self.name = name
        self.states: Tuple[str, ...] = tuple(states)
        self.edges: FrozenSet[Tuple[str, str]] = frozenset(edges)
        self.initial: Tuple[str, ...] = tuple(initial)
        for src, dst in self.edges:
            if src not in self.states or dst not in self.states:
                raise ValueError(
                    f"{name}: edge {src!r}->{dst!r} references an "
                    f"undeclared state")
        for s in self.initial:
            if s not in self.states:
                raise ValueError(f"{name}: initial {s!r} undeclared")

    @property
    def terminal(self) -> Tuple[str, ...]:
        """States with no outgoing edge."""
        srcs = {src for src, _ in self.edges}
        return tuple(s for s in self.states if s not in srcs)

    def has_edge(self, src: str, dst: str) -> bool:
        return (src, dst) in self.edges

    def __contains__(self, state: str) -> bool:
        return state in self.states


# the canonical state spellings — the consumer modules bind their
# local names to THESE (serve/service.py job states, serve/journal.py
# record types, exec/manifest.py shard states), so a respelled state
# is a one-file edit here and an undeclared one cannot be minted
JOB_SUBMITTED = "submitted"
JOB_QUEUED = "queued"
JOB_RUNNING = "running"
JOB_DONE = "done"
JOB_FAILED = "failed"
JOB_CANCELLED = "cancelled"
JOB_COLLECTED = "collected"

SHARD_PENDING = "pending"
SHARD_RUNNING = "running"
SHARD_DONE = "done"
SHARD_QUARANTINED = "quarantined"

# the resident-service job lifecycle.  "submitted" is the journal's
# admission record; in-memory jobs begin at "queued".  running->queued
# is the crash-requeue edge (a server/slot death re-queues the job);
# running->running is a new execution incarnation after a crash (the
# journal's N-running-records crash ladder); done->queued is the
# corrupt-spool re-queue (lost work re-polishes).  done->collected
# retires the job once its one-fetch payload streamed to a client.
JOB_MACHINE = StateMachine(
    "job",
    states=(JOB_SUBMITTED, JOB_QUEUED, JOB_RUNNING, JOB_DONE,
            JOB_FAILED, JOB_CANCELLED, JOB_COLLECTED),
    edges=(
        (JOB_SUBMITTED, JOB_QUEUED), (JOB_SUBMITTED, JOB_FAILED),
        (JOB_QUEUED, JOB_RUNNING), (JOB_QUEUED, JOB_FAILED),
        (JOB_QUEUED, JOB_CANCELLED),
        (JOB_RUNNING, JOB_RUNNING), (JOB_RUNNING, JOB_QUEUED),
        (JOB_RUNNING, JOB_DONE), (JOB_RUNNING, JOB_FAILED),
        (JOB_RUNNING, JOB_CANCELLED),
        (JOB_DONE, JOB_COLLECTED), (JOB_DONE, JOB_QUEUED),
    ),
    initial=(JOB_SUBMITTED, JOB_QUEUED),
)

# journal record types are the job machine's observable alphabet (the
# "rec" field); every append must use one of these
JOURNAL_RECORDS: Tuple[str, ...] = (JOB_SUBMITTED, JOB_RUNNING,
                                    JOB_DONE, JOB_FAILED,
                                    JOB_CANCELLED, JOB_COLLECTED)

# the exec shard machine.  done->pending is the part-CRC re-queue,
# quarantined->pending the retry-quarantined path, running->running
# the stale-lease reclaim (a takeover rewrites the worker, not the
# state), running->pending a requeue of an abandoned shard.
SHARD_MACHINE = StateMachine(
    "shard",
    states=(SHARD_PENDING, SHARD_RUNNING, SHARD_DONE,
            SHARD_QUARANTINED),
    edges=(
        (SHARD_PENDING, SHARD_RUNNING),
        (SHARD_RUNNING, SHARD_RUNNING), (SHARD_RUNNING, SHARD_PENDING),
        (SHARD_RUNNING, SHARD_DONE), (SHARD_RUNNING, SHARD_QUARANTINED),
        (SHARD_DONE, SHARD_PENDING), (SHARD_QUARANTINED, SHARD_PENDING),
    ),
    initial=(SHARD_PENDING,),
)

# the shard-lease lifecycle (racon_tpu/exec/lease.py); the lease.*
# metric names mirror these transitions one-to-one
LEASE_MACHINE = StateMachine(
    "lease",
    states=("free", "claimed", "expired", "lost"),
    edges=(
        ("free", "claimed"),
        ("claimed", "free"), ("claimed", "expired"), ("claimed", "lost"),
        ("expired", "claimed"),
    ),
    initial=("free",),
)

# the fleet-level (gateway's-eye) job lifecycle.  A job is "accepted"
# once its admission record is durably journaled, "queued" in its
# tenant's FIFO, "placed" while an incarnation runs on a member host.
# placed->queued is the drain edge shared by preemption (a higher
# priority job needs the host) and migration (the host went silent
# past TTL) — the job re-enters its tenant queue and is re-placed
# under a NEW incarnation key.  done->collected retires the job once
# its one-fetch payload streamed to a client (mirrors the serve
# retention contract).
TENANT_ACCEPTED = "accepted"
TENANT_QUEUED = "queued"
TENANT_PLACED = "placed"
TENANT_DONE = "done"
TENANT_FAILED = "failed"
TENANT_CANCELLED = "cancelled"
TENANT_COLLECTED = "collected"

TENANT_MACHINE = StateMachine(
    "tenant",
    states=(TENANT_ACCEPTED, TENANT_QUEUED, TENANT_PLACED, TENANT_DONE,
            TENANT_FAILED, TENANT_CANCELLED, TENANT_COLLECTED),
    edges=(
        (TENANT_ACCEPTED, TENANT_QUEUED),
        (TENANT_ACCEPTED, TENANT_FAILED),
        (TENANT_QUEUED, TENANT_PLACED), (TENANT_QUEUED, TENANT_FAILED),
        (TENANT_QUEUED, TENANT_CANCELLED),
        (TENANT_PLACED, TENANT_QUEUED),   # preempt / migrate drain
        (TENANT_PLACED, TENANT_PLACED),   # re-place incarnation
        (TENANT_PLACED, TENANT_DONE), (TENANT_PLACED, TENANT_FAILED),
        (TENANT_PLACED, TENANT_CANCELLED),
        (TENANT_DONE, TENANT_COLLECTED),
    ),
    initial=(TENANT_ACCEPTED,),
)

# the member-host liveness machine (heartbeat files under --fleet-dir,
# refreshed like lease keepers).  "registered" is the beacon's first
# atomic write; "silent" is a missed refresh inside TTL grace;
# silent->dead fires past TTL (the gateway breaks the host's job
# leases and re-places on survivors); dead->alive is a restarted host
# re-registering under the same name.
HOST_REGISTERED = "registered"
HOST_ALIVE = "alive"
HOST_SILENT = "silent"
HOST_DEAD = "dead"

PLACEMENT_MACHINE = StateMachine(
    "placement",
    states=(HOST_REGISTERED, HOST_ALIVE, HOST_SILENT, HOST_DEAD),
    edges=(
        (HOST_REGISTERED, HOST_ALIVE),
        # registered->dead: the gateway's FIRST sight of a beacon can
        # already be stale past the TTL (host crashed before the
        # gateway started) — declared dead without ever being alive
        (HOST_REGISTERED, HOST_DEAD),
        (HOST_ALIVE, HOST_SILENT),
        (HOST_SILENT, HOST_ALIVE), (HOST_SILENT, HOST_DEAD),
        (HOST_DEAD, HOST_ALIVE),
    ),
    initial=(HOST_REGISTERED,),
)

MACHINES: Tuple[StateMachine, ...] = (JOB_MACHINE, SHARD_MACHINE,
                                      LEASE_MACHINE, TENANT_MACHINE,
                                      PLACEMENT_MACHINE)


def selfcheck() -> list:
    """Internal-consistency audit of the registry itself (run by the
    contracts test shard): every metric name parses under the grammar,
    every REPORT_BACKING target is a registered metric or span timer,
    every journal record is a job state, every emitter section is a
    declared section.  Returns human-readable violations ([] = ok)."""
    errors = []
    for name in sorted(METRICS):
        if not METRIC_NAME_RE.match(name):
            errors.append(f"metric {name!r} violates METRIC_NAME_RE")
    for span in sorted(SPANS):
        if not METRIC_NAME_RE.match(span):
            errors.append(f"span {span!r} violates METRIC_NAME_RE")
    for site in FAULT_SITES:
        if not METRIC_NAME_RE.match(site):
            errors.append(f"fault site {site!r} violates the name "
                          f"grammar")
    for key, metric in REPORT_BACKING.items():
        section = key.split(".", 1)[0]
        if section not in SECTION_KEYS:
            errors.append(f"REPORT_BACKING {key!r}: unknown section")
        elif key.split(".", 1)[1] not in SECTION_KEYS[section]:
            errors.append(f"REPORT_BACKING {key!r}: key not in "
                          f"SECTION_KEYS[{section!r}]")
        if metric not in METRICS and metric not in SPANS:
            errors.append(f"REPORT_BACKING {key!r} -> {metric!r}: "
                          f"backing metric is neither a registered "
                          f"metric nor a span timer")
    for rec in JOURNAL_RECORDS:
        if rec not in JOB_MACHINE:
            errors.append(f"journal record {rec!r} is not a job state")
    for section in SECTION_EMITTERS:
        if section != "top" and section not in SECTION_KEYS:
            errors.append(f"SECTION_EMITTERS {section!r}: no key set")
    return errors
