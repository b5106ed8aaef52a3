"""Machine-readable run reports: one schema-versioned ``run_report.json``
per CLI/exec run.

The exec heartbeat, the benchmark's per-layer metrics and any
service-mode job accounting are all *views* over this artifact:
per-phase wall clock, the dispatch-vs-fetch split (from the span timers), pair-arena
occupancy, jit-retrace deltas, bounded-queue stall time, the swallowed-
fault suppression counts, peak RSS, and (for exec runs) one row per
shard.  Everything is pulled from the single metrics registry
(:mod:`racon_tpu.obs.metrics`) at build time — no producer plumbs its
own dict here.

The schema is first-party and versioned (:data:`SCHEMA_VERSION`):
:func:`validate_report` returns a list of human-readable violations
(empty = valid) and is wired into CI's e2e check and
``python -m racon_tpu.obs.report --check FILE``.
"""

from __future__ import annotations

import json
import os
import sys
from typing import Dict, List, Optional

from . import compilewatch, device_time, metrics
from .. import contracts

# v2 (round 12): the "faults" section (fault-class / injected-site /
# lease-event counts) became required and shard rows grew the
# degradation-ladder fields (worker, attempts, crc32, reclaimed).
# v3 (round 13): the "devices" section became required (per-chip
# shard/Mbp/dispatch/fetch rows from the in-process chip scheduler;
# empty object on single-chip runs) and shard rows grew "device" (the
# chip ordinal a shard ran on; -1 = mesh-sharded over all chips)
# v4 (round 14): kind may be "job" — the resident polishing service
# returns one report per submitted job alongside its result, built
# from that job's metric scope (``job.<id>.*``), and "dispatch_fetch"
# grew "compile_s" (real XLA compile seconds via jax.monitoring — THE
# number the service exists to amortize)
# v5 (round 16): the "recovery" section became required — crash-safe
# serving counters (journal replay/append/compaction, jobs recovered
# across a server restart, results served from the CRC-verified spool,
# slot-supervision restarts/quarantines).  Server-level, unscoped;
# all zeros for plain CLI/exec runs.
# v6 (round 17): the "pack" section grew required ALIGNER occupancy
# keys (align_pack_efficiency / align_pad_fraction / align_chunks /
# align_steps_wasted — wavefront-arena occupancy of every dispatched
# align chunk, replacing the blind device/band_escalated counts as the
# aligner's efficiency signal), and "dispatch_fetch"'s align split now
# also lands in Polisher.timings (align_dispatch_s / align_fetch_s in
# the phases dict).
# v7 (round 18): the "compiles" section became required — process-wide
# XLA compile attribution from the one jax.monitoring listener
# (racon_tpu.obs.compilewatch): total attributed seconds, backend-
# compile count, warm-path violations after the serve seal
# ("post_warm", asserted 0 from job #2 on in bench_service), whether
# the warm path is sealed, per-function rollups ("by_function") and
# the trailing attributed events, each carrying (function, shape
# signature, phase, duration).  Per-job reports filter all of it to
# the job's scope.
# v8 (round 19): the "dataflow" section became required — device-
# resident align→consensus accounting (``dataflow.*`` metrics): was
# the resident path live ("resident" gauge), bytes actually fetched
# from device (final layer tables + consensus bytes) vs bytes whose
# host round-trip was avoided (skipped bp-table fetches + skipped lane
# re-uploads), overlap pairs that fell back to the host decode path
# (CIGAR-needed subset + band rejects), bail-out count, and per-window
# insertion-overflow attribution ("ins_overflow_windows").  All zeros
# where the path's flag was off.  Per-job reports filter to the job's
# scope.  The section left at v16, with the path.
# v9 (round 20): the "overlap" section became required — first-party
# overlapper accounting (``overlap.*`` metrics): the overlap source
# ("mode": "auto" for the in-process minimizer+chain overlapper, "paf"
# for precomputed-file runs where the numbers are legitimately zero),
# minimizer-table and candidate-pair volume, frequency-capped bucket
# and chain keep/drop counts, and the seed/chain dispatch-vs-fetch
# seconds from the ``overlap.seed.*``/``overlap.chain.*`` span timers.
# v10 (round 21): the "overlap" section grew required keys for the
# overlap-occupancy work — ragged chain-arena occupancy
# ("lanes_occupied"/"lanes_total"/"chunks", the align/consensus pack
# parity), the device seed-join dispatch-vs-fetch seconds
# ("join_dispatch_s"/"join_fetch_s" from the ``overlap.join.*`` span
# timers) and its counted bail-outs ("join_bailouts" — the host-oracle
# ladder, never silent), and the target seed-table cache accounting
# ("cache_hits"/"cache_misses").
# v11 (round 23): the "fleet" section became required — fleet-serving
# counters from the multi-tenant gateway (``gateway.*``/``fleet.*``
# metrics): admission outcomes at the TCP front door, jobs placed on
# member hosts, migrations after a host death and priority
# preemptions, the host-registry liveness gauges and the admission
# cost-estimate cache accounting.  Gateway-level, unscoped; all zeros
# for plain CLI/exec/serve runs.
# v12 (PR 25): the "device_time" section became required — the
# device-occupancy ledger (racon_tpu.obs.device_time): the window's
# busy and idle seconds as the program itself saw them (what it
# submitted, when the device was done), idle split into head / gaps /
# tail and charged to the innermost host span of the feeding thread
# ("idle_by", mirrored as the ``idle.<span>`` timers), per-program
# occupied seconds, the first 256 ledger rows ("timeline"), the 32
# longest idle intervals ("gaps") and the run's clock pair ("clock":
# the same instant as perf_counter_ns and time_ns).  All zeros when
# nothing was recorded.  A stored v11 report still validates as v11.
# v13 (PR 39): the "compiles" section became a ledger of programs —
# "programs": one row per compiled program (JAX's own name for it, the
# interval on the spans' clock, trace / lower / backend / retrieve
# seconds, cache hit or miss, the frame, thread and span that asked,
# and "dispatches": the exec submissions of the occupancy ledger that
# ran the executable, null for a program the ledger never submits),
# "dropped" (rows the bounded ring lost) and the totals the set-up
# metrics read: "wall_s" (the rows' intervals as wall), "unused" /
# "unused_s" (ledger programs no dispatch ran), "eager_programs",
# "miss_s", "unrowed_s" (stage seconds that reached no backend).
# "by_function" and "events" left (contracts.REMOVED_KEYS); a stored
# v11 / v12 report keeps them and validates as what it is.
# v14 (PR 41): the "rounds" section became required — the rounds of a
# ``--rounds N`` job as cli.main's loop counted them ("count"; 1 for a
# one-shot job, 0 where no loop ran: a shard, a service job), the first
# and the last round's wall, backend compiles and kept overlaps as
# plain keys, "handoff_s" over the hand-offs between rounds, and one
# row per round under "rows".  The job's counters and span timers sum
# over its rounds, as before; spans ``round`` / ``round.handoff`` and
# counters ``rounds.*`` say which round cost what.
# v15 (PR 43): the "shard_run" section became required — the shard
# runner's job as it counted it ("count" shards done in this process,
# "primary" of them on the slot's device engines at the first attempt,
# "retried"; the first and the last done shard's wall and backend
# compiles; "part_bytes" / "extract_bytes"; "boundary_idle_s": device
# idle between one shard's last device interval and the next one's
# first, from the occupancy ledger), all zeros where no shard runner
# ran; the per-shard rows stay under "shards".  Spans ``exec.commit``
# (part write + state saves) and ``exec.drain`` (a slot's one wait for
# its shards' warm-ups) joined the exec spans, and device idle a
# shard's feeding thread holds in no span of its own is cut by the
# slot thread's exec.* spans (``idle.exec.*``).
# v16 (PR 46): the "dataflow" section left with the flag-gated device-
# resident path it accounted for (contracts.REMOVED_KEYS, section
# "top"); a v16 report that carries it is refused as retired, a stored
# v11-v15 report keeps it and validates as what it is.
# "consensus.ins_overflow_windows" stays a counter under "metrics": the
# section only mirrored it.
# the schema's key sets (per section, per version) live in
# racon_tpu/contracts.py — ONE registry shared with the schema-coherence
# lint rule, so a schema bump is a contracts.py edit the gate enforces
# in both directions.  This module keeps the VALIDATOR's view: accepted
# types and requiredness, asserted coherent with the registry below.
SCHEMA_VERSION = contracts.SCHEMA_VERSION

KINDS = contracts.REPORT_KINDS

_NUM = (int, float)

MIN_SCHEMA_VERSION = contracts.MIN_SCHEMA_VERSION

_SCHEMA_KEYS = contracts.schema_keys()

# top-level schema: key -> (accepted types, required)
_TOP = {
    "schema_version": (int, True),
    "kind": (str, True),                # "cli" | "exec" | "job"
    "argv": (list, False),
    "started_unix": (_NUM, True),
    "wall_s": (_NUM, True),
    "phases": (dict, True),             # phase -> seconds
    "dispatch_fetch": (dict, True),     # split -> seconds
    "pack": (dict, True),               # occupancy summary
    "retrace": (dict, True),            # phase -> jit-compile delta
    "queue": (dict, True),              # bounded-queue health
    "swallowed": (dict, True),          # fault key -> occurrence count
    "faults": (dict, True),             # fault class/site/lease counts
    "recovery": (dict, True),           # crash-safe serving counters
    "compiles": (dict, True),           # XLA compile attribution (v7)
    "overlap": (dict, True),            # first-party overlapper (v9/v10)
    "fleet": (dict, True),              # fleet gateway counters (v11)
    "device_time": (dict, True),        # device-occupancy ledger (v12)
    "rounds": (dict, True),             # the job's rounds (v14)
    "shard_run": (dict, True),          # the shard runner's job (v15)
    "devices": (dict, True),            # per-chip rows ({} single-chip)
    "peak_rss_bytes": (int, True),
    "metrics": (dict, True),            # full registry snapshot
    "shards": (list, False),            # exec runs: one row per shard
}

# the validator's top-level view and the registry's must be the SAME
# key set — a bump that touches one side only fails at import, before
# the lint gate even runs
assert frozenset(_TOP) == _SCHEMA_KEYS["top"], \
    "report._TOP drifted from contracts.TOP_KEYS"

# top-level keys that left the schema (contracts.REMOVED_KEYS, section
# "top"): a stored report from before holds them, and is held to them
_TOP_RETIRED = {
    "dataflow": (dict, True),           # resident-dataflow bytes (v8-v15)
}

_QUEUE_KEYS = tuple(sorted(_SCHEMA_KEYS["queue"]))
_PACK_KEYS = tuple(sorted(_SCHEMA_KEYS["pack"]))
_RECOVERY_KEYS = tuple(sorted(_SCHEMA_KEYS["recovery"]))
# "programs" (a list of rows) validates structurally below
_COMPILES_NUM_KEYS = tuple(sorted(_SCHEMA_KEYS["compiles"] - {"programs"}))
_PROGRAM_STR_KEYS = ("program", "fn", "signature", "geometry", "thread",
                     "phase")
_PROGRAM_NUM_KEYS = ("t0_ns", "t1_ns", "trace_s", "lower_s", "backend_s",
                     "retrieve_s")
_PROGRAM_CACHE = ("hit", "miss", "none")
_FLEET_KEYS = tuple(sorted(_SCHEMA_KEYS["fleet"]))
# "rows" (a list of rows) validates structurally below
_ROUNDS_NUM_KEYS = tuple(sorted(_SCHEMA_KEYS["rounds"] - {"rows"}))
_ROUND_ROW_KEYS = ("round", "wall_s", "handoff_s", "compiles",
                   "overlaps_kept")
_SHARD_RUN_KEYS = tuple(sorted(_SCHEMA_KEYS["shard_run"]))
_DEVICE_TIME_NUM_KEYS = ("window_s", "busy_s", "idle_s", "head_idle_s",
                         "tail_idle_s", "boundary_idle_s", "programs",
                         "dropped")
# "mode" is the one string key of the overlap section
_OVERLAP_NUM_KEYS = tuple(sorted(_SCHEMA_KEYS["overlap"] - {"mode"}))
_OVERLAP_MODES = contracts.OVERLAP_MODES

# per-shard row schema: key -> (accepted types, required)
_SHARD_ROW = {
    "id": (int, True),
    "status": (str, True),
    "engine": (str, False),
    "worker": (str, False),             # lease owner that finished it
    "mbp": (_NUM, False),
    "wall_s": (_NUM, False),
    "extract_s": (_NUM, False),
    "timings": (dict, False),
    "retrace": (dict, False),
    "peak_rss_mb": (int, False),
    "reason": (str, False),
    "attempts": (list, False),          # degradation-ladder record
    "crc32": (int, False),              # part checksum (merge verifies)
    "reclaimed": (int, False),          # stale-lease takeover count
    "device": (int, False),             # chip ordinal (-1 = mesh shard)
}


def build_report(kind: str, *, argv: Optional[list] = None,
                 started_unix: float = 0.0, wall_s: float = 0.0,
                 phases: Optional[Dict[str, float]] = None,
                 shards: Optional[List[dict]] = None,
                 scope: str = "") -> dict:
    """Assemble a report from the metrics registry plus the caller's
    phase timings (``Polisher.timings``) and, for exec runs, the
    manifest's shard entries (:func:`shard_row` extracts the row).

    ``scope`` builds the report from ONE metric scope instead of the
    global namespace — the resident polishing service passes the job's
    ``job.<id>.`` prefix, so concurrent jobs' reports stay disjoint
    (every embedded name is unscoped; the scope is a read filter)."""
    rep = {
        "schema_version": SCHEMA_VERSION,
        "kind": kind,
        "argv": [str(a) for a in (argv or [])],
        "started_unix": round(float(started_unix), 3),
        "wall_s": round(float(wall_s), 3),
        "phases": {str(k): round(float(v), 6)
                   for k, v in (phases or {}).items()},
        "dispatch_fetch": {
            "align_dispatch_s": round(
                metrics.timer_s(scope + "align.dispatch"), 3),
            "align_fetch_s": round(
                metrics.timer_s(scope + "align.fetch"), 3),
            "consensus_pack_s": round(
                metrics.timer_s(scope + "poa.pack"), 3),
            "consensus_dispatch_s": round(
                metrics.timer_s(scope + "poa.dispatch"), 3),
            "consensus_fetch_s": round(
                metrics.timer_s(scope + "poa.fetch"), 3),
            # real XLA compile seconds attributed to this run/job (the
            # jax.monitoring hook the service arms; 0 when unarmed)
            "compile_s": round(
                metrics.timer_s(scope + "compile.jax_s"), 3),
        },
        "pack": metrics.pack_summary(scope),
        # process-lifetime totals (the "retrace." gauges hold only the
        # most recent per-phase delta and the exec runner clears them
        # between shards for per-shard attribution; the "_total"
        # counters accumulate across the whole run — identical for
        # single-polisher cli runs)
        "retrace": (metrics.group(scope + "retrace_total.")
                    or metrics.group(scope + "retrace.")),
        "queue": metrics.queue_summary(scope),
        "swallowed": {k: int(v) for k, v in
                      metrics.group(scope + "swallowed.").items()},
        # fault-tolerance visibility: per-class fault counts, injected-
        # site counts and backpressure halvings (``faults.*``) plus the
        # lease lifecycle (``lease.claimed/expired/reclaimed/lost``) —
        # every ladder decision also sits per-attempt in its shard row
        "faults": {
            **{k: int(v)
               for k, v in metrics.group(scope + "faults.").items()},
            **{f"lease.{k}": int(v)
               for k, v in metrics.group(scope + "lease.").items()},
        },
        # crash-safe serving (round 16): journal replay/compaction,
        # restart-recovered jobs, spool verification and slot-
        # supervision counters — server-level, so every kind embeds
        # the hosting process's totals (zeros outside serve mode)
        "recovery": metrics.recovery_summary(),
        # XLA compile attribution (round 18, schema v7; a ledger of
        # programs since v13): one row per compiled program from the
        # process-wide jax.monitoring listener, joined to the
        # occupancy ledger's exec submissions; "post_warm" counts
        # compiles after the serve warm-path seal.  Built BEFORE the
        # metrics snapshot: it pins the compile.retrieve timer
        "compiles": compilewatch.summary(
            scope, device_time.dispatch_counts(scope)),
        # first-party overlapper accounting (round 20 v9, extended
        # round 21 v10): overlap source, table/candidate volume,
        # freq-cap and chain keep/drop counts, chain-arena occupancy,
        # seed/join/chain dispatch-vs-fetch seconds, join bail-outs
        # and target-table cache hits — mode "paf" with zeros for
        # precomputed-overlap runs
        "overlap": metrics.overlap_summary(scope),
        # fleet serving (round 23, schema v11): gateway admission,
        # placement/migration/preemption volume, host-registry
        # liveness and the admission cost-cache accounting —
        # gateway-level, so every kind embeds the hosting process's
        # totals (zeros outside a gateway process)
        "fleet": metrics.fleet_summary(),
        # per-chip attribution (round 13): one row per local device the
        # chip scheduler drove — shards/Mbp counters, polish seconds and
        # the span-timer mirrors (dispatch/fetch per chip). {} on
        # single-chip runs.
        "devices": metrics.device_summary(scope),
        # the occupancy ledger (schema v12): busy/idle seconds of the
        # window that ends now, idle charged to host spans.  Built
        # BEFORE the metrics snapshot below: it writes the idle.<span>
        # timers the snapshot carries
        "device_time": device_time.summary(scope, float(wall_s)),
        # the job's rounds (schema v14): count, the first and the last
        # round's wall / compiles / kept overlaps, a row per round
        "rounds": metrics.rounds_summary(scope),
        # the shard runner's job (schema v15): shards done / on the
        # device engines at the first attempt / retried, the first and
        # the last shard's wall and compiles, the idle at the shard
        # boundaries (device_time.summary, above, wrote its gauge)
        "shard_run": metrics.shard_run_summary(scope),
        "peak_rss_bytes": metrics.peak_rss_bytes(),
        "metrics": metrics.snapshot(scope or None),
    }
    if shards is not None:
        rep["shards"] = [shard_row(e) for e in shards]
    return rep


def shard_row(entry: dict) -> dict:
    """One report row from a manifest shard entry (schema-checked keys
    only — manifest internals like part paths stay out of the report)."""
    row = {"id": int(entry["id"]), "status": str(entry["status"])}
    for key in ("engine", "worker", "mbp", "wall_s", "extract_s",
                "timings", "retrace", "peak_rss_mb", "reason",
                "attempts", "crc32", "reclaimed", "device"):
        if entry.get(key) is not None:
            row[key] = entry[key]
    return row


# ------------------------------------------------------------- validation

def _check_numeric_dict(errors: List[str], d: dict, where: str) -> None:
    for k, v in d.items():
        if not isinstance(k, str) or not isinstance(v, _NUM) \
                or isinstance(v, bool):
            errors.append(f"{where}[{k!r}] is not a numeric value: {v!r}")


def _check_device_time(errors: List[str], dt: dict,
                       version: int) -> None:
    where = "device_time"
    keys = contracts.schema_keys(version)[where]
    for key in sorted(keys - set(dt)):
        errors.append(f"{where}[{key!r}] missing")
    for key in sorted(set(dt) - keys):
        errors.append(f"{where} unknown key {key!r}")
    if errors:
        return
    for key in keys.intersection(_DEVICE_TIME_NUM_KEYS):
        if not isinstance(dt[key], _NUM) or isinstance(dt[key], bool):
            errors.append(f"{where}[{key!r}] non-numeric")
    for key in ("idle_by", "clock"):
        if not isinstance(dt[key], dict):
            errors.append(f"{where}[{key!r}] is not an object")
        else:
            _check_numeric_dict(errors, dt[key], f"{where}.{key}")
    for key in ("by_program", "devices"):
        if not isinstance(dt[key], dict) or not all(
                isinstance(row, dict) for row in dt[key].values()):
            errors.append(f"{where}[{key!r}] is not an object of rows")
    if isinstance(dt["by_program"], dict):
        for name, row in dt["by_program"].items():
            if isinstance(row, dict):
                _check_numeric_dict(errors, row,
                                    f"{where}.by_program[{name!r}]")
    if not isinstance(dt["timeline"], list) or not all(
            isinstance(r, list) and len(r) == 6 for r in dt["timeline"]):
        errors.append(f"{where}['timeline'] is not a list of [device, "
                      f"kind, name, thread, submit_ns, complete_ns] rows")
    if not isinstance(dt["gaps"], list) or not all(
            isinstance(g, list) and len(g) == 3 and isinstance(g[2], dict)
            for g in dt["gaps"]):
        errors.append(f"{where}['gaps'] is not a list of [start_ns, "
                      f"end_ns, {{span: seconds}}] rows")


def _is_num(v) -> bool:
    return isinstance(v, _NUM) and not isinstance(v, bool)


def _check_rounds(errors: List[str], rounds: dict) -> None:
    for key in _ROUNDS_NUM_KEYS:
        if not _is_num(rounds.get(key)):
            errors.append(f"rounds[{key!r}] missing or non-numeric")
    for key in sorted(set(rounds) - _SCHEMA_KEYS["rounds"]):
        errors.append(f"rounds unknown key {key!r}")
    rows = rounds.get("rows")
    if not isinstance(rows, list) or not all(
            isinstance(r, dict) and set(r) == set(_ROUND_ROW_KEYS)
            and all(_is_num(v) for v in r.values()) for r in rows):
        errors.append(f"rounds['rows'] is not a list of "
                      f"{{{', '.join(_ROUND_ROW_KEYS)}}} rows")
    elif _is_num(rounds.get("count")) and len(rows) != rounds["count"]:
        errors.append(f"rounds['rows'] holds {len(rows)} rows, "
                      f"rounds['count'] is {rounds['count']}")


def _check_compiles(errors: List[str], comp: dict, version: int) -> None:
    """The section as its version had it: a stored v11 / v12 report
    holds the retired roll-ups in place of the rows."""
    keys = contracts.schema_keys(version)["compiles"]
    for key in sorted(keys):
        if key not in comp:
            errors.append(f"compiles[{key!r}] missing")
        elif key in _COMPILES_NUM_KEYS and not _is_num(comp[key]):
            errors.append(f"compiles[{key!r}] missing or non-numeric")
    for key in sorted(set(comp) - keys):
        removed = contracts.REMOVED_KEYS.get(key)
        errors.append(f"compiles[{key!r}] retired in schema "
                      f"v{removed[1]}" if removed
                      else f"compiles unknown key {key!r}")
    if "programs" not in keys or "programs" not in comp:
        return
    if not isinstance(comp["programs"], list):
        errors.append("compiles['programs'] is not a list of rows")
        return
    for i, row in enumerate(comp["programs"]):
        ok = isinstance(row, dict) \
            and all(isinstance(row.get(k), str)
                    for k in _PROGRAM_STR_KEYS) \
            and all(_is_num(row.get(k)) for k in _PROGRAM_NUM_KEYS) \
            and row.get("cache") in _PROGRAM_CACHE \
            and "dispatches" in row \
            and (row["dispatches"] is None
                 or (_is_num(row["dispatches"])
                     and row["dispatches"] >= 0))
        if not ok:
            errors.append(
                f"compiles.programs[{i}] is not a program row "
                f"({'/'.join(_PROGRAM_STR_KEYS)}, "
                f"{'/'.join(_PROGRAM_NUM_KEYS)}, cache "
                f"{'|'.join(_PROGRAM_CACHE)}, dispatches)")


def validate_report(rep) -> List[str]:
    """Schema-check a (parsed) report; returns violations, [] = valid."""
    errors: List[str] = []
    if not isinstance(rep, dict):
        return [f"report is not an object: {type(rep).__name__}"]
    version = rep.get("schema_version")
    if isinstance(version, bool) or not isinstance(version, int) \
            or not MIN_SCHEMA_VERSION <= version <= SCHEMA_VERSION:
        errors.append(f"schema_version {version!r} not in "
                      f"{MIN_SCHEMA_VERSION}..{SCHEMA_VERSION}")
        version = SCHEMA_VERSION
    # a stored report of an older version is held to ITS key sets
    keys = contracts.schema_keys(version)
    top = {k: v for k, v in {**_TOP, **_TOP_RETIRED}.items()
           if k in keys["top"]}
    for key, (types, required) in top.items():
        if key not in rep:
            if required:
                errors.append(f"missing required key {key!r}")
            continue
        if not isinstance(rep[key], types) or isinstance(rep[key], bool):
            errors.append(f"{key!r} has type {type(rep[key]).__name__}")
    for key in sorted(set(rep) - set(top)):
        removed = contracts.REMOVED_KEYS.get(key, ("", 0))
        errors.append(f"{key!r} retired in schema v{removed[1]}"
                      if removed[0] == "top" else f"unknown key {key!r}")
    if errors:
        return errors
    if rep["kind"] not in KINDS:
        errors.append(f"kind {rep['kind']!r} not in {KINDS}")
    for key in ("phases", "dispatch_fetch", "retrace", "swallowed",
                "faults"):
        _check_numeric_dict(errors, rep[key], key)
    for dev, row in rep["devices"].items():
        if not isinstance(dev, str) or not isinstance(row, dict):
            errors.append(f"devices[{dev!r}] is not an object row")
        else:
            _check_numeric_dict(errors, row, f"devices[{dev!r}]")
    for key in _QUEUE_KEYS:
        if not isinstance(rep["queue"].get(key), _NUM):
            errors.append(f"queue[{key!r}] missing or non-numeric")
    for key in _RECOVERY_KEYS:
        if not isinstance(rep["recovery"].get(key), _NUM) \
                or isinstance(rep["recovery"].get(key), bool):
            errors.append(f"recovery[{key!r}] missing or non-numeric")
    for key in _PACK_KEYS:
        if not isinstance(rep["pack"].get(key), _NUM):
            errors.append(f"pack[{key!r}] missing or non-numeric")
    for key in sorted(keys.get("dataflow", ())):
        if not _is_num(rep["dataflow"].get(key)):
            errors.append(f"dataflow[{key!r}] missing or non-numeric")
    for key in _FLEET_KEYS:
        if not isinstance(rep["fleet"].get(key), _NUM) \
                or isinstance(rep["fleet"].get(key), bool):
            errors.append(f"fleet[{key!r}] missing or non-numeric")
    if rep["overlap"].get("mode") not in _OVERLAP_MODES:
        errors.append(f"overlap['mode'] {rep['overlap'].get('mode')!r} "
                      f"not in {_OVERLAP_MODES}")
    for key in _OVERLAP_NUM_KEYS:
        if not isinstance(rep["overlap"].get(key), _NUM) \
                or isinstance(rep["overlap"].get(key), bool):
            errors.append(f"overlap[{key!r}] missing or non-numeric")
    _check_compiles(errors, rep["compiles"], version)
    if "device_time" in top:
        _check_device_time(errors, rep["device_time"], version)
    if "rounds" in top:
        _check_rounds(errors, rep["rounds"])
    if "shard_run" in top:
        for key in _SHARD_RUN_KEYS:
            if not _is_num(rep["shard_run"].get(key)):
                errors.append(f"shard_run[{key!r}] missing or non-numeric")
        for key in sorted(set(rep["shard_run"]) - set(_SHARD_RUN_KEYS)):
            errors.append(f"shard_run unknown key {key!r}")
    for kind in ("counters", "gauges", "timers"):
        store = rep["metrics"].get(kind)
        if not isinstance(store, dict):
            errors.append(f"metrics[{kind!r}] missing or not an object")
        else:
            _check_numeric_dict(errors, store, f"metrics.{kind}")
    for i, row in enumerate(rep.get("shards", [])):
        if not isinstance(row, dict):
            errors.append(f"shards[{i}] is not an object")
            continue
        for key, (types, required) in _SHARD_ROW.items():
            if key not in row:
                if required:
                    errors.append(f"shards[{i}] missing {key!r}")
                continue
            if not isinstance(row[key], types) \
                    or isinstance(row[key], bool):
                errors.append(
                    f"shards[{i}][{key!r}] has type "
                    f"{type(row[key]).__name__}")
        for key in set(row) - set(_SHARD_ROW):
            errors.append(f"shards[{i}] unknown key {key!r}")
        for j, att in enumerate(row.get("attempts") or []):
            if not isinstance(att, dict) or "class" not in att \
                    or "action" not in att:
                errors.append(f"shards[{i}].attempts[{j}] is not a "
                              f"ladder record (class/action)")
    return errors


def atomic_write_bytes(path: str, blob: bytes) -> None:
    """tmp + fsync + atomic replace — the manifest's durable-write
    protocol (``exec.manifest.atomic_write``) re-stated here because
    obs must stay import-light (no exec package pull-in). Shared by
    :func:`write_report` and the trace exporter: a crash mid-write
    leaves the previous artifact, never a truncated one."""
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        f.write(blob)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)


def write_report(path: str, rep: dict) -> None:
    """Serialize + durably replace ``path`` (a half-written report is
    worse than none)."""
    atomic_write_bytes(path, json.dumps(rep, indent=1).encode())


def rounds_table(rounds: dict, counters: Optional[dict] = None) -> str:
    """The ``rounds`` section for people: one line per round and, given
    the report's counters, what the job's seed joins took of the read
    table (the host prefilter of ``ops/chain.py``)."""
    lines = [f"{'round':>5} {'handoff_s':>10} {'wall_s':>9} "
             f"{'compiles':>8} {'overlaps_kept':>13}"]
    lines += [f"{r['round']:>5} {r['handoff_s']:>10.3f} {r['wall_s']:>9.3f} "
              f"{r['compiles']:>8} {r['overlaps_kept']:>13}"
              for r in rounds.get("rows", [])]
    offered = (counters or {}).get("overlap.join_read_entries")
    if offered:
        kept = counters.get("overlap.join_read_kept", 0)
        lines.append(f"join: {kept} of {offered} read minimizers crossed "
                     f"to the device ({100 * kept / offered:.1f} %)")
    return "\n".join(lines)


def shards_table(rep: dict) -> str:
    """The shard runner's job for people: the ``shard_run`` section,
    one line per shard row, and where the host held the device while it
    drove them (the ``idle.exec.*`` timers)."""
    run = rep.get("shard_run") or {}
    timers = (rep.get("metrics") or {}).get("timers") or {}
    lines = [f"shards done {run.get('count', 0)} (on the device engines "
             f"at the first attempt {run.get('primary', 0)}, retried "
             f"{run.get('retried', 0)}); parts {run.get('part_bytes', 0)} "
             f"B, extracted inputs {run.get('extract_bytes', 0)} B",
             f"first shard {run.get('first_wall_s', 0.0):.3f} s "
             f"({run.get('first_compiles', 0)} compiles), last "
             f"{run.get('last_wall_s', 0.0):.3f} s "
             f"({run.get('last_compiles', 0)} compiles); device idle at "
             f"the shard boundaries {run.get('boundary_idle_s', 0.0):.3f} s",
             f"{'shard':>5} {'status':>11} {'engine':>9} {'mbp':>7} "
             f"{'wall_s':>8} {'extract_s':>9} {'attempts':>8}"]
    lines += [f"{r['id']:>5} {r['status']:>11} {r.get('engine', '-'):>9} "
              f"{r.get('mbp', 0.0):>7.3f} {r.get('wall_s', 0.0):>8.2f} "
              f"{r.get('extract_s', 0.0):>9.2f} "
              f"{len(r.get('attempts') or []):>8}"
              for r in rep.get("shards", [])]
    spans = sorted(contracts.DRIVER_SPANS)
    lines.append("host seconds / device idle under them: " + ", ".join(
        f"{name} {timers.get(name, 0.0):.3f} / "
        f"{timers.get('idle.' + name, 0.0):.3f}" for name in spans))
    return "\n".join(lines)


def _main(argv) -> int:
    if len(argv) == 2 and argv[0] == "--check":
        try:
            with open(argv[1], "rb") as f:
                rep = json.loads(f.read())
        except (OSError, ValueError) as e:
            print(f"run report {argv[1]}: unreadable ({e})",
                  file=sys.stderr)
            return 2
        errors = validate_report(rep)
        for err in errors:
            print(f"run report {argv[1]}: {err}", file=sys.stderr)
        if not errors:
            print(f"run report {argv[1]}: valid "
                  f"(schema v{rep['schema_version']}, kind={rep['kind']}, "
                  f"{len(rep.get('shards', []))} shard rows)")
        return 1 if errors else 0
    if argv and argv[0] == "gaps":
        from . import gaps
        return gaps.main(argv[1:])
    if argv and argv[0] == "compiles":
        return compilewatch.main(argv[1:])
    if len(argv) == 2 and argv[0] == "rounds":
        with open(argv[1], "rb") as f:
            rep = json.loads(f.read())
        print(rounds_table(rep.get("rounds") or {},
                           (rep.get("metrics") or {}).get("counters")))
        return 0
    if len(argv) == 2 and argv[0] == "shards":
        with open(argv[1], "rb") as f:
            print(shards_table(json.loads(f.read())))
        return 0
    print("usage: python -m racon_tpu.obs --check FILE\n"
          "       python -m racon_tpu.obs gaps RUN_REPORT DEVICE_TRACE\n"
          "       python -m racon_tpu.obs compiles RUN_REPORT\n"
          "       python -m racon_tpu.obs rounds RUN_REPORT\n"
          "       python -m racon_tpu.obs shards RUN_REPORT",
          file=sys.stderr)
    return 2


if __name__ == "__main__":
    sys.exit(_main(sys.argv[1:]))
