"""The process-wide metrics registry — ONE store for every counter,
gauge and timer the pipeline publishes.

Before this module, telemetry lived in five ad-hoc surfaces (stage lines
in ``utils/logger.py``, the exec heartbeat's ``update(...)`` plumbing,
``PhaseRetraceBudget`` class globals and the per-engine ``stats``
dicts).  Those surfaces now all *read* this
registry; producers publish with :func:`inc` / :func:`set_gauge` /
:func:`add_time` at the same sites that update their local state.

Three kinds, uniform dotted names (``consensus.groups``,
``retrace.align``, ``queue.producer_wait_s``):

- **counters** — monotone accumulators (:func:`inc`);
- **gauges**   — last-written values (:func:`set_gauge`);
- **timers**   — accumulated seconds (:func:`add_time`; span exits from
  :mod:`racon_tpu.obs.trace` land here keyed by the span name, which is
  where the run report's dispatch-vs-fetch split comes from).

The module IS the registry (state in module globals under one lock), so
``from racon_tpu.obs import metrics; metrics.inc(...)`` works from
anywhere without wiring an object through the call graph.  Dependency-
free (no jax, no numpy): importable from ``tests/conftest.py`` and
``utils/logger.py`` before any backend initializes.  Updates are a dict
write under a lock — nanoseconds against the chunk/group granularity of
every publishing site.

**Job scopes** (round 14, the resident polishing service): a thread may
declare a scope prefix (:func:`set_scope`, thread-local) and every
write it makes from then on lands under ``<scope><name>`` instead of
the plain name — ``job.7.align.dispatch`` rather than
``align.dispatch``.  That is what lets N concurrent service jobs share
the one registry without trampling each other: each job's worker thread
(and the polisher threads it spawns, which inherit the scope
explicitly) publishes into its own namespace, per-job reports read it
back with :func:`group`/:func:`snapshot` under the scope, and
:func:`clear_run` — whose prefixes never match a ``job.`` name — can no
longer wipe another job's in-flight gauges.  Scoped metrics are dropped
with :func:`clear_job` when the job record is retired.
"""

from __future__ import annotations

import sys
import threading
from typing import Dict, Optional, Set, Union

from .. import contracts

Number = Union[int, float]

_lock = threading.Lock()
_counters: Dict[str, Number] = {}
_gauges: Dict[str, Number] = {}
_timers: Dict[str, float] = {}
# every name ever written this process (scope-stripped, survives every
# clear_*): the RACON_TPU_SANITIZE=1 exit audit diffs this against
# contracts.METRICS to flag registered-but-never-emitted names
_seen: Set[str] = set()

# thread-local job scope: a prefix applied to every metric WRITE made
# by the declaring thread (reads always take explicit names — a reader
# aggregating per-job numbers passes the scope itself)
_tls = threading.local()

JOB_SCOPE_ROOT = contracts.JOB_SCOPE_ROOT


def job_scope(job_id) -> str:
    """The canonical scope prefix for one service job
    (``job.<id>.``)."""
    return f"{JOB_SCOPE_ROOT}{job_id}."


def set_scope(prefix: Optional[str]) -> None:
    """Prefix every metric write from the CURRENT THREAD with
    ``prefix`` (None/"" clears).  Thread-local and not inherited by
    spawned threads — a parent that fans work out re-applies its scope
    on the child (``Polisher.run`` does this for its layer-producer
    thread)."""
    _tls.scope = prefix or None


def get_scope() -> Optional[str]:
    """The current thread's write scope (None when unscoped)."""
    return getattr(_tls, "scope", None)


def _scoped(name: str) -> str:
    s = getattr(_tls, "scope", None)
    return s + name if s else name


def inc(name: str, delta: Number = 1) -> None:
    """Add ``delta`` to counter ``name`` (created at 0)."""
    scoped = _scoped(name)
    with _lock:
        _seen.add(name)
        _counters[scoped] = _counters.get(scoped, 0) + delta


def set_gauge(name: str, value: Number) -> None:
    """Set gauge ``name`` to ``value`` (last write wins)."""
    scoped = _scoped(name)
    with _lock:
        _seen.add(name)
        _gauges[scoped] = value


def add_time(name: str, seconds: float) -> None:
    """Accumulate ``seconds`` onto timer ``name``."""
    scoped = _scoped(name)
    with _lock:
        _seen.add(name)
        _timers[scoped] = _timers.get(scoped, 0.0) + seconds


def replace_timers(prefix: str, values: Dict[str, float],
                   scope: str = "") -> None:
    """Set the whole timer family ``prefix`` of ``scope`` to ``values``
    (``{suffix: seconds}``), dropping what it held — for a family that
    is *derived* at report time (the occupancy ledger's ``idle.<span>``
    seconds), so a report built twice reads the same numbers. The
    scope is explicit, like a reader's: the thread that builds a job's
    report is not the job's."""
    scoped = scope + prefix
    with _lock:
        for k in [k for k in _timers if k.startswith(scoped)]:
            del _timers[k]
        for suffix, seconds in values.items():
            _seen.add(prefix + suffix)
            _timers[scoped + suffix] = seconds


def seen_names() -> Set[str]:
    """Every metric name written this process (scope-stripped,
    cumulative across :func:`clear_run`/:func:`clear_job`) — the exit
    audit's emission record."""
    with _lock:
        return set(_seen)


def counter(name: str, default: Number = 0) -> Number:
    with _lock:
        return _counters.get(name, default)


def gauge(name: str, default: Number = 0) -> Number:
    with _lock:
        return _gauges.get(name, default)


def timer_s(name: str, default: float = 0.0) -> float:
    with _lock:
        return _timers.get(name, default)


def group(prefix: str) -> Dict[str, Number]:
    """Every metric under ``prefix`` (all three kinds merged), keyed by
    the name with the prefix stripped — e.g. ``group("retrace.")`` is
    the per-phase jit-retrace delta dict the heartbeat prints."""
    out: Dict[str, Number] = {}
    with _lock:
        for store in (_counters, _gauges, _timers):
            for k, v in store.items():
                if k.startswith(prefix):
                    out[k[len(prefix):]] = v
    return out


def clear(prefix: Optional[str] = None) -> None:
    """Drop metrics under ``prefix`` (every metric when None) — the
    shard runner clears ``retrace.`` between shards so a shard that
    short-circuits does not inherit the previous shard's churn."""
    with _lock:
        for store in (_counters, _gauges, _timers):
            if prefix is None:
                store.clear()
            else:
                for k in [k for k in store if k.startswith(prefix)]:
                    del store[k]


# every name a run report / runner summary / heartbeat reads describes
# ONE run; span timers land keyed by the span name, hence the phase
# prefixes.  The set itself lives in racon_tpu/contracts.py (one
# registry, statically gate-checked) — this alias keeps existing
# consumers and tests working.
_RUN_PREFIXES = contracts.RUN_PREFIXES


def clear_run() -> None:
    """Drop every per-run metric (:data:`_RUN_PREFIXES`) — called at
    run boundaries (``obs.begin``, ``ShardRunner.run``) so
    back-to-back runs in one process each report their own numbers
    instead of process-lifetime accumulations.  Job-scoped metrics
    (``job.<id>.*``) are deliberately NOT touched: a run boundary in
    one thread (a service job starting) must never wipe a
    concurrent job's in-flight gauges — that is :func:`clear_job`'s
    call, made by the job's own lifecycle.

    A family prefix (``"align."``) also drops the bare name
    (``"align"``): the aggregate ``align`` / ``consensus`` span timers
    match no dotted prefix and used to leak across the runs of one
    process."""
    bare = [p[:-1] for p in _RUN_PREFIXES if p.endswith(".")]
    with _lock:
        for store in (_counters, _gauges, _timers):
            for name in bare:
                store.pop(name, None)
    for prefix in _RUN_PREFIXES:
        clear(prefix)


def clear_job(job_id) -> None:
    """Drop every metric one service job published under its scope
    (the job-scoped analog of :func:`clear_run`)."""
    clear(job_scope(job_id))


def snapshot(scope: Optional[str] = None) -> Dict[str, Dict[str, Number]]:
    """Point-in-time copy of the registry (the run report embeds it
    verbatim).  With ``scope``, only that scope's metrics are returned,
    keyed by their unscoped names — the per-job report's view."""
    with _lock:
        if scope:
            return {
                "counters": {k[len(scope):]: v
                             for k, v in _counters.items()
                             if k.startswith(scope)},
                "gauges": {k[len(scope):]: v for k, v in _gauges.items()
                           if k.startswith(scope)},
                "timers": {k[len(scope):]: round(v, 6)
                           for k, v in _timers.items()
                           if k.startswith(scope)},
            }
        return {"counters": dict(_counters), "gauges": dict(_gauges),
                "timers": {k: round(v, 6) for k, v in _timers.items()}}


# ------------------------------------------------------------ derived views

def pack_summary(scope: str = "") -> Dict[str, Number]:
    """Pair-arena occupancy derived from the ``consensus.*`` counters
    the device engine publishes per launch — the registry twin of
    ``TpuPoaConsensus.pack_metrics()``, cumulative since the last run
    boundary (:func:`clear_run`) — plus the aligner's wavefront-arena
    occupancy (round 17, the ``align.*`` counters mirrored from every
    dispatched chunk; the registry twin of ``TpuAligner.pack_metrics``).
    ``scope`` reads one job's numbers."""
    with _lock:
        tot = _counters.get(scope + "consensus.lanes_total", 0)
        occ = _counters.get(scope + "consensus.lanes_occupied", 0)
        grp = _counters.get(scope + "consensus.groups", 0)
        wins = _counters.get(scope + "consensus.group_windows", 0)
        a_tot = _counters.get(scope + "align.lanes_total", 0)
        a_occ = _counters.get(scope + "align.lanes_occupied", 0)
        a_chunks = _counters.get(scope + "align.chunks", 0)
        a_wasted = _counters.get(scope + "align.steps_wasted", 0)
    eff = occ / tot if tot else 0.0
    a_eff = a_occ / a_tot if a_tot else 0.0
    return {"pack_efficiency": round(eff, 4),
            "pad_fraction": round(1.0 - eff, 4) if tot else 0.0,
            "windows_per_group": round(wins / grp, 2) if grp else 0.0,
            "groups": grp,
            "align_pack_efficiency": round(a_eff, 4),
            "align_pad_fraction": round(1.0 - a_eff, 4) if a_tot else 0.0,
            "align_chunks": a_chunks,
            "align_steps_wasted": a_wasted}


def queue_summary(scope: str = "") -> Dict[str, Number]:
    """The pipelined ``Polisher.run()`` bounded-queue health metrics:
    current depth plus accumulated producer/consumer blocking time.
    ``scope`` reads one job's numbers."""
    with _lock:
        depth = _gauges.get(scope + "queue.depth", 0)
        put_s = _timers.get(scope + "queue.producer_wait_s", 0.0)
        get_s = _timers.get(scope + "queue.consumer_wait_s", 0.0)
    return {"depth": depth,
            "producer_wait_s": round(put_s, 3),
            "consumer_wait_s": round(get_s, 3),
            "stall_s": round(put_s + get_s, 3)}


def device_summary(scope: str = "") -> Dict[str, Dict[str, Number]]:
    """Per-chip telemetry rows derived from the ``device.<ordinal>.*``
    metrics the in-process chip workers publish: shard/Mbp counters,
    polish seconds, and the per-thread span-timer mirrors
    (``device.0.poa.dispatch`` -> row ``"0"``, key ``"poa.dispatch"``).
    Empty for single-chip runs — the run report embeds this as its
    ``devices`` section."""
    rows: Dict[str, Dict[str, Number]] = {}
    for k, v in group(scope + "device.").items():
        dev, _, metric = k.partition(".")
        if not dev or not metric:
            continue
        rows.setdefault(dev, {})[metric] = (
            round(v, 6) if isinstance(v, float) else v)
    return rows


def overlap_summary(scope: str = "") -> Dict[str, Number]:
    """The first-party overlapper accounting the run report's
    ``overlap`` section (schema v10) embeds: the overlap source
    (``auto`` when the in-process minimizer+chain overlapper generated
    the rows — the ``overlap.mode_auto`` gauge — else ``paf`` for
    precomputed-file runs, where every other key is legitimately
    zero), table/candidate volume, the frequency-cap and chain
    keep/drop accounting (capped buckets are counted, never silent),
    the seed/chain/join dispatch-vs-fetch split from the obs span
    timers, and — new in v10 — the ragged chain-arena occupancy
    (``lanes_occupied/lanes_total/chunks``), the device-join bail-out
    count, and the target-table cache hit/miss accounting.  ``scope``
    reads one job's numbers."""
    with _lock:
        return {
            "mode": ("auto"
                     if _gauges.get(scope + "overlap.mode_auto", 0)
                     else "paf"),
            "minimizers": _counters.get(
                scope + "overlap.minimizers", 0),
            "candidate_pairs": _counters.get(
                scope + "overlap.candidate_pairs", 0),
            "freq_capped_buckets": _counters.get(
                scope + "overlap.freq_capped_buckets", 0),
            "chains_kept": _counters.get(
                scope + "overlap.chains_kept", 0),
            "chains_dropped": _counters.get(
                scope + "overlap.chains_dropped", 0),
            "lanes_occupied": _counters.get(
                scope + "overlap.lanes_occupied", 0),
            "lanes_total": _counters.get(
                scope + "overlap.lanes_total", 0),
            "chunks": _counters.get(scope + "overlap.chunks", 0),
            "join_bailouts": _counters.get(
                scope + "overlap.join_bailouts", 0),
            "cache_hits": _counters.get(
                scope + "overlap.cache_hits", 0),
            "cache_misses": _counters.get(
                scope + "overlap.cache_misses", 0),
            "seed_dispatch_s": round(_timers.get(
                scope + "overlap.seed.dispatch", 0.0), 3),
            "seed_fetch_s": round(_timers.get(
                scope + "overlap.seed.fetch", 0.0), 3),
            "join_dispatch_s": round(_timers.get(
                scope + "overlap.join.dispatch", 0.0), 3),
            "join_fetch_s": round(_timers.get(
                scope + "overlap.join.fetch", 0.0), 3),
            "chain_dispatch_s": round(_timers.get(
                scope + "overlap.chain.dispatch", 0.0), 3),
            "chain_fetch_s": round(_timers.get(
                scope + "overlap.chain.fetch", 0.0), 3),
        }


def rounds_summary(scope: str = "") -> dict:
    """The run report's ``rounds`` section (schema v14): the rounds of
    the job as ``cli.main``'s loop counted them — a one-shot job is one
    round; all zeros where no loop ran (a shard, a service job). The
    first and the last round's wall (from targets and reads indexed to
    the last stitch), backend compiles and kept overlaps as plain keys
    a metric's reader can reach, ``handoff_s`` summed over the
    hand-offs between rounds, and every round's row under ``rows``."""
    with _lock:
        count = int(_counters.get(scope + "rounds.completed", 0))

        def column(name: str, k: int):
            return _gauges.get(f"{scope}rounds.{name}.{k}", 0)

        rows = [{"round": k, "wall_s": round(column("wall_s", k), 6),
                 "handoff_s": round(column("handoff_s", k), 6),
                 "compiles": int(column("compiles", k)),
                 "overlaps_kept": int(column("overlaps_kept", k))}
                for k in range(1, count + 1)]
    first = rows[0] if rows else {}
    last = rows[-1] if rows else {}
    return {
        "count": count,
        "first_wall_s": first.get("wall_s", 0.0),
        "last_wall_s": last.get("wall_s", 0.0),
        "first_compiles": first.get("compiles", 0),
        "last_compiles": last.get("compiles", 0),
        "first_overlaps_kept": first.get("overlaps_kept", 0),
        "last_overlaps_kept": last.get("overlaps_kept", 0),
        "handoff_s": round(sum(r["handoff_s"] for r in rows), 6),
        "rows": rows,
    }


def shard_run_summary(scope: str = "") -> Dict[str, Number]:
    """The run report's ``shard_run`` section (schema v15): the job of
    the shard runner as it counted it in this process — shards done,
    those of them on the slot's device engines at the first attempt,
    those that needed another; the first and the last done shard's wall
    (running state saved to terminal state saved) and backend compiles; the bytes of the
    parts and of the extracted inputs; and ``boundary_idle_s``, the
    device-idle seconds between one shard's last device interval and
    the next one's first, summed over the boundaries (from the
    occupancy ledger: ``device_time.summary`` writes the gauge). Plain
    keys, so a metric's reader can reach them; the per-shard rows stay
    under the report's ``shards``. All zeros where no shard runner
    ran."""
    with _lock:
        def counted(name: str) -> int:
            return int(_counters.get(f"{scope}exec.{name}", 0))

        def gauged(name: str):
            return _gauges.get(f"{scope}exec.{name}", 0)

        return {
            "count": counted("shards_done"),
            "primary": counted("shards_primary"),
            "retried": counted("shards_retried"),
            "first_wall_s": round(gauged("first_shard_wall_s"), 6),
            "last_wall_s": round(gauged("last_shard_wall_s"), 6),
            "first_compiles": int(gauged("first_shard_compiles")),
            "last_compiles": int(gauged("last_shard_compiles")),
            "boundary_idle_s": round(gauged("boundary_idle_s"), 6),
            "part_bytes": counted("part_bytes"),
            "extract_bytes": counted("extract_bytes"),
        }


def recovery_summary() -> Dict[str, Number]:
    """The crash-safe-serving counters the run report's ``recovery``
    section (schema v5) embeds: journal replay/append/compaction
    volume, jobs restored across a server restart, spool verification
    outcomes, and slot-supervision churn.  These are SERVER-level
    facts published unscoped (``serve.*`` / ``slot.*`` are not run
    prefixes), so a per-job report shows its hosting server's totals
    — all zeros for plain CLI/exec runs."""
    return {
        "recovered_jobs": counter("serve.recovered_jobs"),
        "requeued_jobs": counter("serve.requeued_jobs"),
        "served_from_spool": counter("serve.spool_served"),
        "spool_corrupt": counter("serve.spool_corrupt"),
        "journal_replayed": counter("serve.journal_replayed"),
        "journal_records": counter("serve.journal_records"),
        "journal_compactions": counter("serve.journal_compactions"),
        "slot_restarts": counter("slot.restarts"),
        "slot_quarantined": counter("slot.quarantined"),
    }


def fleet_summary() -> Dict[str, Number]:
    """The fleet gateway counters the run report's ``fleet`` section
    (schema v11) embeds: admission outcomes at the TCP front door,
    placement/migration/preemption volume, the host-registry liveness
    gauges and the admission cost-estimate cache accounting.  These
    are GATEWAY-level facts published unscoped (``fleet.`` /
    ``gateway.`` are not run prefixes), so a report built inside a
    gateway process shows fleet-lifetime totals — all zeros for plain
    CLI/exec/serve runs."""
    return {
        "jobs_accepted": counter("gateway.accepted"),
        "jobs_rejected": counter("gateway.rejected"),
        "jobs_placed": counter("fleet.placed"),
        "jobs_migrated": counter("fleet.migrated"),
        "jobs_preempted": counter("fleet.preempted"),
        "hosts_alive": gauge("fleet.hosts_alive"),
        "hosts_dead": counter("fleet.hosts_dead"),
        "cost_cache_hits": counter("fleet.cost_cache_hits"),
        "cost_cache_misses": counter("fleet.cost_cache_misses"),
    }


def peak_rss_bytes() -> int:
    """Lifetime peak RSS of this process (ru_maxrss is KiB on Linux,
    bytes on macOS)."""
    import resource
    rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    return rss if sys.platform == "darwin" else rss * 1024
