"""The contract registry (racon_tpu.contracts) and its two enforcement
layers: the import-time selfcheck + state-machine declarations, the
runtime exit audit (sanitize.contract_audit), and the round-22 analyzer
surfaces (--rules-md/--check-readme generation, --changed-only helpers).

The headline test is the validator round-trip: a REAL synthetic polish
(first-party overlapper + device aligner path, span timers armed) built
into all three report kinds, each schema-valid, with ZERO
validator-defaulted keys among the sections that run exercises — every
exercised report key must trace back to a metric that actually fired,
not a section builder's ``.get()`` default."""

import pathlib
import sys

import pytest

from racon_tpu import contracts, sanitize
from racon_tpu.obs import metrics, report, trace

REPO = pathlib.Path(__file__).resolve().parent.parent


# ------------------------------------------------------------- registry

def test_registry_selfcheck_is_clean():
    """The registry's internal-consistency audit: grammar over every
    metric name, REPORT_BACKING targets registered, journal records are
    job states, section emitters declared."""
    assert contracts.selfcheck() == []


def test_a_retired_section_leaves_the_key_sets_at_its_version():
    """``REMOVED_KEYS`` takes a key of the report itself (section
    ``"top"``): from that version on the key is no top-level key and its
    section has no key set; the versions before keep both, which is what
    a stored report of theirs is held to."""
    assert contracts.REMOVED_KEYS["dataflow"] == ("top", 16)
    before, after = contracts.schema_keys(15), contracts.schema_keys(16)
    assert "dataflow" in before["top"] and "dataflow" not in after["top"]
    assert before["dataflow"] == frozenset(
        contracts.SECTION_KEYS["dataflow"]) and "dataflow" not in after
    assert after["top"] == before["top"] - {"dataflow"}
    # a key retired inside a section still leaves only that section
    assert "events" not in after["compiles"]
    assert "events" in contracts.schema_keys(12)["compiles"]
    # no emitter, metric or report key is left for it
    assert "dataflow" not in contracts.SECTION_EMITTERS
    assert not [m for m in contracts.METRICS if m.startswith("dataflow.")]
    assert not [k for k in contracts.REPORT_BACKING
                if k.startswith("dataflow.")]


def test_state_machines_declare_the_lifecycles():
    job, shard, lease = (contracts.JOB_MACHINE, contracts.SHARD_MACHINE,
                         contracts.LEASE_MACHINE)
    # the crash-recovery edges the serve/exec layers rely on
    assert job.has_edge("running", "queued")        # slot-death requeue
    assert job.has_edge("done", "queued")           # corrupt-spool requeue
    assert job.has_edge("running", "running")       # crash incarnation
    assert not job.has_edge("collected", "running")
    assert set(job.terminal) == {"failed", "cancelled", "collected"}
    assert shard.has_edge("done", "pending")        # part-CRC requeue
    assert shard.has_edge("quarantined", "pending")
    assert shard.terminal == ()                     # every state requeues
    assert lease.has_edge("expired", "claimed")
    placement = contracts.PLACEMENT_MACHINE
    # first-sight-stale beacon / restart under the same name
    assert placement.has_edge("registered", "dead")
    assert placement.has_edge("dead", "alive")
    assert "zombie" not in job and "pending" in shard
    # the journal's record alphabet is a subset of the job states
    assert set(contracts.JOURNAL_RECORDS) <= set(job.states)


def test_consumers_import_the_one_registry():
    """The string constants the serve/exec/fault layers use ARE the
    registry's — a drifted copy would bring back the round-21 class of
    bug where a literal and the machine disagreed silently."""
    from racon_tpu import faults
    from racon_tpu.exec import manifest
    from racon_tpu.serve import journal, service

    assert journal.SUBMITTED is contracts.JOB_SUBMITTED
    assert journal.COLLECTED is contracts.JOB_COLLECTED
    assert service.QUEUED is contracts.JOB_QUEUED
    assert manifest.QUARANTINED is contracts.SHARD_QUARANTINED
    assert faults.KNOWN_SITES is contracts.FAULT_SITES
    assert faults.CLASSES is contracts.FAULT_CLASSES


def test_clear_run_covers_aligner_metrics():
    """Drift regression (round 22): the ``aligner.*`` family is a
    per-run prefix — before the registry migration it was missing from
    the clear-list, so back-to-back runs accumulated band-escalation
    counters across run boundaries."""
    assert "aligner." in contracts.RUN_PREFIXES
    metrics.inc("aligner.band_escalated", 3)
    metrics.clear_run()
    assert metrics.counter("aligner.band_escalated", None) is None


# ----------------------------------------------------- runtime exit audit

def test_contract_audit_silent_before_any_emission(monkeypatch):
    monkeypatch.setattr(metrics, "_seen", set())
    audit = sanitize.contract_audit()
    assert audit == {"never_emitted": [], "defaulted_keys": []}


def test_contract_audit_diffs_registry_against_seen(monkeypatch, capsys):
    monkeypatch.setattr(metrics, "_seen", set())
    metrics.inc("queue.depth", 0)
    metrics.add_time("align.dispatch", 0.01)
    audit = sanitize.contract_audit(stream=sys.stderr)
    # the two emitted names are NOT defaulted/never-emitted ...
    assert "queue.depth" not in audit["never_emitted"]
    assert "queue.depth" not in audit["defaulted_keys"]
    assert "dispatch_fetch.align_dispatch_s" not in audit["defaulted_keys"]
    # ... everything else still is
    assert "serve.recovered_jobs" in audit["never_emitted"]
    assert "recovery.recovered_jobs" in audit["defaulted_keys"]
    # counts published as sanitize gauges for the chaos-soak report
    assert metrics.gauge("sanitize.contract_never_emitted") == len(
        audit["never_emitted"])
    assert metrics.gauge("sanitize.contract_defaulted_keys") == len(
        audit["defaulted_keys"])
    assert "contract audit" in capsys.readouterr().err


# ------------------------------------- the validator round-trip (v11)

# report keys whose backing metric a small-but-real polish (first-party
# overlapper, device aligner + consensus, span timers armed) MUST drive.
# Deliberately excludes feature-gated families a CLI run never touches:
# recovery.* (serve-only), compile_s (jax.monitoring availability
# varies) and the event-conditional overlap counters (join_bailouts,
# freq caps, cache hits).
_EXERCISED_KEYS = frozenset((
    "queue.depth", "queue.producer_wait_s", "queue.consumer_wait_s",
    "queue.stall_s",
    "pack.pack_efficiency", "pack.pad_fraction", "pack.windows_per_group",
    "pack.groups", "pack.align_pack_efficiency", "pack.align_pad_fraction",
    "pack.align_chunks", "pack.align_steps_wasted",
    "dispatch_fetch.align_dispatch_s", "dispatch_fetch.align_fetch_s",
    "dispatch_fetch.consensus_pack_s",
    "dispatch_fetch.consensus_dispatch_s", "dispatch_fetch.consensus_fetch_s",
    "overlap.minimizers", "overlap.candidate_pairs",
    "overlap.chains_kept", "overlap.chains_dropped",
    "overlap.lanes_occupied", "overlap.lanes_total", "overlap.chunks",
    "overlap.seed_dispatch_s", "overlap.seed_fetch_s",
    "overlap.chain_dispatch_s", "overlap.chain_fetch_s",
))


def test_report_roundtrip_all_kinds_zero_defaulted_keys(tmp_path):
    """Satellite: round-trip the v11 validator over all three report
    kinds built from ONE real synthetic polish.  Every kind validates
    clean, and the exit audit finds no validator-defaulted key among
    the sections the run exercised — i.e. the REPORT_BACKING map is
    honest: those keys carry measured values, not builder defaults."""
    sys.path.insert(0, str(REPO / "tests"))
    from test_columnar_init import write_synthetic_assembly
    from racon_tpu.core.polisher import create_polisher

    assert set(_EXERCISED_KEYS) <= set(contracts.REPORT_BACKING)

    rp, _pp, lp = write_synthetic_assembly(tmp_path, seed=37, n_contigs=2,
                                           contig=2500)
    trace.deactivate()
    trace.activate()                  # arm span timers (no trace ring)
    try:
        p = create_polisher(str(rp), "auto", str(lp), num_threads=2,
                            aligner_backend="tpu", aligner_batches=1,
                            consensus_backend="tpu", consensus_batches=1)
        polished = p.run(True)
    finally:
        trace.deactivate()
    assert polished

    entry = {"id": 0, "status": "done", "engine": "primary", "mbp": 0.005,
             "wall_s": 1.0, "retrace": {"align": 0}, "timings": {},
             "peak_rss_mb": 64}
    reps = {
        "cli": report.build_report("cli", argv=["x"], started_unix=1.0,
                                   wall_s=2.0, phases={"align_s": 0.5}),
        "exec": report.build_report("exec", shards=[entry]),
        "job": report.build_report("job"),
    }
    assert set(reps) == set(contracts.REPORT_KINDS)
    for kind, rep in reps.items():
        errs = report.validate_report(rep)
        assert errs == [], (kind, errs)
        assert rep["kind"] == kind

    audit = sanitize.contract_audit()
    defaulted = set(audit["defaulted_keys"]) & _EXERCISED_KEYS
    assert not defaulted, (
        f"exercised report keys carried only builder defaults "
        f"(backing metric never fired): {sorted(defaulted)}")
    # and the audit only ever names keys the registry declares
    assert set(audit["defaulted_keys"]) <= set(contracts.REPORT_BACKING)


# ----------------------------------------- analyzer surfaces (round 22)

def test_rules_md_matches_readme():
    """The README rule table is generated — `--check-readme` gates it."""
    from tools import analysis

    md = analysis.rules_md()
    assert analysis._TABLE_NOTE in md
    for rule in analysis.rules.ALL_RULES:
        assert f"`{rule.name}`" in md
    assert analysis.check_readme(str(REPO / "README.md"))
    assert not analysis.check_readme(str(REPO / "ROADMAP.md"))


def test_changed_only_expansion_pulls_import_neighbors(tmp_path):
    from tools import analysis
    from tools.analysis.astutil import Project, load_module

    (tmp_path / "pkg").mkdir()
    files = {"__init__.py": "", "base.py": "X = 1\n",
             "mid.py": "from pkg.base import X\n",
             "leaf.py": "import pkg.mid\n", "far.py": "Y = 2\n"}
    for name, src in files.items():
        (tmp_path / "pkg" / name).write_text(src)
    project = Project([load_module(tmp_path / "pkg" / name, f"pkg/{name}")
                       for name in files])

    got = analysis.expand_changed(project, {"pkg/base.py"})
    assert "pkg/base.py" in got
    assert "pkg/mid.py" in got          # one-hop importer
    assert "pkg/far.py" not in got      # unrelated stays out

    # analyzer/registry edits force a full run (None = no narrowing)
    assert any(t in ("racon_tpu/contracts.py",)
               for t in analysis._FULL_RUN_TRIGGERS)
