"""Observability subsystem (racon_tpu.obs): metrics registry, span
tracer, run reports — and the acceptance contracts: Chrome trace-event
schema on a CLI e2e run, byte-identity of polished output with
``RACON_TPU_TRACE`` on vs off, near-zero disabled-span cost in the
consensus hot loop, heartbeat/registry wiring, and run-report schema
validation for both CLI and exec runs."""

import json
import pathlib
import subprocess
import sys
import threading
import time

import pytest

from racon_tpu.obs import metrics, report, trace

REPO = pathlib.Path(__file__).resolve().parent.parent

# span names the acceptance criteria require a CLI trace to cover:
# parse / align / decode / build / consensus / stitch + queue waits
REQUIRED_SPANS = {"parse.targets", "parse.reads", "parse.overlaps",
                  "align", "bp.decode", "build.backbone",
                  "build.windows", "consensus", "stitch",
                  "queue.put", "queue.get"}


@pytest.fixture
def clean_trace():
    """Reset the tracer around a test that activates it (the registry
    uses test-unique names instead, so cross-test state is harmless)."""
    trace.deactivate()
    yield
    trace.deactivate()


# ---------------------------------------------------------------- metrics

def test_metrics_counter_gauge_timer():
    metrics.clear("t_obs.")
    metrics.inc("t_obs.c")
    metrics.inc("t_obs.c", 4)
    metrics.set_gauge("t_obs.g", 7)
    metrics.set_gauge("t_obs.g", 3)
    metrics.add_time("t_obs.t", 0.25)
    metrics.add_time("t_obs.t", 0.25)
    assert metrics.counter("t_obs.c") == 5
    assert metrics.gauge("t_obs.g") == 3
    assert metrics.timer_s("t_obs.t") == pytest.approx(0.5)
    assert metrics.counter("t_obs.missing", -1) == -1


def test_metrics_group_and_clear():
    metrics.clear("t_grp.")
    metrics.inc("t_grp.a", 2)
    metrics.set_gauge("t_grp.b", 9)
    metrics.add_time("t_grp.c", 1.5)
    assert metrics.group("t_grp.") == {"a": 2, "b": 9, "c": 1.5}
    metrics.clear("t_grp.")
    assert metrics.group("t_grp.") == {}


def test_metrics_thread_safety():
    metrics.clear("t_mt.")

    def worker():
        for _ in range(1000):
            metrics.inc("t_mt.n")

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert metrics.counter("t_mt.n") == 8000


def test_pack_summary_derivation():
    metrics.clear("consensus.")
    metrics.clear("align.")
    assert metrics.pack_summary()["groups"] == 0
    metrics.inc("consensus.lanes_occupied", 600)
    metrics.inc("consensus.lanes_total", 1000)
    metrics.inc("consensus.groups", 2)
    metrics.inc("consensus.group_windows", 10)
    # the round-17 aligner half of the summary
    metrics.inc("align.lanes_occupied", 300)
    metrics.inc("align.lanes_total", 400)
    metrics.inc("align.chunks", 3)
    metrics.inc("align.steps_wasted", 100)
    pack = metrics.pack_summary()
    assert pack == {"pack_efficiency": 0.6, "pad_fraction": 0.4,
                    "windows_per_group": 5.0, "groups": 2,
                    "align_pack_efficiency": 0.75,
                    "align_pad_fraction": 0.25,
                    "align_chunks": 3, "align_steps_wasted": 100}


# ------------------------------------------------------------ span tracer

def test_disabled_span_is_free(clean_trace):
    """The overhead guard: with tracing disabled, obs.span returns ONE
    shared no-op singleton (no allocation beyond the kwargs dict), so
    the consensus hot loop pays a global load + branch per span. 200k
    disabled spans must be far under any measurable slice of a
    consensus run (real cost ~50 ms; the bound is 20x slack for CI)."""
    from racon_tpu import obs

    probe = obs.span("consensus")  # graftlint has tests out of scope,
    assert probe is trace.NULL_SPAN  # but keep the sanctioned pattern
    t0 = time.perf_counter()
    for _ in range(200_000):
        with obs.span("consensus"):
            pass
    dt = time.perf_counter() - t0
    assert dt < 1.0, f"disabled spans cost {dt:.3f}s per 200k"


def test_span_records_timer_and_trace(clean_trace, tmp_path):
    from racon_tpu import obs

    metrics.clear("t_span.")
    trace.activate(tracing=True)
    with obs.span("t_span.outer", k=1):
        with obs.span("t_span.inner"):
            time.sleep(0.01)

    def worker():
        with obs.track("side"), obs.span("t_span.threaded"):
            pass

    t = threading.Thread(target=worker, name="obs-test-worker")
    t.start()
    t.join()
    assert metrics.timer_s("t_span.inner") >= 0.01
    assert metrics.timer_s("t_span.outer") >= metrics.timer_s(
        "t_span.inner")
    out = trace.export(str(tmp_path / "t.json"))
    assert out["events"] >= 3 and out["dropped"] == 0
    doc = json.loads((tmp_path / "t.json").read_bytes())
    events = doc["traceEvents"]
    spans = [e for e in events if e["ph"] == "X"]
    names = {e["name"] for e in spans}
    assert {"t_span.outer", "t_span.inner", "t_span.threaded"} <= names
    for e in spans:
        assert {"name", "cat", "ph", "pid", "tid", "ts", "dur"} <= set(e)
    outer = next(e for e in spans if e["name"] == "t_span.outer")
    inner = next(e for e in spans if e["name"] == "t_span.inner")
    # nesting: inner inside outer on the same track
    assert outer["tid"] == inner["tid"]
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1
    assert outer["args"] == {"k": 1}
    # thread/track metadata rows name every tid
    meta = {e["tid"]: e["args"]["name"] for e in events
            if e["name"] == "thread_name"}
    assert set(meta) == {e["tid"] for e in spans}
    assert any(name.endswith("/side") for name in meta.values())


def test_thread_buffers_survive_deactivate_reactivate(clean_trace,
                                                      tmp_path):
    """A persistent worker thread whose buffer predates a deactivate()
    must re-register on its next span (epoch bump) — its later spans
    must appear in the new export, not vanish into an orphaned ring."""
    from racon_tpu import obs

    barrier_in = threading.Event()
    barrier_go = threading.Event()

    def worker():
        with obs.span("t_epoch.first"):
            pass
        barrier_in.set()
        barrier_go.wait(5)
        with obs.span("t_epoch.second"):
            pass

    trace.activate(tracing=True)
    t = threading.Thread(target=worker)
    t.start()
    barrier_in.wait(5)
    trace.deactivate()
    trace.activate(tracing=True)
    barrier_go.set()
    t.join()
    out_path = tmp_path / "epoch.json"
    trace.export(str(out_path))
    names = {e["name"]
             for e in json.loads(out_path.read_bytes())["traceEvents"]
             if e.get("ph") == "X"}
    assert "t_epoch.second" in names
    assert "t_epoch.first" not in names  # pre-reset events are gone


def test_trace_ring_is_bounded(clean_trace, tmp_path, monkeypatch):
    from racon_tpu import obs

    monkeypatch.setattr(trace, "RING_CAP", 16)
    trace.activate(tracing=True)
    for _ in range(40):
        with obs.span("t_ring.x"):
            pass
    out = trace.export(str(tmp_path / "r.json"))
    assert out["dropped"] == 24
    doc = json.loads((tmp_path / "r.json").read_bytes())
    assert len([e for e in doc["traceEvents"] if e["ph"] == "X"]) == 16


# ----------------------------------------------- registry feeds (producers)

def test_retrace_budget_publishes_registry_gauge():
    from racon_tpu import sanitize

    metrics.clear("retrace.")
    with sanitize.PhaseRetraceBudget("obsphase", prefixes=("no.such.",)):
        pass
    assert metrics.group("retrace.") == {"obsphase": 0}


def test_log_swallowed_counts_suppressed(capsys):
    from racon_tpu.utils import logger

    metrics.clear("swallowed.")
    logger._seen_swallowed.clear()
    for _ in range(3):
        logger.log_swallowed("obs test ctx", ValueError("boom"))
    err = capsys.readouterr().err
    assert err.count("obs test ctx: swallowed ValueError") == 1
    # the registry shows how many faults the once-per-cause line hid
    assert metrics.counter("swallowed.obs test ctx|ValueError") == 3


def test_queue_metrics_from_pipelined_run(tmp_path):
    """Polisher.run() publishes bounded-queue wait/depth to the registry
    unconditionally (the heartbeat's queue[...] field reads them)."""
    from racon_tpu.core.polisher import create_polisher
    from test_columnar_init import write_synthetic_assembly

    metrics.clear("queue.")
    rp, pp, lp = write_synthetic_assembly(tmp_path, seed=31, n_contigs=1,
                                          contig=2000)
    p = create_polisher(str(rp), str(pp), str(lp), num_threads=2)
    polished = p.run(True)
    assert polished
    q = metrics.queue_summary()
    assert q["consumer_wait_s"] >= 0.0 and "stall_s" in q
    assert metrics.gauge("queue.depth", None) is not None


# ------------------------------------------------------------- run reports

def test_report_build_and_validate_roundtrip():
    rep = report.build_report("cli", argv=["a", "b"], started_unix=1.5,
                              wall_s=2.5, phases={"parse_s": 0.1})
    assert report.validate_report(rep) == []
    assert rep["schema_version"] == report.SCHEMA_VERSION
    assert rep["phases"] == {"parse_s": 0.1}


def test_report_validate_rejects_corruption():
    rep = report.build_report("cli")
    bad = dict(rep)
    del bad["queue"]
    assert any("queue" in e for e in report.validate_report(bad))
    bad = dict(rep, kind="daemon")
    assert any("kind" in e for e in report.validate_report(bad))
    bad = dict(rep, schema_version=99)
    assert any("schema_version" in e
               for e in report.validate_report(bad))
    bad = dict(rep, extra_key=1)
    assert any("unknown key" in e for e in report.validate_report(bad))
    bad = dict(rep, shards=[{"status": "done"}])  # missing id
    assert any("shards[0]" in e for e in report.validate_report(bad))
    bad = dict(rep, phases={"parse_s": "fast"})
    assert any("phases" in e for e in report.validate_report(bad))


def test_report_v16_has_no_dataflow_section():
    """Schema v16: the resident-dataflow accounting section left with
    its path. A report built today has none and validates; the counter
    the section mirrored is still a counter under ``metrics``."""
    metrics.set_scope("job.df1.")
    try:
        metrics.inc("consensus.ins_overflow_windows", 2)
    finally:
        metrics.set_scope(None)
    rep = report.build_report("job", scope="job.df1.")
    metrics.clear("job.df1.")
    assert rep["schema_version"] >= 16 and "dataflow" not in rep
    assert report.validate_report(rep) == []
    assert rep["metrics"]["counters"]["consensus.ins_overflow_windows"] == 2


def test_report_v16_refuses_a_dataflow_section_as_retired():
    """A v16 report that still carries the section is refused by name
    and version, not as an unknown key; the stored v11 report holds the
    section, is held to it key by key, and validates as what it is."""
    stored = json.loads((pathlib.Path(__file__).parent / "data" /
                         "run_report_v11.json").read_bytes())
    assert stored["schema_version"] == 11 and "dataflow" in stored
    assert report.validate_report(stored) == []
    rep = dict(report.build_report("cli"), dataflow=stored["dataflow"])
    assert report.validate_report(rep) == [
        "'dataflow' retired in schema v16"]
    # held to ITS key sets: without the section, or with one of its
    # keys gone or non-numeric, the v11 report is refused
    df = stored["dataflow"]
    broken = {k: v for k, v in stored.items() if k != "dataflow"}
    assert any("dataflow" in e for e in report.validate_report(broken))
    bad = dict(stored, dataflow=dict(df, bytes_fetched="lots"))
    assert any("bytes_fetched" in e for e in report.validate_report(bad))
    bad = dict(stored, dataflow={k: v for k, v in df.items()
                                 if k != "resident"})
    assert any("resident" in e for e in report.validate_report(bad))
    # the newest stored version that held it still does
    last = dict(report.build_report("cli"), schema_version=15,
                dataflow=df)
    assert report.validate_report(last) == []


def test_report_v10_requires_overlap_section():
    """Schema v10: the first-party overlapper accounting section is
    required — mode 'paf' with zeros for precomputed-overlap runs,
    mode 'auto' with the seed/join/chain numbers when the in-process
    overlapper generated the rows — and validated key-by-key,
    including the round-21 occupancy/join/cache keys."""
    metrics.clear("overlap.")
    rep = report.build_report("cli")
    assert report.validate_report(rep) == []
    ov = rep["overlap"]
    assert ov["mode"] == "paf"
    for key in ("minimizers", "candidate_pairs", "freq_capped_buckets",
                "chains_kept", "chains_dropped", "lanes_occupied",
                "lanes_total", "chunks", "join_bailouts", "cache_hits",
                "cache_misses", "seed_dispatch_s", "seed_fetch_s",
                "join_dispatch_s", "join_fetch_s", "chain_dispatch_s",
                "chain_fetch_s"):
        assert ov[key] == 0, (key, ov)
    broken = dict(rep)
    del broken["overlap"]
    assert any("overlap" in e for e in report.validate_report(broken))
    bad = dict(rep, overlap=dict(ov, chains_kept="many"))
    assert any("chains_kept" in e for e in report.validate_report(bad))
    bad = dict(rep, overlap=dict(ov, mode="minimap2"))
    assert any("mode" in e for e in report.validate_report(bad))
    bad = dict(rep, overlap={k: v for k, v in ov.items()
                             if k != "minimizers"})
    assert any("minimizers" in e for e in report.validate_report(bad))
    # the v10 keys are required, not merely emitted
    for v10_key in ("lanes_total", "join_bailouts", "cache_hits",
                    "join_dispatch_s"):
        bad = dict(rep, overlap={k: v for k, v in ov.items()
                                 if k != v10_key})
        assert any(v10_key in e for e in report.validate_report(bad)), \
            v10_key

    # an auto run's numbers flow through (scoped, like a job report)
    metrics.set_scope("job.ov1.")
    try:
        metrics.set_gauge("overlap.mode_auto", 1)
        metrics.inc("overlap.minimizers", 1234)
        metrics.inc("overlap.candidate_pairs", 56)
        metrics.inc("overlap.freq_capped_buckets", 7)
        metrics.inc("overlap.chains_kept", 40)
        metrics.inc("overlap.chains_dropped", 16)
        metrics.inc("overlap.lanes_occupied", 900)
        metrics.inc("overlap.lanes_total", 1024)
        metrics.inc("overlap.chunks", 3)
        metrics.inc("overlap.join_bailouts", 1)
        metrics.inc("overlap.cache_hits", 2)
        metrics.inc("overlap.cache_misses", 1)
        metrics.add_time("overlap.seed.dispatch", 0.5)
        metrics.add_time("overlap.join.dispatch", 0.125)
        metrics.add_time("overlap.join.fetch", 0.375)
        metrics.add_time("overlap.chain.fetch", 0.25)
    finally:
        metrics.set_scope(None)
    scoped = report.build_report("job", scope="job.ov1.")
    assert report.validate_report(scoped) == []
    assert scoped["overlap"]["mode"] == "auto"
    assert scoped["overlap"]["minimizers"] == 1234
    assert scoped["overlap"]["candidate_pairs"] == 56
    assert scoped["overlap"]["freq_capped_buckets"] == 7
    assert scoped["overlap"]["chains_kept"] == 40
    assert scoped["overlap"]["chains_dropped"] == 16
    assert scoped["overlap"]["lanes_occupied"] == 900
    assert scoped["overlap"]["lanes_total"] == 1024
    assert scoped["overlap"]["chunks"] == 3
    assert scoped["overlap"]["join_bailouts"] == 1
    assert scoped["overlap"]["cache_hits"] == 2
    assert scoped["overlap"]["cache_misses"] == 1
    assert scoped["overlap"]["seed_dispatch_s"] == 0.5
    assert scoped["overlap"]["join_dispatch_s"] == 0.125
    assert scoped["overlap"]["join_fetch_s"] == 0.375
    assert scoped["overlap"]["chain_fetch_s"] == 0.25
    metrics.clear("job.ov1.")


def test_report_shard_row_filters_manifest_keys():
    entry = {"id": 3, "status": "done", "part": "part_0003.fasta",
             "contigs": [1, 2], "engine": "primary", "mbp": 1.25,
             "wall_s": 9.0, "retrace": {"align": 0}, "timings": {},
             "peak_rss_mb": 100}
    row = report.shard_row(entry)
    assert "part" not in row and "contigs" not in row
    assert row["id"] == 3 and row["engine"] == "primary"
    rep = report.build_report("exec", shards=[entry])
    assert report.validate_report(rep) == []


def test_report_check_cli(tmp_path):
    rep = report.build_report("cli")
    path = tmp_path / "rep.json"
    report.write_report(str(path), rep)
    ok = subprocess.run(
        [sys.executable, "-m", "racon_tpu.obs", "--check", str(path)],
        cwd=REPO, capture_output=True, text=True)
    assert ok.returncode == 0, ok.stderr
    path.write_text("{}")
    bad = subprocess.run(
        [sys.executable, "-m", "racon_tpu.obs", "--check", str(path)],
        cwd=REPO, capture_output=True, text=True)
    assert bad.returncode == 1


# ----------------------------------------------------- CLI e2e (subprocess)

def _cli(tmp_path, inputs, *extra, env_extra=None):
    import os

    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    if env_extra:
        env.update(env_extra)
    proc = subprocess.run(
        [sys.executable, "-m", "racon_tpu", "-t", "4", *extra,
         *map(str, inputs)],
        capture_output=True, timeout=600, cwd=str(REPO), env=env)
    assert proc.returncode == 0, proc.stderr.decode()[-2000:]
    return proc


@pytest.fixture(scope="module")
def synthetic_inputs(tmp_path_factory):
    sys.path.insert(0, str(REPO / "tests"))
    from test_columnar_init import write_synthetic_assembly

    td = tmp_path_factory.mktemp("obs_cli")
    return write_synthetic_assembly(td, seed=29, n_contigs=2, contig=2500)


def test_cli_env_trace_byte_identity_and_schema(synthetic_inputs,
                                                tmp_path):
    """The acceptance triple on a full CLI run, driven by the ENV flags:
    polished stdout byte-identical with RACON_TPU_TRACE on vs off, the
    trace is Chrome trace-event JSON covering the required pipeline
    spans, and run_report.json validates against its schema.  The
    device-aligner path is on (--tpualigner-batches) so the trace shows
    the align dispatch-vs-fetch split."""
    plain = _cli(tmp_path, synthetic_inputs, "--tpualigner-batches", "1")
    tr = tmp_path / "trace.json"
    rp = tmp_path / "report.json"
    traced = _cli(tmp_path, synthetic_inputs, "--tpualigner-batches", "1",
                  env_extra={"RACON_TPU_TRACE": str(tr),
                             "RACON_TPU_RUN_REPORT": str(rp)})
    assert traced.stdout == plain.stdout, \
        "tracing changed the polished output bytes"

    doc = json.loads(tr.read_bytes())
    assert "traceEvents" in doc
    spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    names = {e["name"] for e in spans}
    missing = REQUIRED_SPANS - names
    assert not missing, f"trace missing required spans: {missing}"
    assert {"align.dispatch", "align.fetch"} <= names
    for e in spans:
        assert e["ts"] >= 0 and e["dur"] >= 0

    rep = json.loads(rp.read_bytes())
    assert report.validate_report(rep) == [], report.validate_report(rep)
    assert rep["kind"] == "cli"
    assert rep["phases"].get("align_s") is not None
    assert rep["dispatch_fetch"]["align_dispatch_s"] > 0
    assert rep["queue"]["stall_s"] >= 0


def test_cli_trace_flag_defaults_report_next_to_trace(synthetic_inputs,
                                                      tmp_path):
    """--trace FILE alone also emits run_report.json next to FILE."""
    tr = tmp_path / "t2" / "trace.json"
    tr.parent.mkdir()
    _cli(tmp_path, synthetic_inputs, "--trace", str(tr))
    assert tr.exists()
    rep = json.loads((tr.parent / "run_report.json").read_bytes())
    assert report.validate_report(rep) == []


def test_cli_exec_trace_and_report(synthetic_inputs, tmp_path):
    """Sharded (exec) CLI run: byte-identical output, per-shard trace
    tracks, a valid kind=exec report with one row per shard at BOTH the
    --run-report path and next to the manifest in the work dir."""
    plain = _cli(tmp_path, synthetic_inputs)
    tr = tmp_path / "exec_trace.json"
    rp = tmp_path / "exec_report.json"
    work = tmp_path / "work"
    sharded = _cli(tmp_path, synthetic_inputs, "--shards", "2",
                   "--shard-dir", str(work), "--trace", str(tr),
                   "--run-report", str(rp))
    assert sharded.stdout == plain.stdout
    err = sharded.stderr.decode()
    assert "pack[" in err and "queue[" in err and "retrace[" in err

    doc = json.loads(tr.read_bytes())
    names = {e["name"] for e in doc["traceEvents"] if e.get("ph") == "X"}
    assert {"exec.index", "exec.plan", "exec.extract", "exec.shard",
            "exec.merge"} <= names
    tracks = {e["args"]["name"] for e in doc["traceEvents"]
              if e["name"] == "thread_name"}
    assert any(t.endswith("shard 0") for t in tracks)
    assert any(t.endswith("shard 1") for t in tracks)

    for path in (rp, work / "run_report.json"):
        rep = json.loads(path.read_bytes())
        assert report.validate_report(rep) == [], (
            path, report.validate_report(rep))
        assert rep["kind"] == "exec"
        assert [r["id"] for r in rep["shards"]] == [0, 1]
        assert all(r["status"] == "done" for r in rep["shards"])
        assert all("retrace" in r for r in rep["shards"])


def test_exec_work_dir_report_has_real_timers(synthetic_inputs,
                                              tmp_path):
    """The shard runner persists its work-dir report on EVERY run, so it
    arms the span timers itself — a default run (no --trace /
    --run-report) must record real span seconds, not schema-valid
    zeros, and run-level retrace totals must survive the per-shard
    clear."""
    work = tmp_path / "work_plain"
    _cli(tmp_path, synthetic_inputs, "--shards", "2",
         "--shard-dir", str(work))
    rep = json.loads((work / "run_report.json").read_bytes())
    assert report.validate_report(rep) == []
    timers = rep["metrics"]["timers"]
    assert timers.get("exec.extract", 0) > 0
    assert timers.get("exec.shard", 0) > 0
    # run-level totals cover every shard (gauges are per-shard cleared)
    assert set(rep["retrace"]) >= {"align", "consensus"}


def test_run_boundary_clears_per_run_metrics():
    """clear_run()/obs.begin() drop every per-run name so back-to-back
    runs in one process do not report each other's numbers."""
    from racon_tpu import obs

    metrics.inc("consensus.lanes_total", 123)
    metrics.add_time("align.dispatch", 9.0)
    metrics.add_time("queue.consumer_wait_s", 9.0)
    metrics.inc("retrace_total.align", 7)
    metrics.inc("swallowed.ctx|ValueError", 5)
    metrics.set_gauge("trace.dropped_events", 11)
    obs.begin()
    assert metrics.counter("consensus.lanes_total") == 0
    assert metrics.timer_s("align.dispatch") == 0.0
    assert metrics.queue_summary()["stall_s"] == 0.0
    assert metrics.group("retrace_total.") == {}
    assert metrics.group("swallowed.") == {}
    assert metrics.gauge("trace.dropped_events") == 0


def test_exec_run_is_isolated_from_prior_registry_state(
        synthetic_inputs, tmp_path):
    """A ShardRunner.run() in a process that already polished
    (tests, service mode) must report ITS pack/dispatch numbers, not the
    process-lifetime accumulation."""
    from racon_tpu.exec import ShardRunner

    metrics.inc("consensus.lanes_total", 10**9)
    metrics.add_time("align.dispatch", 1e6)
    rp, pp, lp = synthetic_inputs
    runner = ShardRunner(str(rp), str(pp), str(lp), num_threads=2,
                         n_shards=2, work_dir=str(tmp_path / "iso"))
    with open(tmp_path / "iso.fasta", "wb") as out:
        runner.run(out)
    assert metrics.counter("consensus.lanes_total") < 10**9
    assert runner.report["dispatch_fetch"]["align_dispatch_s"] < 1e5


def test_track_survives_deactivate_mid_track(clean_trace):
    """deactivate() while a thread is inside obs.track() must not make
    the track exit pop from the freshly re-registered (empty) buffer."""
    from racon_tpu import obs

    trace.activate(tracing=True)
    with obs.track("t_mid.shard"):
        trace.deactivate()
        trace.activate(tracing=True)
        with obs.span("t_mid.inner"):
            pass  # re-registers a fresh buffer with an empty track stack
    # the new buffer's (empty) track stack was left alone
    assert trace._buf().tracks == []


def test_cli_create_polisher_error_still_writes_report(tmp_path):
    """A bad input (the most common user error) exits 1 but still writes
    the requested trace/run-report — a report of the failed run is the
    data needed to debug it."""
    import os

    tr = tmp_path / "err_trace.json"
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "racon_tpu", "--trace", str(tr),
         str(tmp_path / "missing.fastq"), str(tmp_path / "missing.paf"),
         str(tmp_path / "missing.fasta")],
        capture_output=True, timeout=300, cwd=str(REPO), env=env)
    assert proc.returncode == 1
    rep = json.loads((tmp_path / "run_report.json").read_bytes())
    assert report.validate_report(rep) == []
    assert rep["kind"] == "cli"
    assert tr.exists()


def test_cli_golden_byte_exact_with_trace(data_dir, tmp_path):
    """λ-phage golden with tracing on: the recorded golden was produced
    WITHOUT tracing, so a byte-exact match proves --trace cannot perturb
    output on real data (skips where the reference set is absent)."""
    golden = REPO / "tests" / "data" / "golden_lambda_fastq_paf.fasta"
    tr = tmp_path / "lambda_trace.json"
    proc = _cli(tmp_path,
                [data_dir / "sample_reads.fastq.gz",
                 data_dir / "sample_overlaps.paf.gz",
                 data_dir / "sample_layout.fasta.gz"],
                "-t", "8", "--trace", str(tr))
    assert proc.stdout == golden.read_bytes()
    rep = json.loads((tmp_path / "run_report.json").read_bytes())
    assert report.validate_report(rep) == []
    names = {e["name"]
             for e in json.loads(tr.read_bytes())["traceEvents"]
             if e.get("ph") == "X"}
    assert REQUIRED_SPANS <= names
