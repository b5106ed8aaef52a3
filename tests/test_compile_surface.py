"""Compile-surface runtime attribution (round 18).

The static half lives in ``tools/analysis`` (jit-shape-hazard /
dtype-drift / jit-in-loop / warmup-coverage / host-transfer-in-jit,
self-tested via ``--selftest``); this file proves the RUNTIME half:
the process-wide ``jax.monitoring`` listener writes one row per
compiled program — JAX's name for it, the stages' interval and seconds,
cache hit or miss, and (function, shape signature, phase, scope) of who
asked — the per-job ``compile_s`` semantics of the absorbed serve
listener are preserved, the run report's ``compiles`` section (a ledger
of programs since schema v13) validates and sums to the stage timers,
and the sanitize gate judges only the offending scope.  (The full
sanitized-serve warm-path acceptance test rides at the end of
``tests/test_serve.py`` — see the note at the bottom of this file.)"""

import json
import os
import pathlib
import subprocess
import sys

import pytest

from racon_tpu import sanitize
from racon_tpu.obs import compilewatch, metrics, report, trace


@pytest.fixture(autouse=True)
def _fresh_watch():
    compilewatch.reset()
    metrics.clear("compile.")
    yield
    compilewatch.reset()
    metrics.clear("compile.")


@pytest.fixture
def fake_clock(monkeypatch):
    """``clock[0]`` is the compile watch's ``perf_counter_ns``."""
    import types

    clock = [0]
    monkeypatch.setattr(compilewatch, "time", types.SimpleNamespace(
        perf_counter_ns=lambda: clock[0]))
    return clock


def _fake_compile(max_len, band, duration=0.5, fun_name=""):
    """Drive the listener directly: attribution walks the stack and —
    with no racon_tpu frame above — lands on THIS frame, whose integer
    locals (max_len/band) form the shape signature."""
    compilewatch._on_duration(
        "/jax/core/compile/backend_compile_duration", duration,
        fun_name=fun_name)


# ------------------------------------------------------------ attribution

def test_attribution_names_function_and_shape_on_forced_retrace(
        tmp_path, monkeypatch):
    """A real forced retrace through a repo driver: the attributed
    event names the driving function and its dispatch geometry."""
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    from racon_tpu import ops
    from racon_tpu.ops import nw

    # a fresh persistent-cache dir so this geometry genuinely compiles
    # — re-pointed BACK afterwards: the cache dir is process-wide, and
    # leaving it on a tmp_path would make every later test in the
    # session compile cold
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    ops.configure_compile_cache(str(tmp_path / "xla_cache"))
    try:
        assert compilewatch.arm()

        # an oddball geometry nothing else in the suite dispatches (XLA
        # path: no Pallas/SWAR multiples required)
        max_len, band, steps, B = 320, 40, 512, 2
        width = band // 2 + max_len + band
        qrp = jnp.zeros((B, width), jnp.uint8)
        tp = jnp.zeros((B, width), jnp.uint8)
        n = jnp.ones((B,), jnp.int32)
        m = jnp.ones((B,), jnp.int32)
        out = nw.align_chain(qrp, tp, n, m, max_len=max_len, band=band,
                             steps=steps, use_pallas=False,
                             use_swar=False)
        jax.block_until_ready(out[1])
    finally:
        ops.configure_compile_cache()

    evs = [e for e in compilewatch.events() if "align_chain" in e["fn"]]
    assert evs, (f"no compile attributed to align_chain: "
                 f"{compilewatch.events()}")
    assert any("max_len=320" in e["signature"]
               and "band=40" in e["signature"] for e in evs), evs
    # the rows name the two programs of the XLA chain as JAX and the
    # device trace do, not the frame that drove them; a fresh cache
    # directory was asked and had neither
    assert {e["program"] for e in evs} == {"jit__nw_wavefront_kernel",
                                           "jit__traceback_kernel"}
    assert all(e["cache"] == "miss" and e["retrieve_s"] == 0
               and e["backend_s"] > 0 for e in evs), evs
    assert metrics.timer_s("compile.jax_s") > 0


def test_phase_attribution_reads_innermost_open_span():
    trace.activate()
    try:
        from racon_tpu import obs
        with obs.span("align.dispatch"):
            assert trace.current_span() == "align.dispatch"
            _fake_compile(128, 16)
        assert trace.current_span() is None
    finally:
        trace.deactivate()
    (ev,) = compilewatch.events()
    assert ev["phase"] == "align.dispatch"
    assert ev["fn"].endswith("._fake_compile")


# ---------------------------------------- serve listener absorbed (dedupe)

def test_scoped_compile_s_preserved_and_serve_listener_absorbed():
    """The round-14 serve contract, now served by the process-wide
    listener: compile seconds fired on a scoped thread land in that
    scope, and ``dispatch_fetch.compile_s`` of the per-job report
    keeps its value.  The serve-only listener is gone."""
    metrics.set_scope("job.t1.")
    try:
        _fake_compile(256, 64, duration=1.25)
        # a non-backend pipeline stage adds time but no event — the
        # exact accumulation semantics of the old serve listener
        compilewatch._on_duration(
            "/jax/core/compile/jaxpr_trace_duration", 0.25)
    finally:
        metrics.set_scope(None)
    assert metrics.timer_s("job.t1.compile.jax_s") == \
        pytest.approx(1.50)
    rep = report.build_report("job", scope="job.t1.")
    assert report.validate_report(rep) == []
    assert rep["dispatch_fetch"]["compile_s"] == pytest.approx(1.50)
    comp = rep["compiles"]
    assert comp["count"] == 1 and comp["post_warm"] == 0
    (row,) = comp["programs"]
    assert row["fn"] == "test_compile_surface._fake_compile"
    assert row["signature"] == "max_len=256,band=64"
    # the bare trace stage reached no backend and preceded none
    assert row["backend_s"] == pytest.approx(1.25)
    assert comp["unrowed_s"] == pytest.approx(0.25)

    from racon_tpu.serve import service
    assert not hasattr(service, "arm_compile_monitor")


def test_report_v7_requires_compiles_section():
    rep = report.build_report("cli")
    assert rep["schema_version"] == report.SCHEMA_VERSION
    assert report.validate_report(rep) == []
    broken = dict(rep)
    del broken["compiles"]
    assert any("compiles" in e for e in report.validate_report(broken))
    bad = dict(rep, compiles=dict(rep["compiles"], post_warm="x"))
    assert any("post_warm" in e for e in report.validate_report(bad))


# -------------------------------------------------------- warm-path seal

def test_seal_flags_only_unwarmed_shapes_with_nearest():
    _fake_compile(256, 64)
    compilewatch.seal("test warm-up complete")
    assert compilewatch.sealed() == "test warm-up complete"
    metrics.set_scope("job.seal.")      # job work is always scoped
    try:
        _fake_compile(256, 64)          # warmed shape: silent
        assert compilewatch.post_warm() == []
        _fake_compile(1024, 64)         # genuinely unwarmed
    finally:
        metrics.set_scope(None)
    viol = compilewatch.post_warm()
    assert len(viol) == 1
    assert "max_len=1024" in viol[0]["signature"]
    assert "max_len=256" in viol[0]["nearest_warmed"]
    msg = compilewatch.describe(viol)
    assert "max_len=1024" in msg and "nearest warmed" in msg
    assert compilewatch.summary()["post_warm"] == 1
    metrics.clear("job.seal.")


def test_unscoped_post_seal_compile_is_warmup_not_violation():
    """An UNSCOPED compile after the seal is warm-up/background work by
    construction (job work always runs under a metric scope): it joins
    the warmed set — so a job later dispatching that geometry is warm —
    and is never recorded as a violation."""
    _fake_compile(256, 64)
    compilewatch.seal("t")
    _fake_compile(4096, 64)             # admission warm-up, unscoped
    assert compilewatch.post_warm() == []
    metrics.set_scope("job.w.")
    try:
        _fake_compile(4096, 64)         # the job re-compiles it: warm
    finally:
        metrics.set_scope(None)
    assert compilewatch.post_warm() == []
    metrics.clear("job.w.")


def test_unseal_relearns_capacity_changed_geometry():
    """The degradation-ladder contract: a capacity change re-opens the
    seal (serve's OOM rung calls ``unseal()``), the shrunk geometry's
    compiles land in the warmed set, and after the re-seal the same
    geometry is silent instead of failing every subsequent job."""
    _fake_compile(1024, 64)
    compilewatch.seal("warm")
    compilewatch.unseal()             # reduce_capacity re-opens
    _fake_compile(512, 64)            # the shrunk-arena re-warm compile
    compilewatch.seal("re-warm after capacity change")
    _fake_compile(512, 64)            # next job, shrunk geometry: warm
    assert compilewatch.post_warm() == []


def test_run_boundary_resets_attribution():
    """A second run in one process must not report the first run's
    events: ``obs.begin()`` (the CLI/exec run boundary) resets the
    watch alongside ``metrics.clear_run()``."""
    from racon_tpu import obs

    _fake_compile(256, 64)
    assert compilewatch.summary()["count"] == 1
    obs.begin()
    assert compilewatch.summary() == {
        "total_s": 0.0, "count": 0, "post_warm": 0, "sealed": 0,
        "programs": [], "dropped": 0, "wall_s": 0.0, "unused": 0,
        "unused_s": 0.0, "eager_programs": 0, "miss_s": 0.0,
        "unrowed_s": 0.0}


def test_scoped_count_exact_past_event_ring_eviction(monkeypatch):
    """The row ring is bounded; a job whose early rows were evicted
    still reports its exact compile count (the scoped counter, not the
    ring) and says how many rows it lost."""
    monkeypatch.setattr(compilewatch, "MAX_ROWS", 4)
    metrics.set_scope("job.ring.")
    try:
        for _ in range(10):
            _fake_compile(128, 8)
    finally:
        metrics.set_scope(None)
    s = compilewatch.summary("job.ring.")
    assert s["count"] == 10
    assert len(s["programs"]) == 4 and s["dropped"] == 6
    metrics.clear("job.ring.")


def test_violation_cap_cannot_disarm_later_jobs():
    """The bounded violation list evicts FIFO and judged scopes are
    pruned — a flood of earlier violations must not make a later job's
    genuine warm-path violation invisible to the sanitized assert."""
    compilewatch.seal("t")
    metrics.set_scope("job.flood.")
    try:
        for k in range(compilewatch.MAX_VIOLATIONS + 8):
            _fake_compile(8192 + k, 8)
    finally:
        metrics.set_scope(None)
    metrics.set_scope("job.later.")
    try:
        _fake_compile(31337, 8)
    finally:
        metrics.set_scope(None)
    assert len(compilewatch.post_warm("job.later.")) == 1
    compilewatch.clear_scope("job.later.")     # the judgment prune
    assert compilewatch.post_warm("job.later.") == []
    assert len(compilewatch.post_warm()) <= compilewatch.MAX_VIOLATIONS
    metrics.clear("job.flood.")
    metrics.clear("job.later.")


def test_sanitize_gate_raises_only_when_armed(monkeypatch):
    _fake_compile(128, 64)
    compilewatch.seal("t")
    metrics.set_scope("job.t9.")
    try:
        _fake_compile(4096, 64)
    finally:
        metrics.set_scope(None)
    monkeypatch.delenv("RACON_TPU_SANITIZE", raising=False)
    assert len(sanitize.check_post_warm_compiles("job.t9.")) == 1
    assert sanitize.check_post_warm_compiles("job.other.") == []
    monkeypatch.setenv("RACON_TPU_SANITIZE", "1")
    with pytest.raises(sanitize.CompileAfterWarmError) as ei:
        sanitize.check_post_warm_compiles("job.t9.")
    assert "nearest warmed" in str(ei.value)
    assert "max_len=4096" in str(ei.value)


# ------------------------------------------------- the ledger of programs

BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"


def test_a_jit_compiled_on_a_named_thread_is_one_row_with_jaxs_name():
    """One ``jit`` call that reaches the backend is one row: JAX's own
    name for the program, the compiling thread, the innermost open span,
    stamps inside that span, and the stages (the nested traces of the
    operators folded in) summing to what the stage timers got."""
    jax = pytest.importorskip("jax")
    import threading
    import time

    import numpy as np

    from racon_tpu import obs

    assert compilewatch.arm()

    def _row_probe_fn(x):
        return x * 3 + 1

    stamps = {}

    def work():
        with obs.span("align.launch"):
            stamps["t0"] = time.perf_counter_ns()
            jax.jit(_row_probe_fn)(np.arange(37, dtype=np.int32))
            stamps["t1"] = time.perf_counter_ns()

    trace.new_run()
    trace.activate()
    try:
        t = threading.Thread(target=work, name="t-compiler")
        t.start()
        t.join()
        comp = compilewatch.summary()
        timers = sum(metrics.timer_s(f"compile.{st}")
                     for st in ("trace", "lower", "backend"))
    finally:
        trace.deactivate()
    (row,) = comp["programs"]
    assert row["program"] == "jit__row_probe_fn"
    assert row["thread"] == "t-compiler"
    assert row["phase"] == "align.launch"
    assert row["fn"].endswith(".work")
    assert stamps["t0"] <= row["t0_ns"] < row["t1_ns"] <= stamps["t1"]
    assert min(row["trace_s"], row["lower_s"], row["backend_s"]) > 0
    # nobody submitted it to the occupancy ledger: an eager helper
    assert row["dispatches"] is None and row["geometry"] == ""
    assert comp["count"] == 1 and comp["dropped"] == 0
    assert comp["eager_programs"] == 1 and comp["unused"] == 0
    assert compilewatch.stage_s(row) + comp["unrowed_s"] == \
        pytest.approx(timers, rel=0.01)
    # one thread: the row's interval is the section's wall
    assert comp["wall_s"] == pytest.approx(
        (row["t1_ns"] - row["t0_ns"]) * 1e-9, abs=1e-5)
    assert comp["wall_s"] >= compilewatch.stage_s(row) - 1e-3


def test_a_cache_hit_reads_retrieval_apart_and_a_miss_reads_none(
        tmp_path, monkeypatch):
    """Two functions of one body are one persistent-cache key: the
    first compile misses (``retrieve_s`` 0), the second is handed the
    executable back — ``cache`` ``hit``, the retrieval's seconds in the
    row and in the ``compile.retrieve`` timer, still inside
    ``compile.backend``."""
    jax = pytest.importorskip("jax")
    import numpy as np

    from racon_tpu import ops

    def make():
        def _cache_probe_fn(x):
            return (x * 5 - 2) % 11
        return jax.jit(_cache_probe_fn)

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    ops.configure_compile_cache(str(tmp_path / "xla_cache"),
                                min_compile_time_s=0.0)
    trace.new_run()
    trace.activate()
    try:
        assert compilewatch.arm()
        x = np.arange(41, dtype=np.int32)
        make()(x)
        make()(x)
        comp = compilewatch.summary()
        retrieved = metrics.timer_s("compile.retrieve")
        backend = metrics.timer_s("compile.backend")
    finally:
        trace.deactivate()
        ops.configure_compile_cache()
    miss, hit = comp["programs"]
    assert miss["program"] == hit["program"] == "jit__cache_probe_fn"
    assert miss["cache"] == "miss" and miss["retrieve_s"] == 0
    assert hit["cache"] == "hit" and hit["retrieve_s"] > 0
    assert hit["retrieve_s"] <= hit["backend_s"] + 1e-6
    assert retrieved == pytest.approx(hit["retrieve_s"], abs=1e-5)
    # compile.backend keeps its meaning: compile or load, both rows'
    assert backend == pytest.approx(miss["backend_s"] + hit["backend_s"],
                                    rel=0.01)
    assert comp["miss_s"] == pytest.approx(compilewatch.stage_s(miss),
                                           abs=1e-5)
    assert metrics.counter("compile.cache_requests") == 2
    assert metrics.counter("compile.cache_hits") == 1


def test_wall_s_of_two_overlapping_rows_is_their_union(fake_clock):
    """Two threads compile at once: ``wall_s`` is the union of the rows'
    intervals on a fake clock, not their sum."""
    import threading

    clock = fake_clock

    def compile_at(now_s, duration):
        clock[0] = int(now_s * 1e9)
        _fake_compile(128, 16, duration=duration, fun_name="jit(_k)")

    compile_at(10.0, 4.0)                       # main: 6 .. 10
    t = threading.Thread(target=compile_at, args=(12.0, 4.0),
                         name="t-other")        # other: 8 .. 12
    t.start()
    t.join()
    compile_at(20.0, 1.0)                       # main again: 19 .. 20
    comp = compilewatch.summary()
    assert [(r["t0_ns"], r["t1_ns"], r["thread"])
            for r in comp["programs"]] == [
        (6_000_000_000, 10_000_000_000, "MainThread"),
        (8_000_000_000, 12_000_000_000, "t-other"),
        (19_000_000_000, 20_000_000_000, "MainThread")]
    assert sum(r["backend_s"] for r in comp["programs"]) == \
        pytest.approx(9.0)
    assert comp["wall_s"] == pytest.approx(7.0)
    assert compilewatch.union_s([]) == 0


def test_stages_fold_into_the_row_of_the_call_that_reached_the_backend(
        fake_clock):
    """An inner jit traced inside the outer trace, the lowering with the
    operators it traces, and the backend are one row (each stage its
    self time); a bare trace before it that reached no backend
    (``eval_shape``) is in no row."""
    clock = fake_clock
    ev = "/jax/core/compile/"

    trace_ev, lower_ev = (ev + "jaxpr_trace_duration",
                          ev + "jaxpr_to_mlir_module_duration")

    def begin(event):
        compilewatch._on_scalar(event, 0.0)

    def end(end_s, event, duration, fun_name):
        clock[0] = int(end_s * 1e9)
        compilewatch._on_duration(event, duration, fun_name=fun_name)

    begin(trace_ev)
    end(1.000, trace_ev, 0.020, "other")                    # bare
    begin(trace_ev)                                         # outer
    begin(trace_ev)
    end(2.015, trace_ev, 0.010, "inner")
    end(2.050, trace_ev, 0.050, "outer")
    begin(lower_ev)
    # a Mosaic lowering traces thousands of operators before it ends:
    # they are its frame's two numbers, and the function's own trace is
    # still the stage before it
    for k in range(5000):
        begin(trace_ev)
        end(2.052 + k * 1e-6, trace_ev, 5e-7, "less")
    end(2.060, lower_ev, 0.010, "jit(outer)")
    compilewatch._on_event(compilewatch.CACHE_REQUEST)
    begin(BACKEND_EVENT)
    end(2.200, BACKEND_EVENT, 0.100, "jit(outer)")
    comp = compilewatch.summary()
    (row,) = comp["programs"]
    assert row["program"] == "jit_outer" and row["cache"] == "miss"
    assert row["t0_ns"] == pytest.approx(2_000_000_000, abs=10)
    assert row["t1_ns"] == 2_200_000_000
    flood = 5000 * 5e-7
    assert row["trace_s"] == pytest.approx(0.050 + flood, abs=1e-6)
    assert row["lower_s"] == pytest.approx(0.010 - flood, abs=1e-6)
    assert row["backend_s"] == pytest.approx(0.100)
    assert comp["unrowed_s"] == pytest.approx(0.020)
    assert comp["wall_s"] == pytest.approx(0.200)
    # a backend event the cache was asked nothing about
    end(3.0, BACKEND_EVENT, 0.001, "jit(plain)")
    assert compilewatch.events()[-1]["cache"] == "none"


def test_v13_validates_rows_and_refuses_the_retired_keys():
    _fake_compile(128, 16, fun_name="jit(_k)")
    rep = report.build_report("cli")
    assert rep["schema_version"] == 16      # the rows are v13's
    assert report.validate_report(rep) == []
    comp = rep["compiles"]
    assert "by_function" not in comp and "events" not in comp
    assert "compile.retrieve" in rep["metrics"]["timers"]
    bad = dict(rep, compiles=dict(comp, by_function={}))
    assert any("by_function" in e and "retired" in e
               for e in report.validate_report(bad))
    bad = dict(rep, compiles={k: v for k, v in comp.items()
                              if k != "programs"})
    assert any("programs" in e for e in report.validate_report(bad))
    row = dict(comp["programs"][0], cache="maybe")
    bad = dict(rep, compiles=dict(comp, programs=[row]))
    assert any("programs[0]" in e for e in report.validate_report(bad))
    row = {k: v for k, v in comp["programs"][0].items()
           if k != "dispatches"}
    bad = dict(rep, compiles=dict(comp, programs=[row]))
    assert any("programs[0]" in e for e in report.validate_report(bad))


# ---------------------------- a process's first job: the warm-up's report

REPO = pathlib.Path(__file__).resolve().parent.parent
NEW_METRICS = ("warmup_job_s", "warmup_compile_idle_s", "compile_wall_s",
               "cache_retrieve_s", "compile_unused_s",
               "compile_eager_programs")


@pytest.fixture(scope="module")
def first_job(tmp_path_factory):
    """A tiny CLI job as a fresh process's first, both device engines on
    their XLA twins, with ``--run-report``: what the benchmark's warm-up
    job is on the chip — every program the job runs is compiled or
    loaded in it. ``(report, path)``."""
    sys.path.insert(0, str(REPO / "tests"))
    from test_columnar_init import write_synthetic_assembly

    td = tmp_path_factory.mktemp("first_job")
    rp, pp, lp = write_synthetic_assembly(td, seed=43, n_contigs=1,
                                          contig=2000)
    rep = td / "run_report.json"
    # one device: the engines' one-device streams, as on the chip (the
    # suite's eight virtual devices would make them a mesh)
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=1"}
    env.pop("RACON_TPU_RUN_REPORT", None)
    with open(td / "polished.fasta", "wb") as out:
        proc = subprocess.run(
            [sys.executable, "-m", "racon_tpu", "-t", "2", "-c", "1",
             "--tpualigner-batches", "1", "--run-report", str(rep),
             str(rp), str(pp), str(lp)],
            cwd=str(REPO), env=env, stdout=out, stderr=subprocess.PIPE,
            timeout=1200)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(rep.read_bytes()), rep


def test_first_job_rows_account_for_the_count_and_the_stage_timers(
        first_job):
    rep, _ = first_job
    assert report.validate_report(rep) == []
    comp = rep["compiles"]
    assert comp["count"] > 0 and comp["post_warm"] == 0
    assert len(comp["programs"]) + comp["dropped"] == comp["count"]
    timers = rep["metrics"]["timers"]
    staged = sum(map(compilewatch.stage_s, comp["programs"]))
    assert staged + comp["unrowed_s"] == pytest.approx(
        sum(timers[f"compile.{st}"]
            for st in ("trace", "lower", "backend")), rel=0.01)
    assert 0 < comp["wall_s"] <= rep["wall_s"]
    assert [r["t0_ns"] for r in comp["programs"]] == sorted(
        r["t0_ns"] for r in comp["programs"])
    assert timers["compile.retrieve"] == pytest.approx(
        sum(r["retrieve_s"] for r in comp["programs"]), abs=1e-4)


def test_first_job_every_ledger_program_has_a_row_that_counts_its_dispatches(
        first_job):
    """The join: every program the occupancy ledger names was compiled
    or loaded in this job, under a row whose geometry is a submission's;
    the rows of one (program, geometry) count that pair's dispatches,
    and together they count every dispatch."""
    rep, _ = first_job
    rows = rep["compiles"]["programs"]
    by_program = rep["device_time"]["by_program"]
    ran = {name: row["count"] for name, row in by_program.items()
           if not name.endswith(".put")}
    assert ran
    for name, count in ran.items():
        programs = {"jit_" + p
                    for p in compilewatch.CHAINS.get(name, (name,))}
        for program in programs:
            mine = [r for r in rows if r["program"] == program
                    and r["dispatches"] is not None]
            assert mine, f"{name}: no row of {program}"
            assert all(r["geometry"] and r["dispatches"] >= 1
                       for r in mine), mine
            # one compile per geometry: the rows' counts are disjoint
            assert len({r["geometry"] for r in mine}) == len(mine)
            assert sum(r["dispatches"] for r in mine) == count
    assert rep["compiles"]["unused"] == 0
    assert rep["compiles"]["eager_programs"] == sum(
        r["dispatches"] is None for r in rows)


def test_first_job_feeds_the_six_set_up_metrics(first_job):
    """The metric files this PR adds load through the benchmark's own
    spec and read numbers from a warm-up report."""
    rep, _ = first_job
    sys.path.insert(0, str(REPO / "benchmark"))
    try:
        from harness import readers, spec
    finally:
        sys.path.remove(str(REPO / "benchmark"))
    cell = spec.load_cell("bact2m-paf30x")
    files = {e["name"]: (e, m) for e, m in cell.per_layer}
    assert set(NEW_METRICS) <= set(files)
    ctx = {"warmup": rep, "traced": None, "window": [], "modules": None,
           "run": {}}
    values = {}
    for name in NEW_METRICS:
        entry, mfile = files[name]
        assert entry["layer"] == "compile" and entry["moves"] == "setup_s"
        assert "workloads" not in entry
        values[name] = readers.read_metric(mfile, ctx)
        assert isinstance(values[name], float), name
    assert values["warmup_job_s"] == rep["wall_s"]
    assert values["compile_wall_s"] == rep["compiles"]["wall_s"]
    assert 0 < values["warmup_compile_idle_s"] <= values["warmup_job_s"]
    assert values["compile_eager_programs"] == \
        rep["compiles"]["eager_programs"]
    # a report of before this PR: the readers find nothing, raise nothing
    old = json.loads((REPO / "tests" / "data" /
                      "run_report_v11.json").read_bytes())
    for name in ("compile_wall_s", "cache_retrieve_s", "compile_unused_s",
                 "compile_eager_programs"):
        assert readers.read_metric(files[name][1],
                                   dict(ctx, warmup=old)) is None


def test_compiles_command_prints_the_table(first_job, capsys):
    rep, path = first_job
    assert report._main(["compiles", str(path)]) == 0
    out = capsys.readouterr().out.splitlines()
    rows = rep["compiles"]["programs"]
    assert len([ln for ln in out if " / jit_" in ln]) == len(rows)
    assert any(f"{rep['compiles']['count']} programs" in ln for ln in out)
    assert any("as wall of the job's" in ln for ln in out)
    old = REPO / "tests" / "data" / "run_report_v11.json"
    assert report._main(["compiles", str(old)]) == 2
    assert report._main(["compiles"]) == 2


# The sanitized serve warm-path acceptance test
# (test_serve_sanitized_warm_path_assert_fires_only_when_unwarmed)
# lives at the END of tests/test_serve.py: it traces the same engine
# geometries test_serve's own warm-path/retrace asserts rely on being
# cold, so in a single-process full run it must execute after them —
# in-file definition order guarantees that; alphabetical file order
# from here would not.
