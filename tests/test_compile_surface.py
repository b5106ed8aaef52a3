"""Compile-surface runtime attribution (round 18).

The static half lives in ``tools/analysis`` (jit-shape-hazard /
dtype-drift / jit-in-loop / warmup-coverage / host-transfer-in-jit,
self-tested via ``--selftest``); this file proves the RUNTIME half:
the process-wide ``jax.monitoring`` listener attributes every XLA
compile to (function, shape signature, phase, scope), the per-job
``compile_s`` semantics of the absorbed serve listener are preserved,
the run report's required schema-v7 ``compiles`` section validates,
and the sanitize gate judges only the offending scope.  (The full
sanitized-serve warm-path acceptance test rides at the end of
``tests/test_serve.py`` — see the note at the bottom of this file.)"""

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


def _fake_compile(max_len, band, duration=0.5):
    """Drive the listener directly: attribution walks the stack and —
    with no racon_tpu frame above — lands on THIS frame, whose integer
    locals (max_len/band) form the shape signature."""
    compilewatch._on_duration(
        "/jax/core/compile/backend_compile_duration", duration)


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
    assert metrics.counter("compile.nw.align_chain") >= 1
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
    assert list(comp["by_function"]) == \
        ["test_compile_surface._fake_compile"]
    assert comp["events"][0]["signature"] == "max_len=256,band=64"

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
        "by_function": {}, "events": []}


def test_scoped_count_exact_past_event_ring_eviction(monkeypatch):
    """The event ring is bounded; a job whose early records were
    evicted still reports its exact compile count (the scoped counter,
    not the ring)."""
    monkeypatch.setattr(compilewatch, "MAX_EVENTS", 4)
    metrics.set_scope("job.ring.")
    try:
        for _ in range(10):
            _fake_compile(128, 8)
    finally:
        metrics.set_scope(None)
    s = compilewatch.summary("job.ring.")
    assert s["count"] == 10
    assert len(s["events"]) <= 4
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


# The sanitized serve warm-path acceptance test
# (test_serve_sanitized_warm_path_assert_fires_only_when_unwarmed)
# lives at the END of tests/test_serve.py: it traces the same engine
# geometries test_serve's own warm-path/retrace asserts rely on being
# cold, so in a single-process full run it must execute after them —
# in-file definition order guarantees that; alphabetical file order
# from here would not.
