"""The device-occupancy ledger (racon_tpu.obs.device_time), its report
section (schema v12), the leaf spans under dispatch and fetch, the run
boundary's timer hygiene and the ``gaps`` command.

The arithmetic is driven with a fake clock (hand-made submit / complete
rows, no device); one module-scoped series of tiny in-process CLI jobs
(both device engines on their XLA twins, recording off / on / on again /
with ``--trace``) feeds every case that needs a real run."""

import contextlib
import io
import json
import pathlib
import sys
import threading
import time

import pytest

from racon_tpu.obs import device_time, gaps, metrics, report, trace

REPO = pathlib.Path(__file__).resolve().parent.parent
MS = 1_000_000


@pytest.fixture
def clean_trace():
    trace.deactivate()
    device_time.reset()
    # a traced job of another file in this worker leaves its watchers
    # with the process: the tests here count their own
    device_time.stop_watchers()
    yield
    trace.deactivate()
    device_time.reset()
    device_time.stop_watchers()


# ------------------------------------------------- arithmetic, fake clock

def test_in_order_queue_busy_idle_head_tail():
    """One device, three programs: the second was submitted while the
    first still ran (it occupies from the first's completion), the third
    after a gap; head before the first, tail after the last."""
    rows = [("0", "exec", "a", "main", 100 * MS, 300 * MS),
            ("0", "exec", "b", "main", 150 * MS, 450 * MS),
            ("0", "exec", "a", "main", 600 * MS, 700 * MS)]
    out = device_time.account(rows, {}, 0, 1000 * MS, "main")
    assert out["window_s"] == 1.0
    assert out["busy_s"] == pytest.approx(0.45)     # 100-450, 600-700
    assert out["idle_s"] == pytest.approx(0.55)
    assert out["head_idle_s"] == pytest.approx(0.1)
    assert out["tail_idle_s"] == pytest.approx(0.3)
    assert out["programs"] == 3
    assert out["by_program"] == {
        "a": {"count": 2, "device_s": pytest.approx(0.3)},
        "b": {"count": 1, "device_s": pytest.approx(0.15)}}
    # no span anywhere: every idle second is unattributed
    assert out["idle_by"] == {"unattributed": pytest.approx(0.55)}
    assert out["busy_s"] + out["idle_s"] == pytest.approx(out["window_s"])
    # the gaps, longest first
    assert [(a, b) for a, b, _ in out["gaps"]] == [
        (700 * MS, 1000 * MS), (450 * MS, 600 * MS), (0, 100 * MS)]


def test_overlapping_h2d_and_exec_union():
    """A transfer that runs beside a program adds nothing to busy; one
    that sticks out of it does. Each kind chains on its own queue."""
    rows = [("0", "exec", "k", "main", 0, 400 * MS),
            ("0", "h2d", "put", "main", 100 * MS, 200 * MS),
            ("0", "h2d", "put", "main", 350 * MS, 500 * MS),
            ("0", "exec", "k", "main", 380 * MS, 900 * MS)]
    out = device_time.account(rows, {}, 0, 1000 * MS, "main")
    assert out["busy_s"] == pytest.approx(0.9)
    assert out["idle_s"] == pytest.approx(0.1)
    assert out["head_idle_s"] == 0 and out["tail_idle_s"] == 0.1
    # the second exec waited for the first: it occupies 400-900, not
    # 380-900; the transfers occupy their own intervals
    assert out["by_program"]["k"]["device_s"] == pytest.approx(0.9)
    assert out["by_program"]["put"]["device_s"] == pytest.approx(0.25)


def test_a_timer_only_leaf_leaves_its_idle_with_its_parent():
    """``poa.lanes`` times the lane block inside ``poa.pack``; the idle
    under it stays ``idle.poa.pack``, the timer the consensus feed's
    idle metric has always summed. ``poa.put`` keeps its own."""
    from racon_tpu import contracts
    assert "poa.lanes" in contracts.TIMER_ONLY_SPANS <= contracts.SPANS
    rows = [("0", "exec", "k", "feeder", 0, 100 * MS),
            ("0", "h2d", "poa.put", "feeder", 500 * MS, 600 * MS)]
    spans = {"feeder": [("poa.pack", 150 * MS, 550 * MS),
                        ("poa.lanes", 200 * MS, 400 * MS),
                        ("poa.put", 450 * MS, 550 * MS)]}
    out = device_time.account(rows, spans, 0, 600 * MS, "feeder")
    assert out["idle_s"] == pytest.approx(0.4)
    assert out["idle_by"] == {"poa.pack": 0.3, "poa.put": 0.05,
                              "unattributed": 0.05}


@pytest.mark.parametrize("leaf", ["overlap.chain.plan", "overlap.emit",
                                  "overlap.rows"])
def test_the_hand_offs_leaves_leave_their_idle_with_align(leaf):
    """The streamed overlap -> align hand-off runs inside ``align`` on
    the feeding thread; its three leaves are timers only, so the idle
    under them stays ``idle.align`` (``idle_align_feed_s``' list is
    whole), while a chain fetch beside them keeps its own."""
    from racon_tpu import contracts
    assert leaf in contracts.TIMER_ONLY_SPANS <= contracts.SPANS
    rows = [("0", "exec", "_chain_kernel", "main", 0, 100 * MS),
            ("0", "h2d", "align.put", "main", 900 * MS, 1000 * MS)]
    spans = {"main": [("align", 50 * MS, 1000 * MS),
                      ("overlap.chain.fetch", 100 * MS, 200 * MS),
                      (leaf, 200 * MS, 700 * MS),
                      ("align.pack", 800 * MS, 900 * MS)]}
    out = device_time.account(rows, spans, 0, 1000 * MS, "main")
    assert out["idle_s"] == pytest.approx(0.8)
    assert out["idle_by"] == {"align": 0.6, "overlap.chain.fetch": 0.1,
                              "align.pack": 0.1, "unattributed": 0.0}


def test_two_devices_rows_and_means():
    """``--chips N``: one row set per device ordinal; the top level is
    the mean over devices, so busy + idle is still the window."""
    rows = [("0", "exec", "k", "w0", 0, 800 * MS),
            ("1", "exec", "k", "w1", 500 * MS, 700 * MS)]
    spans = {"w0": [("poa.pack", 0, 1000 * MS)],
             "w1": [("align.pack", 0, 400 * MS)]}
    out = device_time.account(rows, spans, 0, 1000 * MS, "w0")
    assert set(out["devices"]) == {"0", "1"}
    assert out["devices"]["0"]["busy_s"] == 0.8
    assert out["devices"]["1"]["busy_s"] == 0.2
    assert out["devices"]["1"]["head_idle_s"] == 0.5
    assert out["busy_s"] == pytest.approx(0.5)
    assert out["idle_s"] == pytest.approx(0.5)
    # device 1's head is its own feeding thread's: 400 ms in align.pack,
    # 100 ms in no span; both tails are the report thread's (w0)
    assert out["devices"]["1"]["idle_by"] == {
        "align.pack": 0.4, "poa.pack": 0.3, "unattributed": 0.1}
    assert out["devices"]["0"]["idle_by"] == {
        "poa.pack": 0.2, "unattributed": 0.0}
    assert sum(out["idle_by"].values()) == pytest.approx(out["idle_s"])
    assert out["by_program"]["k"] == {"count": 2, "device_s": 1.0}


def test_idle_goes_to_innermost_span_of_the_submitting_thread():
    """The gap before a submission is cut by the spans of the thread
    that made it — innermost first — while another thread's spans over
    the same interval get nothing."""
    rows = [("0", "exec", "k", "feeder", 0, 100 * MS),
            ("0", "exec", "k", "feeder", 500 * MS, 600 * MS)]
    spans = {
        "feeder": [("align", 0, 600 * MS),
                   ("align.dispatch", 150 * MS, 500 * MS),
                   ("align.pack", 200 * MS, 450 * MS),
                   ("compile.trace", 250 * MS, 300 * MS)],
        "builder": [("build.windows", 0, 1000 * MS)],
    }
    out = device_time.account(rows, spans, 0, 600 * MS, "feeder")
    assert out["idle_s"] == pytest.approx(0.4)
    assert out["idle_by"] == {
        "align": 0.05, "align.dispatch": 0.1, "align.pack": 0.2,
        "compile.trace": 0.05, "unattributed": 0.0}
    assert "build.windows" not in out["idle_by"]
    (a, b, cut), = out["gaps"]
    assert (a, b) == (100 * MS, 500 * MS)
    assert cut == {"align": 0.05, "align.dispatch": 0.1,
                   "align.pack": 0.2, "compile.trace": 0.05}


def test_a_pack_run_ahead_is_charged_only_for_what_outlasts_the_chunk():
    """The align stream packs chunk k+1 while chunk k is on the device
    (its own ``align.dispatch`` span, before the fetch of k), then puts
    and launches under a second one. ``idle.align.pack`` is the part of
    a pack that outlasts the chunk in flight: 100 ms of the first pack
    here, nothing of the second, which ends while its predecessor still
    runs."""
    rows = [("0", "exec", "k", "feeder", 0, 200 * MS),
            ("0", "exec", "k", "feeder", 340 * MS, 700 * MS),
            ("0", "exec", "k", "feeder", 760 * MS, 1000 * MS)]
    spans = {"feeder": [
        ("align", 0, 1000 * MS),
        # chunk 1: packed 10-300 while chunk 0 ran until 200
        ("align.dispatch", 10 * MS, 300 * MS),
        ("align.pack", 10 * MS, 300 * MS),
        ("align.fetch", 300 * MS, 320 * MS),
        ("align.wait", 300 * MS, 300 * MS),
        ("align.get", 300 * MS, 305 * MS),
        ("align.decode", 305 * MS, 320 * MS),
        ("align.dispatch", 320 * MS, 340 * MS),
        ("align.put", 320 * MS, 330 * MS),
        ("align.launch", 330 * MS, 340 * MS),
        # chunk 2: packed 350-600, hidden under chunk 1 (340-700)
        ("align.dispatch", 350 * MS, 600 * MS),
        ("align.pack", 350 * MS, 600 * MS),
        ("align.fetch", 600 * MS, 740 * MS),
        ("align.wait", 600 * MS, 700 * MS),
        ("align.get", 700 * MS, 710 * MS),
        ("align.decode", 710 * MS, 740 * MS),
        ("align.dispatch", 740 * MS, 760 * MS),
        ("align.put", 740 * MS, 750 * MS),
        ("align.launch", 750 * MS, 760 * MS)]}
    out = device_time.account(rows, spans, 0, 1000 * MS, "feeder")
    assert out["busy_s"] == pytest.approx(0.8)
    assert out["idle_s"] == pytest.approx(0.2)      # 200-340, 700-760
    # (the parents are covered by their leaves here: no self time)
    assert out["idle_by"] == {
        "align": 0.0, "align.pack": 0.1, "align.wait": 0.0,
        "align.get": 0.015, "align.decode": 0.045,
        "align.put": 0.02, "align.launch": 0.02, "unattributed": 0.0}
    assert [(a, b) for a, b, _ in out["gaps"]] == [
        (200 * MS, 340 * MS), (700 * MS, 760 * MS)]
    assert out["gaps"][1][2] == {"align.get": 0.01, "align.decode": 0.03,
                                 "align.put": 0.01, "align.launch": 0.01}


def test_wait_for_an_unfinished_prepare_is_the_consumers_queue_get():
    """The hand-off from the aligner to the consensus stream. The
    builder thread stands in ``build.prepare_wait`` for a prepare that
    is still running on its own thread; neither submits anything. The
    idle before the first consensus group is cut by the spans of the
    consumer, which ends it: ``idle.queue.get`` while it waits for the
    first range, exactly as when the builder itself built the pool,
    then the first group's pack. ``idle_build_s`` keeps its meaning."""
    rows = [("0", "exec", "_pallas_align_chain", "main", 0, 100 * MS),
            ("0", "exec", "_refine_loop_packed", "main", 500 * MS,
             600 * MS)]
    spans = {
        "main": [("align", 0, 100 * MS),
                 ("consensus", 110 * MS, 600 * MS),
                 ("queue.get", 120 * MS, 400 * MS),
                 ("consensus.feed", 400 * MS, 500 * MS),
                 ("poa.pack", 410 * MS, 500 * MS)],
        "racon-layers": [("build.windows", 110 * MS, 600 * MS),
                         ("build.prepare_wait", 115 * MS, 300 * MS),
                         ("build.store", 350 * MS, 360 * MS),
                         ("queue.put", 399 * MS, 400 * MS)],
        "racon-prepare": [("build.prepare", 10 * MS, 300 * MS)],
    }
    out = device_time.account(rows, spans, 0, 600 * MS, "main")
    assert out["idle_s"] == pytest.approx(0.4)
    assert out["idle_by"] == {
        "align": 0.0, "consensus": 0.01, "queue.get": 0.28,
        "consensus.feed": 0.01, "poa.pack": 0.09, "unattributed": 0.01}
    assert not any(k.startswith("build.") for k in out["idle_by"])


def test_warm_up_program_is_busy_but_takes_no_blame():
    """A warm-up thread's dummy program occupies the device (the same
    in-order queue as the real programs) but feeds nothing: the idle
    before it is charged to the thread of the next real submission, and
    it does not end the head."""
    rows = [("0", "warm", "k", "warmer", 100 * MS, 200 * MS),
            ("0", "exec", "k", "feeder", 150 * MS, 500 * MS)]
    spans = {"feeder": [("parse.reads", 0, 150 * MS)],
             "warmer": [("exec.shard", 0, 1000 * MS)]}
    out = device_time.account(rows, spans, 0, 500 * MS, "feeder")
    assert out["busy_s"] == 0.4 and out["idle_s"] == 0.1
    assert out["head_idle_s"] == 0.1
    assert out["idle_by"] == {"parse.reads": 0.1, "unattributed": 0.0}
    # one queue: the real program waited for the warm one
    assert out["by_program"]["k"] == {"count": 2, "device_s": 0.4}


def test_nothing_submitted_is_all_head_and_open_spans_count():
    """A job that never touched the device: the whole window is head
    idle, charged to the report thread — a span still open at report
    time (``t1`` None) is cut like a finished one."""
    spans = {"main": [("exec.shard", 200 * MS, None)]}
    out = device_time.account([], spans, 0, 1000 * MS, "main")
    assert out["busy_s"] == 0 and out["idle_s"] == 1.0
    assert out["head_idle_s"] == 1.0 and out["tail_idle_s"] == 0
    assert out["idle_by"] == {"exec.shard": 0.8, "unattributed": 0.2}
    assert out["programs"] == 0 and out["devices"] == {}


# --------------------------------------------------- the live mechanism

class _FakeArray:
    """Stands in for a small device output: ready when told."""

    class _Dev:
        id = 0

    def __init__(self):
        self.ready = threading.Event()

    def devices(self):
        return {self._Dev()}

    def block_until_ready(self):
        assert self.ready.wait(10)


def test_live_ledger_charges_the_feeding_threads_span(clean_trace):
    """Real threads, real watcher: the main thread sits in a span and
    then submits; a second thread is busy in another span the whole
    time. The idle before the submission is the main thread's span."""
    from racon_tpu import obs

    trace.new_run()
    trace.activate()
    t0 = time.perf_counter()
    stop = threading.Event()

    def elsewhere():
        with obs.span("build.windows"):
            stop.wait(10)

    other = threading.Thread(target=elsewhere, name="t-elsewhere")
    other.start()
    with obs.span("align.pack"):
        time.sleep(0.15)
    watch = _FakeArray()
    device_time.submit("exec", "_k", watch)
    assert device_time.watcher_threads() == ["racon-devwatch-0-exec"]
    time.sleep(0.05)
    watch.ready.set()
    stop.set()
    other.join()
    sec = device_time.summary(window_s=time.perf_counter() - t0)
    assert sec["programs"] == 1
    assert sec["timeline"][0][:4] == ["0", "exec", "_k", "MainThread"]
    assert sec["by_program"]["_k"]["device_s"] >= 0.05
    assert sec["idle_by"]["align.pack"] >= 0.14
    assert "build.windows" not in sec["idle_by"]
    assert sum(sec["idle_by"].values()) == pytest.approx(sec["idle_s"],
                                                         abs=1e-5)
    assert sec["busy_s"] + sec["idle_s"] == pytest.approx(sec["window_s"],
                                                          abs=1e-5)
    # the registry holds the same seconds, and a second build of the
    # report does not add them again
    again = device_time.summary(window_s=time.perf_counter() - t0)
    assert metrics.timer_s("idle.align.pack") == pytest.approx(
        again["idle_by"]["align.pack"])
    assert again["idle_by"]["align.pack"] == pytest.approx(
        sec["idle_by"]["align.pack"], abs=1e-3)
    assert sec["clock"] == trace.clock() and sec["clock"]["unix_ns"] > 0


def test_consensus_warmup_submits_only_the_loop(clean_trace):
    """A consensus warm-up's rows in the ledger: the refinement loop of
    each geometry it derived, as ``warm``, and no other program — what a
    warm-up dispatches occupies the device in every job, so a dummy for
    a program no job runs is device time taken from all of them."""
    from racon_tpu.ops.poa import TpuPoaConsensus

    trace.new_run()
    trace.activate()
    t0 = time.perf_counter()
    eng = TpuPoaConsensus(3, -5, -4, mesh=None)
    thread = eng.warmup_async(64, est_pairs=64, est_windows=8)
    assert thread is not None
    thread.join(timeout=300)
    assert not thread.is_alive()
    sec = device_time.summary(window_s=time.perf_counter() - t0)
    warm = [row for row in sec["timeline"] if row[1] == "warm"]
    assert warm and {row[2] for row in warm} == {"_refine_loop_packed"}
    assert all(row[3] == thread.name for row in warm)
    # and nothing else of the thread's reached the device's queues
    assert [row for row in sec["timeline"] if row[3] == thread.name] == warm


def test_off_means_off(clean_trace):
    """No report and no trace asked for: the shared no-op span, no
    ledger entry, no watcher thread started by a submission."""
    from racon_tpu import obs

    before = set(device_time.watcher_threads())
    assert obs.span("align.pack") is trace.NULL_SPAN
    t0 = time.perf_counter()
    for _ in range(100_000):
        device_time.submit("exec", "_k", None)  # never touches `watch`
    assert time.perf_counter() - t0 < 1.0
    assert set(device_time.watcher_threads()) == before
    sec = report.build_report("cli", wall_s=1.0)["device_time"]
    assert sec["programs"] == 0 and sec["busy_s"] == 0
    assert sec["timeline"] == [] and sec["idle_by"] == {"unattributed": 0}


def test_compile_stages_are_backdated_spans(clean_trace):
    """The compile listener back-dates one event per stage onto the
    compiling thread's ring; a stage nested in another gives the outer
    timer only its self time; cache lookups and hits are counted."""
    from racon_tpu.obs import compilewatch

    metrics.clear("compile.")
    compilewatch.reset()
    trace.new_run()
    trace.activate()
    ev = "/jax/core/compile/"
    # JAX announces a stage as it begins (a scalar event) and reports
    # its length as it ends: the outer trace encloses the inner one
    compilewatch._on_scalar(ev + "jaxpr_trace_duration", 0.0)      # outer
    compilewatch._on_scalar(ev + "jaxpr_trace_duration", 0.0)
    compilewatch._on_duration(ev + "jaxpr_trace_duration", 0.010)
    compilewatch._on_duration(ev + "jaxpr_trace_duration", 0.050)  # outer
    compilewatch._on_scalar(ev + "jaxpr_to_mlir_module_duration", 0.0)
    compilewatch._on_duration(ev + "jaxpr_to_mlir_module_duration", 0.002)
    compilewatch._on_event(
        "/jax/compilation_cache/compile_requests_use_cache")
    compilewatch._on_event("/jax/compilation_cache/cache_hits")
    compilewatch._on_event("/jax/some/other/event")
    assert metrics.timer_s("compile.jax_s") == pytest.approx(0.062)
    # 10 ms inner + 40 ms self of the outer, not 60
    assert metrics.timer_s("compile.trace") == pytest.approx(0.050,
                                                             abs=2e-3)
    assert metrics.timer_s("compile.lower") == pytest.approx(0.002)
    assert metrics.counter("compile.cache_requests") == 1
    assert metrics.counter("compile.cache_hits") == 1
    names = [n for n, _, _ in
             trace.snapshot_events(trace.current_buf())]
    assert names == ["compile.trace", "compile.trace", "compile.lower"]
    metrics.clear("compile.")


def test_a_row_counts_the_exec_submissions_of_its_program_and_geometry(
        clean_trace):
    """The join of the ``compiles`` rows to the ledger. A warm-up thread
    compiles a program and submits its dummy run as ``warm``: the row
    takes the submission's geometry and reads ``dispatches`` 0 — in
    ``unused_s`` — until an ``exec`` submission of the same program and
    geometry (the executable the jit cache hands the stream) makes it 1.
    Another geometry's dispatch counts nothing here; a program nobody
    submits is an eager helper, ``null``; a probe's compile of the same
    kernel in an earlier span is not the submitted call's."""
    from racon_tpu import obs
    from racon_tpu.obs import compilewatch

    backend = "/jax/core/compile/backend_compile_duration"
    compilewatch.reset()
    trace.new_run()
    trace.activate()
    geom = device_time.geometry(steps=1280, max_len=768, B=128, swar=True)
    assert geom == "max_len=768,steps=1280,B=128,swar=1"
    other = device_time.geometry(steps=1152, max_len=768, B=128, swar=True)

    def done():
        w = _FakeArray()
        w.ready.set()
        return w

    def warm_up():
        compilewatch._on_duration(backend, 0.050, fun_name="jit(_loop)")
        compilewatch._on_duration(backend, 0.004, fun_name="jit(zeros)")
        device_time.submit("warm", "_loop", done(), geom)

    t = threading.Thread(target=warm_up, name="racon-tpu-warmup")
    t.start()
    t.join()

    def rows():
        comp = compilewatch.summary(ran=device_time.dispatch_counts())
        return comp, {r["program"]: r for r in comp["programs"]}

    comp, by = rows()
    assert by["jit__loop"]["dispatches"] == 0
    assert by["jit__loop"]["geometry"] == geom
    assert by["jit__loop"]["thread"] == "racon-tpu-warmup"
    assert by["jit_zeros"]["dispatches"] is None
    assert comp["unused"] == 1 and comp["eager_programs"] == 1
    assert comp["unused_s"] == pytest.approx(0.050, abs=1e-3)
    # the stream: a probe compiles the kernel in one span, the real call
    # hits the jit cache in the next and is submitted there
    with obs.span("align.pack"):
        compilewatch._on_duration(backend, 0.002, fun_name="jit(_loop)")
    with obs.span("poa.dispatch"):
        device_time.submit("exec", "_loop", done(), other)
    comp, by = rows()
    assert [r["dispatches"] for r in comp["programs"]
            if r["program"] == "jit__loop"] == [0, None]
    with obs.span("poa.dispatch"):
        device_time.submit("exec", "_loop", done(), geom)
    comp, by = rows()
    assert [r["dispatches"] for r in comp["programs"]
            if r["program"] == "jit__loop"] == [1, None]
    assert comp["unused"] == 0 and comp["unused_s"] == 0
    assert comp["eager_programs"] == 2
    assert device_time.dispatch_counts() == {("_loop", other): 1,
                                             ("_loop", geom): 1}
    assert report.validate_report(report.build_report("cli")) == []
    compilewatch.reset()


# ------------------------------------------------------ schema v12 / v11

def test_v12_validates_and_requires_device_time():
    rep = report.build_report("cli", wall_s=0.5)
    assert rep["schema_version"] == 16      # the section is v12's
    assert report.validate_report(rep) == []
    broken = {k: v for k, v in rep.items() if k != "device_time"}
    assert any("device_time" in e for e in report.validate_report(broken))
    bad = dict(rep, device_time=dict(rep["device_time"], busy_s="long"))
    assert any("busy_s" in e for e in report.validate_report(bad))
    bad = dict(rep, device_time={k: v for k, v in
                                 rep["device_time"].items()
                                 if k != "timeline"})
    assert any("timeline" in e for e in report.validate_report(bad))
    bad = dict(rep, device_time=dict(rep["device_time"],
                                     timeline=[["0", "exec"]]))
    assert any("timeline" in e for e in report.validate_report(bad))


def test_stored_v11_report_still_validates_as_v11():
    """A report the chip wrote under schema v11 (PR 24's traced job):
    held to the v11 key sets — valid as it is, invalid with a v12
    section it could not have had."""
    v11 = json.loads((REPO / "tests" / "data" /
                      "run_report_v11.json").read_bytes())
    assert v11["schema_version"] == 11 and "device_time" not in v11
    assert report.validate_report(v11) == []
    extra = dict(v11, device_time=device_time.empty_section())
    assert any("device_time" in e for e in report.validate_report(extra))
    assert any("schema_version" in e for e in
               report.validate_report(dict(v11, schema_version=10)))


def test_job_scope_report_sees_only_its_submissions(clean_trace):
    """A resident-service job's report is built from its metric scope:
    another job's submissions are not in its section."""
    trace.new_run()
    trace.activate()
    t0 = time.perf_counter()
    time.sleep(0.01)    # a job's first submission is not its first act
    for scope in ("job.a.", "job.b.", "job.a."):
        metrics.set_scope(scope)
        try:
            w = _FakeArray()
            w.ready.set()
            device_time.submit("exec", "_k", w)
        finally:
            metrics.set_scope(None)
    rep = report.build_report("job", wall_s=time.perf_counter() - t0,
                              scope="job.a.")
    assert report.validate_report(rep) == []
    assert rep["device_time"]["programs"] == 2
    assert "idle.unattributed" in rep["metrics"]["timers"]
    other = report.build_report("job", wall_s=time.perf_counter() - t0,
                                scope="job.none.")
    assert other["device_time"]["programs"] == 0
    metrics.clear("job.")


# ------------------------------------------------- a real tiny CLI series

@contextlib.contextmanager
def _stdout_to(path):
    """The CLI writes its FASTA to ``sys.stdout.buffer``."""
    saved = sys.stdout
    with open(path, "wb") as fh:
        sys.stdout = io.TextIOWrapper(fh, write_through=True)
        try:
            yield
        finally:
            sys.stdout.flush()
            sys.stdout.detach()
            sys.stdout = saved


@pytest.fixture(scope="module")
def cli_series(tmp_path_factory):
    """Four jobs on the same inputs in THIS process, both device engines
    on: recording off, ``--run-report`` twice, ``--run-report`` +
    ``--trace``. ``{tag: {"fasta": bytes, "report": dict | None}}``."""
    sys.path.insert(0, str(REPO / "tests"))
    from test_columnar_init import write_synthetic_assembly
    from racon_tpu import cli

    td = tmp_path_factory.mktemp("device_time_cli")
    rp, pp, lp = write_synthetic_assembly(td, seed=41, n_contigs=1,
                                          contig=2000)
    base = ["-t", "2", "-c", "1", "--tpualigner-batches", "1"]
    out = {}
    trace.deactivate()
    for tag, extra in (("off", []), ("on1", ["report"]),
                       ("on2", ["report"]), ("traced", ["report", "trace"])):
        fasta = td / f"{tag}.fasta"
        rep = td / f"{tag}.report.json"
        argv = list(base)
        if "report" in extra:
            argv += ["--run-report", str(rep)]
        if "trace" in extra:
            argv += ["--trace", str(td / f"{tag}.trace.json")]
        with _stdout_to(str(fasta)):
            rc = cli.main([*argv, str(rp), str(pp), str(lp)])
        assert rc == 0
        out[tag] = {"fasta": fasta.read_bytes(),
                    "report": (json.loads(rep.read_bytes())
                               if rep.exists() else None),
                    "trace": td / f"{tag}.trace.json"}
    trace.deactivate()
    return out


def test_fasta_bytes_equal_with_recording_on_off_and_traced(cli_series):
    assert cli_series["off"]["fasta"].startswith(b">")
    assert cli_series["on1"]["fasta"] == cli_series["off"]["fasta"]
    assert cli_series["on2"]["fasta"] == cli_series["off"]["fasta"]
    assert cli_series["traced"]["fasta"] == cli_series["off"]["fasta"]
    assert cli_series["off"]["report"] is None


@pytest.mark.parametrize("tag", ["on1", "on2", "traced"])
def test_cli_report_idle_sums_and_window(cli_series, tag):
    rep = cli_series[tag]["report"]
    assert report.validate_report(rep) == []
    dt = rep["device_time"]
    assert dt["programs"] > 0 and dt["busy_s"] > 0
    assert dt["window_s"] == pytest.approx(rep["wall_s"], abs=2e-3)
    assert dt["busy_s"] + dt["idle_s"] == pytest.approx(dt["window_s"],
                                                        abs=1e-5)
    assert sum(dt["idle_by"].values()) == pytest.approx(dt["idle_s"],
                                                        abs=1e-4)
    timers = rep["metrics"]["timers"]
    idle = {k[len("idle."):]: v for k, v in timers.items()
            if k.startswith("idle.")}
    assert idle == dt["idle_by"]
    assert dt["head_idle_s"] + dt["tail_idle_s"] <= dt["idle_s"] + 1e-6
    assert dt["dropped"] == 0
    assert len(dt["timeline"]) == dt["programs"]
    assert {r[1] for r in dt["timeline"]} == {"exec", "h2d"}
    for row in dt["timeline"]:
        assert row[4] <= row[5]
    assert dt["clock"]["perf_ns"] > 0 and dt["clock"]["unix_ns"] > 0
    # every idle.<span> name is idle. + a registered span
    from racon_tpu import contracts
    assert set(idle) - {"unattributed"} <= contracts.SPANS


@pytest.mark.parametrize("parent,leaves", [
    ("align.dispatch", ("align.pack", "align.put", "align.launch")),
    ("align.fetch", ("align.wait", "align.get", "align.decode")),
    ("poa.pack", ("poa.put", "poa.lanes")),
    ("poa.fetch", ("poa.wait", "poa.get", "poa.decode")),
])
def test_leaf_spans_sum_to_no_more_than_their_parent(cli_series, parent,
                                                     leaves):
    for tag in ("on1", "on2", "traced"):
        timers = cli_series[tag]["report"]["metrics"]["timers"]
        assert all(leaf in timers for leaf in leaves), (tag, leaves)
        total = sum(timers[leaf] for leaf in leaves)
        assert total <= timers[parent] * 1.01 + 1e-4, (tag, parent)
        assert total > 0


def test_every_chunk_opens_two_dispatch_spans_and_pack_is_in_the_first(
        cli_series):
    """A chunk's launch runs in two halves: ``align.pack`` under one
    ``align.dispatch`` span, ``align.put`` + ``align.launch`` under a
    later one on the same thread."""
    doc = json.loads(cli_series["traced"]["trace"].read_bytes())
    chunks = cli_series["traced"]["report"]["metrics"]["counters"][
        "align.chunks"]
    by = {}
    for e in doc["traceEvents"]:
        if e.get("ph") == "X" and e["name"].startswith("align."):
            by.setdefault(e["name"], []).append(e)
    assert len(by["align.pack"]) == len(by["align.put"]) == chunks > 0
    assert len(by["align.dispatch"]) == 2 * chunks

    def parent(leaf):
        # same thread, and the child's interval inside the parent's
        # (1 us of slack: the export rounds begin and duration apart)
        (found,) = [d for d in by["align.dispatch"]
                    if d["tid"] == leaf["tid"]
                    and d["ts"] <= leaf["ts"] + 1
                    and leaf["ts"] + leaf["dur"]
                    <= d["ts"] + d["dur"] + 1]
        return found

    for pack, put, launch in zip(by["align.pack"], by["align.put"],
                                 by["align.launch"]):
        first, second = parent(pack), parent(put)
        assert first is not second
        assert parent(launch) is second
        assert first["ts"] + first["dur"] <= second["ts"] + 1


@pytest.mark.parametrize("tag", ["on1", "on2", "traced"])
def test_cli_report_carries_the_prepare_spans_and_counters(cli_series, tag):
    """``-t 2`` with overlaps from a file: prepare ran on its own
    thread beside the aligner (never inside ``build.store``, never
    charged with device idle), the barrier recorded its wait, and each
    job's report counts its own pool once."""
    m = cli_series[tag]["report"]["metrics"]
    assert m["timers"]["build.prepare"] > 0
    assert "build.prepare_wait" in m["timers"]
    assert "idle.build.prepare" not in m["timers"]
    pool = m["counters"]["build.pool_bytes"]
    assert pool > 0
    assert pool == cli_series["on1"]["report"]["metrics"]["counters"][
        "build.pool_bytes"]
    assert m["counters"]["build.pool_bytes_ahead"] in (0, pool)


@pytest.mark.parametrize("tag", ["on2", "traced"])
def test_a_later_job_in_the_process_compiles_nothing_and_has_no_rows(
        cli_series, tag):
    """What the benchmark's window jobs are: every program is in the jit
    cache, so the section is empty — and says so in every total."""
    comp = cli_series[tag]["report"]["compiles"]
    assert comp["count"] == 0 and comp["programs"] == []
    assert comp["dropped"] == 0 and comp["post_warm"] == 0
    assert comp["wall_s"] == 0 and comp["unrowed_s"] == 0
    assert comp["unused"] == 0 and comp["eager_programs"] == 0
    assert cli_series[tag]["report"]["metrics"]["timers"][
        "compile.retrieve"] == 0


def test_second_job_in_one_process_reports_its_own_aggregates(cli_series):
    """The aggregate ``align`` / ``consensus`` span timers used to leak
    across runs in one process (no dotted run prefix matches them)."""
    for tag in ("on2", "traced"):
        rep = cli_series[tag]["report"]
        timers = rep["metrics"]["timers"]
        assert 0 < timers["align"] <= rep["wall_s"]
        assert 0 < timers["consensus"] <= rep["wall_s"]
        # and the ledger holds this job's submissions only
        first = cli_series["on1"]["report"]["device_time"]["programs"]
        assert rep["device_time"]["programs"] == first


def test_trace_export_carries_the_clock_pair(cli_series):
    doc = json.loads(cli_series["traced"]["trace"].read_bytes())
    clock = doc["metadata"]["clock"]
    rep = cli_series["traced"]["report"]
    assert clock == rep["device_time"]["clock"]
    assert abs(clock["unix_ns"] / 1e9 - rep["started_unix"]) < 5.0
    names = {e["name"] for e in doc["traceEvents"] if e.get("ph") == "X"}
    assert {"align.pack", "align.put", "align.launch", "align.wait",
            "align.get", "align.decode", "poa.put", "poa.wait",
            "poa.get", "poa.decode"} <= names
    # the trace holds this job's spans only: ts 0 is the job's begin
    assert all(e["ts"] >= 0 for e in doc["traceEvents"]
               if e.get("ph") == "X")


# ---------------------------------------------------------- the gaps tool

def _synthetic_pair(offset_ns: int):
    """A report's device_time and a device trace of the same made-up
    job: the host observes each completion ``offset + jitter`` after
    the device ended it."""
    dev = [("jit__build_rows_packed2(11)", 1_000 * MS, 200 * MS),
           ("jit__pallas_align_chain(12)", 1_200 * MS, 300 * MS),
           ("jit_convert_element_type(13)", 1_900 * MS, 1 * MS),
           ("jit__build_rows_packed2(14)", 2_000 * MS, 200 * MS),
           ("jit__pallas_align_chain(15)", 2_200 * MS, 400 * MS)]
    ops = [("fusion.1", 1_000 * MS, 200 * MS),
           ("custom-call.2", 1_200 * MS, 100 * MS),
           ("custom-call.2", 1_350 * MS, 150 * MS),     # 50 ms inside gap
           ("convert.3", 1_900 * MS, 1 * MS),
           ("fusion.1", 2_000 * MS, 200 * MS),
           ("custom-call.2", 2_200 * MS, 400 * MS)]
    events = {"0": {"XLA Modules": dev, "XLA Ops": ops,
                    "Async XLA Ops": [("copy-start", 1_300 * MS, 20 * MS)]}}
    jitter = [0, 2 * MS, 1 * MS, 3 * MS]
    names = ["_build_rows_packed2", "_pallas_align_chain"] * 2
    ends = [1_200 * MS, 1_500 * MS, 2_200 * MS, 2_600 * MS]
    timeline = [["0", "exec", n, "MainThread", e + offset_ns - 50 * MS,
                 e + offset_ns + j]
                for n, e, j in zip(names, ends, jitter)]
    timeline.insert(0, ["0", "h2d", "align.put", "MainThread",
                        900 * MS + offset_ns, 950 * MS + offset_ns])
    host_gap = [1_500 * MS + offset_ns, 2_000 * MS + offset_ns,
                {"align.pack": 0.3, "align.put": 0.15,
                 "unattributed": 0.05}]
    section = dict(device_time.empty_section(), timeline=timeline,
                   gaps=[host_gap], programs=5)
    return {"device_time": section}, events


def test_gaps_recovers_a_planted_offset_and_names_the_gap():
    planted = 7_654_321_000
    rep, events = _synthetic_pair(planted)
    res = gaps.analyze(rep, events)
    assert res["offset_ns"] == planted
    assert res["pairs"] == 4
    assert res["residual_ns"]["max"] == 3 * MS
    assert res["unpaired"] == {
        "jit_convert_element_type": {"count": 1, "seconds": 0.001}}
    between = [g for g in res["gaps"] if g["group"].startswith("before:")]
    inside = [g for g in res["gaps"] if g["group"].startswith("inside:")]
    # 1500-1900 before the eager helper, 1901-2000 before the next chunk
    assert [round(g["seconds"], 3) for g in between] == [0.4, 0.099]
    assert between[0]["group"] == "before:jit_convert_element_type"
    assert between[0]["spans"]["align.pack"] == pytest.approx(0.24)
    assert between[0]["named_s"] == pytest.approx(0.4)
    (g,) = inside
    assert g["group"] == "inside:jit__pallas_align_chain"
    assert g["seconds"] == pytest.approx(0.05)
    assert g["async_covered_s"] == pytest.approx(0.02)
    text = gaps.render(res)
    assert "clock offset" in text and "before:jit_convert_element_type" \
        in text


def test_gaps_refuses_a_pairing_whose_names_or_counts_differ(tmp_path):
    rep, events = _synthetic_pair(10**9)
    renamed = json.loads(json.dumps(rep))
    renamed["device_time"]["timeline"][1][2] = "_build_rows_packed"
    with pytest.raises(gaps.GapsError, match="no program"):
        gaps.analyze(renamed, events)
    fewer = json.loads(json.dumps(rep))
    del fewer["device_time"]["timeline"][-1]
    with pytest.raises(gaps.GapsError, match="times"):
        gaps.analyze(fewer, events)
    # the command: exit 1 and a message, exit 0 and the table
    rp, tp = tmp_path / "rep.json", tmp_path / "trace_events.json"
    rp.write_text(json.dumps(renamed))
    tp.write_text(json.dumps(events))
    assert report._main(["gaps", str(rp), str(tp)]) == 1
    rp.write_text(json.dumps(rep))
    assert report._main(["gaps", str(rp), str(tp)]) == 0
