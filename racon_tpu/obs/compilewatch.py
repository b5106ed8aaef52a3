"""Process-wide XLA compile attribution (round 18) — the runtime
companion of graftlint's compile-surface rules.

One ``jax.monitoring`` duration listener (armed once per process,
:func:`arm` — it absorbs the serve-only ``compile_s`` listener of
round 14) observes every ``/jax/core/compile/*`` event and:

- accumulates real compile seconds into the ``compile.jax_s`` timer —
  fired on the compiling thread, so a service job's worker thread
  lands the time in THAT job's metric scope (the measured numerator of
  ``service_compile_fraction``, exactly as before);
- back-dates one span event per stage (``compile.trace``,
  ``compile.lower``, ``compile.backend``, from the event's own
  duration) onto the compiling thread's ring and into the timers of
  those names, so what the persistent cache saves (backend) reads apart
  from what it does not (trace + lowering). A stage nested in another
  (a jit traced inside a trace; JAX announces each stage as it begins,
  the scalar listener keeps the thread's open ones) gives its parent's
  timer only the parent's self time: the three timers sum to thread
  time in the compile pipeline, where ``compile.jax_s`` sums every
  event whole;
- counts the persistent cache's lookups and hits
  (``compile.cache_requests`` / ``compile.cache_hits``) from JAX's own
  ``/jax/compilation_cache/*`` events, and back-dates the cache's
  retrieval seconds as ``compile.retrieve`` — a timer-only leaf inside
  ``compile.backend``, which keeps meaning "compile or load";
- writes **one row per compiled program**: the stages JAX ran on one
  thread for one ``jit`` call that reached the backend, under the name
  JAX gives every stage listener (``fun_name``: the row's ``program``
  is the name the device trace prints), with the row's interval on the
  spans' clock, its stage seconds, whether the persistent cache hit,
  and who asked — the nearest ``racon_tpu`` frame on the compiling
  thread's stack (``fn``), its integer geometry locals
  (``max_len``/``band``/``steps``/``B``/..., the ``signature``), the
  innermost open obs span (``phase``), the thread and its metric scope.
  The rows ride a bounded ring (:func:`events`) and the run report's
  ``compiles`` section (:func:`summary`);
- **joins rows to dispatches**: the occupancy ledger
  (:mod:`.device_time`) tells :func:`claim` the program and static
  geometry of every submission, on the thread that made it; the row a
  thread has just compiled for that program takes the submission's
  geometry string as its own, and at report time counts the ``exec``
  submissions that carry the same pair. A program no submission ever
  named (an eager ``jnp`` one-liner) keeps ``dispatches`` None;
- enforces the **warm-path claim** once :func:`seal` is called (the
  resident server seals after its first job completes): a compile
  whose ``(function, signature)`` was never seen pre-seal is a
  violation, recorded with the *nearest warmed* signature next to the
  offending one.  Under ``RACON_TPU_SANITIZE=1`` the serve path turns
  violations into hard job failures
  (:func:`racon_tpu.sanitize.check_post_warm_compiles`); unsanitized
  they are warned and counted (``bench_service`` asserts the count is
  zero from job #2 on).

Import cost is nil: jax is touched only inside :func:`arm`.
"""

from __future__ import annotations

import math
import re
import sys
import threading
import time
from typing import Dict, List, Optional, Tuple

from . import metrics, trace

# integer locals that form a dispatch-geometry signature when found in
# the attributed frame (the repo's geometry vocabulary)
GEOM_LOCALS = ("max_len", "band", "steps", "B", "nWp", "Lq", "Lb",
               "Lq2", "rounds", "w", "NW", "L", "K", "n_windows",
               "window_length", "est_len", "est_pairs", "max_nm",
               "max_n")

MAX_ROWS = 4096         # bounded row ring (newest kept; a job compiles
                        # tens of programs, a cold one a few hundred)
MAX_VIOLATIONS = 64

# JAX's compile-pipeline stages -> the span each is back-dated as
STAGE_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "compile.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile.lower",
    "/jax/core/compile/backend_compile_duration": "compile.backend",
}
BACKEND = "compile.backend"
# a row's stage seconds, by the span each sums
STAGE_KEYS = {"compile.trace": "trace_s", "compile.lower": "lower_s",
              BACKEND: "backend_s"}
# JAX's persistent-cache events -> the counter each feeds
CACHE_REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
CACHE_COUNTERS = {CACHE_REQUEST: "compile.cache_requests",
                  CACHE_HIT: "compile.cache_hits"}
# the cache's own timer of a hit: reading and deserialising the
# executable (inside backend_compile_duration, which wraps the lookup)
CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
RETRIEVE = "compile.retrieve"
MAX_UNCLAIMED = 16      # per thread: finished outermost stages no row took
MAX_PENDING = 64        # per thread: rows no submission has claimed yet

# a ledger name that stands for several programs dispatched back to
# back: the XLA twin of the Mosaic ``_pallas_align_chain``
CHAINS = {"align_chain": ("_nw_wavefront_kernel", "_traceback_kernel")}

_tls = threading.local()

_lock = threading.Lock()
_armed = False
_sealed: Optional[str] = None
_total_count = 0
_epoch = 0                          # bumped by reset(): a thread's stages
                                    # and rows of an earlier run are dropped
_rows: List[dict] = []
_unrowed_ns: Dict[str, int] = {}    # scope -> stage ns in no row (yet)
_seen: set = set()                  # (fn, signature) warmed pre-seal
_violations: List[dict] = []


def geometry(**statics) -> str:
    """The join key of a dispatch: a program's static arguments and
    batch shape as ``k=v`` pairs, the vocabulary of ``GEOM_LOCALS``
    first and in its order. The stream's submit site and the warm-up
    thread both build it with this function from the values they hand
    the jitted call, so a warm-up row and the dispatch that uses its
    executable carry the same string."""
    known = [k for k in GEOM_LOCALS if k in statics]
    rest = [k for k in statics if k not in GEOM_LOCALS]
    return ",".join(f"{k}={int(statics[k])}" for k in known + rest)


def program_name(fun_name: str) -> str:
    """``jit(_refine_loop_packed)`` (what JAX hands the lowering and
    backend listeners) -> ``jit__refine_loop_packed`` (the XLA module,
    as the device trace prints it)."""
    m = re.match(r"^(\w+)\((.*)\)$", fun_name)
    return f"{m.group(1)}_{m.group(2)}" if m else fun_name


def _attribute() -> Tuple[str, str]:
    """(function, shape signature) of the compile in progress: the
    nearest ``racon_tpu`` frame (the tracer internals and this package
    excluded) on the compiling thread's stack, its integer geometry
    locals formatted ``k=v`` — falls back to the nearest non-jax frame
    (tests driving kernels directly), then ``<unattributed>``."""
    try:
        frame = sys._getframe(2)
    except ValueError:  # pragma: no cover - interpreter shutdown
        return "<unattributed>", ""
    best = None
    fallback = None
    f = frame
    while f is not None:
        fname = f.f_code.co_filename.replace("\\", "/")
        if "/racon_tpu/" in fname and "/racon_tpu/obs/" not in fname:
            best = f
            break
        if fallback is None and "/jax/" not in fname \
                and "/jaxlib/" not in fname \
                and not fname.endswith(("contextlib.py", "threading.py")) \
                and f.f_code.co_name != "<module>":
            fallback = f
        f = f.f_back
    f = best if best is not None else fallback
    if f is None:
        return "<unattributed>", ""
    stem = f.f_code.co_filename.replace("\\", "/").rsplit("/", 1)[-1]
    if stem.endswith(".py"):
        stem = stem[:-3]
    fn = f"{stem}.{f.f_code.co_name}"
    parts = []
    for k in GEOM_LOCALS:
        v = f.f_locals.get(k)
        if isinstance(v, int) and not isinstance(v, bool):
            parts.append(f"{k}={v}")
    return fn, ",".join(parts)


def _mine():
    """The calling thread's ``(open stages, finished stages no row has
    taken, rows no submission has claimed)``, started empty at each run
    boundary (:func:`reset`)."""
    if getattr(_tls, "epoch", None) != _epoch:
        _tls.epoch = _epoch
        _tls.open, _tls.unclaimed, _tls.pending = [], [], []
    return _tls.open, _tls.unclaimed, _tls.pending


def _unrowed(scope: str, ns: int) -> None:
    with _lock:
        _unrowed_ns[scope] = _unrowed_ns.get(scope, 0) + ns


def _add(total: Dict[str, int], more: Dict[str, int]) -> None:
    for k, v in more.items():
        total[k] = total.get(k, 0) + v


def _on_scalar(event, value, **kwargs) -> None:
    """The registered scalar listener: JAX announces every stage as it
    begins (``LogElapsedTimeContextManager.__enter__``). The frame it
    opens collects what ends inside the stage — a Mosaic lowering
    traces thousands of operators — as two numbers, not as a list."""
    span = STAGE_SPANS.get(str(event))
    if span is not None:
        # [span, {span: ns} of the stages that ended inside, their ns]
        _mine()[0].append([span, {}, 0])


def _record_stage(name: str, duration: float, fun_name: str,
                  scope: str) -> tuple:
    """Back-date one finished stage onto this thread's ring; its timer
    gets the stage's self time: what ended inside it was handed to its
    frame as it ended (children fire first). A stage nested in another
    hands its seconds, by span, up to that one; an outermost stage is
    kept for the row of the backend stage that follows it. Returns the
    stage as ``(t0, t1, {span: ns}, span, fun_name)``: its self time
    plus what it enclosed, so a row sums to the timers."""
    t1 = time.perf_counter_ns()
    ns = int(duration * 1e9)
    stack, unclaimed, _ = _mine()
    held: Dict[str, int] = {}
    inside = 0
    for i in range(len(stack) - 1, -1, -1):
        if stack[i][0] == name:
            # a frame above it never ended (JAX drops a stage's end
            # only while the interpreter exits): it takes nothing along
            _, held, inside = stack[i]
            del stack[i:]
            break
    self_ns = max(0, ns - inside)
    held[name] = held.get(name, 0) + self_ns
    _unrowed(scope, self_ns)
    trace.record(name, t1 - ns, t1, seconds=self_ns * 1e-9)
    entry = (t1 - ns, t1, held, name, fun_name)
    if stack:
        stack[-1][2] += ns
        if name != BACKEND:     # a nested backend's seconds are its row's
            _add(stack[-1][1], held)
    else:
        unclaimed.append(entry)
        del unclaimed[:-MAX_UNCLAIMED]
    return entry


def _take_row(scope: str, backend: tuple) -> Tuple[int, int, Dict[str, int]]:
    """One row's ``(t0_ns, t1_ns, {span: ns})``: the backend stage just
    recorded on this thread and, where it is an outermost one, the
    lowering and the trace that led to it (the same program's, directly
    before it among the thread's finished outermost stages)."""
    t0, t1, ns_by, _, name = backend
    ns_by = dict(ns_by)
    unclaimed = _mine()[1]
    if unclaimed and unclaimed[-1] is backend:
        unclaimed.pop()
        for span in ("compile.lower", "compile.trace"):
            if not unclaimed or unclaimed[-1][3] != span:
                continue
            have = unclaimed[-1][4]
            # lowering carries the backend's name, tracing the
            # function's (``jit(f)`` / ``f``); a listener driven without
            # names matches by position alone
            if name and have and have != name \
                    and not name.endswith(f"({have})"):
                continue
            t0, _, more, _, _ = unclaimed.pop()
            _add(ns_by, more)
    _unrowed(scope, -sum(ns_by.values()))
    return t0, t1, ns_by


def _on_event(event, **kwargs) -> None:
    """The registered plain-event listener: the persistent cache's
    lookups and hits, counted, and kept for the row of the backend
    event that follows on this thread."""
    event = str(event)
    name = CACHE_COUNTERS.get(event)
    if name is not None:
        metrics.inc(name)
        _tls.cache = "hit" if event == CACHE_HIT else "miss"


def _on_duration(event, duration, fun_name: str = "", **kwargs) -> None:
    """The registered listener: every compile-pipeline stage feeds the
    ``compile.jax_s`` timer (the round-14 serve semantics, verbatim)
    and its own back-dated span; a backend compile closes one row."""
    global _total_count
    event = str(event)
    if event == CACHE_RETRIEVAL:
        t1 = time.perf_counter_ns()
        trace.record(RETRIEVE, t1 - int(duration * 1e9), t1)
        _tls.retrieve_s = float(duration)
        return
    if not event.startswith("/jax/core/compile/"):
        return
    metrics.add_time("compile.jax_s", duration)
    stage = STAGE_SPANS.get(event)
    if stage is None:
        return
    scope = metrics.get_scope() or ""
    fun_name = str(fun_name or "")
    entry = _record_stage(stage, duration, fun_name, scope)
    if stage != BACKEND:
        return
    fn, signature = _attribute()
    phase = trace.current_span() or ""
    # scoped exact count: the row ring is bounded (a job's rows can be
    # evicted by later compiles before its report is built), so the
    # per-scope `count` reads this counter, not the ring
    metrics.inc("compile.backend_total")
    t0, t1, ns_by = _take_row(scope, entry)
    row = {"program": program_name(fun_name), "fn": fn,
           "signature": signature,
           # the submission that runs this executable names both
           # (claim); None: no submission has, an eager helper so far
           "geometry": "", "submitted_as": None,
           "thread": threading.current_thread().name, "phase": phase,
           "scope": scope, "t0_ns": t0, "t1_ns": t1,
           "retrieve_s": getattr(_tls, "retrieve_s", 0.0),
           # "none": JAX asked the persistent cache nothing
           "cache": getattr(_tls, "cache", None) or "none"}
    for span, key in STAGE_KEYS.items():
        row[key] = ns_by.get(span, 0) * 1e-9
    _tls.cache = None
    _tls.retrieve_s = 0.0
    pending = _mine()[2]
    pending.append(row)
    del pending[:-MAX_PENDING]
    warn_msg = None
    with _lock:
        _total_count += 1
        _rows.append(row)
        if len(_rows) > MAX_ROWS:
            del _rows[0]
        key = (fn, signature)
        if _sealed is None or not scope:
            # pre-seal, every compile warms.  Post-seal, an UNSCOPED
            # compile is warm-up/background work by construction (job
            # work always runs under a metric scope): it EXTENDS the
            # warmed set — admission warm-up of a new geometry is the
            # design, not a violation.  Only scoped (job) compiles can
            # violate the warm-path claim.
            _seen.add(key)
        elif key not in _seen:
            viol = {"fn": fn, "signature": signature, "phase": phase,
                    "scope": scope,
                    "duration_s": round(float(duration), 4),
                    "nearest_warmed": _nearest_locked(fn, signature)}
            # FIFO-bounded, never refuse the newest: judged scopes are
            # pruned (clear_scope), so the cap only backstops unjudged
            # ones — refusing new records here would silently disarm
            # the sanitized warm-path assert for every later job
            _violations.append(viol)
            if len(_violations) > MAX_VIOLATIONS:
                del _violations[0]
            warn_msg = (
                f"compile AFTER warm-up sealed ({_sealed}): "
                f"`{fn}` [{signature or 'no geometry locals'}] "
                f"({duration:.2f}s; phase={phase or '-'}, "
                f"scope={scope or '-'}) — nearest warmed signature: "
                f"{viol['nearest_warmed']}")
    if warn_msg is not None:
        from ..utils.logger import warn
        warn(warn_msg)


def claim(name: str, geom: str) -> None:
    """The occupancy ledger's hook (``device_time.submit``, recording
    on): the calling thread has just dispatched program ``name`` with
    static geometry ``geom``. A row this thread compiled for that
    program inside the span it is in now — the call that compiled it is
    the call being submitted; an availability probe's compile of the
    same kernel sits in an earlier span — is that executable's: it
    takes the submission's name and geometry, the pair :func:`summary`
    counts dispatches by."""
    pending = _mine()[2]
    if not pending:
        return
    since = trace.current_span_t0() or 0
    programs = {"jit_" + p for p in CHAINS.get(name, (name,))}
    mine = [r for r in pending
            if r["program"] in programs and r["t0_ns"] >= since]
    # what is older than the open span is an earlier step's for good
    pending[:] = [r for r in pending
                  if r["program"] not in programs and r["t0_ns"] >= since]
    with _lock:
        for row in mine:
            row["geometry"] = geom
            row["submitted_as"] = name


def _sig_ints(signature: str) -> Dict[str, int]:
    out: Dict[str, int] = {}
    for part in signature.split(","):
        if "=" in part:
            k, _, v = part.partition("=")
            try:
                out[k] = int(v)
            except ValueError:
                pass
    return out


def _nearest_locked(fn: str, signature: str) -> str:
    """The warmed (fn, signature) closest to an offending one — same
    function preferred, then minimal per-field log-distance.  Called
    with ``_lock`` held."""
    if not _seen:
        return "<nothing warmed>"
    want = _sig_ints(signature)
    best, best_d = None, None
    for sfn, ssig in _seen:
        have = _sig_ints(ssig)
        d = 0.0 if sfn == fn else 1000.0
        keys = set(want) | set(have)
        for k in keys:
            a, b = want.get(k), have.get(k)
            if a is None or b is None:
                d += 10.0
            elif a != b:
                d += abs(math.log2(max(a, 1)) - math.log2(max(b, 1))) \
                    + 1.0
        if best_d is None or d < best_d:
            best, best_d = (sfn, ssig), d
    return f"`{best[0]}` [{best[1] or 'no geometry locals'}]"


# ---------------------------------------------------------------- control

def arm() -> bool:
    """Register the process-wide listener (idempotent).  Safe without
    jax — attribution then reads 0, like the round-14 serve fallback."""
    global _armed
    with _lock:
        if _armed:
            return True
    try:
        import jax.monitoring as jmon
    # graftlint: disable=swallowed-exception (logged: attribution is telemetry, never fatal)
    except Exception as e:
        from ..utils.logger import log_swallowed
        log_swallowed(
            "obs: jax.monitoring compile listener unavailable "
            "(compile attribution and per-job compile_s will read 0)",
            e)
        return False
    with _lock:
        if not _armed:
            jmon.register_event_duration_secs_listener(_on_duration)
            jmon.register_event_listener(_on_event)
            jmon.register_scalar_listener(_on_scalar)
            _armed = True
    return True


def armed() -> bool:
    return _armed


def seal(reason: str) -> None:
    """Declare warm-up complete: from now on, a compile of a never-seen
    (function, signature) is a warm-path violation.  First seal wins
    (idempotent); :func:`unseal` reopens (tests, capacity changes)."""
    global _sealed
    with _lock:
        if _sealed is None:
            _sealed = reason


def sealed() -> Optional[str]:
    return _sealed


def unseal() -> None:
    global _sealed
    with _lock:
        _sealed = None


def clear_scope(scope: str) -> None:
    """Drop one scope's violation records (the serve worker calls this
    after a job is JUDGED — counted into its header / asserted — so the
    bounded global list only ever holds unjudged scopes and a
    long-running sanitized server cannot fill it up and quietly stop
    flagging later jobs).  Rows are kept: they are telemetry, and the
    ring bounds itself."""
    if not scope:
        return
    with _lock:
        _violations[:] = [v for v in _violations
                          if v["scope"] != scope]


def reset() -> None:
    """Drop recorded rows/warmed set/violations and reopen the seal
    (tests and run boundaries that must not inherit attribution)."""
    global _sealed, _total_count, _epoch
    with _lock:
        _sealed = None
        _total_count = 0
        _epoch += 1
        _rows.clear()
        _unrowed_ns.clear()
        _seen.clear()
        _violations.clear()


# ---------------------------------------------------------------- queries

def events(scope: Optional[str] = None) -> List[dict]:
    """The rows, one per compiled program (bounded ring, oldest first);
    ``scope`` filters to one job's."""
    with _lock:
        return [dict(r) for r in _rows
                if scope is None or r["scope"] == scope]


def post_warm(scope: Optional[str] = None) -> List[dict]:
    """Warm-path violations recorded since :func:`seal` (``scope``
    filters to one job's)."""
    with _lock:
        return [dict(v) for v in _violations
                if scope is None or v["scope"] == scope]


def describe(violations: List[dict]) -> str:
    """One human-readable line per violation — the offending signature
    next to the nearest warmed one."""
    lines = [f"{len(violations)} compile(s) observed after warm-up "
             f"completed:"]
    for v in violations:
        lines.append(
            f"  `{v['fn']}` [{v['signature'] or 'no geometry locals'}] "
            f"({v['duration_s']:.2f}s, phase={v['phase'] or '-'}) — "
            f"nearest warmed: {v['nearest_warmed']}")
    return "\n".join(lines)


def union_s(intervals) -> float:
    """Seconds covered by at least one of ``(t0_ns, t1_ns)``: compile
    stages as wall, however many threads ran them at once."""
    covered, end = 0, None
    for t0, t1 in sorted(intervals):
        if end is None or t0 > end:
            covered += t1 - t0
            end = t1
        elif t1 > end:
            covered += t1 - end
            end = t1
    return covered * 1e-9


def stage_s(row: dict) -> float:
    """A row's seconds in the three stage timers."""
    return row["trace_s"] + row["lower_s"] + row["backend_s"]


def summary(scope: str = "", ran: Optional[Dict[tuple, int]] = None
            ) -> dict:
    """The run report's required ``compiles`` section (schema v13): one
    row per compiled program with the ``exec`` submissions that ran it
    (``ran``: the occupancy ledger's ``dispatch_counts``), and the
    totals the set-up metrics read.  With ``scope``, every piece is
    filtered to that job's records."""
    ran = ran or {}
    with _lock:
        rows = [dict(r) for r in _rows
                if not scope or r["scope"] == scope]
        viol = [v for v in _violations
                if not scope or v["scope"] == scope]
        total = _total_count
        is_sealed = _sealed is not None
        unrowed = sum(ns for sc, ns in _unrowed_ns.items()
                      if not scope or sc == scope)
    # scoped: the exact per-scope counter (the bounded row ring may
    # have evicted early rows); unscoped: the module total
    count = total if not scope else int(
        metrics.counter(scope + "compile.backend_total", len(rows)))
    for row in rows:
        del row["scope"]
        name = row.pop("submitted_as")
        row["dispatches"] = None if name is None else ran.get(
            (name, row["geometry"]), 0)
        for key in ("trace_s", "lower_s", "backend_s", "retrieve_s"):
            row[key] = round(row[key], 6)
    rows.sort(key=lambda r: r["t0_ns"])
    unused = [r for r in rows if r["dispatches"] == 0]
    # a hit still costs its retrieval: the timer reads 0, not absent,
    # where nothing was retrieved (a metric sums it in every report)
    metrics.replace_timers(RETRIEVE, {
        "": metrics.timer_s(scope + RETRIEVE)}, scope)
    return {
        "total_s": round(metrics.timer_s(scope + "compile.jax_s"), 3),
        "count": count,
        "post_warm": len(viol),
        "sealed": 1 if is_sealed else 0,
        "programs": rows,
        "dropped": max(0, count - len(rows)),
        "wall_s": round(union_s((r["t0_ns"], r["t1_ns"])
                                for r in rows), 6),
        "unused": len(unused),
        "unused_s": round(float(sum(map(stage_s, unused))), 6),
        "eager_programs": sum(r["dispatches"] is None for r in rows),
        "miss_s": round(float(sum(stage_s(r) for r in rows
                                  if r["cache"] == "miss")), 6),
        "unrowed_s": round(unrowed * 1e-9, 6),
    }


# ------------------------------------------------------------- the table

def table(comp: dict, wall_s: float = 0.0) -> str:
    """A report's ``compiles`` section as the table an operator reads:
    one line per program in start order, then the totals."""
    rows = comp["programs"]
    origin = min((r["t0_ns"] for r in rows), default=0)
    lines = [f"{'start_s':>8} {'wall_s':>7} {'trace':>7} {'lower':>6} "
             f"{'backend':>8} {'retr':>6} cache disp  thread / phase / "
             f"program [geometry | frame signature]"]
    for r in rows:
        disp = "-" if r["dispatches"] is None else str(r["dispatches"])
        what = r["geometry"] or f"{r['fn']} {r['signature']}".strip()
        lines.append(
            f"{(r['t0_ns'] - origin) * 1e-9:8.2f} "
            f"{(r['t1_ns'] - r['t0_ns']) * 1e-9:7.2f} "
            f"{r['trace_s']:7.2f} {r['lower_s']:6.2f} "
            f"{r['backend_s']:8.2f} {r['retrieve_s']:6.2f} "
            f"{r['cache']:<5} {disp:>4}  {r['thread']} / "
            f"{r['phase'] or '-'} / {r['program']} [{what}]")
    staged = sum(map(stage_s, rows))
    lines += [
        "",
        f"{comp['count']} programs ({len(rows)} rows, "
        f"{comp['dropped']} dropped), {comp['eager_programs']} never "
        f"submitted to the ledger (eager helpers)",
        f"stage seconds {staged:.2f} summed over threads "
        f"(+ {comp['unrowed_s']:.2f} in no row), {comp['wall_s']:.2f} s "
        f"as wall" + (f" of the job's {wall_s:.2f}" if wall_s else ""),
        f"cache misses {comp['miss_s']:.2f} s; "
        f"{comp['unused']} programs no dispatch ran, "
        f"{comp['unused_s']:.2f} s",
        f"post-warm compiles {comp['post_warm']} "
        f"(sealed: {bool(comp['sealed'])})"]
    return "\n".join(lines)


def main(argv) -> int:
    """``python -m racon_tpu.obs compiles RUN_REPORT``."""
    import json
    if len(argv) != 1:
        print("usage: python -m racon_tpu.obs compiles RUN_REPORT",
              file=sys.stderr)
        return 2
    try:
        with open(argv[0], "rb") as f:
            rep = json.loads(f.read())
        comp = rep["compiles"]
        comp["programs"]
    except (OSError, ValueError, KeyError, TypeError) as e:
        print(f"run report {argv[0]}: no table of programs "
              f"(schema v13 has one): {e!r}", file=sys.stderr)
        return 2
    print(table(comp, float(rep.get("wall_s", 0.0))))
    return 0
