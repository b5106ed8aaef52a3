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
  (a jit traced inside a trace) gives its parent's timer only the
  parent's self time: the three timers sum to thread time in the
  compile pipeline, where ``compile.jax_s`` sums every event whole;
- counts the persistent cache's lookups and hits
  (``compile.cache_requests`` / ``compile.cache_hits``) from JAX's own
  ``/jax/compilation_cache/*`` events;
- **attributes** every backend compile to ``(function, shape
  signature, phase, scope)``: the nearest ``racon_tpu`` frame on the
  compiling thread's stack names the driving function, its integer
  geometry locals (``max_len``/``band``/``steps``/``B``/...) form the
  shape signature, the innermost open obs span is the phase, and the
  thread's metric scope is the job.  Counters land as
  ``compile.<fn>`` in the one registry; the full records ride the
  bounded event ring (:func:`events`) and the run report's required
  ``compiles`` section (schema v7, :func:`summary`);
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

MAX_EVENTS = 256        # bounded event ring (newest kept)
MAX_VIOLATIONS = 64

# JAX's compile-pipeline stages -> the span each is back-dated as
STAGE_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "compile.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile.lower",
    "/jax/core/compile/backend_compile_duration": "compile.backend",
}
# JAX's persistent-cache events -> the counter each feeds
CACHE_COUNTERS = {
    "/jax/compilation_cache/compile_requests_use_cache":
        "compile.cache_requests",
    "/jax/compilation_cache/cache_hits": "compile.cache_hits",
}
MAX_UNCLAIMED = 256     # per thread: stage events no parent has claimed

_tls = threading.local()

_lock = threading.Lock()
_armed = False
_sealed: Optional[str] = None
_total_count = 0
_events: List[dict] = []
_seen: set = set()                  # (fn, signature) warmed pre-seal
_violations: List[dict] = []


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


def _record_stage(name: str, duration: float) -> None:
    """Back-date one finished stage onto this thread's ring; its timer
    gets the stage's self time (a stage that ended inside this one was
    recorded before it: children fire first)."""
    t1 = time.perf_counter_ns()
    t0 = t1 - int(duration * 1e9)
    unclaimed = getattr(_tls, "unclaimed", None)
    if unclaimed is None:
        unclaimed = _tls.unclaimed = []
    inside = 0
    while unclaimed and unclaimed[-1][0] >= t0:
        c0, c1 = unclaimed.pop()
        inside += c1 - c0
    unclaimed.append((t0, t1))
    del unclaimed[:-MAX_UNCLAIMED]
    trace.record(name, t0, t1, seconds=max(0, t1 - t0 - inside) * 1e-9)


def _on_event(event, **kwargs) -> None:
    """The registered plain-event listener: the persistent cache's
    lookups and hits."""
    name = CACHE_COUNTERS.get(str(event))
    if name is not None:
        metrics.inc(name)


def _on_duration(event, duration, **kwargs) -> None:
    """The registered listener: every compile-pipeline stage feeds the
    ``compile.jax_s`` timer (the round-14 serve semantics, verbatim)
    and its own back-dated span; backend compiles additionally produce
    one attributed record."""
    global _total_count
    if not str(event).startswith("/jax/core/compile/"):
        return
    metrics.add_time("compile.jax_s", duration)
    stage = STAGE_SPANS.get(str(event))
    if stage is not None:
        _record_stage(stage, duration)
    if "backend_compile" not in str(event):
        return
    fn, signature = _attribute()
    scope = metrics.get_scope() or ""
    phase = trace.current_span() or ""
    metrics.inc(f"compile.{fn}")
    # scoped exact count: the event ring is bounded (a job's records
    # can be evicted by later compiles before its report is built), so
    # the per-scope `count` reads this counter, not the ring
    metrics.inc("compile.backend_total")
    ev = {"fn": fn, "signature": signature, "phase": phase,
          "scope": scope, "duration_s": round(float(duration), 4)}
    warn_msg = None
    with _lock:
        _total_count += 1
        _events.append(ev)
        if len(_events) > MAX_EVENTS:
            del _events[0]
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
            viol = dict(ev)
            viol["nearest_warmed"] = _nearest_locked(fn, signature)
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
    flagging later jobs).  Events are kept: they are telemetry, and the
    ring bounds itself."""
    if not scope:
        return
    with _lock:
        _violations[:] = [v for v in _violations
                          if v["scope"] != scope]


def reset() -> None:
    """Drop recorded events/warmed set/violations and reopen the seal
    (tests and run boundaries that must not inherit attribution)."""
    global _sealed, _total_count
    with _lock:
        _sealed = None
        _total_count = 0
        _events.clear()
        _seen.clear()
        _violations.clear()


# ---------------------------------------------------------------- queries

def events(scope: Optional[str] = None) -> List[dict]:
    """Attributed compile records (bounded ring, oldest first);
    ``scope`` filters to one job's."""
    with _lock:
        return [dict(e) for e in _events
                if scope is None or e["scope"] == scope]


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


def summary(scope: str = "") -> dict:
    """The run report's required ``compiles`` section (schema v7):
    total attributed seconds, counts, the post-warm violation count,
    per-function rollups and the trailing attributed events.  With
    ``scope``, every piece is filtered to that job's records."""
    with _lock:
        evs = [e for e in _events if not scope or e["scope"] == scope]
        viol = [v for v in _violations
                if not scope or v["scope"] == scope]
        total = _total_count
        is_sealed = _sealed is not None
    by_fn: Dict[str, Dict[str, float]] = {}
    for e in evs:
        row = by_fn.setdefault(e["fn"], {"count": 0, "seconds": 0.0})
        row["count"] += 1
        row["seconds"] = round(row["seconds"] + e["duration_s"], 4)
    return {
        "total_s": round(metrics.timer_s(scope + "compile.jax_s"), 3),
        # scoped: the exact per-scope counter (the bounded event ring
        # may have evicted early records); unscoped: the module total
        "count": total if not scope else
        int(metrics.counter(scope + "compile.backend_total",
                            len(evs))),
        "post_warm": len(viol),
        "sealed": 1 if is_sealed else 0,
        "by_function": by_fn,
        "events": [{"fn": e["fn"], "signature": e["signature"],
                    "phase": e["phase"],
                    "duration_s": e["duration_s"]}
                   for e in evs[-32:]],
    }
