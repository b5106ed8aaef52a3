"""Span tracing: low-overhead pipeline spans exported as Chrome
trace-event JSON (loadable in Perfetto / chrome://tracing).

The one public span surface is the context manager::

    with obs.span("align.dispatch", pairs=len(chunk)):
        ...

(the graftlint rule ``span-discipline`` enforces the ``with`` form —
manual begin/end pairs leak open spans when an exception unwinds).

One switch, **active** (:func:`activate`; a run report *or* a trace was
asked for): span exits accumulate their duration into the metrics
registry's timers keyed by the span name (the run report's
dispatch-vs-fetch split reads them) and land in per-thread ring buffers
(bounded: the oldest events of a thread drop first, counted in
``trace.dropped_events``) — :func:`export` writes them under ``--trace``
and the device-occupancy ledger (:mod:`.device_time`) reads them to name
what the feeding thread did while the device had nothing.
(``activate``'s ``tracing`` argument is what callers pass when an export
was asked for; recording no longer depends on it.)

**One clock**: every stamp is ``time.perf_counter_ns()``.
:func:`new_run` (``obs.begin``) and the first :func:`activate` record
the pair (``perf_counter_ns``, ``time.time_ns``) once — :func:`clock` —
and the Chrome trace's metadata and the run report carry it, so any span
can be placed on wall time and any two files of one run on each other.

When recording is off — the default — ``span()`` returns one shared
no-op singleton: the cost is a module-global load, a branch and a
constant return, which is what keeps always-compiled-in spans out of the
hot loops' profile (guarded by ``tests/test_obs.py``).  Output bytes are
identical either way: spans observe, they never steer.

Threads get their own buffer (and their own Perfetto track) the first
time they record a span; :func:`track` pushes a named sub-track for the
current thread (the shard runner wraps each shard in one, so a run's
shards land on separate rows of the trace viewer).
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import List, Optional

from . import metrics

# events kept per (thread, ring): ~64 bytes/event -> a few MB per thread
RING_CAP = 1 << 18

_lock = threading.Lock()
_active = False
_clock = None          # (perf_counter_ns, time_ns) of the run's start
_threads: List["_ThreadBuf"] = []
_epoch = 0             # bumped by deactivate(): stale thread-local
                       # buffers re-register instead of recording into
                       # orphaned (never-exported) rings
_tls = threading.local()


class _ThreadBuf:
    """Per-thread ring buffer of finished span events plus the thread's
    current :func:`track` stack."""

    __slots__ = ("name", "events", "pos", "dropped", "tracks", "epoch",
                 "open")

    def __init__(self, name: str, epoch: int):
        self.name = name
        self.events: list = []     # (track, name, t0_ns, t1_ns, args)
        self.pos = 0
        self.dropped = 0
        self.tracks: List[str] = []
        self.epoch = epoch
        self.open: list = []       # the thread's open spans, outermost first

    def append(self, ev) -> None:
        # a _ThreadBuf is single-writer by construction: _buf() hands
        # every thread its OWN instance through thread-local storage,
        # so these ring-state writes never race (export() reads other
        # threads' rings, racing at worst into one stale event)
        if len(self.events) < RING_CAP:
            self.events.append(ev)
        else:
            self.events[self.pos] = ev
            self.pos = (self.pos + 1) % RING_CAP
            self.dropped += 1


def _buf() -> _ThreadBuf:
    b = getattr(_tls, "buf", None)
    if b is None or b.epoch != _epoch:
        b = _ThreadBuf(threading.current_thread().name, _epoch)
        _tls.buf = b
        with _lock:
            _threads.append(b)
    return b


class _NullSpan:
    """Shared no-op span/track returned whenever recording is off."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return False


NULL_SPAN = _NullSpan()


class _Span:
    __slots__ = ("name", "args", "_t0", "_b")

    def __init__(self, name: str, args: dict):
        self.name = name
        self.args = args

    def __enter__(self):
        # the thread's open-span list feeds phase attribution (the
        # compile watch reads the innermost open span when XLA compiles
        # on this thread) and the occupancy ledger (a span still open
        # at report time is cut like a finished one)
        self._b = b = _buf()
        b.open.append(self)
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb):
        t1 = time.perf_counter_ns()
        b = self._b
        if b.open and b.open[-1] is self:
            b.open.pop()
        seconds = (t1 - self._t0) * 1e-9
        metrics.add_time(self.name, seconds)
        # per-thread timer prefix (set_timer_prefix): the chip-worker
        # threads mirror their spans under device.<ordinal>.* so the
        # run report can attribute dispatch/fetch seconds per chip
        pfx = getattr(_tls, "timer_prefix", None)
        if pfx:
            metrics.add_time(pfx + self.name, seconds)
        b.append((b.tracks[-1] if b.tracks else None,
                  self.name, self._t0, t1, self.args or None))
        return False


def record(name: str, t0_ns: int, t1_ns: int, seconds=None) -> None:
    """Back-date one finished event onto the CURRENT THREAD's ring and
    its duration into the timer ``name`` — for work whose length is
    only known once it is over (the compile listener's stages).
    ``seconds`` overrides what the timer gets (an event's self time,
    where its children were recorded before it)."""
    if not _active:
        return
    metrics.add_time(name, (t1_ns - t0_ns) * 1e-9 if seconds is None
                     else seconds)
    b = _buf()
    b.append((b.tracks[-1] if b.tracks else None, name, t0_ns, t1_ns,
              None))


def span(name: str, **args):
    """A context manager timing the enclosed block as span ``name``
    (optional ``args`` become the event's Perfetto args). Use ONLY as
    ``with obs.span(...):`` — the span-discipline lint enforces it."""
    if not _active:
        return NULL_SPAN
    return _Span(name, args)


class _Track:
    __slots__ = ("name", "_b")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self._b = _buf()
        self._b.tracks.append(self.name)
        return self

    def __exit__(self, exc_type, exc, tb):
        # pop from the buffer we pushed onto — if deactivate() bumped
        # the epoch mid-track, the thread-local buffer was replaced and
        # our push lives only on the orphaned one (popping a fresh
        # buffer's empty list would raise)
        b = getattr(_tls, "buf", None)
        if b is self._b and b.tracks:
            b.tracks.pop()
        return False


def track(name: str):
    """Route the current thread's spans onto a named sub-track until
    exit (e.g. one track per shard in the trace viewer)."""
    if not _active:
        return NULL_SPAN
    return _Track(name)


def current_span():
    """The CURRENT THREAD's innermost open span name (None when no
    span is open or recording is off) — the compile watch stamps it as
    the phase of every XLA compile attributed to this thread."""
    b = getattr(_tls, "buf", None)
    return b.open[-1].name if b is not None and b.open else None


def current_span_t0():
    """When the CURRENT THREAD's innermost open span began
    (``perf_counter_ns``; None with no span open): what the thread did
    before it belongs to an earlier step."""
    b = getattr(_tls, "buf", None)
    return b.open[-1]._t0 if b is not None and b.open else None


def current_buf():
    """The CURRENT THREAD's ring (registered on first use) — the
    occupancy ledger keeps it with every submission, so an idle
    interval can be cut by the spans of the thread that ended it."""
    return _buf()


def snapshot_events(b) -> list:
    """``(name, t0_ns, t1_ns)`` of ring ``b``'s finished spans plus its
    still-open ones (``t1_ns`` None). Another thread's ring is read
    racing at worst into one stale event, like :func:`export`."""
    out = [(name, t0, t1) for _, name, t0, t1, _ in list(b.events)]
    out += [(sp.name, sp._t0, None) for sp in list(b.open)]
    return out


def get_timer_prefix():
    """The CURRENT THREAD's span-timer mirror prefix (None when unset)
    — readers that want an uncontaminated per-thread timer (e.g. the
    polisher's align dispatch/fetch split under concurrent chip
    workers) prepend this to the span name."""
    return getattr(_tls, "timer_prefix", None)


def set_timer_prefix(prefix) -> None:
    """Mirror the CURRENT THREAD's span timers under ``prefix + name``
    in addition to the plain span name (None clears). The in-process
    chip workers set ``device.<ordinal>.`` so per-chip dispatch/fetch
    seconds land in the registry without any span call site changing."""
    _tls.timer_prefix = prefix or None


# ------------------------------------------------------------- lifecycle

def _stamp_clock() -> None:
    global _clock
    _clock = (time.perf_counter_ns(), time.time_ns())


def activate(tracing: bool = False) -> None:
    """Turn span recording on (timers and rings). Idempotent; the clock
    pair is stamped at the first activation unless :func:`new_run`
    already did. ``tracing`` (an export was asked for) changes nothing
    any more: the rings fill either way."""
    global _active
    if _clock is None:
        _stamp_clock()
    _active = True


def new_run() -> None:
    """A run boundary (``obs.begin``): stamp the run's clock pair and
    start its rings empty, so a second job in one process neither
    exports nor is charged the first job's spans. Live threads'
    buffers re-register on their next span (the epoch bump)."""
    global _threads, _epoch
    with _lock:
        _threads = []
        _epoch += 1
    _stamp_clock()


def clock() -> dict:
    """``{"perf_ns", "unix_ns"}``: the same instant on the span clock
    and on wall time (zeros before any activation)."""
    perf_ns, unix_ns = _clock or (0, 0)
    return {"perf_ns": perf_ns, "unix_ns": unix_ns}


def deactivate() -> None:
    """Full reset (tests): recording off, every thread buffer dropped.
    Live threads' stale thread-local buffers re-register on their next
    span (the epoch bump makes ``_buf`` replace them), so no thread
    keeps recording into an orphaned, never-exported ring."""
    global _active, _threads, _epoch, _clock
    with _lock:
        _active = False
        _threads = []
        _epoch += 1
        _clock = None


def is_active() -> bool:
    return _active


# ---------------------------------------------------------------- export

def export(path: str) -> dict:
    """Write every recorded span as Chrome trace-event JSON to ``path``
    and return ``{"events": n, "dropped": n}``.

    Format: ``{"traceEvents": [...]}`` with complete ("X") events in
    microseconds relative to the run's clock pair (``metadata.clock``:
    ``ts`` 0 is ``perf_ns`` on the span clock and ``unix_ns`` on wall
    time), one tid per (thread, track) pair, and ``thread_name``
    metadata rows — exactly what Perfetto and chrome://tracing load
    directly."""
    pid = os.getpid()
    origin = clock()["perf_ns"]
    with _lock:
        bufs = list(_threads)
    events: list = []
    dropped = 0
    tids: dict = {}
    for b in bufs:
        dropped += b.dropped
        # ring order does not matter: the viewer sorts by ts
        for track_name, name, t0, t1, args in b.events:
            key = (b.name, track_name)
            tid = tids.get(key)
            if tid is None:
                tid = len(tids) + 1
                tids[key] = tid
                label = (b.name if track_name is None
                         else f"{b.name}/{track_name}")
                events.append({"name": "thread_name", "ph": "M",
                               "pid": pid, "tid": tid,
                               "args": {"name": label}})
            ev = {"name": name, "cat": name.split(".", 1)[0], "ph": "X",
                  "pid": pid, "tid": tid,
                  "ts": (t0 - origin) / 1e3,
                  "dur": (t1 - t0) / 1e3}
            if args:
                ev["args"] = args
            events.append(ev)
    if dropped:
        metrics.set_gauge("trace.dropped_events", dropped)
    events.insert(0, {"name": "process_name", "ph": "M", "pid": pid,
                      "args": {"name": "racon_tpu"}})
    from .report import atomic_write_bytes
    atomic_write_bytes(path, json.dumps(
        {"traceEvents": events, "displayTimeUnit": "ms",
         "metadata": {"clock": clock()}}).encode())
    return {"events": len(events), "dropped": dropped}
