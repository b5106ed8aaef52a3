"""The device-occupancy ledger: what the program gave the device, when
the device was done with it, and — for every interval in which the
device had nothing — what the feeding thread was doing instead.

**Submit.** Where a stream has enqueued a device program (after
``align.launch`` / ``poa.dispatch`` return) or started a host-to-device
transfer, it calls :func:`submit` with the kind (``exec`` / ``h2d``),
the jitted function's name as the device trace prints it, and ONE SMALL
output array to watch (the aligner's ``score`` vector, never a table:
the watcher must not hold anything the direction-matrix budget counts)
and the static geometry it dispatched with (``compilewatch.geometry``:
the key that joins the submission to the row of the program it ran, in
the report's ``compiles`` section).
The background warm-up threads submit the dummy programs they execute
as kind ``warm``: the device is busy with them like with any program
(same queue as ``exec``), but they feed nothing, so the idle before one
is charged to the thread of the next real submission.
With recording off :func:`submit` is a module load and a branch.

**Complete.** One watcher thread per local device and kind (started on
first use, only while recording is active) takes the watched arrays in
submission order, blocks on each, stamps the moment it returns and drops
the reference. The device runs one queue of programs in order, so the
stamps do not depend on when the feeding thread comes back to fetch;
transfers run beside the programs, so they have a watcher of their own
(behind a running program's watch a finished transfer would be stamped
late).

**Busy and idle** (:func:`account`, pure arithmetic on rows and span
lists — the tests drive it with a fake clock). Program *i* of a device
and kind occupies ``[max(submit_i, complete_{i-1}), complete_i]``; the
union over kinds is busy; the rest of the window is idle, split into
head (before the first submission), gaps, and tail (after the last
completion).

**Attribution.** Each idle interval is charged to the thread whose
submission ended it (head: the thread of the first submission; tail:
the thread that writes the report; after a ``warm`` row: the thread of
the next real submission) and cut by that thread's innermost
spans open in it, from the span rings (:mod:`.trace`); time in no span
goes to ``unattributed`` — unless the shard runner's slot thread (the
one that holds ``contracts.DRIVER_SPANS``) fed the same device: a
shard's pipeline feeds from threads born inside ``exec.shard``, so what
such a thread holds in no span of its own is cut by the slot thread's
spans (``idle.exec.*``: the commit, the extract, the index, the merge,
``exec.shard``'s own time; and the stitch before them). A leaf in ``contracts.TIMER_ONLY_SPANS`` is
read through: its stretch stays its parent's. The result is the run
report's ``device_time`` section and the timers ``idle.<span>`` /
``idle.unattributed``, which by construction sum to the idle seconds.

All stamps are ``time.perf_counter_ns()``, the spans' clock; the
section carries the run's clock pair, and ``python -m racon_tpu.obs
gaps`` (:mod:`.gaps`) lays the section on a device trace.
"""

from __future__ import annotations

import bisect
import queue
import threading
import time
from typing import Dict, List

from . import compilewatch, metrics, trace
from .. import contracts

MAX_ENTRIES = 1 << 16       # ledger bound (a long-lived server); oldest go
TIMELINE_ROWS = 256         # rows a report carries; the rest are counted
GAP_ROWS = 32               # the longest idle intervals a report carries
FLUSH_TIMEOUT_S = 5.0       # report time: wait this long for the watchers
WATCHER_PREFIX = "racon-devwatch-"
WARM = "warm"               # a warm-up thread's program: busy, not a feeder
UNATTRIBUTED = "unattributed"
IDLE_PREFIX = "idle."

# the submit sites' one formatter of a dispatch's static geometry
geometry = compilewatch.geometry

_cond = threading.Condition()
_entries: List["_Entry"] = []
_evicted = 0
_watchers: Dict[tuple, "_Watcher"] = {}     # (device, kind) -> thread


class _Entry:
    """One submission. ``complete_ns`` is written once, by the device's
    watcher, under ``_cond``."""

    __slots__ = ("device", "kind", "name", "geometry", "buf", "scope",
                 "submit_ns", "complete_ns")

    def __init__(self, device, kind, name, geometry, buf, scope,
                 submit_ns):
        self.device = device
        self.kind = kind
        self.name = name
        self.geometry = geometry
        self.buf = buf
        self.scope = scope
        self.submit_ns = submit_ns
        self.complete_ns = None


class _Watcher(threading.Thread):
    """Blocks on one device's watched arrays of one kind, in submission
    order."""

    def __init__(self, device: str, kind: str):
        super().__init__(name=f"{WATCHER_PREFIX}{device}-{kind}",
                         daemon=True)
        self.inbox: "queue.SimpleQueue" = queue.SimpleQueue()

    def run(self) -> None:
        while True:
            item = self.inbox.get()
            if item is None:        # stop_watchers
                return
            entry, watch = item
            try:
                # graftlint: disable=host-sync-in-hot-loop (this thread exists to block: it is the ledger's completion stamp, off every dispatch path)
                watch.block_until_ready()
            # graftlint: disable=swallowed-exception (a program that failed or whose output was donated is over for the ledger; the feeding thread meets the error at its own fetch)
            except Exception:
                pass
            watch = None        # drop the reference before stamping
            done = time.perf_counter_ns()
            with _cond:
                entry.complete_ns = done
                _cond.notify_all()


def _device_key(watch) -> str:
    """The device ordinal of ``watch`` as the ``devices`` section keys
    it; an array sharded over several chips is the ``mesh`` row."""
    devs = watch.devices()
    if len(devs) != 1:
        return "mesh"
    return str(next(iter(devs)).id)


def _queue(kind: str) -> str:
    """Programs share the device's one in-order queue, whoever
    launched them; transfers run beside it."""
    return "h2d" if kind == "h2d" else "exec"


def submit(kind: str, name: str, watch, geometry: str = "") -> None:
    """Tell the ledger that the calling thread has just enqueued device
    program ``name`` (``kind`` ``"exec"``, or ``"warm"`` from a warm-up
    thread) or started a transfer (``"h2d"``); ``watch`` is one small
    array whose readiness marks the end, ``geometry`` the program's
    static geometry (``compilewatch.geometry``; a transfer has none).
    Off — no report and no trace asked for — this returns at the first
    branch and no watcher thread exists."""
    if not trace._active:
        return
    global _evicted
    device = _device_key(watch)
    entry = _Entry(device, kind, name, geometry, trace.current_buf(),
                   metrics.get_scope() or "", time.perf_counter_ns())
    if kind != "h2d":
        # the row this thread compiled for the call, if it did
        compilewatch.claim(name, geometry)
    with _cond:
        _entries.append(entry)
        if len(_entries) > MAX_ENTRIES:
            del _entries[0]
            _evicted += 1
        lane = (device, _queue(kind))
        watcher = _watchers.get(lane)
        if watcher is None:
            watcher = _watchers[lane] = _Watcher(*lane)
            watcher.start()
        # under the lock: the inbox order is the ledger's order
        watcher.inbox.put((entry, watch))


def reset() -> None:
    """A run boundary (``obs.begin``): forget the previous run's
    submissions. The watchers stay; they hold nothing while idle."""
    global _evicted
    with _cond:
        _entries.clear()
        _evicted = 0


def stop_watchers() -> None:
    """Stop and join the watcher threads, once they have stamped what
    they were given. A process keeps its watchers; a test that counts
    them hands the next one a process with none."""
    with _cond:
        watchers = list(_watchers.values())
        _watchers.clear()
    for watcher in watchers:
        watcher.inbox.put(None)
    for watcher in watchers:
        watcher.join(FLUSH_TIMEOUT_S)


def dispatch_counts(scope: str = "") -> Dict[tuple, int]:
    """``{(program, geometry): exec submissions}`` of this run (of
    ``scope``'s job): how often each executable really ran. ``warm``
    submissions ran a dummy and count nothing."""
    out: Dict[tuple, int] = {}
    with _cond:
        for e in _entries:
            if e.kind == "exec" and (not scope or e.scope == scope):
                key = (e.name, e.geometry)
                out[key] = out.get(key, 0) + 1
    return out


def watcher_threads() -> List[str]:
    """Names of the live watcher threads (the off-means-off test)."""
    return [t.name for t in threading.enumerate()
            if t.name.startswith(WATCHER_PREFIX)]


# ------------------------------------------------------------ arithmetic

def _flatten(events: list, start_ns: int, end_ns: int) -> tuple:
    """One thread's spans ``(name, t0, t1)`` (``t1`` None: still open)
    as disjoint, sorted segments each named for the INNERMOST span
    covering it, clipped to the window: ``(starts, ends, names)``."""
    evs = []
    for name, t0, t1 in events:
        t1 = end_ns if t1 is None else min(t1, end_ns)
        t0 = max(t0, start_ns)
        if t1 > t0:
            evs.append((t0, -t1, name))
    evs.sort()
    segs: list = []
    stack: list = []            # (name, end) of the open spans
    cur = start_ns

    def close(upto: int) -> None:
        nonlocal cur
        while stack and stack[-1][1] <= upto:
            name, end = stack.pop()
            if end > cur:
                segs.append((cur, end, name))
                cur = end

    for t0, neg_t1, name in evs:
        t1 = -neg_t1
        close(t0)
        if stack:
            if t0 > cur:
                segs.append((cur, t0, stack[-1][0]))
            t1 = min(t1, stack[-1][1])      # a child ends with its parent
        cur = max(cur, t0)
        if t1 > cur:
            stack.append((name, t1))
    close(end_ns)
    return ([s for s, _, _ in segs], [e for _, e, _ in segs],
            [n for _, _, n in segs])


def _charge(flat: tuple, a: int, b: int, under: tuple = None
            ) -> Dict[str, int]:
    """Nanoseconds of ``[a, b]`` by innermost span of one thread's
    flattened spans; what no span covers is cut by ``under`` (another
    flattened span list) where one is given, and is ``unattributed``
    where that covers nothing either."""
    starts, ends, names = flat
    out: Dict[str, int] = {}

    def bare(x: int, y: int) -> None:
        cut = {UNATTRIBUTED: y - x} if under is None \
            else _charge(under, x, y)
        for k, v in cut.items():
            out[k] = out.get(k, 0) + v

    pos = a
    i = max(0, bisect.bisect_right(ends, a))
    while i < len(starts) and starts[i] < b:
        lo, hi = max(starts[i], a), min(ends[i], b)
        if hi > lo:
            if lo > pos:
                bare(pos, lo)
            out[names[i]] = out.get(names[i], 0) + hi - lo
            pos = hi
        i += 1
    if b > pos:
        bare(pos, b)
    return out


def _boundary_idle(occupied: list, idle: list, shards: list) -> int:
    """Idle nanoseconds of one device at its shard boundaries: for two
    ``exec.shard`` spans ``(t0, t1)`` that follow each other, from the
    end of the last occupied interval begun inside the first (its ``t1``
    where it gave the device nothing) to the begin of the first occupied
    interval inside the second (its ``t0`` where it gave nothing)."""
    total = 0
    for (t0, t1), (u0, u1) in zip(shards, shards[1:]):
        lo = max((end for begin, end, _ in occupied if t0 <= begin < t1),
                 default=t1)
        hi = min((begin for begin, _, _ in occupied if u0 <= begin < u1),
                 default=u0)
        total += sum(max(0, min(b, hi) - max(a, lo)) for a, b, _ in idle)
    return total


def _device_rows(rows: list, start_ns: int, end_ns: int) -> tuple:
    """One device's rows ``(kind, name, thread, submit_ns,
    complete_ns)`` in submission order -> (occupied intervals
    ``(begin, end, row index)``, per-program ``{name: [count, ns]}``)."""
    last: Dict[str, int] = {}       # queue -> previous completion
    occupied, by_program = [], {}
    for i, (kind, name, _, submit_ns, complete_ns) in enumerate(rows):
        kind = _queue(kind)
        prev = last.get(kind, start_ns)
        # an in-order queue completes in order; a stamp that reads
        # earlier is the watcher's jitter, not the device's
        complete_ns = max(end_ns if complete_ns is None else complete_ns,
                          prev)
        last[kind] = complete_ns
        begin = min(max(submit_ns, prev, start_ns), end_ns)
        end = min(max(complete_ns, begin), end_ns)
        occupied.append((begin, end, i))
        row = by_program.setdefault(name, [0, 0])
        row[0] += 1
        row[1] += end - begin
    return occupied, by_program


def _idle_intervals(occupied: list, start_ns: int, end_ns: int) -> tuple:
    """(busy ns, idle intervals ``(a, b, row index of the submission
    that ended it | None for the tail)``) of one device's window."""
    busy, idle = 0, []
    cur_s = cur_e = None
    for begin, end, i in sorted(occupied):
        if cur_e is None or begin > cur_e:
            if cur_e is not None:
                busy += cur_e - cur_s
            a = start_ns if cur_e is None else cur_e
            if begin > a:
                idle.append((a, begin, i))
            cur_s, cur_e = begin, end
        elif end > cur_e:
            cur_e = end
    if cur_e is None:
        return 0, [(start_ns, end_ns, None)] if end_ns > start_ns else []
    busy += cur_e - cur_s
    if end_ns > cur_e:
        idle.append((cur_e, end_ns, None))
    return busy, idle


def _s(ns: float) -> float:
    return round(ns * 1e-9, 6)


def account(rows: list, spans: dict, start_ns: int, end_ns: int,
            report_thread) -> dict:
    """The ``device_time`` numbers from ledger ``rows`` ``(device, kind,
    name, thread, submit_ns, complete_ns)`` in submission order and
    ``spans`` ``{thread: [(name, t0_ns, t1_ns | None)]}``. Pure: no
    clock, no device. With several devices the seconds are the mean
    over the devices that were given work (so ``busy_s + idle_s ==
    window_s`` still), counts and ``by_program`` are sums, and
    ``devices`` holds one row set per device."""
    end_ns = max(end_ns, start_ns)
    flats: dict = {}

    def flat(thread):
        if thread not in flats:
            flats[thread] = _flatten(
                [ev for ev in spans.get(thread, ())
                 if ev[0] not in contracts.TIMER_ONLY_SPANS],
                start_ns, end_ns)
        return flats[thread]

    slots: dict = {}

    def slot_thread(thread) -> bool:
        if thread not in slots:
            slots[thread] = any(ev[0] in contracts.DRIVER_SPANS
                                for ev in spans.get(thread, ()))
        return slots[thread]

    by_device: Dict[str, list] = {}
    for device, *row in rows:
        by_device.setdefault(str(device), []).append(tuple(row))
    per_device = {}
    for device, drows in sorted(by_device.items() or [("0", [])]):
        occupied, by_program = _device_rows(drows, start_ns, end_ns)
        busy, idle = _idle_intervals(occupied, start_ns, end_ns)
        # the shard runner's slot thread, where it fed this device (it
        # dispatches its shards' consensus groups): what a shard's own
        # feeding thread holds in no span is cut by that thread's spans
        drive = [ev for thread in {r[2] for r in drows if r[0] != WARM}
                 | {report_thread} if slot_thread(thread)
                 for ev in spans.get(thread, ())
                 if ev[0] not in contracts.TIMER_ONLY_SPANS]
        driver = _flatten(drive, start_ns, end_ns) if drive else None
        shard_spans = sorted(
            (max(t0, start_ns), end_ns if t1 is None else min(t1, end_ns))
            for name, t0, t1 in drive if name == "exec.shard")
        idle_by: Dict[str, int] = {}
        gaps = []
        head = tail = 0
        feeders = [j for j, r in enumerate(drows) if r[0] != WARM]
        first = min((drows[j][3] for j in feeders), default=None)
        for a, b, i in idle:
            if i is not None and drows[i][0] == WARM:
                # a warm-up program fed nothing: the next real
                # submission's thread is who kept the device waiting
                i = next((j for j in feeders if j > i), None)
            thread = report_thread if i is None else drows[i][2]
            cut = _charge(flat(thread), a, b, driver)
            for k, v in cut.items():
                idle_by[k] = idle_by.get(k, 0) + v
            gaps.append((b - a, a, b, cut))
            if first is None or b <= max(first, start_ns):
                head += b - a       # never given anything: all head
            elif i is None:
                tail += b - a
        # a span seen on a charged thread reads 0, not absent: a metric
        # that sums a layer's idle.* timers then finds something to read
        for thread in {drows[j][2] for j in feeders} | {report_thread}:
            for name in set(flat(thread)[2]):
                idle_by.setdefault(name, 0)
        for name in set(driver[2]) if driver else ():
            idle_by.setdefault(name, 0)
        idle_by.setdefault(UNATTRIBUTED, 0)
        gaps.sort(key=lambda g: (-g[0], g[1]))
        per_device[device] = {
            "busy": busy, "idle": sum(b - a for a, b, _ in idle),
            "head": head, "tail": tail, "programs": len(drows),
            "boundary": _boundary_idle(occupied, idle, shard_spans),
            "by_program": by_program, "idle_by": idle_by,
            "gaps": [[a, b, {k: _s(v) for k, v in sorted(cut.items())}]
                     for _, a, b, cut in gaps[:GAP_ROWS]]}

    n = len(per_device)

    def mean(key: str) -> float:
        return _s(sum(d[key] for d in per_device.values()) / n)

    idle_by: Dict[str, float] = {}
    by_program: Dict[str, list] = {}
    for d in per_device.values():
        for k, v in d["idle_by"].items():
            idle_by[k] = idle_by.get(k, 0) + v / n
        for k, (count, ns) in d["by_program"].items():
            row = by_program.setdefault(k, [0, 0])
            row[0] += count
            row[1] += ns
    # longest first, like each device's own list
    all_gaps = sorted((g for d in per_device.values() for g in d["gaps"]),
                      key=lambda g: (g[0] - g[1], g[0]))

    def programs(table: dict) -> dict:
        return {k: {"count": c, "device_s": _s(ns)}
                for k, (c, ns) in sorted(table.items())}

    return {
        "window_s": _s(end_ns - start_ns),
        "busy_s": mean("busy"), "idle_s": mean("idle"),
        "head_idle_s": mean("head"), "tail_idle_s": mean("tail"),
        # idle between one shard's last device interval and the next
        # one's first, summed over the shard boundaries (0: no shards)
        "boundary_idle_s": mean("boundary"),
        "programs": len(rows),
        "by_program": programs(by_program),
        "idle_by": {k: _s(v) for k, v in sorted(idle_by.items())},
        "gaps": all_gaps[:GAP_ROWS],
        # the caller's: which rows it showed, and the run's clock pair
        "timeline": [], "dropped": 0,
        "clock": {"perf_ns": 0, "unix_ns": 0},
        "devices": {} if n < 2 else {
            dev: {"busy_s": _s(d["busy"]), "idle_s": _s(d["idle"]),
                  "head_idle_s": _s(d["head"]),
                  "tail_idle_s": _s(d["tail"]),
                  "programs": d["programs"],
                  "by_program": programs(d["by_program"]),
                  "idle_by": {k: _s(v)
                              for k, v in sorted(d["idle_by"].items())},
                  "gaps": d["gaps"]}
            for dev, d in per_device.items()},
    }


# ---------------------------------------------------------------- report

def _settled(entries: list, timeout_s: float) -> None:
    """Wait until the watchers have stamped every entry (at report time
    the feeding threads have fetched everything, so this returns at
    once; the timeout only bounds a wedged device)."""
    deadline = time.monotonic() + timeout_s
    with _cond:
        while any(e.complete_ns is None for e in entries):
            left = deadline - time.monotonic()
            if left <= 0:
                return
            _cond.wait(left)


def empty_section() -> dict:
    """The section of a run that recorded nothing."""
    out = account([], {}, 0, 0, None)
    out["clock"] = trace.clock()
    return out


def summary(scope: str = "", window_s: float = 0.0,
            flush_timeout_s: float = FLUSH_TIMEOUT_S) -> dict:
    """The run report's required ``device_time`` section (schema v12)
    for the window of ``window_s`` seconds that ends now, and — as a
    side effect, so the report's metrics snapshot holds them — the
    ``idle.<span>`` timers under ``scope``. ``scope`` keeps a service
    job's report to the submissions made under its metric scope."""
    if not trace.is_active():
        return empty_section()
    end_ns = time.perf_counter_ns()
    start_ns = end_ns - int(window_s * 1e9)
    with _cond:
        entries = [e for e in _entries
                   if (not scope or e.scope == scope)
                   and e.submit_ns >= start_ns]
        evicted = _evicted
    _settled(entries, flush_timeout_s)
    me = trace.current_buf()
    bufs = {id(e.buf): e.buf for e in entries}
    bufs[id(me)] = me
    spans = {key: trace.snapshot_events(b) for key, b in bufs.items()}
    rows = [(e.device, e.kind, e.name, id(e.buf), e.submit_ns,
             e.complete_ns) for e in entries]
    out = account(rows, spans, start_ns, end_ns, id(me))
    out["timeline"] = [
        [e.device, e.kind, e.name, e.buf.name, e.submit_ns,
         end_ns if e.complete_ns is None else e.complete_ns]
        for e in entries[:TIMELINE_ROWS]]
    out["dropped"] = max(0, len(entries) - TIMELINE_ROWS) + evicted
    out["clock"] = trace.clock()
    metrics.replace_timers(IDLE_PREFIX, out["idle_by"], scope)
    # the report's shard_run section reads it beside its counters
    metrics.set_gauge("exec.boundary_idle_s", out["boundary_idle_s"])
    return out
