"""Runtime sanitizer (``RACON_TPU_SANITIZE=1``) — the dynamic half of
graftlint (``tools/analysis``).

Five independent detectors, all off unless the flag is set:

- **SWAR shadow execution** — sampled packed-lane aligner chunks re-run
  on the int32 kernels and every output is compared bit-for-bit
  (:func:`should_shadow` / :func:`shadow_compare`).  The static guards
  (``swar.swar_fits`` + the kernels' trace-time assert) make a real
  int16 overflow unreachable *when they are in place*; the shadow path
  is the net that catches the day someone loosens them.  The consensus
  shadow re-dispatches WHOLE launches from their pre-round state
  (``TpuPoaConsensus._dispatch_rounds``), so it follows whatever layout
  the launch used — ragged per-bucket geometry and int8-matmul vote
  groups shadow exactly like padded single-geometry ones (the ragged
  parity suite re-runs under the sanitizer in CI to prove it).
- **Kernel-output canaries** — cheap host-side invariant checks on every
  fetched chunk/group (:func:`check_aligner_canaries`,
  :func:`check_consensus_canaries`): a wrapped int16 lane surfaces as a
  negative or out-of-range score, a poisoned f32 vote surfaces as an
  out-of-alphabet consensus code or an impossible backbone length.
- **jit-retrace budget** — :class:`PhaseRetraceBudget` snapshots the
  total jit cache size across the kernel modules around a pipeline
  phase and flags silent-recompile regressions (a shape leaking into
  the batch geometry recompiles per chunk — historically a 30 s/chunk
  stealth tax).
- **Queue watchdog** — :class:`QueueWatchdog` arms a monitor over the
  pipelined ``Polisher.run()`` bounded queue and dumps every thread's
  stack to stderr when producer/consumer progress stalls past the
  timeout (deadlock triage without attaching a debugger).
- **Lock-order witness** (round 15, the runtime companion of the
  ``lock-discipline``/``blocking-under-lock`` lint rules) — the
  project's named locks (:func:`named_lock`: the exec runner's
  manifest/notes/states locks, the serve scheduler's state lock, the
  heartbeat and index locks) are wrapped in :class:`WitnessedLock`,
  the cross-thread acquisition-order graph is recorded (one stack per
  first-seen edge), and any cycle — a potential deadlock, even one the
  current interleaving never hit — is reported at process exit with
  the stack of every edge on the cycle.  ``obs``-internal locks stay
  plain (the witness publishes through the metrics registry, so the
  registry lock cannot be witnessed without recursing).

Import cost is nil when disabled: numpy only, jax is touched lazily and
only for the retrace scan.
"""

from __future__ import annotations

import atexit
import sys
import threading
import time
import traceback
from typing import Dict, List, Optional, Sequence, Tuple

from . import contracts, flags
from .obs import metrics
from .utils.logger import warn


class SanitizerError(AssertionError):
    """Base of every sanitizer-raised fault (an AssertionError so plain
    test harnesses treat it as a hard failure)."""


class SwarShadowMismatch(SanitizerError):
    """The packed (SWAR) kernel output diverged from the int32 shadow."""


class CanaryError(SanitizerError):
    """A fetched kernel output violated a value-range invariant."""


class RetraceBudgetExceeded(SanitizerError):
    """A pipeline phase compiled more new jit entries than its budget."""


class CompileAfterWarmError(SanitizerError):
    """An XLA compile was observed after the warm path was sealed —
    the resident server's warm-path claim (jobs dispatch into a hot
    jit cache) is violated; the message names the offending
    (function, shape signature) next to the nearest warmed one."""


def enabled() -> bool:
    """Master switch, read from the environment on every call so tests
    can toggle ``RACON_TPU_SANITIZE`` without re-importing."""
    return flags.sanitize_enabled()


def reraise_if_sanitizer(exc: BaseException) -> None:
    """Guard for broad fallback handlers: a sanitizer fault must fail
    the run, never be retried/downgraded like an ordinary kernel fault
    (the Pallas fallback chains catch ``Exception``, and
    :class:`SanitizerError` would otherwise vanish into them)."""
    if isinstance(exc, SanitizerError):
        raise exc


# ------------------------------------------------------ shadow execution

class ShadowSampler:
    """Sampling gate for SWAR shadow execution: chunk 0 always, then
    every ``RACON_TPU_SANITIZE_SAMPLE``-th chunk. One instance per
    engine/run (TpuAligner owns one), so the first chunk of EVERY run
    is checked — a process-global counter would leave short follow-up
    runs unsampled. Thread-safe: chunks launch from pipelined producer
    threads too."""

    def __init__(self):
        self._seen = 0
        self._lock = threading.Lock()

    def should_shadow(self) -> bool:
        if not enabled():
            return False
        n = max(1, flags.get_int("RACON_TPU_SANITIZE_SAMPLE"))
        with self._lock:
            k = self._seen
            self._seen += 1
        return k % n == 0


def shadow_compare(packed_out: Sequence, shadow_out: Sequence,
                   names: Sequence[str], context: str) -> None:
    """Bit-exact comparison of a packed-path output tuple against its
    int32 shadow. Raises :class:`SwarShadowMismatch` naming the first
    diverging output and the lane count that differs."""
    import numpy as np

    for name, a, b in zip(names, packed_out, shadow_out):
        ah, bh = np.asarray(a), np.asarray(b)
        if ah.shape != bh.shape:
            raise SwarShadowMismatch(
                f"{context}: {name} shape {ah.shape} != shadow {bh.shape}")
        if not np.array_equal(ah, bh):
            bad = int(np.count_nonzero(ah != bh))
            raise SwarShadowMismatch(
                f"{context}: {name} diverged from the int32 shadow on "
                f"{bad}/{ah.size} lanes (packed-lane overflow or a "
                f"kernel regression — the bit-exactness contract in "
                f"ops/swar.py is broken)")


# -------------------------------------------------------------- canaries

def check_aligner_canaries(score, fi, fj, *, big: int,
                           context: str) -> None:
    """Host-side invariants on a fetched aligner chunk: scores are
    edit counts in ``[0, big]`` (a wrapped int16 lane goes negative or
    lands between the saturation classes' ceiling and ``big``), walk
    endpoints are non-negative."""
    import numpy as np

    s = np.asarray(score)
    if s.size and (int(s.min()) < 0 or int(s.max()) > big):
        raise CanaryError(
            f"{context}: score outside [0, {big}] "
            f"(min {int(s.min())}, max {int(s.max())}) — packed-lane "
            f"wraparound or kernel corruption")
    for name, v in (("fi", fi), ("fj", fj)):
        vh = np.asarray(v)
        if vh.size and int(vh.min()) < 0:
            raise CanaryError(f"{context}: negative walk endpoint {name}")


def check_consensus_canaries(bcodes, blen, covs, *, Lb: int,
                             context: str) -> None:
    """Host-side invariants on a fetched consensus group: backbone codes
    stay inside the 6-symbol alphabet (a NaN-poisoned f32 vote argmax or
    a corrupted packed fetch shows up as code 6/7), lengths stay inside
    the device buffer, coverage counts are non-negative."""
    import numpy as np

    bc = np.asarray(bcodes)
    if bc.size and int(bc.max()) > 5:
        raise CanaryError(
            f"{context}: backbone code {int(bc.max())} outside the "
            f"ACGTN- alphabet — vote matrix corruption")
    bl = np.asarray(blen)
    if bl.size and (int(bl.min()) < 0 or int(bl.max()) > Lb):
        raise CanaryError(
            f"{context}: backbone length outside [0, {Lb}]")
    cv = np.asarray(covs)
    if cv.size and int(cv.min()) < 0:
        raise CanaryError(f"{context}: negative coverage count")


# -------------------------------------------------------- retrace budget

def retrace_count(prefixes: Sequence[str] = ("racon_tpu",)) -> int:
    """Total live jit-cache entries across modules matching
    ``prefixes`` — the monotone counter :class:`PhaseRetraceBudget`
    differences.  Walks the already-imported modules for jitted
    callables (objects exposing ``_cache_size``), so nothing has to
    register itself. Phase budgets pass their own module scope so the
    background consensus warm-up thread's compiles (``ops.poa``) are
    not attributed to the concurrently-open align phase."""
    total = 0
    prefixes = tuple(prefixes)
    for mod_name, mod in list(sys.modules.items()):
        if not mod_name.startswith(prefixes):
            continue
        for attr in list(vars(mod).values()):
            size = getattr(attr, "_cache_size", None)
            if callable(size):
                try:
                    total += int(size())
                except Exception:  # graftlint: disable=swallowed-exception (foreign jit internals)
                    pass
    return total


class PhaseRetraceBudget:
    """Context manager asserting a pipeline phase compiles at most
    ``budget`` new jit entries (default from
    ``RACON_TPU_SANITIZE_RETRACE_BUDGET``). The delta is **always**
    measured and published to the metrics registry as the gauge
    ``retrace.<phase>`` on a clean exit (the scan walks already-imported
    modules — microseconds per phase — so the run report and the
    shard runner's heartbeat line read compile churn from the one
    registry without paying for shadow execution); the budget itself is
    only *enforced* when the sanitizer is armed.

    ``prefixes`` scopes the counted modules: the polisher's align phase
    counts the aligner kernel modules only, so consensus compiles from
    the concurrent warm-up thread (``warmup_async``) cannot push a
    healthy align phase over budget. (The one-time availability probes
    may still add a few shared-module entries — the default budget has
    ample headroom for those; what the budget hunts is per-chunk
    recompile *growth*.)"""

    def __init__(self, phase: str, budget: Optional[int] = None,
                 prefixes: Sequence[str] = ("racon_tpu",)):
        self.phase = phase
        self.budget = budget
        self.prefixes = tuple(prefixes)
        self._start = 0
        self._armed = False

    def __enter__(self):
        self._armed = enabled()
        self._start = retrace_count(self.prefixes)
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is not None:
            return False
        delta = retrace_count(self.prefixes) - self._start
        # gauge: the MOST RECENT delta per phase (heartbeat/per-shard
        # attribution; the exec runner clears the prefix between
        # shards); counter: the run-lifetime total (run reports — it
        # survives the per-shard clear)
        metrics.set_gauge(f"retrace.{self.phase}", delta)
        metrics.inc(f"retrace_total.{self.phase}", delta)
        if not self._armed:
            return False
        budget = (self.budget if self.budget is not None
                  else flags.get_int("RACON_TPU_SANITIZE_RETRACE_BUDGET"))
        if delta > budget:
            raise RetraceBudgetExceeded(
                f"phase {self.phase!r} compiled {delta} new jit entries "
                f"(budget {budget}) — a shape is leaking into the batch "
                f"geometry and forcing silent recompiles")
        return False


def check_post_warm_compiles(scope=None) -> list:
    """The warm-path assert (round 18): raise
    :class:`CompileAfterWarmError` when the process-wide compile watch
    (:mod:`racon_tpu.obs.compilewatch`) recorded a compile after
    :func:`~racon_tpu.obs.compilewatch.seal` — for the resident server
    that means a job dispatched a geometry neither the warm-up profile
    nor any earlier job compiled.  Armed only under
    ``RACON_TPU_SANITIZE=1`` (the violations are warned and counted
    either way); returns the violation records when not raising, so
    unsanitized callers can surface them."""
    from .obs import compilewatch
    violations = compilewatch.post_warm(scope)
    if violations and enabled():
        raise CompileAfterWarmError(compilewatch.describe(violations))
    return violations


# -------------------------------------------------------- queue watchdog

def dump_all_stacks(reason: str, stream=None) -> None:
    """Write every live thread's stack to ``stream`` (stderr default) —
    the deadlock-triage dump the queue watchdog fires."""
    stream = stream if stream is not None else sys.stderr
    lines = [f"[racon_tpu::sanitize] watchdog: {reason} — "
             f"dumping {threading.active_count()} thread stacks"]
    names = {t.ident: t.name for t in threading.enumerate()}
    for ident, frame in sys._current_frames().items():
        lines.append(f"--- thread {names.get(ident, '?')} ({ident}) ---")
        lines.extend(l.rstrip("\n")
                     for l in traceback.format_stack(frame))
    print("\n".join(lines), file=stream)
    stream.flush()


class QueueWatchdog:
    """Stall monitor for a bounded producer/consumer queue: call
    :meth:`beat` on every put/get; if no beat lands for ``timeout``
    seconds the watchdog dumps all thread stacks (once per stall) and
    counts the firing.

    **Escalation** (round 12): with an ``escalate_cb``, a stall that
    persists past ``timeout * escalate_after`` fires the callback once
    per stall — the pipelined polisher uses it to fail the attempt with
    a ``stall``-class fault (:class:`racon_tpu.faults.StallError`) so
    the shard runner's degradation ladder can retry/quarantine the
    shard instead of the process hanging forever. Without a callback
    the watchdog stays purely passive — it reports, it never kills the
    run."""

    def __init__(self, timeout: float, name: str = "queue",
                 stream=None, escalate_cb=None,
                 escalate_after: float = 2.0):
        self.timeout = float(timeout)
        self.name = name
        self.fired = 0
        self._stream = stream
        self._escalate_cb = escalate_cb
        self._escalate_after = max(1.0, float(escalate_after))
        self._last = time.monotonic()
        self._dumped_for_beat = -1.0
        self._escalated_for_beat = -1.0
        self._stop = threading.Event()
        self.stalled = threading.Event()  # test hook: set on each dump
        self.escalated = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def beat(self) -> None:
        self._last = time.monotonic()
        self.stalled.clear()

    def start(self) -> "QueueWatchdog":
        self._thread = threading.Thread(
            target=self._watch, name=f"racon-watchdog-{self.name}",
            daemon=True)
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join()

    def _watch(self) -> None:
        poll = max(0.01, self.timeout / 4.0)
        while not self._stop.wait(poll):
            last = self._last
            idle = time.monotonic() - last
            if idle > self.timeout and self._dumped_for_beat != last:
                self._dumped_for_beat = last
                self.fired += 1
                warn(f"{self.name} stalled for > {self.timeout:.1f}s")
                dump_all_stacks(
                    f"{self.name} made no progress for "
                    f"{self.timeout:.1f}s", self._stream)
                self.stalled.set()
            if (self._escalate_cb is not None
                    and idle > self.timeout * self._escalate_after
                    and self._escalated_for_beat != last):
                self._escalated_for_beat = last
                metrics.inc("faults.stall_escalations")
                warn(f"{self.name} still stalled after "
                     f"{self.timeout * self._escalate_after:.1f}s — "
                     f"escalating to a stall-class fault")
                try:
                    self._escalate_cb()
                except Exception as e:
                    warn(f"{self.name} stall-escalation callback "
                         f"failed: {type(e).__name__}: {e}")
                self.escalated.set()


def queue_watchdog(name: str,
                   escalate_cb=None) -> Optional[QueueWatchdog]:
    """A started watchdog with the flag-configured timeout when the
    sanitizer is on, else None (callers guard beats with ``if wd:``)."""
    if not enabled():
        return None
    return QueueWatchdog(
        flags.get_float("RACON_TPU_SANITIZE_WATCHDOG_S"), name,
        escalate_cb=escalate_cb).start()


# ----------------------------------------------------- lock-order witness

class LockOrderWitness:
    """Acquisition-order recorder over the project's named locks.

    Every successful acquire of a :class:`WitnessedLock` while the
    thread already holds others adds directed edges ``held -> acquired``
    to a process-wide graph, stamped (on first sight only — steady-state
    cost is a TLS list append) with the acquiring stack.  A cycle in
    that graph is a potential deadlock: two threads can reach the two
    edges' program points concurrently and wait on each other forever,
    whether or not *this* run's interleaving did.  :meth:`report`
    prints every cycle with the first-seen stack of each edge on it —
    wired to process exit via :func:`lock_witness`, and exercised by
    the exec/serve chaos soaks under ``RACON_TPU_SANITIZE=1``.

    Same-name edges are skipped: instances of one lock *class* (per-
    shard keepers, say) share a witness name, and nesting two distinct
    instances is ordered by a different key than the name records."""

    def __init__(self):
        self._mu = threading.Lock()
        # (held name, acquired name) -> first-seen acquiring stack
        self._edges: Dict[Tuple[str, str], str] = {}
        self._tls = threading.local()

    def _held(self) -> List[str]:
        held = getattr(self._tls, "held", None)
        if held is None:
            held = self._tls.held = []
        return held

    def note_acquire(self, name: str) -> None:
        held = self._held()
        if held:
            fresh = [(p, name) for p in held
                     if p != name and (p, name) not in self._edges]
            if fresh:
                stack = "".join(traceback.format_stack()[:-1])
                with self._mu:
                    for edge in fresh:
                        self._edges.setdefault(edge, stack)
        held.append(name)

    def note_release(self, name: str) -> None:
        held = self._held()
        for i in range(len(held) - 1, -1, -1):
            if held[i] == name:
                del held[i]
                return

    def edges(self) -> Dict[Tuple[str, str], str]:
        with self._mu:
            return dict(self._edges)

    def cycles(self) -> List[List[str]]:
        """Every distinct simple cycle in the recorded order graph,
        as name lists (``[a, b]`` means ``a -> b -> a``)."""
        edges = self.edges()
        adj: Dict[str, List[str]] = {}
        for a, b in edges:
            adj.setdefault(a, []).append(b)
        out: List[List[str]] = []
        seen: set = set()

        def dfs(node: str, path: List[str]) -> None:
            if len(path) > 32:   # defensive: graphs here are tiny
                return
            for nxt in adj.get(node, ()):
                if nxt in path:
                    cyc = path[path.index(nxt):]
                    # canonical rotation (not a set): A->B->C->A and its
                    # reverse are DIFFERENT potential deadlocks over the
                    # same locks and must both report
                    k = cyc.index(min(cyc))
                    key = tuple(cyc[k:] + cyc[:k])
                    if key not in seen:
                        seen.add(key)
                        out.append(cyc)
                else:
                    dfs(nxt, path + [nxt])

        for start in sorted(adj):
            dfs(start, [start])
        return out

    def report(self, stream=None) -> int:
        """Print every cycle (with each edge's first-seen acquiring
        stack) to ``stream`` (stderr default); returns the cycle
        count.  Registered at process exit by :func:`lock_witness`."""
        cycles = self.cycles()
        if not cycles:
            return 0
        stream = stream if stream is not None else sys.stderr
        edges = self.edges()
        lines: List[str] = []
        for cyc in cycles:
            ring = " -> ".join(cyc + [cyc[0]])
            lines.append(f"[racon_tpu::sanitize] lock-order witness: "
                         f"cycle {ring} (potential deadlock)")
            for a, b in zip(cyc, cyc[1:] + [cyc[0]]):
                lines.append(f"  edge {a} -> {b} first acquired at:")
                lines.append(edges.get((a, b), "  <stack unavailable>")
                             .rstrip("\n"))
        print("\n".join(lines), file=stream)
        stream.flush()
        metrics.set_gauge("sanitize.lock_order_cycles", len(cycles))
        return len(cycles)


_witness: Optional[LockOrderWitness] = None
_witness_mu = threading.Lock()


def lock_witness() -> LockOrderWitness:
    """The process-wide witness (created on first use; the exit-time
    cycle report is registered exactly once)."""
    global _witness
    with _witness_mu:
        if _witness is None:
            _witness = LockOrderWitness()
            atexit.register(_witness.report)
    return _witness


class WitnessedLock:
    """A ``threading.Lock`` that reports its acquisition order to a
    :class:`LockOrderWitness` under the lock's witness *name* (one name
    per coordination point, shared by instances of the same class).

    Duck-type compatible with ``threading.Condition(lock)``: the
    Condition's default ``_release_save``/``_acquire_restore``/
    ``_is_owned`` fallbacks drive ``acquire``/``release``, so a
    ``cond.wait()`` correctly pops and re-pushes the witness's held
    record around the sleep."""

    def __init__(self, name: str,
                 witness: Optional[LockOrderWitness] = None):
        self.name = name
        self._lock = threading.Lock()
        self._witness = witness if witness is not None else lock_witness()

    def acquire(self, blocking: bool = True, timeout: float = -1) -> bool:
        ok = self._lock.acquire(blocking, timeout)
        if ok:
            self._witness.note_acquire(self.name)
        return ok

    def release(self) -> None:
        self._witness.note_release(self.name)
        self._lock.release()

    def locked(self) -> bool:
        return self._lock.locked()

    def __enter__(self) -> "WitnessedLock":
        self.acquire()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.release()
        return False

    def __repr__(self) -> str:
        return f"<WitnessedLock {self.name!r} at {id(self):#x}>"


def named_lock(name: str):
    """A lock for a named cross-thread coordination point: witnessed
    (:class:`WitnessedLock`) when the sanitizer is armed at creation
    time, a plain ``threading.Lock`` otherwise — the zero-overhead
    default mirrors every other sanitizer half."""
    if enabled():
        return WitnessedLock(name)
    return threading.Lock()


# ------------------------------------------------------------------------
# process-exit contract audit (the runtime half of the round-22 contract
# layer): the static rules prove every EMISSION SITE is registered; this
# audit reports the other direction at the end of a real run — names the
# registry promises that the process never actually produced.


def contract_audit(stream=None) -> Dict[str, List[str]]:
    """Diff the contract registry against what the process really
    emitted: registered metrics no site ever wrote
    (``never_emitted``), and report keys whose backing metric/span
    timer (:data:`racon_tpu.contracts.REPORT_BACKING`) never fired —
    i.e. keys the report carries only because the emitters defaulted
    them (``defaulted_keys``).  Informational, never fatal: a CLI run
    legitimately never touches the serve metrics.  Counts land in the
    ``sanitize.contract_*`` gauges so chaos-soak reports carry them."""
    seen = metrics.seen_names()
    audit: Dict[str, List[str]] = {"never_emitted": [], "defaulted_keys": []}
    if not seen:
        return audit     # nothing ran — everything would be "missing"

    def emitted(name: str) -> bool:
        if name in seen:
            return True
        return any(s.startswith(name + ".") for s in seen)

    audit["never_emitted"] = sorted(
        m for m in contracts.METRICS if m not in seen)
    audit["defaulted_keys"] = sorted(
        key for key, backing in contracts.REPORT_BACKING.items()
        if not emitted(backing))
    stream = stream if stream is not None else sys.stderr
    ne, dk = audit["never_emitted"], audit["defaulted_keys"]
    metrics.set_gauge("sanitize.contract_never_emitted", len(ne))
    metrics.set_gauge("sanitize.contract_defaulted_keys", len(dk))
    if ne:
        print(f"[racon_tpu::sanitize] contract audit: "
              f"{len(ne)} registered metric(s) never emitted this "
              f"process: {', '.join(ne[:12])}"
              + (" ..." if len(ne) > 12 else ""), file=stream)
    if dk:
        print(f"[racon_tpu::sanitize] contract audit: "
              f"{len(dk)} report key(s) backed by silent metrics "
              f"(validator defaults): {', '.join(dk[:12])}"
              + (" ..." if len(dk) > 12 else ""), file=stream)
    stream.flush()
    return audit


def _exit_contract_audit() -> None:
    # armed lazily at exit so a test toggling RACON_TPU_SANITIZE
    # mid-process still gets/loses the audit correctly
    if enabled():
        try:
            contract_audit()
        except Exception:  # graftlint: disable=swallowed-exception (exit path: a dead stderr must not mask the real exit status)
            pass


atexit.register(_exit_contract_audit)
