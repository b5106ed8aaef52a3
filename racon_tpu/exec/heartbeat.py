"""Long-run progress heartbeat: shard i/N, Mbp/s, peak RSS, pack
occupancy, queue health and jit-retrace counters.

A 100 Mbp+ polish runs for hours; the per-stage progress bars only show
the *current* shard. The heartbeat thread prints one self-contained line
every ``RACON_TPU_HEARTBEAT_S`` seconds (0 disables the periodic timer),
and the runner also emits one at every shard completion, so logs from
killed runs always end with an accurate position.

Every telemetry field is read from the ONE process-wide metrics
registry (:mod:`racon_tpu.obs.metrics`): pack occupancy from the
``consensus.*`` counters the device engine publishes per launch,
bounded-queue depth/stall from the ``queue.*`` metrics the pipelined
``Polisher.run()`` publishes, and per-phase jit-retrace deltas from the
``retrace.*`` gauges :class:`racon_tpu.sanitize.PhaseRetraceBudget`
records whether or not the sanitizer is armed — the heartbeat carries
no plumbing of its own, so a shard that suddenly recompiles per chunk
(or a queue that stalls) shows up here long before it shows up in
wall-clock.
"""

from __future__ import annotations

import sys
import threading
import time
from typing import Optional

from .. import flags, sanitize
from ..obs import metrics
from ..obs.metrics import peak_rss_bytes  # noqa: F401  (re-export: the
#   canonical implementation moved into the obs registry module;
#   rampler and the runner keep importing it from here)


def retrace_summary(scope: str = "") -> str:
    """Per-phase jit-retrace deltas as a heartbeat field; ``scope``
    renders one service job's numbers (``metrics.job_scope``)."""
    deltas = metrics.group(scope + "retrace.")
    if not deltas:
        return "-"
    return ",".join(f"{k}={v}" for k, v in sorted(deltas.items()))


def pack_summary_str(scope: str = "") -> str:
    """Real packing occupancy of the consensus pair arenas (round 10),
    the aligner wavefront arenas (round 17), and the overlap chain
    arenas (round 21, ``o:``): occupied/total lanes, mean windows per
    dispatched group and align/chain chunk counts, derived from the
    registry counters (``-`` before any launch); ``scope`` renders one
    service job's numbers."""
    pack = metrics.pack_summary(scope)
    parts = []
    if pack["groups"]:
        parts.append(f"{pack['pack_efficiency']:.2f}eff,"
                     f"{pack['windows_per_group']:.0f}w/g,"
                     f"{pack['groups']}g")
    if pack["align_chunks"]:
        parts.append(f"a:{pack['align_pack_efficiency']:.2f}eff,"
                     f"{pack['align_chunks']}c")
    o_total = metrics.counter(scope + "overlap.lanes_total")
    if o_total:
        o_eff = metrics.counter(scope + "overlap.lanes_occupied") \
            / o_total
        parts.append(f"o:{o_eff:.2f}eff")
    return ";".join(parts) if parts else "-"


def queue_summary_str(scope: str = "") -> str:
    """Bounded init->polish queue health: current depth plus cumulative
    producer/consumer stall seconds (``-`` before any pipelined run);
    ``scope`` renders one service job's numbers."""
    q = metrics.queue_summary(scope)
    if not q["stall_s"] and not q["depth"]:
        return "-"
    return f"d={int(q['depth'])},stall={q['stall_s']:.1f}s"


class Heartbeat:
    """Shared-state progress reporter for the shard runner."""

    def __init__(self, n_shards: int, stream=None,
                 worker: Optional[str] = None):
        self.n_shards = n_shards
        self.worker = worker
        self._stream = stream if stream is not None else sys.stderr
        self._t0 = time.perf_counter()
        self._lock = sanitize.named_lock("exec.heartbeat")
        self._done = 0
        self._mbp = 0.0
        # per-worker Mbp accumulators (round 13): concurrent in-process
        # chip workers used to fold into ONE runner-side accumulator,
        # which made any per-chip rate a fiction — the heartbeat now
        # owns the split so per-chip Mbp/s is truthful
        self._per: dict = {}
        self._phase = "indexing"
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def start(self) -> "Heartbeat":
        interval = flags.get_float("RACON_TPU_HEARTBEAT_S")
        if interval > 0:
            self._thread = threading.Thread(
                target=self._tick, args=(interval,),
                name="racon-heartbeat", daemon=True)
            self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join()

    def update(self, done: Optional[int] = None,
               mbp: Optional[float] = None,
               phase: Optional[str] = None) -> None:
        with self._lock:
            if done is not None:
                self._done = done
            if mbp is not None:
                self._mbp = mbp
            if phase is not None:
                self._phase = phase

    def add_mbp(self, worker_key: Optional[str], mbp: float) -> None:
        """Credit ``mbp`` polished megabases to ``worker_key`` (a chip
        worker id, a remote worker's identity, ...). Negative deltas
        (a re-queued shard's retraction) clamp at zero per key and in
        the total."""
        key = worker_key or "?"
        with self._lock:
            self._per[key] = max(0.0, self._per.get(key, 0.0) + mbp)
            self._mbp = max(0.0, self._mbp + mbp)

    @staticmethod
    def _short(key: str) -> str:
        """Display key: the chip suffix of an in-process worker id
        (``host:123#chip2`` -> ``chip2``), the full id otherwise."""
        return key.rsplit("#", 1)[-1]

    def _per_worker_str(self, dt: float) -> str:
        """``chip0=0.12,chip1=0.11`` Mbp/s rates when more than one
        worker has contributed (empty otherwise — single-worker lines
        stay exactly the round-12 format)."""
        with self._lock:
            per = dict(self._per)
        if len(per) < 2:
            return ""
        rates = ",".join(f"{self._short(k)}={v / dt:.4f}"
                         for k, v in sorted(per.items()))
        return f" per[{rates} Mbp/s]"

    def emit(self, tag: str = "heartbeat") -> None:
        with self._lock:
            done, mbp, phase = self._done, self._mbp, self._phase
        dt = max(1e-9, time.perf_counter() - self._t0)
        who = f" [{self.worker}]" if self.worker else ""
        print(f"[racon_tpu::exec] {tag}{who}: "
              f"shard {done}/{self.n_shards} "
              f"({phase}) {mbp:.2f} Mbp in {dt:.1f}s "
              f"({mbp / dt:.4f} Mbp/s)"
              f"{self._per_worker_str(dt)} "
              f"peak_rss={peak_rss_bytes() >> 20}MB "
              f"pack[{pack_summary_str()}] "
              f"queue[{queue_summary_str()}] "
              f"retrace[{retrace_summary()}]",
              file=self._stream)
        self._stream.flush()

    def _tick(self, interval: float) -> None:
        while not self._stop.wait(interval):
            self.emit()
