"""racon_tpu.obs — the unified observability subsystem.

Four layers over one registry:

- **spans** (:mod:`.trace`) — ``with obs.span("align.dispatch"): ...``
  context-manager tracing threaded through the whole pipeline, exported
  as Chrome trace-event JSON (``--trace FILE`` / ``RACON_TPU_TRACE``,
  load in Perfetto).  Disabled spans cost one branch; spans never
  change output bytes.
- **metrics** (:mod:`.metrics`) — THE process-wide registry of named
  counters/gauges/timers.  Producers (engines, sanitizer, logger,
  polisher queue) publish; the heartbeat, ``consensus_stats``
  and the run report read.
- **run reports** (:mod:`.report`) — schema-versioned
  ``run_report.json`` per CLI/exec run (``--run-report FILE`` /
  ``RACON_TPU_RUN_REPORT``), validated first-party.
- **device time** (:mod:`.device_time`) — the occupancy ledger: what
  the program submitted to each device and when the device was done,
  every idle second charged to the host span of the feeding thread (the
  report's ``device_time`` section, the ``idle.<span>`` timers);
  ``python -m racon_tpu.obs gaps`` lays it on a device trace.

One clock: spans, submissions and completions are stamped with
``time.perf_counter_ns()``; :func:`begin` records the pair
(``perf_counter_ns``, ``time.time_ns``) that the Chrome trace's metadata
and the run report carry.
"""

from __future__ import annotations

from . import compilewatch, device_time, metrics, report, trace
from .trace import span, track  # noqa: F401  (the public span surface)


def begin(trace_path=None, report_path=None) -> None:
    """Mark a run boundary (per-run metrics, compile attribution and
    the occupancy ledger reset) and set span recording — timers and
    rings — to what this run asked for: on when either output was
    requested, off otherwise (a job without ``--run-report`` that
    follows one with it in the same process pays for nothing)."""
    metrics.clear_run()
    # compile attribution resets with the run metrics it rides next to
    # (clear_run drops the compile.* timers/counters) — a second run in
    # the same process must not report the first run's events.  Called
    # once per CLI/exec run; the resident server jobs never pass
    # through here, so the serve warm-path seal is untouched.
    compilewatch.reset()
    device_time.reset()
    if trace_path or report_path:
        trace.new_run()
        trace.activate(tracing=bool(trace_path))
    else:
        trace.deactivate()
