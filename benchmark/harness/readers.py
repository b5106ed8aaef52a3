"""The per-layer metrics' reader kinds. A metric is a data file
(``benchmark/metrics/<name>.json``) that names one of these kinds and
gives its parameters; a reader that finds nothing to read returns
``None`` and the harness leaves the metric out of the line.

What a reader may look at (``ctx``):

* ``warmup``   the run report of set-up's warm-up job (the one that
  compiles or loads every program)
* ``traced``   the run report of the traced window job
* ``window``   the run reports of every window job
* ``modules``  ``{program name: device seconds}`` of the traced job, from
  the benchmark's own profiler bracket (absent without a device trace)
* ``run``      what the harness itself measured in this run, by name
  (``residual_ppm``: the polished FASTA's distance to the truth)

Kinds:

``report-span-sum``       ``spans``: span timer names summed from
                          ``metrics.timers`` of the ``of`` report
``report-counter-ratio``  ``numerator`` / ``denominator``: counters of
                          the ``of`` report, times ``scale``
``report-value``          ``path``: keys into the ``of`` report; ``of``
                          may also be ``window-median``
``xplane-family-time``    ``patterns``: regular expressions over the
                          trace's program names; summed device seconds
``run-value``             ``key``: a name in ``run``
"""

from __future__ import annotations

import statistics

from . import xplane


def _dig(report: dict, path: list):
    for key in path:
        if not isinstance(report, dict) or key not in report:
            return None
        report = report[key]
    return report


def _report(ctx: dict, of: str):
    if of not in ("warmup", "traced"):
        raise ValueError(f"a reader's 'of' is 'warmup' or 'traced', "
                         f"not {of!r}")
    return ctx.get(of)


def span_sum(params: dict, ctx: dict):
    rep = _report(ctx, params.get("of", "traced"))
    timers = _dig(rep, ["metrics", "timers"]) or {}
    found = [timers[s] for s in params["spans"] if s in timers]
    return sum(found) if found else None


def counter_ratio(params: dict, ctx: dict):
    rep = _report(ctx, params.get("of", "traced"))
    counters = _dig(rep, ["metrics", "counters"]) or {}
    num = counters.get(params["numerator"])
    den = counters.get(params["denominator"])
    if num is None or not den:
        return None
    return params.get("scale", 1) * num / den


def report_value(params: dict, ctx: dict):
    of = params.get("of", "traced")
    if of == "window-median":
        vals = [_dig(r, params["path"]) for r in ctx.get("window", [])]
        vals = [v for v in vals if v is not None]
        return statistics.median(vals) if vals else None
    return _dig(_report(ctx, of), params["path"])


def family_time(params: dict, ctx: dict):
    if not ctx.get("modules"):
        return None
    return xplane.family_seconds(ctx["modules"], params["patterns"])


def run_value(params: dict, ctx: dict):
    return (ctx.get("run") or {}).get(params["key"])


KINDS = {"report-span-sum": span_sum,
         "report-counter-ratio": counter_ratio,
         "report-value": report_value,
         "xplane-family-time": family_time,
         "run-value": run_value}


def read_metric(mfile: dict, ctx: dict):
    """The metric's value, or ``None`` when its reader finds nothing."""
    kind = mfile.get("reader")
    if kind not in KINDS:
        raise ValueError(f"metric {mfile.get('name')!r}: unknown reader "
                         f"{kind!r} (known: {sorted(KINDS)})")
    value = KINDS[kind](mfile, ctx)
    return None if value is None else float(value)
