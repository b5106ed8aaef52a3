"""``python -m racon_tpu.obs gaps RUN_REPORT DEVICE_TRACE`` — lay a run
report's occupancy ledger (``device_time``, :mod:`.device_time`) on the
device trace of the same job.

The device trace is an ``.xplane.pb`` (an operator's ``--profile DIR``)
or the ``{device: {line: [[name, start_ns, dur_ns], ...]}}`` JSON the
benchmark keeps per traced run (``trace_events.json`` next to
``run_report_w0.json``). Its ``start_ns`` is nanoseconds since the
profiler session began, not epoch time, so the two clocks are joined
through the programs both sides saw:

1. the ledger's program rows (``exec``, and a warm-up thread's
   ``warm``) pair, per device and in order, with the ``XLA Modules``
   events of the same program (``jit_`` + the row's name, the
   fingerprint cut off). A row with no event of its name, or
   a count that differs, is an error and not a guess;
2. the clock offset is the smallest ``complete_ns - device_end_ns`` over
   the pairs (a completion is observed after it happened, never
   before); the spread of the residuals is the clock's uncertainty;
3. device programs the ledger never submitted (eager ``jit_*`` helpers)
   are listed with their seconds and left unpaired;
4. every device idle gap over 10 ms (complement of the ``XLA Ops``
   union) is printed with the host spans the report's ``gaps`` rows
   charge it to — gaps *between* programs apart from gaps *inside* one
   program's interval, and for those whether ``Async XLA Ops`` events
   cover them (copies run beside the busy union; no host span will
   explain such a gap).
"""

from __future__ import annotations

import bisect
import json
import re
import statistics
import sys
from typing import Dict, List

MIN_GAP_NS = 10_000_000
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ASYNC_LINE = "Async XLA Ops"
DEVICE_PLANE_RE = re.compile(r"^/device:TPU:(\d+)$")


class GapsError(Exception):
    """The report and the trace cannot be paired."""


def load_trace(path: str) -> dict:
    """``{device ordinal (str): {line: [(name, start_ns, dur_ns)]}}``
    from either kind of file."""
    if path.endswith(".json"):
        with open(path, "r", encoding="utf-8") as fh:
            doc = json.load(fh)
        return {str(dev): {line: [tuple(ev) for ev in evs]
                           for line, evs in lines.items()}
                for dev, lines in doc.items()}
    from jax.profiler import ProfileData
    out: dict = {}
    for plane in ProfileData.from_file(path).planes:
        m = DEVICE_PLANE_RE.match(plane.name)
        if not m:
            continue
        lines = out.setdefault(m.group(1), {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (ev.name, float(ev.start_ns), float(ev.duration_ns))
                for ev in line.events)
    return out


def program_name(event_name: str) -> str:
    """``jit__pallas_align_chain(1234567)`` -> ``jit__pallas_align_chain``
    (the fingerprint changes with every shape and every build)."""
    return event_name.split("(", 1)[0]


def _union(intervals: list) -> tuple:
    """(merged pieces, gaps between them) of ``(start, end)`` pairs."""
    pieces: list = []
    for s, e in sorted(intervals):
        if pieces and s <= pieces[-1][1]:
            pieces[-1][1] = max(pieces[-1][1], e)
        else:
            pieces.append([s, e])
    gaps = [(a[1], b[0]) for a, b in zip(pieces, pieces[1:])]
    return pieces, gaps


def _covered(pieces: list, a: float, b: float) -> float:
    return sum(max(0.0, min(e, b) - max(s, a)) for s, e in pieces)


def pair_programs(timeline: list, events: dict) -> tuple:
    """(pairs ``(device, name, complete_ns, device_end_ns)``, unpaired
    ``{program: [count, seconds]}``); raises :class:`GapsError` where
    the two sides disagree."""
    pairs, unpaired = [], {}
    ledger: Dict[str, Dict[str, list]] = {}
    for device, kind, name, _, _, complete_ns in timeline:
        if kind != "h2d":           # exec, and a warm-up thread's "warm"
            ledger.setdefault(str(device), {}).setdefault(
                "jit_" + name, []).append(complete_ns)
    for device, by_name in sorted(ledger.items()):
        if device not in events:
            raise GapsError(f"the ledger has device {device!r}, the trace "
                            f"has {sorted(events)}")
        traced: Dict[str, list] = {}
        for name, start, dur in sorted(
                events[device].get(MODULES_LINE, []), key=lambda ev: ev[1]):
            traced.setdefault(program_name(name), []).append(start + dur)
        for name, completes in sorted(by_name.items()):
            ends = traced.pop(name, None)
            if ends is None:
                raise GapsError(
                    f"device {device}: the ledger submitted {name[4:]!r} "
                    f"{len(completes)} times, the trace has no program "
                    f"{name!r} (it has {sorted(traced)})")
            if len(ends) != len(completes):
                raise GapsError(
                    f"device {device}: the ledger submitted {name[4:]!r} "
                    f"{len(completes)} times, the trace ran {name!r} "
                    f"{len(ends)} times")
            pairs += [(device, name, c, e)
                      for c, e in zip(completes, ends)]
        for name, ends in traced.items():
            row = unpaired.setdefault(name, [0, 0.0])
            row[0] += len(ends)
    for device, lines in events.items():
        for name, _, dur in lines.get(MODULES_LINE, []):
            if program_name(name) in unpaired:
                unpaired[program_name(name)][1] += dur / 1e9
    return pairs, unpaired


def analyze(report: dict, events: dict) -> dict:
    """Everything the command prints, as data."""
    dt = report.get("device_time")
    if not isinstance(dt, dict):
        raise GapsError("the report has no device_time section "
                        "(schema v12 or later)")
    if dt.get("dropped"):
        raise GapsError(f"the report's timeline left out {dt['dropped']} "
                        f"ledger rows: the programs cannot be paired")
    pairs, unpaired = pair_programs(dt["timeline"], events)
    if not pairs:
        raise GapsError("the ledger holds no exec row to pair")
    diffs = [c - e for _, _, c, e in pairs]
    offset = min(diffs)         # host perf_ns = device ns + offset
    residuals = sorted(d - offset for d in diffs)
    quart = (statistics.quantiles(residuals, n=4)
             if len(residuals) > 1 else [0.0, 0.0, 0.0])
    ledger_gaps = [(a - offset, b - offset, cut)
                   for a, b, cut in dt["gaps"]]     # on the device clock
    out_gaps, groups = [], {}
    for device, lines in sorted(events.items()):
        pieces, gaps = _union([(s, s + d)
                               for _, s, d in lines.get(OPS_LINE, [])])
        mods = sorted(lines.get(MODULES_LINE, []), key=lambda ev: ev[1])
        starts = [s for _, s, _ in mods]
        async_pieces, _ = _union([(s, s + d) for _, s, d in
                                  lines.get(ASYNC_LINE, [])])

        for a, b in gaps:
            # inside one program only if ONE module event holds both
            # ends: two runs of the same program back to back (a warm-up
            # thread's eager helpers, consecutive consensus groups) have
            # a gap between them, not inside either
            i = bisect.bisect_right(starts, a) - 1
            inside = i >= 0 and b <= mods[i][1] + mods[i][2]
            nxt = bisect.bisect_right(starts, a)    # the first to start after
            key = ("inside:" + program_name(mods[i][0]) if inside else
                   "before:" + program_name(mods[nxt][0])
                   if nxt < len(mods) else "before:end-of-trace")
            cut: Dict[str, float] = {}
            named = 0.0
            for c, d, spans in ledger_gaps:
                overlap = min(b, d) - max(a, c)
                if overlap <= 0 or d <= c:
                    continue
                named += overlap
                for span, seconds in spans.items():
                    cut[span] = cut.get(span, 0.0) \
                        + seconds * overlap / (d - c)
            g = groups.setdefault(key, {"seconds": 0.0, "named_s": 0.0,
                                        "gaps": 0, "spans": {}})
            g["seconds"] += (b - a) / 1e9
            g["named_s"] += named / 1e9
            g["gaps"] += 1
            for span, seconds in cut.items():
                g["spans"][span] = g["spans"].get(span, 0.0) + seconds
            if b - a >= MIN_GAP_NS:
                row = {"device": device, "group": key,
                       "start_s": a / 1e9, "seconds": (b - a) / 1e9,
                       "named_s": named / 1e9, "spans": cut}
                if inside:
                    row["async_covered_s"] = _covered(async_pieces,
                                                      a, b) / 1e9
                out_gaps.append(row)
    return {"offset_ns": offset, "pairs": len(pairs),
            "residual_ns": {"q1": quart[0], "median": quart[1],
                            "q3": quart[2], "max": residuals[-1],
                            "spread": quart[2] - quart[0]},
            "unpaired": {k: {"count": c, "seconds": s}
                         for k, (c, s) in sorted(unpaired.items())},
            "groups": groups, "gaps": out_gaps,
            "clock": dt.get("clock", {})}


def _spans_text(spans: dict, total_s: float) -> str:
    top = sorted(spans.items(), key=lambda kv: -kv[1])
    return ", ".join(f"{k} {v:.3f}" for k, v in top if v >= 0.0005) \
        or ("-" if total_s else "")


def render(res: dict) -> str:
    r = res["residual_ns"]
    lines = [
        f"pairs: {res['pairs']} ledger exec rows against XLA Modules "
        f"events",
        f"clock offset: host perf_counter_ns = device ns + "
        f"{res['offset_ns']:.0f}",
        f"residuals (ms): q1 {r['q1'] / 1e6:.3f}  median "
        f"{r['median'] / 1e6:.3f}  q3 {r['q3'] / 1e6:.3f}  max "
        f"{r['max'] / 1e6:.3f}  spread (q3-q1) {r['spread'] / 1e6:.3f}",
        "", "device programs the ledger never submitted (unpaired):"]
    lines += [f"  {k}: {v['count']} runs, {v['seconds']:.6f} s"
              for k, v in res["unpaired"].items()] or ["  none"]
    lines += ["", "idle groups (all gaps; seconds, share named by the "
              "report's gap rows, host spans):"]
    for key, g in sorted(res["groups"].items(),
                         key=lambda kv: -kv[1]["seconds"]):
        share = g["named_s"] / g["seconds"] if g["seconds"] else 0.0
        lines.append(f"  {key}: {g['seconds']:.3f} s in {g['gaps']} gaps, "
                     f"{100 * share:.1f} % named: "
                     f"{_spans_text(g['spans'], g['seconds'])}")
    for title, inside in (("between programs", False),
                          ("inside one program", True)):
        rows = [g for g in res["gaps"]
                if g["group"].startswith("inside:") == inside]
        lines += ["", f"idle gaps over {MIN_GAP_NS / 1e6:.0f} ms, {title} "
                  f"({len(rows)}):"]
        for g in rows:
            extra = (f"  async ops cover {g['async_covered_s']:.3f} s"
                     if inside else "")
            lines.append(
                f"  dev {g['device']} @{g['start_s']:.3f} s  "
                f"{g['seconds']:.3f} s  {g['group']}{extra}  "
                f"[{_spans_text(g['spans'], g['seconds'])}]")
    return "\n".join(lines)


def main(argv: List[str]) -> int:
    as_json = "--json" in argv
    argv = [a for a in argv if a != "--json"]
    if len(argv) != 2:
        print("usage: python -m racon_tpu.obs gaps [--json] RUN_REPORT "
              "DEVICE_TRACE(.xplane.pb | trace_events.json)",
              file=sys.stderr)
        return 2
    try:
        with open(argv[0], "r", encoding="utf-8") as fh:
            report = json.load(fh)
        res = analyze(report, load_trace(argv[1]))
    except (OSError, ValueError, GapsError) as e:
        print(f"gaps: {e}", file=sys.stderr)
        return 1
    print(json.dumps(res, indent=1) if as_json else render(res))
    return 0
