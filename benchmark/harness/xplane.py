"""Reduce a ``jax.profiler`` trace (``*.xplane.pb``) to the numbers the
benchmark reports: device busy seconds, the device time of a family of
programs, the operations that took most time, and the idle gaps.

Two steps, so that the arithmetic is testable without a chip:
:func:`extract` turns the file into plain ``(name, start_ns, dur_ns)``
events per device and line (``jax.profiler.ProfileData``, nothing
else), and :func:`reduce_events` does everything after that on those
lists — ``benchmark/tests/data/trace_events.json`` is such a list,
recorded on the chip.

What the v5e's trace looks like (read by hand in PR 24): one plane per
chip, ``/device:TPU:<n>``; its ``XLA Modules`` line has one event per
executed program, named ``jit_<function>(<fingerprint>)``; its
``XLA Ops`` line has one event per HLO operation inside them. Busy time
is the union of the ``XLA Ops`` intervals; a family's device time is the
sum of the ``XLA Modules`` events whose name matches one of the
family's patterns. A trace with no device plane (a CPU rehearsal)
reduces to nothing: no number is made up.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

DEVICE_PLANE_RE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP_N = 10


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def extract(path: str) -> dict:
    """``{device ordinal: {line name: [(event name, start_ns, dur_ns)]}}``
    of every device plane in the file."""
    from jax.profiler import ProfileData
    out: dict = {}
    for plane in ProfileData.from_file(path).planes:
        m = DEVICE_PLANE_RE.match(plane.name)
        if not m:
            continue
        lines = out.setdefault(int(m.group(1)), {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (ev.name, float(ev.start_ns), float(ev.duration_ns))
                for ev in line.events)
    return out


def _union(intervals: list) -> tuple:
    """(summed length of the union, the gaps between its pieces as
    ``(start, end)``) of ``(start, end)`` intervals."""
    busy, gaps = 0.0, []
    cur_s = cur_e = None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                busy += cur_e - cur_s
                gaps.append((cur_e, s))
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        busy += cur_e - cur_s
    return busy, gaps


def program_name(event_name: str) -> str:
    """``jit__pallas_align_chain(1234567)`` -> ``jit__pallas_align_chain``:
    the fingerprint changes with every shape and every build."""
    return event_name.split("(", 1)[0]


def op_name(event_name: str) -> str:
    """The trace names an operation by its whole HLO text, ``%name =
    type op(operands...)``, kilobytes of it: keep ``name``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def short_names(events: dict) -> dict:
    """``events`` with every operation's name cut to :func:`op_name`:
    small enough to keep beside a run, and what the tests' recorded
    trace is an excerpt of."""
    return {dev: {line: [(op_name(n), s, d) for n, s, d in evs]
                  for line, evs in lines.items()}
            for dev, lines in events.items()}


def _self_times(op_events: list) -> list:
    """``(name, start, self_ns)`` per operation: its duration less what
    the operations nested inside it cover (a ``while`` spans its body's
    operations, and would otherwise count them twice)."""
    out, stack = [], []     # stack of [name, start, end, self_ns]

    def close(upto: float) -> None:
        while stack and stack[-1][2] <= upto:
            name, s, _, self_ns = stack.pop()
            out.append((name, s, self_ns))

    for name, s, d in sorted(op_events, key=lambda ev: (ev[1], -ev[2])):
        close(s)
        if stack:
            stack[-1][3] -= min(d, stack[-1][2] - s)
        stack.append([name, s, s + d, d])
    close(float("inf"))
    return out


def reduce_events(events: dict, chips: int) -> dict:
    """``events`` as :func:`extract` returns them. Returns ``{}`` when no
    device plane holds an operation (nothing to read), else

    * ``busy_s``: seconds in which an operation ran, averaged over the
      ``chips`` devices the cell uses (a chip with no event counts as 0)
    * ``busy_s_per_device``: the same, by device ordinal
    * ``device_ops``: the ``TOP_N`` operations by summed self time, over
      all devices, ``["<program>/<operation>", seconds]``
    * ``idle_gaps``: the ``TOP_N`` groups of idle gaps by summed time,
      each named for the program that ended the gap
      (``before:<program>``: what the device was waiting to be given)
      or that it lies inside (``inside:<program>``)
    * ``modules``: ``{program name: seconds}`` summed over devices
    """
    per_dev, ops, gaps_by, modules = {}, {}, {}, {}
    for dev, lines in events.items():
        op_events = lines.get(OPS_LINE, [])
        busy_ns, gaps = _union([(s, s + d) for _, s, d in op_events])
        per_dev[dev] = busy_ns / 1e9
        mods = sorted(lines.get(MODULES_LINE, []), key=lambda ev: ev[1])
        starts = [s for _, s, _ in mods]
        for name, _, d in mods:
            key = program_name(name)
            modules[key] = modules.get(key, 0.0) + d / 1e9

        def running(t: float):
            """The program whose interval holds ``t``, else ``None``."""
            i = bisect.bisect_right(starts, t) - 1
            if i >= 0 and t <= mods[i][1] + mods[i][2]:
                return program_name(mods[i][0])
            return None

        for name, s, self_ns in _self_times(op_events):
            key = f"{running(s) or '?'}/{op_name(name)}"
            ops[key] = ops.get(key, 0.0) + self_ns / 1e9
        for g0, g1 in gaps:
            # inside one program, else before the first program that
            # starts after the gap began (its first operation ends it)
            prog = running(g0)
            nxt = bisect.bisect_right(starts, g0)
            if prog is not None and prog == running(g1):
                key = "inside:" + prog
            elif nxt < len(mods):
                key = "before:" + program_name(mods[nxt][0])
            else:
                key = "before:end-of-trace"
            gaps_by[key] = gaps_by.get(key, 0.0) + (g1 - g0) / 1e9
    if not any(per_dev.values()):
        return {}

    def top(d):
        return [[k, v] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:TOP_N]]

    return {"busy_s": sum(per_dev.values()) / max(1, chips),
            "busy_s_per_device": {str(k): v
                                  for k, v in sorted(per_dev.items())},
            "device_ops": top(ops), "idle_gaps": top(gaps_by),
            "modules": modules}


def family_seconds(modules: dict, patterns: list) -> float | None:
    """Summed device time of the programs whose name matches any of
    ``patterns`` (regular expressions, searched); ``None`` when none
    matches — the reader then has nothing to read."""
    regs = [re.compile(p) for p in patterns]
    hit = [v for k, v in modules.items() if any(r.search(k) for r in regs)]
    return sum(hit) if hit else None
