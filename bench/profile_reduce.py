"""From a profiler trace (``.xplane.pb``) to device time, by program.

The device's planes are named ``/device:TPU:<n>``. Each has a line of
XLA programs ("XLA Modules") and a line of their operations ("XLA
Ops"); busy time is the union of the operations' intervals. The host's
plane carries the benchmark's own zero-length markers: ``bench.window``
where the traced part of the window opens and where it closes, and
``bench.call.start`` / ``bench.call.end`` around each call into the
serving entry (markers, because the profiler may stop inside a call,
and a span still open then is never written). Device and host events share one clock in the
trace, to about a millisecond (in a trace of a TPU v5e the device's
events sat 1.1 ms early against the host's), so an idle gap of the
device can be put down to what the host was doing: inside a call or
between calls, after which program.
"""
from __future__ import annotations

import collections
import re

WINDOW = "bench.window"
CALL_START, CALL_END = "bench.call.start", "bench.call.end"


def union(intervals: list) -> list:
    """Sorted, merged (start, end) intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: list, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(busy: list, lo: float, hi: float) -> list:
    """Idle (start, end) intervals of ``[lo, hi]`` between merged
    busy intervals."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def call_spans(marks: list, lo: float, hi: float) -> list:
    """(start, end) of each call from its start and end markers; a call
    open when the window opens or closes runs to that edge."""
    out, opened = [], lo
    for n, s, _ in sorted(marks, key=lambda m: m[1]):
        if n == CALL_START:
            opened = s
        elif n == CALL_END and opened is not None:
            out.append((opened, s))
            opened = None
    if opened is not None:
        out.append((opened, hi))
    return union(out)


def _program(name: str) -> str:
    """'jit_decode_megastep_rows(123)' -> 'jit_decode_megastep_rows'."""
    return re.sub(r"\(\d+\)$", "", name)


def _op(name: str) -> str:
    """'%fusion.3 = bf16[..] fusion(..), kind=kOutput, ..' ->
    'fusion.3 kOutput': the instruction and its kind, without shapes."""
    head = name.split(" = ")[0].lstrip("%")
    kind = re.search(r"kind=(\w+)", name)
    return f"{head} {kind.group(1)}" if kind else head


def _events(plane, line_name: str) -> list:
    for line in plane.lines:
        if line.name == line_name:
            return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                    for ev in line.events]
    return []


def reduce(planes) -> dict:
    """``planes``: ``jax.profiler.ProfileData(...).planes``.

    Returns busy_s and window_s (averaged over the devices that ran
    anything), program_s {program: device seconds} and
    program_launches, and the breakdown lists: device_ops (the ten
    operations that took the most time) and idle_gaps (idle time by
    what the host was doing, ten largest)."""
    devices, host_ann = [], []
    for plane in planes:
        if plane.name.startswith("/device:TPU"):
            devices.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host_ann += [(ev.name, ev.start_ns,
                              ev.start_ns + ev.duration_ns)
                             for ev in line.events
                             if ev.name in (WINDOW, CALL_START,
                                            CALL_END)]
    windows = [(s, e) for n, s, e in host_ann if n == WINDOW]
    if not windows:
        raise ValueError(f"no {WINDOW!r} marker in the trace")
    lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    calls = call_spans(host_ann, lo, hi)

    busy_total, used = 0.0, 0
    program_s: collections.Counter = collections.Counter()
    launches: collections.Counter = collections.Counter()
    op_s: collections.Counter = collections.Counter()
    idle: collections.Counter = collections.Counter()
    for dev in devices:
        mods = [(n, s, e) for n, s, e in _events(dev, "XLA Modules")
                if e > lo and s < hi]
        ops = [(n, s, e) for n, s, e in _events(dev, "XLA Ops")
               if e > lo and s < hi] or mods
        if not ops:
            continue
        used += 1
        busy = union(clip([(s, e) for _, s, e in ops], lo, hi))
        busy_total += sum(e - s for s, e in busy)
        for n, s, e in mods:
            program_s[_program(n)] += (min(e, hi) - max(s, lo)) / 1e9
            launches[_program(n)] += 1
        starts = sorted((s, e, _program(n)) for n, s, e in mods)
        k = 0
        for n, s, e in sorted(ops, key=lambda o: o[1]):
            while k + 1 < len(starts) and starts[k + 1][0] <= s:
                k += 1
            owner = starts[k][2] if starts and starts[k][0] <= s \
                < starts[k][1] else "?"
            op_s[f"{owner}/{_op(n)}"] += (min(e, hi) - max(s, lo)) / 1e9
        ends = sorted((e, _program(n)) for n, _, e in mods)
        j, last = 0, "window start"
        cuts = sorted({t for c in calls for t in c})
        for s, e in gaps(busy, lo, hi):
            while j < len(ends) and ends[j][0] <= s:
                last = ends[j][1]
                j += 1
            # a gap that spans a call's start or end is split there
            edges = [s] + [t for t in cuts if s < t < e] + [e]
            for a, b in zip(edges, edges[1:]):
                mid = (a + b) / 2
                inside = any(cs <= mid <= ce for cs, ce in calls)
                where = "in call" if inside else "between calls"
                idle[f"{where}, after {last}"] += (b - a) / 1e9
    used = max(used, 1)
    return {
        "busy_s": busy_total / 1e9 / used,
        "window_s": (hi - lo) / 1e9,
        "devices": used,
        "program_s": {k: v / used for k, v in program_s.items()},
        "program_launches": dict(launches),
        "device_ops": [[n, v / used] for n, v in op_s.most_common(10)],
        "idle_gaps": [[n, v / used] for n, v in idle.most_common(10)],
    }


def read(path: str) -> dict:
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(path).planes)
