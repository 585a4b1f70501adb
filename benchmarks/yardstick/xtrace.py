"""The one reduction from a ``jax.profiler`` trace (``.xplane.pb``) to
numbers: device busy time as the union of device-op intervals, device
seconds by program and by operation, and the idle gaps by which of the
harness's own calls the host was in. A capture may be longer than the
measured window (a driver adds a check of the device path behind it):
the drivers mark the window with a host annotation, and what a per-layer
metric reads is cut to it. Read with nothing but
``jax.profiler.ProfileData``.

What a TPU v5e trace looks like (looked at by hand, PR 22; PERF.md has
the notes): one plane ``/device:TPU:<n>`` per chip, whose line ``XLA
Modules`` has one event per executed program, named ``jit_<function>(<
fingerprint>)``, and whose line ``XLA Ops`` has one event per device
operation inside it; host threads are lines of the plane ``/host:CPU``,
and a ``TraceAnnotation`` is an event on its thread's line, on the same
clock.
"""

from __future__ import annotations

import re
from collections import defaultdict

from . import stats

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
OUTSIDE = "outside-harness-calls"
TOP = 10


def program_name(event_name: str) -> str:
    """``jit_verify_kernel(4512…)`` -> ``verify_kernel``."""
    name = event_name.split("(", 1)[0]
    return name[4:] if name.startswith("jit_") else name


OPCODE = re.compile(r"(?:^|[\s)])([a-z][\w\-]*)\(")


def op_name(event_name: str) -> str:
    """An ``XLA Ops`` event is named by its whole HLO text,
    ``%while.51 = (s32[]{...}, ...) while(...)``: keep the instruction's
    own name and its opcode, ``%while.51 while``."""
    head, sep, rest = event_name.partition(" = ")
    if not sep:
        return event_name[:80]
    # the opcode is the first lower-case word followed by "(" after the
    # result shape (a tuple shape is parenthesised itself)
    depth = 0
    for i, ch in enumerate(rest):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
        elif ch == " " and depth == 0:
            rest = rest[i:]
            break
    kind = OPCODE.search(rest)
    return (head + (" " + kind.group(1) if kind else ""))[:80]


def _merged(intervals):
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def reduce_profile(profile, annotations: set[str], chips: int = 1,
                   window: str | None = None) -> dict | None:
    """-> None when the trace holds no device plane (a CPU run), else
    ``busy_s`` (over the whole capture, averaged over the chips used; 0.0
    when the device plane holds no operation), ``span_s`` (first to last
    event of the trace), ``device_ops`` and ``idle_gaps`` (top 10 each, as
    [name, seconds]) and ``annotated_s`` by annotation name, all over the
    whole capture; and, cut to the measured window, ``window_s``,
    ``window_busy_s`` and ``programs`` {name: [seconds, calls]}. The
    window is the interval of the host annotation named ``window`` (the
    trace's own clock); without one it is the whole capture."""
    device_planes, host_events = [], []
    wanted = set(annotations) | ({window} if window else set())
    t_min, t_max = None, None
    for plane in profile.planes:
        is_dev = bool(DEVICE_PLANE.match(plane.name))
        lines = {}
        for line in plane.lines:
            evs = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                   for ev in line.events]
            if evs:
                lo = min(e[1] for e in evs)
                hi = max(e[2] for e in evs)
                t_min = lo if t_min is None else min(t_min, lo)
                t_max = hi if t_max is None else max(t_max, hi)
            if is_dev:
                lines[line.name] = evs
            elif plane.name == HOST_PLANE:
                host_events.extend(e for e in evs if e[0] in wanted)
        if is_dev:
            device_planes.append((plane.name, lines))
    if not device_planes or t_min is None:
        return None
    device_planes.sort()
    marks = [e for e in host_events if e[0] == window]
    host_events = [e for e in host_events if e[0] in annotations]
    if marks:
        _n, w_lo, w_hi = max(marks, key=lambda e: e[2] - e[1])
    else:
        w_lo, w_hi = t_min, t_max

    busy_ns, window_busy_ns = [], []
    programs: dict[str, list] = defaultdict(lambda: [0.0, 0])
    ops: dict[str, float] = defaultdict(float)
    merged_first = None
    for _name, lines in device_planes:
        op_events = lines.get(OPS_LINE)
        if op_events is None:  # no op line: fall back to the programs
            op_events = lines.get(MODULES_LINE, [])
        merged = _merged((s, e) for _n, s, e in op_events)
        if merged_first is None:
            merged_first = merged
        busy_ns.append(sum(e - s for s, e in merged))
        window_busy_ns.append(sum(
            max(0, min(e, w_hi) - max(s, w_lo)) for s, e in merged))
        for n, s, e in lines.get(MODULES_LINE, []):
            if w_lo <= s < w_hi:  # a program belongs where it starts
                slot = programs[program_name(n)]
                slot[0] += (e - s) / 1e9
                slot[1] += 1
        for n, s, e in lines.get(OPS_LINE, []):
            ops[op_name(n)] += (e - s) / 1e9
    used = min(chips, len(busy_ns))
    busy_s = sum(busy_ns[:chips]) / 1e9 / used
    window_busy_s = sum(window_busy_ns[:chips]) / 1e9 / used

    # idle gaps of the first device, by the harness call the host was in
    gaps = []
    cursor = t_min
    for s, e in merged_first or []:
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    if t_max > cursor:
        gaps.append((cursor, t_max))
    idle: dict[str, float] = defaultdict(float)
    host_events.sort(key=lambda e: e[1])
    for gs, ge in gaps:
        covered = []
        for n, s, e in host_events:
            if s >= ge:
                break
            lo, hi = max(s, gs), min(e, ge)
            if hi > lo:
                idle[n] += (hi - lo) / 1e9
                covered.append((lo, hi))
        rest = (ge - gs) - stats.union_seconds(covered)
        if rest > 0:
            idle[OUTSIDE] += rest / 1e9
    annotated: dict[str, float] = defaultdict(float)
    for n, s, e in host_events:
        annotated[n] += (e - s) / 1e9

    def top(d):
        return [[k, v] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]

    return {
        "busy_s": busy_s,
        "span_s": (t_max - t_min) / 1e9,
        "window_s": (w_hi - w_lo) / 1e9,
        "window_busy_s": window_busy_s,
        "window_marked": bool(marks),
        "chips_traced": len(device_planes),
        "programs": {k: v for k, v in programs.items()},
        "device_ops": top(ops),
        "idle_gaps": top(idle),
        "annotated_s": dict(annotated),
    }


def reduce_file(path: str, annotations: set[str], chips: int = 1,
                window: str | None = None):
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path), annotations, chips,
                          window)


def describe(profile, limit: int = 12) -> str:
    """Planes, lines and the commonest event names: for looking at a
    trace by hand before trusting the reduction."""
    out = []
    for plane in profile.planes:
        out.append(f"plane {plane.name!r}")
        for line in plane.lines:
            names: dict[str, int] = defaultdict(int)
            n = 0
            for ev in line.events:
                names[ev.name] += 1
                n += 1
            common = sorted(names.items(), key=lambda kv: -kv[1])[:limit]
            out.append(f"  line {line.name!r}: {n} events; "
                       + ", ".join(f"{k} x{v}" for k, v in common))
    return "\n".join(out)
