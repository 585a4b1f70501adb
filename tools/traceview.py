#!/usr/bin/env python
"""traceview: fetch, validate, and save a node's `trace_dump`.

The tracing plane (stellard_tpu/node/tracer.py) exports Chrome
trace-event JSON through the `trace_dump` admin RPC. This tool wraps the
three things an operator (and the tier-1 gate) needs around that RPC:

  fetch     POST trace_dump to a node's HTTP RPC door and save the
            trace to a file Perfetto / chrome://tracing loads directly:
                python tools/traceview.py --url http://127.0.0.1:5005 \\
                    -o trace.json
  validate  schema-check an already-saved dump:
                python tools/traceview.py --validate trace.json
            and print who ran: by span name the count, wall ms, CPU ms
            (`cpu_us`, the recording thread's own clock) and the share
            of wall the thread did not run; by role the CPU seconds of
            the node's threads over the dump's close cycles
  smoke     boot an in-process standalone node, flood ~200 transactions
            through the full async pipeline, close ledgers, fetch
            trace_dump over the REAL HTTP door, validate the JSON
            schema AND the causal span tree per transaction
            (submit → verify → close → persist), print the same
            tables, and fail on a same-thread span whose `cpu_us`
            exceeds its `dur` by more than a millisecond (or one tick of
            the thread clock, where the dump says it is coarser):
                python tools/traceview.py --smoke
  merge     fetch trace_dump from N nodes and emit ONE Perfetto file
            with a process lane per node — cross-node trace propagation
            ([trace] propagate=1) makes spans on different nodes share
            trace/parent ids, so a sampled tx renders as one causal
            tree across lanes:
                python tools/traceview.py --merge \\
                    http://127.0.0.1:5005 http://127.0.0.1:5006 \\
                    -o merged.json
  xplane    put a saved dump and the device trace of a `profile`
            capture on ONE timeline. `profile` start/stop write the
            tracer's clock anchor into the XPlane (node/tracer.py
            `anchor()`), the dump carries the tracer's epoch, so every
            span lands on the profiler's clock. The output holds the
            program's spans and the device's `XLA Modules` events (one
            per executed program, not its hundred thousand operations),
            and the idle seconds of the device by the innermost span
            the host was in are printed:
                python tools/traceview.py --validate trace.json \\
                    --xplane /tmp/trace/plugins/profile/*/*.xplane.pb \\
                    -o timeline.json

The schema validator is hand-rolled (no jsonschema dependency) against
the trace-event format's documented requirements; `validate_chrome_trace`,
`validate_span_trees`, `merge_dumps` and `validate_merged_trace` are
importable by tests; so are the clock join (`xplane_anchors`,
`place_spans`, `join_xplane`), `idle_by_span`, and the CPU tables
(`cpu_by_span`, `cpu_overruns`, `cpu_by_role`). The `--xplane` timeline
carries `cpu_us` in a span's `args` as any other attribute.
"""

from __future__ import annotations

import argparse
import heapq
import json
import os
import re
import sys
import urllib.request

# runnable as "python tools/traceview.py" from anywhere: a script in
# tools/ does not get the repo root on sys.path by itself
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# phases from the trace-event format spec (Duration, Complete, Instant,
# Counter, Async, Flow, Sample, Object, Metadata, Memory-dump, Mark,
# Clock-sync, Context)
_KNOWN_PHASES = set("BEXiICbnesftPOoNDMVvRcGT(),")
_INSTANT_SCOPES = {"g", "p", "t"}


def validate_chrome_trace(obj) -> list[str]:
    """-> list of schema problems (empty = valid Chrome trace-event
    JSON). Checks the object form: {"traceEvents": [events...], ...}."""
    problems: list[str] = []
    if not isinstance(obj, dict):
        return [f"top level must be an object, got {type(obj).__name__}"]
    events = obj.get("traceEvents")
    if not isinstance(events, list):
        return ["missing or non-array traceEvents"]
    for i, ev in enumerate(events):
        where = f"traceEvents[{i}]"
        if not isinstance(ev, dict):
            problems.append(f"{where}: event must be an object")
            continue
        ph = ev.get("ph")
        if not isinstance(ph, str) or len(ph) != 1 or ph not in _KNOWN_PHASES:
            problems.append(f"{where}: bad phase {ph!r}")
            continue
        name = ev.get("name")
        if ph != "M" and not isinstance(name, str):
            problems.append(f"{where}: missing/non-string name")
        ts = ev.get("ts")
        if not isinstance(ts, (int, float)) or ts < 0:
            problems.append(f"{where}: missing/negative ts")
        for key in ("pid", "tid"):
            if not isinstance(ev.get(key), int):
                problems.append(f"{where}: missing/non-integer {key}")
        if ph == "X":
            dur = ev.get("dur")
            if not isinstance(dur, (int, float)) or dur < 0:
                problems.append(f"{where}: complete event needs dur >= 0")
        if ph == "i" and ev.get("s") not in _INSTANT_SCOPES:
            problems.append(f"{where}: instant scope must be g/p/t")
        if "cat" in ev and not isinstance(ev["cat"], str):
            problems.append(f"{where}: non-string cat")
        if "args" in ev and not isinstance(ev["args"], dict):
            problems.append(f"{where}: non-object args")
        if len(problems) > 40:
            problems.append("... (truncated)")
            break
    return problems


def validate_span_trees(obj, require_stages=(
    "submit", "verify", "close", "persist",
)) -> list[str]:
    """Check the causal structure the tracing plane promises: every
    transaction trace present in the dump carries events for (at least)
    the given lifecycle stages, and child spans resolve their parent
    ids. A tx trace id is the 64-hex txid; ledger traces are
    "ledger-<seq>"."""
    problems: list[str] = []
    by_trace: dict[str, list[dict]] = {}
    span_ids = set()
    for ev in obj.get("traceEvents", []):
        args = ev.get("args") or {}
        if "span" in args:
            span_ids.add(args["span"])
        trace = args.get("trace")
        if isinstance(trace, str) and len(trace) == 64:
            by_trace.setdefault(trace, []).append(ev)
    if not by_trace:
        return ["no transaction traces in dump"]
    for trace, evs in by_trace.items():
        cats = {ev.get("cat") for ev in evs}
        missing = [c for c in require_stages if c not in cats]
        if missing:
            problems.append(
                f"tx {trace[:16]}: missing stages {missing} (has {sorted(cats)})"
            )
        for ev in evs:
            args = ev.get("args") or {}
            parent = args.get("parent")
            if args.get("remote"):
                # cross-node adoption: the parent span lives in ANOTHER
                # node's ring — unresolvable by design in a single-node
                # dump (the merge validator checks it across dumps)
                continue
            if parent is not None and parent not in span_ids:
                problems.append(
                    f"tx {trace[:16]}: span {args.get('span')} "
                    f"references unknown parent {parent}"
                )
    return problems


# -- cross-node merge (tentpole leg 1) --------------------------------------


def merge_dumps(dumps: list[tuple[str, dict]]) -> dict:
    """N per-node `trace_dump` objects -> ONE Chrome trace with a
    process lane per node. Span/parent ids need NO remapping: the
    tracer folds a 32-bit node tag into the high half of every span id,
    so ids from different nodes never collide and cross-node parent
    links resolve as-is. Timestamps stay per-node (each tracer's epoch
    is process-local) — lanes align structurally, not on a shared
    clock."""
    events: list[dict] = []
    other: dict[str, dict] = {}
    for pid, (label, dump) in enumerate(dumps, start=1):
        events.append({
            "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
            "ts": 0, "args": {"name": label},
        })
        for ev in dump.get("traceEvents", []):
            ev = dict(ev)
            ev["pid"] = pid
            events.append(ev)
        other[label] = dump.get("otherData", {})
    return {
        "traceEvents": events,
        "displayTimeUnit": "ms",
        "otherData": other,
    }


def validate_merged_trace(obj, min_processes: int = 3) -> list[str]:
    """Check what cross-node propagation promises a MERGED dump: at
    least one sampled tx has events in >= min_processes distinct
    process lanes, every cross-node parent link resolves somewhere in
    the merged dump, and each such tx's causal tree is single-rooted
    (exactly one CONNECTED root — a span with children but no parent;
    orphan instants with neither don't count as roots)."""
    problems: list[str] = []
    events = obj.get("traceEvents", [])
    all_spans = set()
    by_trace: dict[str, list[dict]] = {}
    for ev in events:
        if ev.get("ph") == "M":
            continue
        args = ev.get("args") or {}
        if "span" in args:
            all_spans.add(args["span"])
        trace = args.get("trace")
        if isinstance(trace, str) and len(trace) == 64:
            by_trace.setdefault(trace, []).append(ev)
    if not by_trace:
        return ["no transaction traces in merged dump"]
    wide = 0
    for trace, evs in sorted(by_trace.items()):
        pids = {ev.get("pid") for ev in evs}
        spans: dict[int, object] = {}
        for ev in evs:
            a = ev.get("args") or {}
            if a.get("span") is not None:
                spans.setdefault(a["span"], a.get("parent"))
        for s, p in spans.items():
            if p is not None and p not in all_spans:
                problems.append(
                    f"tx {trace[:16]}: span {s} parent {p} unresolved "
                    f"in the merged dump"
                )
        if len(pids) < min_processes:
            continue
        wide += 1
        referenced = {p for p in spans.values() if p is not None}
        roots = sorted(
            s for s, p in spans.items() if p is None and s in referenced
        )
        if not roots:
            problems.append(
                f"tx {trace[:16]}: no connected root span "
                f"({len(pids)} processes)"
            )
        elif len(roots) > 1:
            problems.append(
                f"tx {trace[:16]}: multi-rooted causal tree "
                f"({len(roots)} roots across {len(pids)} processes)"
            )
    if wide == 0:
        problems.append(
            f"no tx trace spans >= {min_processes} processes "
            f"(propagation broken or sampling disjoint)"
        )
    return problems


# -- one timeline with the device trace (--xplane) --------------------------

_DEVICE_PLANE = re.compile(r"^/device:[A-Za-z]+:\d+$")
_MODULES_LINE = "XLA Modules"
_OPS_LINE = "XLA Ops"
NO_SPAN = "span:none"


def xplane_anchors(profile) -> dict[str, list[tuple[int, int]]]:
    """The clock anchors of a profiler trace, by node tag: (pc_ns,
    trace_ns) pairs, the `perf_counter` reading each anchor's name
    carries and where the profiler put it on its own clock."""
    from stellard_tpu.node.tracer import parse_anchor

    out: dict[str, list[tuple[int, int]]] = {}
    for plane in profile.planes:
        if _DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                hit = parse_anchor(ev.name)
                if hit is not None:
                    out.setdefault(hit[0], []).append(
                        (hit[1], int(ev.start_ns)))
    return out


def place_spans(dump: dict, profile) -> tuple[list[dict], dict]:
    """-> (the dump's events with `ts` moved to the profiler's clock, in
    microseconds; what the join rested on). Every event is placed the
    same way, a span recorded after the fact (`complete()`) or ended on
    another thread included: its `ts` is `perf_counter` minus the
    tracer's epoch, the anchor gives `perf_counter` on the trace's
    clock. Raises ValueError when the dump carries no epoch or the
    trace no anchor of this dump's node tag."""
    from stellard_tpu.node.tracer import place_on_trace_clock

    other = dump.get("otherData") or {}
    if "epoch_ns" not in other or "node_tag" not in other:
        raise ValueError("the dump carries no epoch_ns/node_tag "
                         "(written by a tracer that predates the anchor)")
    anchors = xplane_anchors(profile).get(other["node_tag"])
    if not anchors:
        raise ValueError(
            f"the trace holds no clock anchor of node tag "
            f"{other['node_tag']} (was the capture started through the "
            f"`profile` RPC of this node?)")
    place = place_on_trace_clock(anchors, int(other["epoch_ns"]))
    placed = []
    for ev in dump.get("traceEvents", []):
        ev = dict(ev)
        ev["ts"] = place(ev["ts"]) / 1000.0
        placed.append(ev)
    first, last = min(anchors), max(anchors)
    info = {
        "anchors": len(anchors),
        "offset_ns": first[1] - first[0],
        # how far the two clocks moved apart between the first and the
        # last anchor (0 with one anchor)
        "drift_ns": (last[1] - last[0]) - (first[1] - first[0]),
        "anchored_s": (last[0] - first[0]) / 1e9,
    }
    return placed, info


def device_events(profile) -> dict[str, dict[str, list]]:
    """-> {device plane: {line: [(name, start_ns, end_ns)]}} for the
    `XLA Modules` and `XLA Ops` lines."""
    out: dict[str, dict[str, list]] = {}
    for plane in profile.planes:
        if not _DEVICE_PLANE.match(plane.name):
            continue
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            if line.name in (_MODULES_LINE, _OPS_LINE):
                lines[line.name] = [
                    (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                    for ev in line.events]
    return out


def join_xplane(dump: dict, profile) -> tuple[dict, dict]:
    """-> (ONE Chrome trace: the program's spans as process 1 and a
    process per device plane holding its `XLA Modules` events, all on
    the profiler's clock counted from the first event; the join's
    info, with `idle` = `idle_by_span` over the first device)."""
    placed, info = place_spans(dump, profile)
    devices = device_events(profile)
    starts = [ev["ts"] for ev in placed]
    for lines in devices.values():
        starts += [s / 1000.0 for _n, s, _e in lines.get(_MODULES_LINE, [])]
    t0 = min(starts) if starts else 0.0
    # (name, tid, start ns, end ns) of the complete spans, for the idle rows
    quads = [(ev["name"], ev["tid"], ev["ts"] * 1000.0,
              (ev["ts"] + ev["dur"]) * 1000.0)
             for ev in placed if ev.get("ph") == "X"]
    other = dump.get("otherData") or {}
    events = [{"name": "process_name", "ph": "M", "pid": 1, "tid": 0,
               "ts": 0, "args": {"name": f"stellard {other['node_tag']}"}}]
    for ev in placed:
        ev["ts"] -= t0
        ev["pid"] = 1
        events.append(ev)
    for pid, (plane, lines) in enumerate(sorted(devices.items()), start=2):
        events.append({"name": "process_name", "ph": "M", "pid": pid,
                       "tid": 0, "ts": 0, "args": {"name": plane}})
        events.append({"name": "thread_name", "ph": "M", "pid": pid,
                       "tid": 1, "ts": 0, "args": {"name": _MODULES_LINE}})
        for name, s, e in lines.get(_MODULES_LINE, []):
            events.append({"name": name.split("(", 1)[0], "cat": "device",
                           "ph": "X", "ts": s / 1000.0 - t0,
                           "dur": (e - s) / 1000.0, "pid": pid, "tid": 1,
                           "args": {"module": name}})
    if devices:
        first = devices[sorted(devices)[0]]
        busy = [(s, e) for _n, s, e in
                first.get(_OPS_LINE) or first.get(_MODULES_LINE, [])]
        edges = [t for iv in busy + [(q[2], q[3]) for q in quads] for t in iv]
        info["idle"] = idle_by_span(
            quads, busy, min(edges, default=0.0), max(edges, default=0.0))
    return ({"traceEvents": events, "displayTimeUnit": "ms",
             "otherData": {**other, "join": {
                 k: v for k, v in info.items() if k != "idle"}}}, info)


def _merge(intervals) -> list[list[float]]:
    """Sorted union of (start, end) intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _innermost_segments(spans: list) -> list[tuple[float, float, str]]:
    """One thread's spans (name, start, end) -> disjoint (start, end,
    name) segments, each named by the INNERMOST span open there: the one
    that started last (self time, so a nested span is never counted in
    its parent's row too)."""
    bounds = sorted({t for _n, s, e in spans for t in (s, e)})
    by_start = sorted(spans, key=lambda x: x[1])
    heap: list = []  # (-start, end, name): the latest start on top
    out: list[tuple[float, float, str]] = []
    k = 0
    for lo, hi in zip(bounds, bounds[1:]):
        while k < len(by_start) and by_start[k][1] <= lo:
            n, s, e = by_start[k]
            heapq.heappush(heap, (-s, e, n))
            k += 1
        while heap and heap[0][1] <= lo:
            heapq.heappop(heap)
        if heap:
            name = heap[0][2]
            if out and out[-1][2] == name and out[-1][1] == lo:
                out[-1] = (out[-1][0], hi, name)
            else:
                out.append((lo, hi, name))
    return out


def idle_by_span(spans: list, busy: list, lo: float, hi: float) -> dict:
    """The device's idle time inside [lo, hi] by what the host was
    doing: `spans` are (name, tid, start, end) and `busy` the device's
    (start, end) intervals, all on one clock (any unit; the rows come
    back in it). Each instant of an idle gap goes, for every host
    thread, to the innermost span open on that thread (`span:<name>`),
    and to `span:none` where no thread has a span open. Rows of one
    thread never double count; rows of different threads may overlap,
    so the rows can add up to more than `idle` (and without `span:none`
    to less)."""
    merged = _merge((max(s, lo), min(e, hi)) for s, e in busy)
    gaps, cursor = [], lo
    for s, e in merged:
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    if hi > cursor:
        gaps.append((cursor, hi))

    def overlap(segments: list, rows: dict | None) -> float:
        """Two-pointer walk of disjoint sorted segments against the
        gaps; -> the overlap in all, and by name into `rows`."""
        total, g = 0.0, 0
        for seg in segments:
            s, e = seg[0], seg[1]
            while g < len(gaps) and gaps[g][1] <= s:
                g += 1
            j = g
            while j < len(gaps) and gaps[j][0] < e:
                part = min(e, gaps[j][1]) - max(s, gaps[j][0])
                if part > 0:
                    total += part
                    if rows is not None:
                        key = f"span:{seg[2]}"
                        rows[key] = rows.get(key, 0.0) + part
                j += 1
        return total

    rows: dict[str, float] = {}
    by_tid: dict = {}
    for name, tid, s, e in spans:
        if e > s:
            by_tid.setdefault(tid, []).append((name, s, e))
    covered: list[list[float]] = []
    for tid_spans in by_tid.values():
        segments = _innermost_segments(tid_spans)
        overlap(segments, rows)
        covered.extend([s, e] for s, e, _n in segments)
    idle = sum(e - s for s, e in gaps)
    rows[NO_SPAN] = max(0.0, idle - overlap(_merge(covered), None))
    return {"idle": idle, "window": hi - lo, "rows": rows}


# -- who ran: the spans' CPU clocks and the close cycles' roles --------------

# a span's thread cannot run longer than the span took; the two clocks
# are read a few hundred nanoseconds apart and tick differently: a
# millisecond of slack, or one tick of the thread clock where the dump
# says it is coarser (`otherData.cpu_tick_us`: 10 ms on a kernel that
# accounts CPU time by timer tick, where a single span reads 0 or a
# whole tick and only sums mean anything)
CPU_SLACK_US = 1000


def cpu_slack_us(dump) -> int:
    other = (dump.get("otherData") or {}) if isinstance(dump, dict) else {}
    return max(CPU_SLACK_US, int(other.get("cpu_tick_us") or 0))

_ROLE_KEY = re.compile(r"^cpu_([a-z]+)_s$")


def _complete(events) -> list:
    return [ev for ev in events or ()
            if isinstance(ev, dict) and ev.get("ph") == "X"]


def cpu_by_span(events) -> dict[str, dict]:
    """name -> count, wall_ms, and over the spans that carry `cpu_us`
    (ended on the thread that began them, or clocked by their caller):
    clocked, cpu_ms, waited_share = 100 x (1 - CPU / wall of those
    spans). A span without `cpu_us` is "not known", never 0."""
    out: dict[str, dict] = {}
    for ev in _complete(events):
        row = out.setdefault(ev["name"], {
            "count": 0, "wall_ms": 0.0, "clocked": 0, "cpu_ms": 0.0,
            "_clocked_wall_ms": 0.0})
        row["count"] += 1
        row["wall_ms"] += ev["dur"] / 1000.0
        cpu = (ev.get("args") or {}).get("cpu_us")
        if cpu is not None:
            row["clocked"] += 1
            row["cpu_ms"] += cpu / 1000.0
            row["_clocked_wall_ms"] += ev["dur"] / 1000.0
    for row in out.values():
        wall = row.pop("_clocked_wall_ms")
        row["waited_share"] = (
            None if not row["clocked"] or wall <= 0
            else 100.0 * max(0.0, 1.0 - row["cpu_ms"] / wall))
    return out


def cpu_overruns(events, slack_us: int = CPU_SLACK_US) -> list[str]:
    """Spans whose thread ran longer than the span took."""
    return [
        f"{ev['name']}: cpu_us {ev['args']['cpu_us']} > dur {ev['dur']}"
        for ev in _complete(events)
        if (ev.get("args") or {}).get("cpu_us") is not None
        and ev["args"]["cpu_us"] > ev["dur"] + slack_us]


def cpu_by_role(events) -> dict:
    """The dump's close cycles summed (`close.total` spans that carry
    `cycle_s`): cycles, cycle_s, process_cpu_s, close_cpu_s (their own
    `cpu_us`, where the closing thread is in no role: a close that says
    its `closer` is a part of that role's seconds already and is summed
    under `closers` instead) and role -> CPU seconds, `other` among
    them."""
    out = {"cycles": 0, "cycle_s": 0.0, "process_cpu_s": 0.0,
           "close_cpu_s": 0.0, "closers": {}, "roles": {}}
    for ev in _complete(events):
        args = ev.get("args") or {}
        if ev["name"] != "close.total" or "cycle_s" not in args:
            continue
        out["cycles"] += 1
        out["cycle_s"] += args["cycle_s"]
        out["process_cpu_s"] += args.get("process_cpu_s", 0.0)
        own = (args.get("cpu_us") or 0) / 1e6
        if args.get("closer"):
            out["closers"][args["closer"]] = \
                out["closers"].get(args["closer"], 0.0) + own
        else:
            out["close_cpu_s"] += own
        for key, val in args.items():
            m = _ROLE_KEY.match(key)
            if m:
                out["roles"][m.group(1)] = \
                    out["roles"].get(m.group(1), 0.0) + val
    return out


def cpu_of_replay(events) -> dict:
    """The dump's `replay.span` roots summed: spans, wall_s, cpu_s (the
    replaying thread's) and process_cpu_s (every thread's)."""
    out = {"spans": 0, "wall_s": 0.0, "cpu_s": 0.0, "process_cpu_s": 0.0}
    for ev in _complete(events):
        args = ev.get("args") or {}
        if ev["name"] == "replay.span" and "process_cpu_s" in args:
            out["spans"] += 1
            out["wall_s"] += ev["dur"] / 1e6
            out["cpu_s"] += args.get("cpu_s", 0.0)
            out["process_cpu_s"] += args["process_cpu_s"]
    return out


def print_cpu_tables(events, file=None) -> None:
    file = file or sys.stdout
    rows = cpu_by_span(events)
    if rows:
        print(f"{'span':34} {'count':>7} {'wall ms':>12} {'cpu ms':>12} "
              f"{'not run %':>9}", file=file)
    for name in sorted(rows, key=lambda n: -rows[n]["wall_ms"]):
        row = rows[name]
        # one span in a few is clocked: the column is the clocked spans'
        # CPU scaled to all of the name
        cpu = (f"{row['cpu_ms'] * row['count'] / row['clocked']:12.3f}"
               if row["clocked"] else f"{'-':>12}")
        waited = (f"{row['waited_share']:9.1f}"
                  if row["waited_share"] is not None else f"{'-':>9}")
        part = ("" if row["clocked"] in (0, row["count"])
                else f"  (from {row['clocked']} clocked)")
        print(f"{name:34} {row['count']:7d} {row['wall_ms']:12.3f} {cpu} "
              f"{waited}{part}", file=file)
    by_role = cpu_by_role(events)
    if by_role["cycles"]:
        wall = by_role["cycle_s"]
        print(f"close cycles: {by_role['cycles']}, {wall:.3f} s of wall, "
              f"process {by_role['process_cpu_s']:.3f} CPU s "
              f"({by_role['process_cpu_s'] / wall:.2f} cores busy)",
              file=file)
        roles = dict(by_role["roles"], close=by_role["close_cpu_s"])
        for role in sorted(roles, key=lambda r: -roles[r]):
            print(f"  {role:8} {roles[role]:10.3f} CPU s "
                  f"{100.0 * roles[role] / wall:7.1f} % of a core",
                  file=file)
        for role, own in sorted(by_role["closers"].items()):
            print(f"  of {role}: {own:.3f} CPU s are closes that ran on "
                  f"a thread of that role", file=file)
    replay = cpu_of_replay(events)
    if replay["spans"]:
        wall = replay["wall_s"]
        print(f"replay spans: {replay['spans']}, {wall:.3f} s of wall, "
              f"the replaying thread {replay['cpu_s']:.3f} CPU s, process "
              f"{replay['process_cpu_s']:.3f} CPU s "
              f"({replay['process_cpu_s'] / wall:.2f} cores busy)",
              file=file)


def fetch_dump(url: str, reset: bool = False, timeout: float = 30.0) -> dict:
    """POST trace_dump to a node's HTTP RPC door; -> the trace object."""
    body = json.dumps({
        "method": "trace_dump",
        "params": [{"reset": bool(reset)}],
    }).encode()
    req = urllib.request.Request(
        url, data=body, headers={"Content-Type": "application/json"}
    )
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        reply = json.loads(resp.read())
    result = reply.get("result", {})
    if result.get("status") != "success":
        raise RuntimeError(f"trace_dump failed: {result}")
    result.pop("status", None)  # transport envelope, not trace data
    return result


# -- smoke gate (tier-1) ----------------------------------------------------


def run_smoke(n_txs: int = 200, out: str | None = None) -> int:
    """Boot a standalone node, flood `n_txs` payments through the full
    async pipeline, close every 50, fetch trace_dump over the real HTTP
    door, and fail loudly unless (a) the JSON validates against the
    trace-event schema and (b) every transaction trace carries its
    submit/verify/close/persist stages with resolvable parent links."""
    import threading

    from stellard_tpu.node.config import Config
    from stellard_tpu.node.node import Node
    from stellard_tpu.protocol.formats import TxType
    from stellard_tpu.protocol.keys import KeyPair
    from stellard_tpu.protocol.sfields import sfAmount, sfDestination
    from stellard_tpu.protocol.stamount import STAmount
    from stellard_tpu.protocol.sttx import SerializedTransaction

    # sample=1.0: the smoke asserts EVERY tx has its full span tree
    node = Node(Config(rpc_port=0, trace_sample=1.0)).setup().serve()
    try:
        master = KeyPair.from_passphrase("masterpassphrase")
        dests = [
            KeyPair.from_passphrase(f"trace-smoke-{i}").account_id
            for i in range(8)
        ]
        done = threading.Semaphore(0)
        results = []

        def cb(tx, ter, applied):
            results.append((ter, applied))
            done.release()

        for chunk in range(0, n_txs, 50):
            txs = []
            for i in range(chunk, min(chunk + 50, n_txs)):
                tx = SerializedTransaction.build(
                    TxType.ttPAYMENT, master.account_id, 1 + i, 10,
                    {sfAmount: STAmount.from_drops(250_000_000),
                     sfDestination: dests[i % len(dests)]},
                )
                tx.sign(master)
                txs.append(tx)
            for tx in txs:
                node.ops.submit_transaction(tx, cb)
            for _ in txs:
                done.acquire()
            node.ops.accept_ledger()
        if not node.close_pipeline.flush(timeout=60):
            print("trace smoke: close pipeline failed to drain", file=sys.stderr)
            return 1

        url = f"http://127.0.0.1:{node.http_server.port}"
        dump = fetch_dump(url)
    finally:
        node.stop()

    problems = validate_chrome_trace(dump)
    if problems:
        print("trace smoke: SCHEMA INVALID:", file=sys.stderr)
        for p in problems[:20]:
            print(f"  - {p}", file=sys.stderr)
        return 1
    tree_problems = validate_span_trees(dump)
    if tree_problems:
        print("trace smoke: SPAN TREES BROKEN:", file=sys.stderr)
        for p in tree_problems[:20]:
            print(f"  - {p}", file=sys.stderr)
        return 1
    events = dump["traceEvents"]
    print_cpu_tables(events)
    overruns = cpu_overruns(events, cpu_slack_us(dump))
    if overruns:
        print("trace smoke: A THREAD RAN LONGER THAN ITS SPAN:",
              file=sys.stderr)
        for p in overruns[:20]:
            print(f"  - {p}", file=sys.stderr)
        return 1
    traces = {
        (ev.get("args") or {}).get("trace")
        for ev in events
        if len((ev.get("args") or {}).get("trace") or "") == 64
    }
    if out:
        with open(out, "w") as fh:
            json.dump(dump, fh)
    print(
        f"trace smoke OK: {len(events)} events, {len(traces)} tx traces, "
        f"schema valid, span trees causally linked"
    )
    return 0


def run_xplane(dump: dict, xplane_path: str, out: str | None) -> int:
    """Join a dump with the `.xplane.pb` of a `profile` capture: write
    the one timeline, print what the join rested on and the device's
    idle seconds by span."""
    from jax.profiler import ProfileData

    try:
        merged, info = join_xplane(dump, ProfileData.from_file(xplane_path))
    except ValueError as exc:
        print(f"traceview: {exc}", file=sys.stderr)
        return 1
    problems = validate_chrome_trace(merged)
    for p in problems[:20]:
        print(f"  - {p}", file=sys.stderr)
    print(f"{info['anchors']} clock anchor(s) over "
          f"{info['anchored_s']:.3f}s, drift {info['drift_ns'] / 1000.0:.1f} "
          f"us; {len(merged['traceEvents'])} events on one timeline")
    idle = info.get("idle")
    if idle:
        print(f"device idle {idle['idle'] / 1e9:.3f}s of "
              f"{idle['window'] / 1e9:.3f}s, by the innermost span of "
              f"each host thread:")
        for name, ns in sorted(idle["rows"].items(),
                               key=lambda kv: -kv[1])[:20]:
            print(f"  {ns / 1e9:10.3f}s  {name}")
    if out:
        with open(out, "w") as fh:
            json.dump(merged, fh)
        print(f"wrote {out}")
    return 0 if not problems else 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--url", help="node RPC door, e.g. http://127.0.0.1:5005")
    ap.add_argument("--validate", metavar="FILE",
                    help="validate an already-saved dump file")
    ap.add_argument("--smoke", action="store_true",
                    help="in-process end-to-end gate (tier-1)")
    ap.add_argument("--merge", nargs="+", metavar="URL",
                    help="fetch trace_dump from N nodes, emit one "
                         "Perfetto file with a lane per node")
    ap.add_argument("--min-processes", type=int, default=3,
                    help="merge: require >=1 tx spanning this many "
                         "process lanes (default 3)")
    ap.add_argument("--xplane", metavar="FILE",
                    help="with --validate or --url: the .xplane.pb of a "
                         "`profile` capture; -o gets the dump's spans and "
                         "the device's programs on one timeline")
    ap.add_argument("--reset", action="store_true",
                    help="clear the node's ring after dumping")
    ap.add_argument("-o", "--out", help="write the trace JSON here")
    ap.add_argument("-n", type=int, default=200,
                    help="smoke: transactions to flood (default 200)")
    args = ap.parse_args(argv)

    if args.smoke:
        return run_smoke(n_txs=args.n, out=args.out)
    if args.merge:
        dumps = [
            (url, fetch_dump(url, reset=args.reset)) for url in args.merge
        ]
        merged = merge_dumps(dumps)
        problems = validate_chrome_trace(merged)
        problems += validate_merged_trace(
            merged, min_processes=min(args.min_processes, len(dumps))
        )
        for p in problems[:30]:
            print(f"  - {p}", file=sys.stderr)
        if args.out:
            with open(args.out, "w") as fh:
                json.dump(merged, fh)
            print(
                f"wrote {len(merged['traceEvents'])} events from "
                f"{len(dumps)} nodes to {args.out} "
                f"({'valid' if not problems else 'INVALID'})"
            )
        return 0 if not problems else 1
    if args.xplane:
        if args.validate:
            with open(args.validate) as fh:
                dump = json.load(fh)
        elif args.url:
            dump = fetch_dump(args.url, reset=args.reset)
        else:
            ap.error("--xplane needs a dump: --validate FILE or --url URL")
        return run_xplane(dump, args.xplane, args.out)
    if args.validate:
        with open(args.validate) as fh:
            obj = json.load(fh)
        problems = validate_chrome_trace(obj)
        for p in problems:
            print(f"  - {p}", file=sys.stderr)
        if not problems:
            print_cpu_tables(obj["traceEvents"])
            over = cpu_overruns(obj["traceEvents"], cpu_slack_us(obj))
            tick = (obj.get("otherData") or {}).get("cpu_tick_us")
            print(f"smallest non-zero cpu_us {tick} (no step of the thread "
                  f"clock exceeds it); {len(over)} span(s) whose thread "
                  f"ran longer than they took")
        print("valid" if not problems else f"{len(problems)} problems")
        return 0 if not problems else 1
    if args.url:
        dump = fetch_dump(args.url, reset=args.reset)
        problems = validate_chrome_trace(dump)
        for p in problems:
            print(f"  - {p}", file=sys.stderr)
        if args.out:
            with open(args.out, "w") as fh:
                json.dump(dump, fh)
            print(f"wrote {len(dump.get('traceEvents', []))} events to "
                  f"{args.out} ({'valid' if not problems else 'INVALID'})")
        return 0 if not problems else 1
    ap.print_help()
    return 2


if __name__ == "__main__":
    sys.exit(main())
