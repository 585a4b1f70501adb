"""Per-layer metric readers. A metric is a file of its own under
``layers/``: ``<metric>.json`` names one of the generic readers below
and its parameters, ``<metric>.py`` holds ``read(sources)`` where a
metric needs code. A reader that finds nothing to read returns None,
and the harness leaves that metric out of the line.

``sources`` is what a driver hands over: ``counters`` (the window's
counter differences), ``samples`` (named lists of readings), ``spans``
(the program's span events, Chrome trace format) and ``trace`` (the
reduced profiler trace, cut to the same window: see ``xtrace``).
"""

from __future__ import annotations

import importlib.util
import json
import os

from . import stats


def _counter(sources: dict, path: str):
    value = (sources.get("counters") or {}).get(path)
    return value if isinstance(value, (int, float)) else None


def counter(sources, spec):
    """The window's difference of one counter."""
    return _counter(sources, spec["path"])


def counter_ratio(sources, spec):
    """``scale`` x sum(num) / sum(den) over the window."""
    num = [_counter(sources, p) for p in spec["num"]]
    den = [_counter(sources, p) for p in spec["den"]]
    if None in num or None in den or sum(den) <= 0:
        return None
    return float(spec.get("scale", 1.0)) * sum(num) / sum(den)


def sample_percentile(sources, spec):
    values = (sources.get("samples") or {}).get(spec["sample"])
    if not values:
        return None
    return stats.percentile(values, float(spec["q"]))


def span_self_times(events: list) -> dict[int, float]:
    """Self time (microseconds) of every complete span: its duration
    minus the part its child spans cover."""
    by_id = {}
    children: dict[int, list] = {}
    for ev in events:
        if ev.get("ph") != "X":
            continue
        sid = ev["args"].get("span")
        by_id[sid] = ev
        parent = ev["args"].get("parent")
        if parent is not None:
            children.setdefault(parent, []).append(ev)
    out = {}
    for sid, ev in by_id.items():
        lo, hi = ev["ts"], ev["ts"] + ev["dur"]
        covered = stats.union_seconds(
            (max(lo, c["ts"]), min(hi, c["ts"] + c["dur"]))
            for c in children.get(sid, ())
        )
        out[sid] = max(0.0, ev["dur"] - covered)
    return out


def span_ms_per(sources, spec):
    """Milliseconds in the named spans over a count: ``per`` is
    ``"span"`` (a mean over the spans themselves) or a counter path."""
    events = sources.get("spans")
    if not events:
        return None
    names = set(spec["spans"])
    picked = [ev for ev in events
              if ev.get("ph") == "X" and ev["name"] in names]
    if not picked:
        return None
    total_us = sum(ev["dur"] for ev in picked)
    per = spec.get("per", "span")
    n = len(picked) if per == "span" else _counter(sources, per)
    if not n:
        return None
    return total_us / 1000.0 / n


def program_us_per(sources, spec):
    """Device microseconds of the programs whose name starts with one of
    ``prefixes`` (from the profiler trace, the runs that started inside
    the measured window) over a counter's difference across the same
    window."""
    trace = sources.get("trace")
    if not trace:
        return None
    prefixes = tuple(spec["prefixes"])
    seconds = sum(v[0] for name, v in trace["programs"].items()
                  if name.startswith(prefixes))
    n = _counter(sources, spec["per"])
    if not n or seconds <= 0:
        return None
    return seconds * 1e6 / n


def trace_idle_share(sources, _spec):
    """1 minus the union of device-op intervals over the measured
    window, both on the trace's clock: 100 when no operation ran on the
    device inside the window."""
    trace = sources.get("trace")
    if not trace or not trace.get("window_s"):
        return None
    return 100.0 * (1.0 - trace["window_busy_s"] / trace["window_s"])


GENERIC = {
    "counter": counter,
    "counter_ratio": counter_ratio,
    "sample_percentile": sample_percentile,
    "span_ms_per": span_ms_per,
    "program_us_per": program_us_per,
    "trace_idle_share": trace_idle_share,
}


def read_metric(path: str, sources: dict):
    """Run the reader file at ``path`` -> a number or None. A metric is
    named with the one end-to-end metric it moves, so the same reading
    in a cell that reports another end-to-end metric is a metric of its
    own: its file is ``{"same_as": "<metric>"}`` and is read through
    that metric's file beside it, not copied."""
    if path.endswith(".json"):
        with open(path) as fh:
            spec = json.load(fh)
        if "same_as" in spec:
            folder = os.path.dirname(path)
            for suffix in (".json", ".py"):
                twin = os.path.join(folder, spec["same_as"] + suffix)
                if os.path.exists(twin) and twin != path:
                    return read_metric(twin, sources)
            raise FileNotFoundError(
                f"{path}: no reader file for {spec['same_as']!r}")
        return GENERIC[spec["reader"]](sources, spec)
    module_spec = importlib.util.spec_from_file_location(
        "layer_reader", path)
    module = importlib.util.module_from_spec(module_spec)
    module_spec.loader.exec_module(module)
    return module.read(sources)
