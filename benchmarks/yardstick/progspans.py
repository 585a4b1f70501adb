"""What the per-layer readers of PR 23 share: the program's spans of the
measured window, from whichever ring holds them, and a test for whether
the program under test records them at all (the same reader files run
against a parent commit that does not: there they return None).

A node cell's driver drains its node's ring at every close into
``sources["spans"]`` and starts that list at the window. ``catchup``
hands over no spans: ``replay_range`` records into the process tracer
(``get_tracer()``), which a reader can still read after the run; the
window is then the capture's own ``perf_counter`` interval, placed on
the ring's clock with the epoch the tracer exports.
"""

from __future__ import annotations

_CACHE_KEY = "_progspans.process"


def program_records() -> bool:
    """True when the program under test has the spans and counters PR 23
    added (its tracer module exports the collector probe)."""
    try:
        from stellard_tpu.node import tracer
    except ImportError:
        return False
    return hasattr(tracer, "GC_PROBE")


def complete(events, names) -> list:
    names = set(names)
    return [ev for ev in events or ()
            if ev.get("ph") == "X" and ev["name"] in names]


def seconds(events) -> float:
    return sum(ev["dur"] for ev in events) / 1e6


def full_collections(sources: dict):
    """-> the window's ``gc.collect`` spans of generation 2 (a node
    cell; every full collection is a span, whatever its length), or
    None where the program has no collector probe."""
    if not program_records() or not sources.get("spans"):
        return None
    return [ev for ev in complete(sources["spans"], ("gc.collect",))
            if ev["args"].get("generation") == 2]


def process_window_spans(sources: dict):
    """-> the process tracer's complete spans that START inside the
    capture (a traced run's capture is the measured window), or None
    when there is nothing sound to read: an untraced run, a program
    whose tracer exports no epoch, or a ring that wrapped (a torn tree
    gives no number)."""
    if _CACHE_KEY in sources:
        return sources[_CACHE_KEY]
    sources[_CACHE_KEY] = out = _process_window_spans(sources)
    return out


def _process_window_spans(sources: dict):
    cap = sources.get("capture")
    if cap is None or getattr(cap, "t_start", None) is None \
            or getattr(cap, "t_stop", None) is None:
        return None
    if not program_records():
        return None
    from stellard_tpu.node.tracer import get_tracer

    tracer = get_tracer()
    dump = tracer.chrome_trace()
    other = dump.get("otherData") or {}
    if "epoch_ns" not in other or other.get("dropped"):
        return None
    lo = (cap.t_start * 1e9 - other["epoch_ns"]) / 1000.0
    hi = (cap.t_stop * 1e9 - other["epoch_ns"]) / 1000.0
    return [ev for ev in dump["traceEvents"]
            if ev.get("ph") == "X" and lo <= ev["ts"] < hi]


def replay_spans(sources: dict):
    """-> (the window's ``replay.span`` roots, every window span that
    lies inside one of them), or None."""
    events = process_window_spans(sources)
    if not events:
        return None
    roots = [ev for ev in events if ev["name"] == "replay.span"]
    if not roots:
        return None
    inside = [ev for ev in events if any(
        r["ts"] <= ev["ts"] and ev["ts"] + ev["dur"] <= r["ts"] + r["dur"]
        and ev is not r for r in roots)]
    return roots, inside


def root_attr_share(sources: dict, attr: str):
    """100 x the sum of a ``replay.span`` attribute (seconds) over the
    roots' own length."""
    got = replay_spans(sources)
    if got is None:
        return None
    roots, _inside = got
    values = [r["args"].get(attr) for r in roots]
    total = seconds(roots)
    if None in values or total <= 0:
        return None
    return 100.0 * sum(values) / total
