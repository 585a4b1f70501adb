"""What the per-layer readers of PR 34 share: who had the interpreter.

The host is one interpreter with a dozen threads and one lock, so a
span's wall clock measures its neighbours too. Since PR 34 a span the
program records may carry ``cpu_us``, what its own thread ran inside it
(absent where the span ended on another thread or was not clocked: "not
known", never 0; the program clocks its stages always and, of the spans
a thread opens thousands of times a close, one family in a few, so a
reader of those scales the clocked spans' sum by their share of all),
and every ``close.total`` carries its close CYCLE's differences
(since the last close ended): ``cycle_s`` of wall, ``process_cpu_s``,
and ``cpu_<role>_s`` for the roles the node's threads enter (intake,
drain, seal, door, fanout, net, upkeep). Against a parent whose spans
carry neither, everything here returns None.

The window's FIRST cycle is left out: it began in the warm-up, before
the capture started, and its seconds are not the window's. A window of
one close therefore reads nothing.
"""

from __future__ import annotations

from . import progspans


def cycles(sources: dict):
    """-> the window's ``close.total`` spans that carry a cycle, in
    order, without the first; None where there is none left or one of
    them lacks ``cycle_s``."""
    closes = sorted(progspans.complete(sources.get("spans"),
                                       ("close.total",)),
                    key=lambda ev: ev["ts"])[1:]
    if not closes or any(
            not isinstance(ev["args"].get("cycle_s"), (int, float))
            for ev in closes):
        return None
    return closes


def cycle_ratio(sources: dict, attr: str, scale: float = 1.0):
    """``scale`` x the sum of a cycle attribute (seconds; ``cpu_us`` is
    the span's own CPU clock, in microseconds) over the sum of
    ``cycle_s`` on the window's cycles."""
    closes = cycles(sources)
    if closes is None:
        return None
    if attr == "cpu_us":
        values = [None if ev["args"].get("cpu_us") is None
                  else ev["args"]["cpu_us"] / 1e6 for ev in closes]
    else:
        values = [ev["args"].get(attr) for ev in closes]
    wall = sum(ev["args"]["cycle_s"] for ev in closes)
    if None in values or wall <= 0:
        return None
    return scale * sum(values) / wall


def span_self_cpu(events) -> dict:
    """Self CPU (microseconds) of every complete span that carries
    ``cpu_us``: its own less its SAME-THREAD children's (a child on
    another thread ran on that thread's clock, and one without
    ``cpu_us`` took nothing that is known)."""
    by_id = {}
    children: dict = {}
    for ev in events or ():
        if ev.get("ph") != "X":
            continue
        by_id[ev["args"].get("span")] = ev
        parent = ev["args"].get("parent")
        if parent is not None:
            children.setdefault(parent, []).append(ev)
    out = {}
    for sid, ev in by_id.items():
        own = ev["args"].get("cpu_us")
        if own is None:
            continue
        kids = sum(c["args"].get("cpu_us") or 0
                   for c in children.get(sid, ())
                   if c.get("tid") == ev.get("tid"))
        out[sid] = max(0.0, own - kids)
    return out


def cpu_ms(spans):
    """Milliseconds of ``cpu_us`` over ``spans``; None where there is no
    span or one of them carries none."""
    values = [ev["args"].get("cpu_us") for ev in spans]
    if not values or None in values:
        return None
    return sum(values) / 1000.0


def scaled_cpu_ms(spans):
    """Milliseconds of CPU over ``spans`` where the program clocks one
    in a few: the clocked spans' ``cpu_us`` times all over clocked; None
    where none is clocked."""
    clocked = [ev["args"]["cpu_us"] for ev in spans
               if ev["args"].get("cpu_us") is not None]
    if not clocked:
        return None
    return sum(clocked) / 1000.0 * len(spans) / len(clocked)
