"""Host milliseconds of apply work a validated transaction: the self
time of the open-ledger spans (``open.speculate``, ``open.apply``; the
program samples these per transaction, so they are averaged over the
sampled transactions) plus the self time of ``close.apply`` (one span a
close, over every transaction of the window)."""

from yardstick.readers import span_self_times

OPEN = ("open.speculate", "open.apply")
CLOSE = ("close.apply",)


def read(sources):
    events = sources.get("spans") or []
    txs = (sources.get("counters") or {}).get("txs")
    done = [ev for ev in events if ev.get("ph") == "X"]
    opened = [ev for ev in done if ev["name"] in OPEN]
    closed = [ev for ev in done if ev["name"] in CLOSE]
    sampled = len({ev["args"].get("trace") for ev in opened})
    if not sampled or not closed or not txs:
        return None
    self_us = span_self_times(events)
    open_ms = sum(self_us[ev["args"]["span"]] for ev in opened) / 1000.0
    close_ms = sum(self_us[ev["args"]["span"]] for ev in closed) / 1000.0
    return open_ms / sampled + close_ms / txs
