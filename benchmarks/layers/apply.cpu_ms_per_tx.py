"""CPU milliseconds of apply work a validated transaction:
``apply.ms_per_tx``'s own sum (``open.speculate`` + ``open.apply`` a
sampled transaction, ``close.apply`` a transaction of the window) over
self CPU in place of self time: a span's ``cpu_us`` less its same-thread
children's. The program clocks one open-ledger span in a few, so each
name's clocked spans are scaled by all over clocked. The difference
between the two metrics is what the applying thread waited inside those
spans. Nothing to read (None) where a ``close.apply`` or every span of
an open-ledger name carries no ``cpu_us``."""

from yardstick import hostcpu

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
    self_us = hostcpu.span_self_cpu(events)
    if any(ev["args"]["span"] not in self_us for ev in closed):
        return None
    open_us = 0.0
    for name in OPEN:
        spans = [ev for ev in opened if ev["name"] == name]
        clocked = [self_us[ev["args"]["span"]] for ev in spans
                   if ev["args"]["span"] in self_us]
        if spans and not clocked:
            return None
        if clocked:
            open_us += sum(clocked) * len(spans) / len(clocked)
    close_ms = sum(self_us[ev["args"]["span"]] for ev in closed) / 1000.0
    return open_us / 1000.0 / sampled + close_ms / txs
