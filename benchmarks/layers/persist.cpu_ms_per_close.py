"""CPU milliseconds the drain thread ran a persisted ledger: the mean
``cpu_us`` of the window's ``persist.total`` spans, beside
``persist.ms_per_close`` (their wall). The difference is the drain
blocked in I/O (the ``persist.nodestore.fsync`` spans) or waiting for
the interpreter's lock. Nothing to read (None) where a span carries no
``cpu_us``."""

from yardstick import hostcpu, progspans


def read(sources):
    spans = progspans.complete(sources.get("spans"), ("persist.total",))
    total = hostcpu.cpu_ms(spans)
    return None if total is None else total / len(spans)
