"""Milliseconds a close waited for the persist queue to take it, per
close of the window: the ``persist.backpressure`` spans
(``ClosePipeline._submit`` blocked on a full queue) over the window's
closes. 0.0 when the program records the span and no close waited."""

from yardstick import progspans


def read(sources):
    closes = (sources.get("counters") or {}).get("closes")
    if (not progspans.program_records() or not sources.get("spans")
            or not closes):
        return None
    waits = progspans.complete(sources["spans"], ("persist.backpressure",))
    return progspans.seconds(waits) * 1000.0 / closes
