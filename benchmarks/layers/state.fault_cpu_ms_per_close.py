"""CPU milliseconds the faulting threads ran inside the hot cache's
loaders a close: the ``cpu_us`` of the window's ``cache.fault`` spans
(the program clocks one in a few: the clocked spans' sum times all over
clocked) over the window's closes, beside ``state.fault_ms_per_close``
(the cache's own ``fault_s``, wall). The difference is a fault waiting,
for the read or for the interpreter's lock. Nothing to read (None)
where no span carries ``cpu_us``."""

from yardstick import hostcpu, progspans


def read(sources):
    closes = (sources.get("counters") or {}).get("closes")
    total = hostcpu.scaled_cpu_ms(
        progspans.complete(sources.get("spans"), ("cache.fault",)))
    if total is None or not closes:
        return None
    return total / closes
