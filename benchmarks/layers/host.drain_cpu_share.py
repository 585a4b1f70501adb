"""Percent of one core that the persist side ran: 100 x ``cpu_drain_s``
(the close pipeline's worker, the node store's writer, segstore's
maintenance) over ``cycle_s`` on the window's ``close.total`` spans:
what persist takes from the interpreter, whatever its wall. The
window's first cycle is left out (``yardstick/hostcpu.py``)."""

from yardstick import hostcpu


def read(sources):
    return hostcpu.cycle_ratio(sources, "cpu_drain_s", 100.0)
