"""Percent of one core that closing took: 100 x the ``cpu_us`` of the
window's ``close.total`` spans (what the closing thread ran from the
close's start to its stages being noted: apply, seal, opening the next
ledger) over their ``cycle_s``. The window's first cycle is left out
(``yardstick/hostcpu.py``)."""

from yardstick import hostcpu


def read(sources):
    return hostcpu.cycle_ratio(sources, "cpu_us", 100.0)
