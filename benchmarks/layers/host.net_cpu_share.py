"""Percent of one core that the net's bookkeeping ran: 100 x
``cpu_net_s`` (the overlay's readers and writers, the daemon's run loop
and the consensus timer) over ``cycle_s`` on the window's
``close.total`` spans. A networked node closes on one of these threads,
so its closes are in here. The window's first cycle is left out
(``yardstick/hostcpu.py``)."""

from yardstick import hostcpu


def read(sources):
    return hostcpu.cycle_ratio(sources, "cpu_net_s", 100.0)
