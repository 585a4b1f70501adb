"""Percent of one core that the doors ran: 100 x ``cpu_door_s`` (the
HTTP door answers on its one loop thread; the WebSocket door and the
callback senders beside it) over ``cycle_s`` on the window's
``close.total`` spans. Near 100 the door's thread is saturated; far
below, with a long tail at the generator, it is starved of the
interpreter. The window's first cycle is left out
(``yardstick/hostcpu.py``)."""

from yardstick import hostcpu


def read(sources):
    return hostcpu.cycle_ratio(sources, "cpu_door_s", 100.0)
