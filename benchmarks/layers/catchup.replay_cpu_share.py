"""Share of catch-up's spans that the replaying thread ran: 100 x
``cpu_s`` over the length of the window's ``replay.span`` roots. The
rest the thread waited: on the chip (a device batch), the disk (the
loads) or the interpreter's lock. Nothing to read (None) where the
roots carry no ``cpu_s``."""

from yardstick import progspans


def read(sources):
    return progspans.root_attr_share(sources, "cpu_s")
