"""Cores the node's process kept busy: the sum of ``process_cpu_s`` over
the sum of ``cycle_s`` on the window's ``close.total`` spans. 1.0 is one
saturated interpreter on a host of 13 cores; what is above it ran
outside the interpreter's lock (SQLite, ``fsync``, the native
libraries, the XLA runtime's threads). The window's first cycle began
in the warm-up and is left out (``yardstick/hostcpu.py``)."""

from yardstick import hostcpu


def read(sources):
    return hostcpu.cycle_ratio(sources, "process_cpu_s")
