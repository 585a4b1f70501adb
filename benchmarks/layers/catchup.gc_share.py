"""Share of catch-up the interpreter's collector took: the pauses of
every generation (``GC_PROBE``, a ``gc.callbacks`` hook) as
``replay_range`` took their difference over each span (the
``gc_pause_s`` attribute of ``replay.span``), over the seconds of those
spans."""

from yardstick import progspans


def read(sources):
    return progspans.root_attr_share(sources, "gc_pause_s")
