"""Seconds of the window inside full (generation 2) collections of the
interpreter: the ``gc.collect`` spans with ``generation`` 2 (every full
collection is a span, whatever its length)."""

from yardstick import progspans


def read(sources):
    spans = progspans.full_collections(sources)
    return None if spans is None else progspans.seconds(spans)
