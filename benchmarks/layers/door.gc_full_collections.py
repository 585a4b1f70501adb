"""Full (generation 2) collections of the interpreter inside the window:
the count of ``gc.collect`` spans with ``generation`` 2."""

from yardstick import progspans


def read(sources):
    spans = progspans.full_collections(sources)
    return None if spans is None else len(spans)
