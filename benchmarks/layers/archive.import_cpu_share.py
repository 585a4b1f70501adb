"""What the importing thread ran inside ``archive.import`` over the
spans' wall seconds (PR 34's thread CPU clock): the rest it waited, for
the interpreter's lock, the disk or the device."""

from yardstick import archivespans


def read(sources):
    return archivespans.import_cpu_share(sources)
