"""Share of the window in which a shard was being fetched WHILE another
was being imported (``archive.fetch`` and ``archive.import`` open at
once): 0 where the import runs on the link's reader thread."""

from yardstick import archivespans


def read(sources):
    return archivespans.overlap_share(sources)
