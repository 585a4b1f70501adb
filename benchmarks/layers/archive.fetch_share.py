"""Share of a shard's seconds (``archive.shard``: first request of its
file until it counts) spent fetching it: ``archive.fetch``, first request
to last chunk. With the import off the link's reader thread a shard's
fetch runs beside the import of the one before it."""

from yardstick import archivespans


def read(sources):
    return archivespans.share_of_shard(sources, ("archive.fetch",))
