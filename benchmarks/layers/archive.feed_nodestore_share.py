"""Share of a shard's seconds (``archive.shard``) feeding its records
to the nodestore: ``archive.feed.nodestore`` (one read of the shard's
records, one batch append a node type)."""

from yardstick import archivespans


def read(sources):
    return archivespans.share_of_shard(sources, ("archive.feed.nodestore",))
