"""Share of a shard's seconds (``archive.shard``) writing it:
``shard.install`` (the file, its ``fsync``, the store's index)."""

from yardstick import archivespans


def read(sources):
    return archivespans.share_of_shard(sources, ("shard.install",))
