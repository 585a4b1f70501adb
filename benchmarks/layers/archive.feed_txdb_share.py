"""Share of a shard's seconds (``archive.shard``) rebuilding its rows
of the never-trimming SQL index: ``archive.feed.txdb`` (each
transaction parsed for its type, account and sequence, its metadata
for its result, the batched statements)."""

from yardstick import archivespans


def read(sources):
    return archivespans.share_of_shard(sources, ("archive.feed.txdb",))
