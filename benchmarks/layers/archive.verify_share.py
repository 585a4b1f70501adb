"""Share of a shard's seconds (``archive.shard``) inside the offline
contract on its bytes in memory: ``shard.verify`` (the CRC, every
record's content hash through the hash plane, the header chain)."""

from yardstick import archivespans


def read(sources):
    return archivespans.share_of_shard(sources, ("shard.verify",))
