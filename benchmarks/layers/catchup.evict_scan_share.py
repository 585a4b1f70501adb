"""Share of catch-up spent in the hot-node cache's victim scans:
``HotNodeCache.evict_scan_s`` (the list builds of eviction passes 0 and
1, each a walk of the whole table) as ``replay_range`` took its
difference over each span (the ``evict_scan_s`` attribute of
``replay.span``), over the seconds of those spans."""

from yardstick import progspans


def read(sources):
    return progspans.root_attr_share(sources, "evict_scan_s")
