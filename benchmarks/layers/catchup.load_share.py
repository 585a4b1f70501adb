"""Share of catch-up spent loading ledgers from the store: the seconds
inside the ``ledger.load`` spans of the window's ``replay.span`` trees
(``Ledger.load`` itself: the targets and each ledger's parent) over the
seconds of those ``replay.span`` roots. The inside view of what
``catchup.reapply_share`` and ``catchup.verify_share`` leave over."""

from yardstick import progspans


def read(sources):
    got = progspans.replay_spans(sources)
    if got is None:
        return None
    roots, inside = got
    loads = progspans.complete(inside, ("ledger.load",))
    total = progspans.seconds(roots)
    if not loads or total <= 0:
        return None
    return 100.0 * progspans.seconds(loads) / total
