"""Share of catch-up's ledgers that took their parent from the chain:
the ledger ``replay_range`` re-closed just before, to its stored hash,
instead of a load of that state from the store (the ``chained`` and
``ledgers`` attributes of the window's ``replay.span`` roots). A span of
n contiguous ledgers that all replay reads ``100 (n - 1) / n``: its
first ledger's parent is the one state it loads. Nothing to read (None)
where the program's ``replay.span`` carries no ``chained``."""

from yardstick import progspans


def read(sources):
    got = progspans.replay_spans(sources)
    if got is None:
        return None
    roots, _inside = got
    chained = [r["args"].get("chained") for r in roots]
    ledgers = sum(r["args"].get("ledgers") or 0 for r in roots)
    if None in chained or ledgers <= 0:
        return None
    return 100.0 * sum(chained) / ledgers
