"""Percent of the window's closed transactions that the open window did
not speculate: 100 x (the sum of ``txs`` less the sum of ``speculated``)
over the sum of ``txs`` on the window's ``close.apply`` spans (``txs``:
the transactions that close applied; ``speculated``: how many of them
had a record of a close-mode dry run to consult). The ledger master
stops speculating, three open windows in four, while its closes throw
nearly every record away (the splice share of the close before, under
one in eight), so this reads 0 where records splice and about 75 on an
exchange; the three ``apply.*_share`` metrics beside it are of the
closes that consulted records. Nothing to read (None) where a span
carries no ``txs``, as on a parent that speculates every window."""

from yardstick import progspans


def read(sources):
    spans = progspans.complete(sources.get("spans"), ("close.apply",))
    txs = [ev["args"].get("txs") for ev in spans]
    if not spans or None in txs or not sum(txs):
        return None
    speculated = sum(ev["args"].get("speculated") or 0 for ev in spans)
    return 100.0 * (sum(txs) - speculated) / sum(txs)
