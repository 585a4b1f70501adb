"""Share of a closed ledger's transactions for which the streams built a
message: 100 x the sum of ``built`` over the sum of ``txs`` on the
window's ``subs.publish`` spans (one a close, recorded by
``SubscriptionManager._pub_ledger``). 0 is the interest summary engaged
on a node nobody listens to: no transaction of a closed ledger was
parsed or rendered for the streams. 100 is a node with a
``transactions`` listener, which is due every message, or the eager
path come back: a message for every transaction, whoever listens.
Between the two, account listeners: a message for what touches a
listened account. Nothing to read (None) where the program records no
such span or the window's ledgers were empty."""

from yardstick import progspans


def read(sources):
    spans = progspans.complete(sources.get("spans"), ("subs.publish",))
    built = [ev["args"].get("built") for ev in spans]
    txs = [ev["args"].get("txs") for ev in spans]
    if not spans or None in built or None in txs or not sum(txs):
        return None
    return 100.0 * sum(built) / sum(txs)
