"""Rows a SQL statement of the persist stage carries: the sum of ``rows``
over the sum of ``statements`` on the window's ``persist.txdb`` and
``persist.clf`` spans (what the txdb and the CLF mirror bound for a
closed ledger, a deleted row's key among them, and in how many
statements). CPython's ``sqlite3`` gives the interpreter lock up once a
statement, so this is rows a hand-over of the lock on the drain thread: a
program that sends a row a statement would read 1. Nothing to read
(None) where the spans carry neither attribute."""

from yardstick import progspans

STAGES = ("persist.txdb", "persist.clf")


def read(sources):
    spans = progspans.complete(sources.get("spans"), STAGES)
    rows = [ev["args"].get("rows") for ev in spans]
    statements = [ev["args"].get("statements") for ev in spans]
    if not spans or None in rows or None in statements or not sum(statements):
        return None
    return sum(rows) / sum(statements)
