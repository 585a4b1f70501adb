"""Disputed transactions a consensus round of the measured validator:
the mean of ``disputes`` over the window's ``consensus.round`` spans. A
dispute is a transaction some peer's position held and ours did not, or
the reverse: the relay lagging the close. Nothing to read (None) where
the program records no such span."""

from yardstick import progspans


def read(sources):
    rounds = progspans.complete(sources.get("spans"), ("consensus.round",))
    disputes = [ev["args"].get("disputes") for ev in rounds]
    if not rounds or None in disputes:
        return None
    return sum(disputes) / len(rounds)
