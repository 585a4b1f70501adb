"""The median length of a consensus round of the measured validator, in
milliseconds: the window's ``consensus.round`` spans (round start to
ACCEPTED: the open phase, the establish phase and the accept). The
protocol's floor is 5,000 (2 s least open, 3 s least consensus).
Nothing to read (None) where the program records no such span."""

from yardstick import progspans, stats


def read(sources):
    rounds = progspans.complete(sources.get("spans"), ("consensus.round",))
    if not rounds:
        return None
    return stats.median([ev["dur"] / 1000.0 for ev in rounds])
