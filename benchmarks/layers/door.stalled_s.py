"""Seconds of the window in which the HTTP door's event loop could not
run: the sum of the ``rpc.loop_lag`` spans (a 50 ms tick on the door's
own loop records one whenever it runs 25 ms or more late). Requests due
in those seconds waited, whatever held the interpreter (a full
collection, a close, a long handler). 0.0 when the program has the probe
and no tick was late."""

from yardstick import progspans


def read(sources):
    if not progspans.program_records() or not sources.get("spans"):
        return None
    return progspans.seconds(
        progspans.complete(sources["spans"], ("rpc.loop_lag",)))
