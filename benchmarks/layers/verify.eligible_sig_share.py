"""Share of the window's signatures that came in batches large enough
for the chip: those the device verified plus those the cost model priced
back to the host (``why=priced``), over all, from the ``verify.batch``
spans (every batch is one; ``n``, ``routed``, ``why``). The rest stayed
on the host for another reason: a batch under ``min_device_batch``
(``small``), a prewarm still running (``cold``), an arm being measured
(``explore``), a retired device plane (``wedged``)."""

from yardstick import progspans


def read(sources):
    batches = progspans.complete(sources.get("spans"), ("verify.batch",))
    if not batches or any("why" not in ev["args"] for ev in batches):
        return None
    total = sum(ev["args"]["n"] for ev in batches)
    eligible = sum(
        ev["args"]["n"] for ev in batches
        if ev["args"].get("routed") != "cpu"
        or ev["args"]["why"] == "priced")
    return 100.0 * eligible / total if total else None
