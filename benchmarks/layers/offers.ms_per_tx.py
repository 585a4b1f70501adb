"""Host milliseconds of apply work an OfferCreate costs in the open
window: the self time of its sampled ``open.apply`` and
``open.speculate`` spans (those the program marks ``type`` =
``ttOFFER_CREATE``) plus its ``offer.cross`` spans, which the program
records beneath them (so ``apply.ms_per_tx``, which reads every type's
self time, leaves the book walk out), averaged over the sampled
OfferCreates. Nothing to read (None) where the program's spans carry no
``type``."""

from yardstick.readers import span_self_times

OPEN = ("open.speculate", "open.apply")
CROSS = "offer.cross"
OFFER_CREATE = "ttOFFER_CREATE"


def read(sources):
    events = sources.get("spans") or []
    done = [ev for ev in events if ev.get("ph") == "X"]
    opened = [ev for ev in done if ev["name"] in OPEN
              and ev["args"].get("type") == OFFER_CREATE]
    traces = {ev["args"].get("trace") for ev in opened}
    if not traces:
        return None
    self_us = span_self_times(events)
    parents = {ev["args"]["span"] for ev in opened}
    total = sum(self_us[ev["args"]["span"]] for ev in opened)
    total += sum(self_us[ev["args"]["span"]] for ev in done
                 if ev["name"] == CROSS
                 and ev["args"].get("parent") in parents)
    return total / 1000.0 / len(traces)
