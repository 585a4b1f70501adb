"""Percent of one core that the open window's pipeline ran: 100 x
``cpu_intake_s`` (the job queue's workers, the speculation's workers and
committer, the verify plane's flusher and the host verifier's pool)
over ``cycle_s`` on the window's ``close.total`` spans. The window's
first cycle is left out (``yardstick/hostcpu.py``)."""

from yardstick import hostcpu


def read(sources):
    return hostcpu.cycle_ratio(sources, "cpu_intake_s", 100.0)
