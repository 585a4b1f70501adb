"""The node ages its own heap.

The interpreter walks its whole old generation whenever the objects
promoted into it since the last full collection exceed a quarter of
what that collection left, so in steady state every survivor is walked
four to five times before the heap has grown enough to leave it alone.
What survives a close is long-lived by nature (the closed ledger's
trees, its transactions and metadata, the hot-node cache's entries) and
dies hundreds of closes later, by reference count: the walks are nearly
pure cost (what they did free in bulk, every open window's speculation
state, is cut at its source now: ``LedgerMaster._retire_open``).

While the process has an owner (a node from ``setup`` to ``stop``, a
replay from entry to exit) the interpreter walks the oldest generation
on its own only as a last resort (its threshold is ``_UNATTENDED``,
which an owner that ages never meets); generations 0 and 1 run as ever.
At the boundaries where the owner knows its survivors are long-lived it
calls ``age()``: one collection of the young generations, then
``gc.freeze()``, which takes every survivor out of the collector's
sight without a walk. An object frozen and dropped later still dies by
reference count. The first step of an owner walks the whole heap once.
A cycle frozen and dropped later waits for the backstop: a pass over
the whole heap whenever the interpreter's allocated blocks have grown
``BACKSTOP_FACTOR`` times since the last pass that walked everything,
so a heap at its plateau never pays one. When the last owner leaves,
the thresholds are what the first found and nothing is frozen.

No option: one policy for every process. A step is a collection of
generation 1 to the ``gc.callbacks`` hooks and a pass over the whole
heap one of generation 2, so ``GC_PROBE`` counts both and records the
long ones as ``gc.collect`` spans like any other.
"""

from __future__ import annotations

import gc
import sys
import threading
import time

__all__ = ["HEAP_AGING", "BACKSTOP_FACTOR"]

# the oldest generation is collected automatically once the middle one
# has been collected this many times since the last pass or freeze. A
# step's ``gc.freeze()`` sets that count back to 0 and a close's worth
# of promotions is 50-80 of them, so an owner that reaches its
# boundaries never meets it; one that reaches none (a standalone node
# nobody closes, serving requests) gets the interpreter's own full
# collection after some seven million promotions and does not keep its
# cycles for ever
_UNATTENDED = 1000

# a pass over the whole heap when the allocated blocks are this many
# times what the last such pass left (``sys.getallocatedblocks()``: a
# millisecond, where ``gc.get_freeze_count()`` is itself a walk of the
# frozen heap, 0.1 us an object). The passes walk a geometric series, so
# an object ever promoted is walked 1/(F-1) to F/(F-1) times by them
# (F=16: 0.07-1.07; the interpreter's own rule: 4-5), and cyclic garbage
# frozen before it died is at most (F-1)/F of the heap before a pass
# takes it. 16 and not 2: a pass over millions of objects holds every
# thread for seconds, a node grows for its first 256 closes, and what
# the steady path still freezes in cycles is a hundred objects a close
# (PERF.md section 6, PR 27)
BACKSTOP_FACTOR = 16


class _HeapAging:
    def __init__(self) -> None:
        # re-entrant: a finaliser run by a pass may stop a node
        self._lock = threading.RLock()
        self.owners = 0
        self._found: tuple = ()
        self._blocks_at_pass = 0  # allocated blocks the last whole pass left
        self.aged = 0
        self.aged_pause_s = 0.0
        self.aged_collected = 0
        self.backstop_passes = 0
        self.backstop_pause_s = 0.0
        self.backstop_collected = 0
        self.frozen_objects = 0
        self._ticks: list = []

    def acquire(self) -> None:
        with self._lock:
            if self.owners == 0:
                self._found = gc.get_threshold()
                gc.set_threshold(self._found[0], self._found[1],
                                 _UNATTENDED)
                self._blocks_at_pass = 0
            self.owners += 1

    def release(self) -> None:
        with self._lock:
            if self.owners == 0:
                return
            self.owners -= 1
            if self.owners == 0:
                gc.set_threshold(*self._found)
                gc.unfreeze()
                self.frozen_objects = 0

    def _tick(self, phase: str, info: dict) -> None:
        self._ticks.append(time.perf_counter())

    def age(self) -> None:
        """One aging step, on the caller's thread (it holds every
        thread for its length: call it off the timed paths)."""
        with self._lock:
            if self.owners == 0:
                return
            whole = self._blocks_at_pass == 0 or (
                sys.getallocatedblocks()
                >= BACKSTOP_FACTOR * self._blocks_at_pass)
            backstop = whole and self._blocks_at_pass > 0
            if backstop:
                gc.unfreeze()
            # timed as GC_PROBE times it, between the collector's own
            # two callbacks: a clock read on this side of the call would
            # count the wait for the interpreter lock as well
            ticks = self._ticks = []
            gc.callbacks.append(self._tick)
            try:
                collected = gc.collect() if whole else gc.collect(1)
            finally:
                gc.callbacks.remove(self._tick)
            dt = ticks[-1] - ticks[-2] if len(ticks) >= 2 else 0.0
            if self.owners:  # (a finaliser may have stopped the last)
                gc.freeze()
            if whole:
                self._blocks_at_pass = sys.getallocatedblocks()
                # (a walk of the frozen heap itself: behind a pass only)
                self.frozen_objects = gc.get_freeze_count()
            if backstop:
                self.backstop_passes += 1
                self.backstop_pause_s += dt
                self.backstop_collected += collected
            else:
                self.aged += 1
                self.aged_pause_s += dt
                self.aged_collected += collected

    def get_json(self) -> dict:
        return {
            "owners": self.owners,
            "aged": self.aged,
            "aged_pause_s": round(self.aged_pause_s, 6),
            "aged_collected": self.aged_collected,
            "frozen_objects": self.frozen_objects,
            "backstop_passes": self.backstop_passes,
            "backstop_pause_s": round(self.backstop_pause_s, 6),
            "backstop_collected": self.backstop_collected,
        }


HEAP_AGING = _HeapAging()
