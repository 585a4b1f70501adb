"""The node ages its own heap (ISSUE 27, node/heapaging.py).

While a node or a replay owns the process, the interpreter never walks
the old generation on its own; the owner walks each survivor once at
its boundaries and freezes it, and a geometric backstop takes the
cycles that were frozen alive. When the last owner leaves, the process
is as it was found.
"""

import gc
import os
import shutil
import sys
import weakref

import pytest
from test_lazy_resume import (  # noqa: F401  (store: a fixture)
    CLOSES, INI, drive, outcomes_ok, plain_hashes, store,
)
from test_trace_clock import chain  # noqa: F401  (a fixture)
from yardstick import nodedrive, prepared

from stellard_tpu.node import heapaging
from stellard_tpu.node.config import Config
from stellard_tpu.node.heapaging import HEAP_AGING
from stellard_tpu.node.ledgertools import replay_ledger, replay_range
from stellard_tpu.node.node import Node
from stellard_tpu.node.tracer import GC_PROBE, Tracer
from stellard_tpu.rpc.handlers import Context, Role, dispatch
from stellard_tpu.state.shamap import inner_node_cache

CPU_INI = "[signature_backend]\ntype=cpu\n[hash_backend]\ntype=cpu\n"


@pytest.fixture(autouse=True)
def as_found():
    """Every test here starts from, and must leave, a process nobody
    owns: the thresholds as found, nothing frozen."""
    assert HEAP_AGING.owners == 0 and nothing_frozen()
    found = gc.get_threshold()
    yield found
    left = HEAP_AGING.owners
    while HEAP_AGING.owners:  # a failed test must not fail the rest
        HEAP_AGING.release()
    assert left == 0
    assert gc.get_threshold() == found
    assert nothing_frozen()


def nothing_frozen():
    """CPython 3.12 keeps a few hundred objects of its own in the
    permanent generation (375 here: there at start, gone after
    ``gc.unfreeze()``, back after the next full collection); a frozen
    heap is a hundred thousand and more."""
    return gc.get_freeze_count() < 1000


def counters():
    return dict(HEAP_AGING.get_json())


def churn(n):
    """``n`` tracked objects that survive: promotions into the old
    generation, as a close's trees and transactions are."""
    return [[] for _ in range(n)]


class Collections:
    """Counts collections by generation through a hook of its own."""

    def __init__(self):
        self.by_gen = [0, 0, 0]

    def __call__(self, phase, info):
        if phase == "stop":
            self.by_gen[info["generation"]] += 1

    def __enter__(self):
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self)


def own_with_two_nodes(order):
    a = Node(Config.from_ini(CPU_INI)).setup()
    assert HEAP_AGING.owners == 1
    b = Node(Config.from_ini(CPU_INI)).setup()
    assert HEAP_AGING.owners == 2
    first, second = (a, b) if order == "first-in-first-out" else (b, a)
    first.stop()
    # one node left: the process is still its to age
    assert HEAP_AGING.owners == 1
    assert gc.get_threshold()[2] == heapaging._UNATTENDED
    second.ops.accept_ledger()
    second.close_pipeline.flush(timeout=30)
    assert not nothing_frozen()
    second.stop()


class TestOwnership:
    def test_counted_and_restored_by_the_last_release(self, as_found):
        HEAP_AGING.acquire()
        HEAP_AGING.acquire()
        assert gc.get_threshold() == (as_found[0], as_found[1],
                                      heapaging._UNATTENDED)
        HEAP_AGING.age()
        assert not nothing_frozen()
        # (to a frame or two: the frozen die by reference count)
        assert abs(HEAP_AGING.frozen_objects - gc.get_freeze_count()) < 100
        HEAP_AGING.release()
        assert HEAP_AGING.owners == 1 and not nothing_frozen()
        assert gc.get_threshold()[2] == heapaging._UNATTENDED
        HEAP_AGING.release()
        assert HEAP_AGING.owners == 0
        HEAP_AGING.release()  # one too many changes nothing
        assert HEAP_AGING.owners == 0

    def test_nobody_owns_nothing_ages(self):
        before = counters()
        HEAP_AGING.age()
        assert counters() == before and nothing_frozen()

    @pytest.mark.parametrize("ini", [
        CPU_INI,
        CPU_INI + "[trace]\nenabled=0\n",
        CPU_INI + "[close_pipeline]\nenabled=0\n",
    ], ids=["traced", "trace-disabled", "serial-persist"])
    def test_a_node_owns_from_setup_to_stop(self, ini, as_found):
        before = counters()
        node = Node(Config.from_ini(ini)).setup()
        try:
            assert HEAP_AGING.owners == 1
            assert gc.get_threshold() == (as_found[0], as_found[1],
                                          heapaging._UNATTENDED)
            # set-up's own step froze what set-up built
            assert not nothing_frozen()
            aged = counters()["aged"] + counters()["backstop_passes"]
            assert aged == before["aged"] + before["backstop_passes"] + 1
            # a second setup() of the same node is not a second owner
            node.setup()
            assert HEAP_AGING.owners == 1
            node.ops.accept_ledger()
            node.close_pipeline.flush(timeout=30)
            now = counters()
            assert now["aged"] + now["backstop_passes"] >= aged + 2
            # the policy's counters are on the probe's surfaces, with
            # the tracer off too
            runtime = dispatch(Context(node, {}, Role.ADMIN),
                               "get_counts")["runtime"]["gc"]
            for key in ("aged", "aged_pause_s", "aged_collected",
                        "frozen_objects", "backstop_passes",
                        "backstop_pause_s", "backstop_collected"):
                assert key in runtime, key
            assert runtime["frozen_objects"] == now["frozen_objects"] > 0
            assert (GC_PROBE.installed > 0) == node.tracer.enabled
        finally:
            node.stop()
        # stopping twice takes no second share
        node.stop()

    @pytest.mark.parametrize(
        "order", ["first-in-first-out", "last-in-first-out"])
    def test_two_nodes_in_one_process(self, order):
        own_with_two_nodes(order)

    @pytest.mark.parametrize("tool", ["replay_range", "replay_ledger"])
    def test_a_replay_owns_from_entry_to_exit(self, chain, tool, as_found):
        db, ledgers = chain
        seen = []

        def verify_many(requests):
            seen.append((HEAP_AGING.owners, gc.get_threshold()))
            return [True] * len(requests)

        before = counters()
        if tool == "replay_range":
            out = replay_range(db, [l.hash() for l in ledgers],
                               verify_many=verify_many,
                               tracer=Tracer(enabled=False))
            now = counters()
            # one step behind every replayed ledger
            assert (now["aged"] + now["backstop_passes"]
                    == before["aged"] + before["backstop_passes"]
                    + len(ledgers))
        else:
            out = replay_ledger(db, ledgers[1].hash(),
                                verify_many=verify_many,
                                tracer=Tracer(enabled=False))
            # alone it owns, and leaves its caller's heap unwalked
            assert counters()["aged"] == before["aged"]
        assert out["ok"]
        assert seen == [(1, (as_found[0], as_found[1],
                             heapaging._UNATTENDED))]

    def test_a_replay_that_raises_still_leaves(self, chain):
        db, ledgers = chain

        def boom(requests):
            raise RuntimeError("injected")

        with pytest.raises(RuntimeError):
            replay_range(db, [l.hash() for l in ledgers], verify_many=boom,
                         tracer=Tracer(enabled=False))

    def test_a_replay_makes_no_cycle_for_the_collector(self, chain):
        """`_replay_ledger`'s loads and hashes left one closure cycle
        each (1,704 objects a ledger at the benchmark's size): a replay
        now frees what it built by reference count."""
        db, ledgers = chain
        hashes = [l.hash() for l in ledgers]
        tr = Tracer(enabled=False)
        replay_ledger(db, hashes[0], tracer=tr)  # imports, caches
        inner_node_cache().clear()
        gc.collect()
        gc.disable()
        try:
            replay_ledger(db, hashes[1], tracer=tr)
            inner_node_cache().clear()
            assert gc.collect() == 0
        finally:
            gc.enable()


class TestTheOldGenerationIsTheOwners:
    def test_no_automatic_full_collection_while_owned(self):
        # the control: nobody owns, the interpreter's quarter rule runs
        gc.collect()
        with Collections() as free:
            keep = churn(1_000_000)
        assert free.by_gen[2] >= 1, free.by_gen
        del keep
        gc.collect()
        HEAP_AGING.acquire()
        try:
            with Collections() as owned:
                keep = churn(1_000_000)
            # the young generations ran exactly as they do
            assert owned.by_gen[2] == 0, owned.by_gen
            assert owned.by_gen[0] >= 0.9 * free.by_gen[0] > 1000
            assert owned.by_gen[1] >= 0.9 * free.by_gen[1] > 100
            del keep
        finally:
            HEAP_AGING.release()

    def test_an_owner_that_never_ages_is_not_left_alone_for_ever(self):
        """The last resort: the interpreter's own full collection, once
        the middle generation has been collected `_UNATTENDED` times
        with no step in between (with small young thresholds here, so
        that a thousand of them are a hundred thousand objects)."""
        found = gc.get_threshold()
        gc.set_threshold(10, 2, found[2])
        HEAP_AGING.acquire()
        try:
            assert gc.get_threshold() == (10, 2, heapaging._UNATTENDED)
            with Collections() as owned:
                keep = churn(200_000)
            assert owned.by_gen[1] > heapaging._UNATTENDED
            assert 1 <= owned.by_gen[2] <= 4, owned.by_gen
            del keep
        finally:
            HEAP_AGING.release()
            assert gc.get_threshold() == (10, 2, found[2])
            gc.set_threshold(*found)

    def test_a_step_walks_only_the_young(self):
        HEAP_AGING.acquire()
        try:
            keep = churn(1_500_000)
            before = counters()
            HEAP_AGING.age()  # an owner's first step: the whole heap
            first = counters()
            whole_s = first["aged_pause_s"] - before["aged_pause_s"]
            assert first["frozen_objects"] >= 1_500_000
            small = []
            with Collections() as seen:
                for _ in range(5):
                    # enough to be promoted into the old generation
                    small.append(churn(100_000))
                    frozen = gc.get_freeze_count()
                    HEAP_AGING.age()
                    # every survivor is out of the collector's sight,
                    # the promoted ones unwalked (less the few frozen
                    # earlier that died by reference count meanwhile)
                    assert gc.get_freeze_count() >= frozen + 99_000
                    assert len(gc.get_objects()) < 1_000
            now = counters()
            assert now["aged"] == before["aged"] + 6
            assert now["backstop_passes"] == before["backstop_passes"]
            assert seen.by_gen[2] == 0 and seen.by_gen[1] >= 5
            step_s = (now["aged_pause_s"] - first["aged_pause_s"]) / 5
            # a step's pause does not grow with the heap: a hundred
            # thousand promoted objects cost it nothing, 1.5 million
            # frozen ones neither
            assert step_s < whole_s / 20, (step_s, whole_s)
            # the frozen count is itself a walk: read behind a pass only
            assert now["frozen_objects"] == first["frozen_objects"]
            del keep, small
        finally:
            HEAP_AGING.release()

    def test_frozen_objects_still_die_by_reference_count(self):
        class Plain:
            pass

        HEAP_AGING.acquire()
        try:
            obj = Plain()
            ref = weakref.ref(obj)
            HEAP_AGING.age()
            frozen = gc.get_freeze_count()
            del obj
            assert ref() is None
            assert gc.get_freeze_count() < frozen
        finally:
            HEAP_AGING.release()

    def test_the_backstop_takes_a_cycle_frozen_alive(self, monkeypatch):
        class Knot:
            pass

        # (the shipped factor would want tens of millions of objects)
        monkeypatch.setattr(heapaging, "BACKSTOP_FACTOR", 2)
        HEAP_AGING.acquire()
        try:
            a, b = Knot(), Knot()
            a.other, b.other = b, a
            ref = weakref.ref(a)
            HEAP_AGING.age()  # frozen alive
            base = counters()
            blocks = HEAP_AGING._blocks_at_pass
            assert 0 < blocks <= sys.getallocatedblocks()
            del a, b
            # dead now, and no step walks the frozen: it stays
            HEAP_AGING.age()
            assert ref() is not None
            assert counters()["backstop_passes"] == base["backstop_passes"]
            # the heap grows past the factor: the next step is a pass
            # over everything
            keep = churn(blocks + 10_000)  # a block an empty list
            HEAP_AGING.age()
            now = counters()
            assert now["backstop_passes"] == base["backstop_passes"] + 1
            assert ref() is None
            # the two instances and their two dicts, at the least
            assert now["backstop_collected"] >= base["backstop_collected"] + 2
            assert now["backstop_pause_s"] > base["backstop_pause_s"]
            assert now["frozen_objects"] >= base["frozen_objects"] + blocks
            # and the mark moved: growth below the factor meets no pass
            assert HEAP_AGING._blocks_at_pass >= 2 * blocks
            more = churn(10_000)
            HEAP_AGING.age()
            assert counters()["backstop_passes"] == now["backstop_passes"]
            del keep, more
        finally:
            HEAP_AGING.release()

    def test_a_heap_at_its_plateau_meets_no_pass(self, monkeypatch):
        monkeypatch.setattr(heapaging, "BACKSTOP_FACTOR", 2)
        HEAP_AGING.acquire()
        try:
            HEAP_AGING.age()
            base = counters()
            blocks = HEAP_AGING._blocks_at_pass
            for _ in range(6):
                # as much again as the whole heap comes and goes, by
                # reference count, every cycle
                keep = churn(blocks // 2)
                HEAP_AGING.age()
                del keep
            now = counters()
            assert now["backstop_passes"] == base["backstop_passes"]
            assert now["aged"] == base["aged"] + 6
        finally:
            HEAP_AGING.release()


class TestEveryPassIsTheProbes:
    def test_every_pass_and_every_step_goes_through_the_hook(
            self, monkeypatch):
        monkeypatch.setattr(heapaging, "BACKSTOP_FACTOR", 2)
        tr = Tracer(sample=1.0)
        assert GC_PROBE.install(tr)
        before = counters()
        probe = (list(GC_PROBE.collections), list(GC_PROBE.pause_s),
                 list(GC_PROBE.collected))
        HEAP_AGING.acquire()
        try:
            HEAP_AGING.age()  # the first: the whole heap
            small = churn(50_000)
            HEAP_AGING.age()  # a step: the young generations
            keep = churn(HEAP_AGING._blocks_at_pass + 10_000)
            HEAP_AGING.age()  # the backstop
        finally:
            HEAP_AGING.release()
            GC_PROBE.remove(tr)
        del keep, small
        now = counters()
        assert now["aged"] == before["aged"] + 2
        assert now["backstop_passes"] == before["backstop_passes"] + 1
        # a pass over the old generation is a span of generation 2,
        # whatever its length, with what it freed on it
        spans = [ev for ev in tr.chrome_trace()["traceEvents"]
                 if ev.get("ph") == "X" and ev["name"] == "gc.collect"
                 and ev["args"]["generation"] == 2]
        assert len(spans) == 2 == GC_PROBE.collections[2] - probe[0][2]
        # and a step a collection of generation 1, beside the
        # interpreter's own: nothing the policy does escapes the probe.
        # What the policy timed is what the hook timed
        assert GC_PROBE.collections[1] - probe[0][1] >= 1
        mine = (now["aged_pause_s"] - before["aged_pause_s"]
                + now["backstop_pause_s"] - before["backstop_pause_s"])
        old = GC_PROBE.pause_s[2] - probe[1][2]
        young = GC_PROBE.pause_s[1] - probe[1][1]
        assert old <= mine <= old + young + 0.002
        assert mine == pytest.approx(old, rel=0.05, abs=0.005)
        assert sum(ev["dur"] for ev in spans) / 1e6 == pytest.approx(
            old, rel=0.01, abs=0.001)
        freed = (now["aged_collected"] - before["aged_collected"]
                 + now["backstop_collected"] - before["backstop_collected"])
        assert sum(ev["args"]["collected"] for ev in spans) <= freed
        assert freed <= sum(GC_PROBE.collected[g] - probe[2][g]
                            for g in (1, 2))


class TestSameLedgers:
    def test_twelve_closes_hash_as_the_plain_node_without_the_policy(
            self, store, tmp_path, monkeypatch):
        # the plain reference (cpu/hashlib, serial apply, full seal)
        # with the policy taken out: the interpreter's own collector
        with monkeypatch.context() as m:
            for name in ("acquire", "release", "age"):
                m.setattr(HEAP_AGING, name, lambda: None)
            want = plain_hashes(store, tmp_path / "plain")
            assert HEAP_AGING.owners == 0 and nothing_frozen()
        directory, entries = store
        workdir, meta = prepared.copy_for_run(directory,
                                              str(tmp_path / "aged"))
        ini = nodedrive.ini_text(INI, workdir=os.path.join(workdir, "db"),
                                 start_up="load")
        inner_node_cache().clear()
        before = counters()
        node = Node(Config.from_ini(ini)).setup()
        try:
            got, pump = drive(node, meta, entries)
            outcomes_ok(pump, entries)
            now = counters()
            frozen = gc.get_freeze_count()
        finally:
            node.stop()
            inner_node_cache().clear()
            shutil.rmtree(workdir, ignore_errors=True)
        assert len(got) == CLOSES == len(want)
        assert got == want
        # set-up's step and one behind every persisted ledger
        passes = (now["aged"] + now["backstop_passes"]
                  - before["aged"] - before["backstop_passes"])
        assert passes == 1 + CLOSES
        assert frozen > 0 and now["frozen_objects"] > 0
