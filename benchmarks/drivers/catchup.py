"""Traffic kind ``catchup``: a cold process re-verifies a stored history
span by span with ``node.ledgertools.replay_range`` (the program's bulk
catch-up) through ``make_crypto_planes(Config.from_ini(...))``, the
wiring ``--replay`` and ``Node`` use. The window ends at the first span
boundary after ``--seconds`` and the rate is taken to that boundary.

Parameters (the traffic file): ``warmup_ledgers`` (the first ledgers of a
span, replayed unmeasured: they load the verify program and warm its one
padded shape), ``corrupt_signatures`` (how many signatures of one extra
ledger are corrupted on their way to the verifier, for ``correct``).
The profiler capture of a traced run is the measured window. ``--seed``
draws the order of the spans, the ledger that is corrupted and the
positions in it; the stored history itself is the configuration's data
set and is the same for every seed.
"""

from __future__ import annotations

import dataclasses
import functools
import os
import random
import time

from yardstick import nodedrive, prepared, stats
from yardstick.capture import WINDOW


def corrupting(verify_many, positions: list[int]):
    """``verify_many`` with the signatures at ``positions`` corrupted on
    the way in (R byte, low S byte, public key in turn)."""

    def wrapped(requests):
        requests = list(requests)
        for k, p in enumerate(positions):
            r = requests[p]
            sig, pub = bytearray(r.signature), bytearray(r.public)
            if k % 3 == 0:
                sig[5] ^= 0x40
            elif k % 3 == 1:
                sig[32] ^= 0x01
            else:
                pub[3] ^= 0x80
            requests[p] = dataclasses.replace(
                r, signature=bytes(sig), public=bytes(pub))
        return verify_many(requests)

    return wrapped


def run(ctx) -> dict:
    tr, cfg = ctx.traffic, ctx.config
    from stellard_tpu.node.config import Config
    from stellard_tpu.node.ledgertools import replay_ledger, replay_range
    from stellard_tpu.node.node import make_crypto_planes
    from stellard_tpu.nodestore.core import make_database

    prepared_dir = prepared.ensure(cfg, ctx.ini_template, ctx.cache_dir)
    workdir, meta = prepared.copy_for_run(prepared_dir, ctx.work_root)
    ini = nodedrive.ini_text(
        ctx.ini_template, workdir=os.path.join(workdir, "db"),
        start_up="load")
    config = Config.from_ini(ini)
    problems: list[str] = []
    libs_ok, libs = nodedrive.host_libraries_ok()
    if not libs_ok:
        problems.append(f"host libraries: {libs}")

    spans = [[bytes.fromhex(l["hash"]) for l in span]
             for span in meta["spans"]]
    rng = random.Random(ctx.seed)
    first = rng.randrange(len(spans))
    order = spans[first:] + spans[:first]

    hasher, plane = make_crypto_planes(config)
    try:
        verify_s = [0.0]

        def verify_many(requests):
            t = time.perf_counter()
            with cap.annotate("verify_many"):
                out = plane.verify_many(requests)
            verify_s[0] += time.perf_counter() - t
            return out

        def replay(span):
            db = make_database(type=config.node_db_type,
                               path=config.node_db_path)
            try:
                with cap.annotate("replay_range"):
                    return replay_range(db, span, hash_batch=hasher,
                                        verify_many=verify_many)
            finally:
                db.close()

        cap = ctx.capture()
        snap = functools.partial(nodedrive.counters, plane, hasher)
        k = 0
        if not replay(order[-1][:int(tr["warmup_ledgers"])])["ok"]:
            problems.append("the warm-up ledgers did not replay")
        verify_s[0] = 0.0
        before = snap()

        # ---- the measured window ----
        cap.start()
        with cap.annotate(WINDOW):
            t0 = time.perf_counter()
            replayed = []
            while True:
                replayed.append(replay(order[k % len(order)]))
                k += 1
                if time.perf_counter() - t0 >= ctx.seconds:
                    break
            t1 = time.perf_counter()
        # ---- end of the window ----
        after = snap()
        cap.finish()
        window_s = t1 - t0
        verify_in_window = verify_s[0]

        ledgers = [l for out in replayed for l in out["ledgers"]]
        bad = [l["ledger_seq"] for l in ledgers
               if not (l["ok"] and l["state_hash_ok"] and l["tx_hash_ok"])]
        if bad:
            problems.append(f"ledgers {bad} did not replay to their hashes")
        txs = sum(out["tx_count"] for out in replayed)

        # one extra ledger with corrupted signatures must fail while its
        # neighbour passes: the verdicts of the device are really used
        span = order[rng.randrange(len(order))]
        at = rng.randrange(len(span) - 1)
        db = make_database(type=config.node_db_type, path=config.node_db_path)
        try:
            n_tx = ledgers[0]["tx_count"]
            positions = rng.sample(range(n_tx), int(tr["corrupt_signatures"]))
            broken = replay_ledger(
                db, span[at], hash_batch=hasher,
                verify_many=corrupting(plane.verify_many, positions))
            neighbour = replay_ledger(
                db, span[at + 1], hash_batch=hasher,
                verify_many=plane.verify_many)
        finally:
            db.close()
        if broken["ok"]:
            problems.append(
                f"ledger {broken['ledger_seq']} replayed to its hash with "
                f"{len(positions)} corrupted signatures")
        if not neighbour["ok"]:
            problems.append(
                f"ledger {neighbour['ledger_seq']} (the neighbour) failed")
    finally:
        plane.stop()

    window = nodedrive.delta(after, before)
    window.update({
        "window_s": window_s, "txs": txs, "ledgers": len(ledgers),
        "spans": len(replayed),
        "replay.ledger_elapsed_s": sum(l["elapsed_s"] for l in ledgers),
        "replay.span_elapsed_s": sum(out["elapsed_s"] for out in replayed),
        "replay.verify_s": verify_in_window,
    })
    ctx.say(f"window {window_s:.2f}s, {len(replayed)} spans "
            f"({[round(o['elapsed_s'], 2) for o in replayed]}), "
            f"{txs} transactions")
    return {
        "correct": not problems,
        "problems": problems,
        "attempted": len(ledgers),
        "failed": len(bad),
        "t_first_measured": t0,
        "annotations": ["verify_many", "replay_range"],
        "end_to_end": {"catchup_tx_per_s": stats.rate(txs, window_s)},
        "sources": {
            "counters": window,
            "samples": {"span_s": [o["elapsed_s"] for o in replayed]},
            "spans": [],
            "capture": cap,
        },
    }
