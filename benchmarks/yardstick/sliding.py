"""Seeded traffic over a population far larger than its hot set: signed
payments to Zipf destinations from a WINDOW of senders that slides.

Destinations are drawn Zipf(``zipf_theta``) over a seeded ranking of
all the population's accounts, fixed for the run: gateways stay hot, the
tail is cold. Senders are ``senders`` consecutive accounts of a second
seeded ranking, taken in round robin; at every close (every
``close_every`` valid payments) the window moves on by ``slide``
accounts: new users arrive, old ones go quiet, so the hot set moves
(``workloads.md``), and because the window is far wider than the
submission loop no account has two payments in flight. ``--seed`` draws
both rankings, the Zipf draws and the planted positions; the population
is the configuration's and the same for every seed.

The entries have the shape of ``workload.payment_stream``'s, and the
planted signatures are made as there (``workload.corrupt``).
"""

from __future__ import annotations

import random

from . import workload


def sender_positions(senders: int, slide: int, close_every: int,
                     count: int) -> list[int]:
    """-> for each of ``count`` valid payments, its sender's position in
    the sender ranking. A cursor walks the window ``[base, base +
    senders)`` and wraps inside it; a close moves ``base`` on by
    ``slide`` and takes the cursor along where it fell behind."""
    out = []
    base = cursor = 0
    for k in range(count):
        if k and k % close_every == 0:
            base += slide
            cursor = max(cursor, base)
        out.append(cursor)
        cursor += 1
        if cursor >= base + senders:
            cursor = base
    return out


def sliding_stream(*, seed: int, pop: dict, params: dict, count: int) -> list:
    """``count`` signed payments (``count`` a multiple of
    ``close_every``) plus ``planted_per_1024`` corrupted copies in every
    1,024. A sender never pays itself. -> entries ``(blob, planted,
    sender index, destination index, txid)``; a planted entry follows
    its source."""
    population, accounts = pop["name"], int(pop["accounts"])
    senders, slide = int(params["senders"]), int(params["slide"])
    close_every = int(params["close_every"])
    amount_drops, fee_drops = int(params["amount_drops"]), int(params["fee_drops"])
    planted_per_1024 = int(params.get("planted_per_1024", 0))
    from stellard_tpu.protocol.formats import TxType
    from stellard_tpu.protocol.sfields import sfAmount, sfDestination
    from stellard_tpu.protocol.stamount import STAmount
    from stellard_tpu.protocol.sttx import SerializedTransaction

    positions = sender_positions(senders, slide, close_every, count)
    if positions and max(positions) >= accounts:
        raise ValueError(
            f"{count} payments slide the sender window past the "
            f"population's {accounts} accounts")
    rng = random.Random(seed)
    dest_ranking = list(range(accounts))
    rng.shuffle(dest_ranking)  # rank k -> account dest_ranking[k]
    sender_ranking = list(range(accounts))
    rng.shuffle(sender_ranking)
    ranks = workload.zipf_ranks(rng, accounts, float(params["zipf_theta"]),
                                count)
    planted_at: set[int] = set()
    for base in range(0, count, 1024):
        hi = min(base + 1024, count)
        planted_at.update(rng.sample(
            range(base, hi), min(planted_per_1024, hi - base)))

    keys = workload.population_keys(
        population, {sender_ranking[p] for p in positions})
    ids = {i: k.account_id for i, k in keys.items()}

    def account_id(i: int) -> bytes:
        got = ids.get(i)
        if got is None:
            got = ids[i] = workload.population_keys(
                population, [i])[i].account_id
        return got

    amount = STAmount.from_drops(amount_drops)
    next_seq: dict[int, int] = {}
    entries: list = []
    n_planted = 0
    for k in range(count):
        s = sender_ranking[positions[k]]
        d = dest_ranking[ranks[k]]
        if d == s:
            d = dest_ranking[(ranks[k] + 1) % accounts]
        seq = next_seq.get(s, 1)
        next_seq[s] = seq + 1
        tx = SerializedTransaction.build(
            TxType.ttPAYMENT, keys[s].account_id, seq, fee_drops,
            {sfAmount: amount, sfDestination: account_id(d)},
        )
        tx.sign(keys[s])
        blob = tx.serialize()
        entries.append((blob, False, s, d, tx.txid()))
        if k in planted_at:
            bad = workload.corrupt(blob, n_planted)
            n_planted += 1
            entries.append(
                (bad, True, s, d, SerializedTransaction.from_bytes(bad).txid()))
    return entries
