"""Seeded traffic: the account population of a deployment, signed
payment streams with Zipf destinations, and planted bad signatures.

The population is part of the configuration (its data set, like the
tables of a database benchmark at a stated scale): account ``i`` is the
key pair of the passphrase ``<population>:<i>``, funded by the master
account. ``--seed`` draws everything a run sends: which accounts send,
whom they pay, in which order, and where the bad signatures sit.

The planted-signature generator is copied from ``chip_smoke.py``
(``make_workload``): a planted transaction is an EXTRA copy of a valid
one with its R byte, low S byte or public key corrupted in turn, so a
refusal leaves no gap in the sender's sequence chain.
"""

from __future__ import annotations

import random

PIN_CLOSE_TIME = 900_000_000  # close times are hashed into the ledger
CLOSE_STEP_S = 30


def population_keys(population: str, indexes):
    """-> {index: KeyPair} for the accounts whose keys a run needs."""
    from stellard_tpu.protocol.keys import KeyPair

    return {i: KeyPair.from_passphrase(f"{population}:{i}") for i in indexes}


def population_ids(population: str, n: int) -> list[bytes]:
    """The 20-byte account ids of the whole population, in index order."""
    return [k.account_id for k in population_keys(population, range(n)).values()]


def zipf_ranks(rng: random.Random, n: int, theta: float, count: int) -> list[int]:
    """``count`` draws of a rank in [0, n) with P(rank k) ~ 1/(k+1)^theta,
    by inversion of the cumulative weights (bisect), from ``rng`` alone."""
    import bisect
    import itertools

    cum = list(itertools.accumulate((k + 1) ** -theta for k in range(n)))
    total = cum[-1]
    return [bisect.bisect_left(cum, rng.random() * total) for _ in range(count)]


def corrupt(blob: bytes, kind: int) -> bytes:
    """A copy of a signed transaction with one byte of its signature (R,
    then S) or of its public key flipped; ``kind`` picks which, mod 3."""
    from stellard_tpu.protocol.sfields import sfSigningPubKey, sfTxnSignature
    from stellard_tpu.protocol.stobject import STObject
    from stellard_tpu.protocol.sttx import SerializedTransaction

    obj = STObject.from_bytes(blob)
    sig = bytearray(obj[sfTxnSignature])
    kind %= 3
    if kind == 0:
        sig[5] ^= 0x40  # R byte: encode([S]B + [h](-A)) != R
    elif kind == 1:
        sig[32] ^= 0x01  # low S byte: S stays canonical, wrong point
    else:
        pub = bytearray(obj[sfSigningPubKey])
        pub[3] ^= 0x80  # public key: bad decompress, or a wrong A
        obj[sfSigningPubKey] = bytes(pub)
    obj[sfTxnSignature] = bytes(sig)
    return SerializedTransaction(obj).serialize()


def payment_stream(*, seed: int, pop: dict, params: dict, count: int) -> list:
    """``count`` signed payments of ``amount_drops`` (fee ``fee_drops``)
    from ``senders`` seeded accounts of the population, in round robin,
    to destinations drawn Zipf(``zipf_theta``) over a seeded ranking of
    all its accounts, plus ``planted_per_1024`` corrupted copies in every
    1,024; ``params`` is a traffic file or a configuration's ``history``.
    A sender never pays itself. -> entries ``(blob, planted, sender
    index, destination index, txid)``; a planted entry follows its
    source."""
    population, accounts = pop["name"], int(pop["accounts"])
    senders = int(params["senders"])
    amount_drops, fee_drops = int(params["amount_drops"]), int(params["fee_drops"])
    zipf_theta = float(params["zipf_theta"])
    planted_per_1024 = int(params.get("planted_per_1024", 0))
    from stellard_tpu.protocol.formats import TxType
    from stellard_tpu.protocol.sfields import sfAmount, sfDestination
    from stellard_tpu.protocol.stamount import STAmount
    from stellard_tpu.protocol.sttx import SerializedTransaction

    rng = random.Random(seed)
    sender_idx = rng.sample(range(accounts), senders)
    ranking = list(range(accounts))
    rng.shuffle(ranking)  # rank k -> account ranking[k]
    ranks = zipf_ranks(rng, accounts, zipf_theta, count)
    keys = population_keys(population, sender_idx)
    ids = {}  # destination ids are derived on demand (most are never paid)

    def account_id(i: int) -> bytes:
        got = ids.get(i)
        if got is None:
            got = ids[i] = population_keys(population, [i])[i].account_id
        return got

    planted_at: set[int] = set()
    for base in range(0, count, 1024):
        hi = min(base + 1024, count)
        k = min(planted_per_1024, hi - base)
        planted_at.update(rng.sample(range(base, hi), k))

    amount = STAmount.from_drops(amount_drops)
    next_seq: dict[int, int] = {}
    entries: list = []
    n_planted = 0
    for k in range(count):
        s = sender_idx[k % senders]
        d = ranking[ranks[k]]
        if d == s:
            d = ranking[(ranks[k] + 1) % accounts]
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
            bad = corrupt(blob, n_planted)
            n_planted += 1
            bad_id = SerializedTransaction.from_bytes(bad).txid()
            entries.append((bad, True, s, d, bad_id))
    return entries


class BalanceModel:
    """The benchmark's own arithmetic of balances and sequences: what
    ``account_info`` must answer after the acknowledged payments."""

    def __init__(self, funded_drops: int, fee_drops: int):
        self.funded = funded_drops
        self.fee = fee_drops
        self.delta: dict[int, int] = {}
        self.seq: dict[int, int] = {}

    def applied(self, sender: int, dest: int, amount_drops: int) -> None:
        self.delta[sender] = self.delta.get(sender, 0) - amount_drops - self.fee
        self.delta[dest] = self.delta.get(dest, 0) + amount_drops
        self.seq[sender] = self.seq.get(sender, 1) + 1

    def balance(self, i: int) -> int:
        return self.funded + self.delta.get(i, 0)

    def sequence(self, i: int) -> int:
        return self.seq.get(i, 1)

    def touched(self) -> list[int]:
        return sorted(self.delta)
