"""The exchange deployment: its market (a configuration's
``population``: gateways, currencies, market makers, takers, trust
lines, pairs, the standing order books) and its seeded traffic.

The market is fixed data, the same for every ``--seed``. Account ``i``
is the key pair of ``<name>:<i>`` (as ``workload.population_keys``
derives it): gateways first, then makers, then takers. Gateway ``g``
issues the currencies ``2g`` and ``2g + 1``; a maker holds a trust line
in every currency; taker ``j`` holds the currencies of one of the
file's ``taker_line_sets`` rotated by ``j``. A pair is (base, quote),
the quote of a pair against STR being STR; its mid is the ratio of the
two currencies' fixed values in STR, and every price is the mid moved by
a whole number of ticks: an ASK sells the base at ``mid (1 + k tick)``,
a BID buys it at ``mid (1 - k tick)``, ``k`` positive behind the mid
(a resting quote) and negative through it (a marketable limit). So the
generator needs no model of the book to price an order.

Every directed book (272 of 136 pairs) stands with one offer of
``seed_offer_units`` of the base at each of ``book_levels`` levels.

The traffic (``offer_stream``) is a traffic file's six-way mix over a
seeded Zipf ranking of the pairs; see its docstring.
"""

from __future__ import annotations

import random
from collections import OrderedDict, deque
from fractions import Fraction

from . import workload

ASK, BID = 0, 1
KINDS = ("rest", "cross", "cancel", "xpay", "ipay", "spay")
QUOTE_DECIMALS = 6  # a quote amount is rounded to 10^-6 (a drop, for STR)
QUANTUM = 10 ** QUOTE_DECIMALS


def rounded(units: Fraction) -> Fraction:
    """``units`` to the nearest 10^-6."""
    return Fraction(round(units * QUANTUM), QUANTUM)


class Market:
    """A configuration's ``population`` as indexes and amounts."""

    def __init__(self, pop: dict):
        self.pop = pop
        self.name = pop["name"]
        self.n_gateways = int(pop["gateways"])
        self.n_makers = int(pop["makers"])
        self.n_takers = int(pop["takers"])
        self.codes = list(pop["currencies"])
        self.values = [Fraction(v) for v in pop["values_str"]]
        n = len(self.codes)
        if n != 2 * self.n_gateways or len(self.values) != n:
            raise ValueError("two currencies a gateway, one value each")
        self.tick = Fraction(pop["tick"])
        self.levels = int(pop["book_levels"])
        self.seed_units = int(pop["seed_offer_units"])
        self.line_limit = int(pop["line_limit"])
        self.line_funding = int(pop["line_funding"])
        self.line_sets = [list(s) for s in pop["taker_line_sets"]]
        self.first_maker = self.n_gateways
        self.first_taker = self.n_gateways + self.n_makers
        self.accounts = self.first_taker + self.n_takers
        # (base, quote); quote None is STR
        self.pairs = [(c, None) for c in range(n)] + [
            (a, b) for a in range(n) for b in range(a + 1, n)]
        self.books = 2 * len(self.pairs)
        self._keys: dict = {}
        self._holders: dict = {}
        self._seed_counts: dict = {}

    # -- accounts ---------------------------------------------------------

    def key(self, i: int):
        got = self._keys.get(i)
        if got is None:
            got = self._keys[i] = workload.population_keys(
                self.name, [i])[i]
        return got

    def account_id(self, i: int) -> bytes:
        return self.key(i).account_id

    def gateway_of(self, currency: int) -> int:
        return currency // 2

    def makers(self) -> range:
        return range(self.first_maker, self.first_taker)

    def takers(self) -> range:
        return range(self.first_taker, self.accounts)

    def currencies_of(self, i: int) -> list[int]:
        """The currencies account ``i`` holds a trust line in."""
        n = len(self.codes)
        if i < self.first_maker:
            return []
        if i < self.first_taker:
            return list(range(n))
        j = i - self.first_taker
        base = self.line_sets[(j // n) % len(self.line_sets)]
        return sorted({(x + j) % n for x in base})

    def lines(self) -> int:
        return sum(len(self.currencies_of(i))
                   for i in range(self.first_maker, self.accounts))

    def holders(self, pair: int) -> list[int]:
        """The takers who hold both sides of ``pair``."""
        got = self._holders.get(pair)
        if got is None:
            base, quote = self.pairs[pair]
            need = {base} if quote is None else {base, quote}
            got = self._holders[pair] = [
                i for i in self.takers()
                if need <= set(self.currencies_of(i))]
        return got

    def currency_holders(self, currency: int) -> list[int]:
        return self.holders(currency)  # pair c is (c, STR)

    # -- amounts ----------------------------------------------------------

    def currency_bytes(self, currency: int) -> bytes:
        from stellard_tpu.protocol.stamount import currency_from_iso

        return currency_from_iso(self.codes[currency])

    def iou(self, currency: int, units: Fraction):
        """``units`` of a currency (a multiple of 10^-6) as an amount of
        its gateway's."""
        from stellard_tpu.protocol.stamount import STAmount

        scaled = units * QUANTUM
        if scaled.denominator != 1:
            raise ValueError(f"{units} is no multiple of 10^-6")
        return STAmount.from_iou(
            self.currency_bytes(currency),
            self.account_id(self.gateway_of(currency)),
            scaled.numerator, -QUOTE_DECIMALS)

    def amount(self, currency, units: Fraction):
        """An amount of ``currency`` (None: STR, ``units`` in STR)."""
        from stellard_tpu.protocol.stamount import STAmount

        if currency is None:
            return STAmount.from_drops(int(units * 1_000_000))
        return self.iou(currency, units)

    def mid(self, pair: int) -> Fraction:
        base, quote = self.pairs[pair]
        return self.values[base] / (
            1 if quote is None else self.values[quote])

    def price(self, pair: int, side: int, ticks: int) -> Fraction:
        step = ticks * self.tick
        return self.mid(pair) * (1 + step if side == ASK else 1 - step)

    def quote_units(self, pair: int, side: int, ticks: int,
                    units: Fraction) -> Fraction:
        """What ``units`` of the base cost at that price, rounded to the
        quote's 10^-6."""
        return rounded(units * self.price(pair, side, ticks))

    def offer_amounts(self, pair: int, side: int, ticks: int,
                      units: Fraction):
        """-> (TakerPays, TakerGets) of an order for ``units`` of the
        base on ``side`` at ``ticks`` from the mid."""
        base, quote = self.pairs[pair]
        base_amt = self.amount(base, units)
        quote_amt = self.amount(
            quote, self.quote_units(pair, side, ticks, units))
        return (quote_amt, base_amt) if side == ASK else (base_amt, quote_amt)

    # -- the standing books -----------------------------------------------

    def book_no(self, pair: int, side: int) -> int:
        return 2 * pair + side

    def seed_maker(self, book: int, level: int) -> int:
        return self.first_maker + (
            book * self.levels + level - 1) % self.n_makers

    def seeds(self):
        """Every standing offer, in the order set-up places them:
        (maker, pair, side, level)."""
        for pair in range(len(self.pairs)):
            for side in (ASK, BID):
                book = self.book_no(pair, side)
                for level in range(1, self.levels + 1):
                    yield self.seed_maker(book, level), pair, side, level

    def setup_transactions(self, i: int) -> int:
        """How many transactions account ``i`` sent in set-up (its next
        sequence is one more)."""
        if i < self.first_maker:  # a gateway funds the lines in its two
            return sum(1 for a in range(self.first_maker, self.accounts)
                       for c in self.currencies_of(a)
                       if self.gateway_of(c) == i)
        n = len(self.currencies_of(i))
        if i < self.first_taker:
            n += self._seeds_by_maker().get(i, 0)
        return n

    def _seeds_by_maker(self) -> dict:
        if not self._seed_counts:
            for maker, _p, _s, _l in self.seeds():
                self._seed_counts[maker] = self._seed_counts.get(maker, 0) + 1
        return self._seed_counts

    def issued(self) -> list[int]:
        """Units each currency's gateway paid out in set-up."""
        out = [0] * len(self.codes)
        for a in range(self.first_maker, self.accounts):
            for c in self.currencies_of(a):
                out[c] += self.line_funding
        return out


# --------------------------------------------------------------------------
# set-up: the transactions that make the market, in the order sent


def setup_stream(market: Market, fee_drops: int):
    """Yield the signed transactions of set-up in four phases: the
    master account funds every account, every holder sets its trust
    lines, the gateways pay ``line_funding`` into each, the makers place
    the standing offers. -> (phase, SerializedTransaction)."""
    from stellard_tpu.node.node import MASTER_PASSPHRASE
    from stellard_tpu.protocol.formats import TxType
    from stellard_tpu.protocol.keys import KeyPair
    from stellard_tpu.protocol.sfields import (
        sfAmount, sfDestination, sfLimitAmount, sfTakerGets, sfTakerPays,
    )
    from stellard_tpu.protocol.stamount import STAmount
    from stellard_tpu.protocol.sttx import SerializedTransaction

    seqs: dict[int, int] = {}

    def signed(i, key, tx_type, fields):
        seq = seqs.get(i, 1)
        seqs[i] = seq + 1
        tx = SerializedTransaction.build(
            tx_type, key.account_id, seq, fee_drops, fields)
        tx.sign(key)
        return tx

    master = KeyPair.from_passphrase(MASTER_PASSPHRASE)
    funded = STAmount.from_drops(int(market.pop["funded_drops"]))
    for i in range(market.accounts):
        yield "fund", signed(-1, master, TxType.ttPAYMENT, {
            sfAmount: funded, sfDestination: market.account_id(i)})
    for i in range(market.first_maker, market.accounts):
        for c in market.currencies_of(i):
            yield "trust", signed(i, market.key(i), TxType.ttTRUST_SET, {
                sfLimitAmount: market.iou(c, Fraction(market.line_limit))})
    for i in range(market.first_maker, market.accounts):
        for c in market.currencies_of(i):
            g = market.gateway_of(c)
            yield "issue", signed(g, market.key(g), TxType.ttPAYMENT, {
                sfAmount: market.iou(c, Fraction(market.line_funding)),
                sfDestination: market.account_id(i)})
    for maker, pair, side, level in market.seeds():
        pays, gets = market.offer_amounts(
            pair, side, level, Fraction(market.seed_units))
        yield "seed", signed(maker, market.key(maker), TxType.ttOFFER_CREATE,
                             {sfTakerPays: pays, sfTakerGets: gets})


# --------------------------------------------------------------------------
# the traffic


class _Senders:
    """Round robin over lists of accounts, never an account that sent
    one of the last ``gap`` transactions (the loop keeps fewer than that
    in flight, so no account has two in flight)."""

    def __init__(self, gap: int):
        self.recent: deque = deque()
        self.count: dict[int, int] = {}
        self.gap = gap
        self.cursor: dict = {}

    def busy(self, account: int) -> bool:
        return account in self.count

    def used(self, account: int) -> None:
        self.recent.append(account)
        self.count[account] = self.count.get(account, 0) + 1
        if len(self.recent) > self.gap:
            old = self.recent.popleft()
            if self.count[old] == 1:
                del self.count[old]
            else:
                self.count[old] -= 1

    def next(self, key, accounts, skip=()) -> int:
        n = len(accounts)
        at = self.cursor.get(key, 0)
        for step in range(n):
            got = accounts[(at + step) % n]
            if got not in self.count and got not in skip:
                self.cursor[key] = (at + step + 1) % n
                return got
        raise ValueError(f"every account of {key!r} sent one of the last "
                         f"{self.gap} transactions")


def pair_ranking(seed: int, market: Market) -> list[int]:
    """The run's ranking of the pairs: rank k -> pair."""
    ranking = list(range(len(market.pairs)))
    random.Random(f"pairs:{seed}").shuffle(ranking)
    return ranking


def offer_stream(*, seed: int, market: Market, params: dict,
                 count: int) -> list:
    """``count`` signed transactions of the mix ``params["mix"]`` (shares
    by count of ``rest``, ``cross``, ``cancel``, ``xpay``, ``ipay``,
    ``spay``) plus ``planted_per_1024`` corrupted copies in every 1,024.

    The pair of every offer and cross-currency payment is drawn
    Zipf(``zipf_theta``) over a seeded ranking of the pairs, fixed for
    the run; its side by a fair coin.

    - ``rest``: a maker's quote of ``quote_units`` of the base,
      ``maker_ticks`` behind the mid. Every ``replace_every``-th carries
      ``OfferSequence`` and replaces the oldest quote the run has placed
      in that book and not yet replaced or cancelled (its maker sends
      it); the others come from the makers in round robin.
    - ``cross``: a taker's limit ``taker_through_ticks`` through the
      mid, sized to take ``taker_offers`` standing offers, the last
      partly (units of ``seed_offer_units``); one in ``ioc_every``
      ``tfImmediateOrCancel``, one in ``sell_every`` ``tfSell``.
    - ``cancel``: ``OfferCancel`` of the newest quote the run has placed
      in the drawn book (the one most likely still to stand); a book
      that holds none of the run's quotes passes the draw on to the next
      pair of the ranking.
    - ``xpay``: a taker pays ``payment_units`` of one side of the pair
      to another holder, sending the other side (``SendMax`` with
      ``sendmax_slack`` of room, no explicit path: one book).
    - ``ipay``: ``payment_units`` of the pair's base from one holder to
      another through the issuer; ``spay``: ``amount_drops`` of STR.

    Takers send in round robin over those who hold the pair, makers
    over all; none sends while one of the last ``sender_gap``
    transactions is its own. The standing offers of set-up are never
    replaced or cancelled: they are the deployment's book.

    -> entries ``(blob, planted, kind, sender index, txid)``; a planted
    entry follows its source."""
    from stellard_tpu.engine.flags import tfImmediateOrCancel, tfSell
    from stellard_tpu.protocol.formats import TxType
    from stellard_tpu.protocol.sfields import (
        sfAmount, sfDestination, sfFlags, sfOfferSequence, sfSendMax,
        sfTakerGets, sfTakerPays,
    )
    from stellard_tpu.protocol.stamount import STAmount
    from stellard_tpu.protocol.sttx import SerializedTransaction

    rng = random.Random(seed)
    n_pairs = len(market.pairs)
    ranking = pair_ranking(seed, market)
    ranks = workload.zipf_ranks(rng, n_pairs, float(params["zipf_theta"]),
                                count)
    mix = params["mix"]
    kinds = rng.choices(KINDS, weights=[float(mix[k]) for k in KINDS],
                        k=count)
    planted_per_1024 = int(params.get("planted_per_1024", 0))
    planted_at: set[int] = set()
    for lo in range(0, count, 1024):
        hi = min(lo + 1024, count)
        planted_at.update(rng.sample(
            range(lo, hi), min(planted_per_1024, hi - lo)))

    fee = int(params["fee_drops"])
    maker_lo, maker_hi = (int(x) for x in params["maker_ticks"])
    through = int(params["taker_through_ticks"])
    take_lo, take_hi = (int(x) for x in params["taker_offers"])
    quote_lo, quote_hi = (int(x) for x in params["quote_units"])
    pay_lo, pay_hi = (int(x) for x in params["payment_units"])
    slack = Fraction(params["sendmax_slack"])
    makers = list(market.makers())
    senders = _Senders(int(params["sender_gap"]))
    next_seq: dict[int, int] = {}
    # the run's own quotes by book, oldest first: (maker, sequence)
    placed: list[OrderedDict] = [OrderedDict() for _ in range(market.books)]
    counters = {"rest": 0, "cross": 0}

    def sequence(i: int) -> int:
        seq = next_seq.get(i)
        if seq is None:
            seq = market.setup_transactions(i) + 1
        next_seq[i] = seq + 1
        return seq

    def other_holder(key, accounts, sender: int) -> int:
        # a destination may be busy (it sends nothing), not the sender
        n = len(accounts)
        at = senders.cursor.get(key, 0)
        got = accounts[at % n]
        if got == sender:
            at += 1
            got = accounts[at % n]
        senders.cursor[key] = (at + 1) % n
        return got

    def quote_of_the_run(book: int, newest: bool):
        entries = placed[book]
        for maker, seq in (reversed(entries) if newest else entries):
            if not senders.busy(maker):
                return maker, seq
        return None

    entries: list = []
    n_planted = 0
    for k in range(count):
        kind = kinds[k]
        rank = ranks[k]
        pair = ranking[rank]
        side = ASK if rng.random() < 0.5 else BID
        book = market.book_no(pair, side)
        fields: dict = {}
        tx_type = TxType.ttOFFER_CREATE

        if kind == "cancel":
            found = None
            for step in range(n_pairs):
                pair = ranking[(rank + step) % n_pairs]
                book = market.book_no(pair, side)
                found = quote_of_the_run(book, newest=True)
                if found is not None:
                    break
            if found is None:
                kind = "rest"  # the run has placed nothing yet
            else:
                sender, old = found
                del placed[book][found]
                tx_type = TxType.ttOFFER_CANCEL
                fields[sfOfferSequence] = old

        if kind == "rest":
            counters["rest"] += 1
            found = None
            if counters["rest"] % int(params["replace_every"]) == 0:
                found = quote_of_the_run(book, newest=False)
            if found is not None:
                sender, old = found
                del placed[book][found]
                fields[sfOfferSequence] = old
            else:
                sender = senders.next("makers", makers)
            ticks = rng.randint(maker_lo, maker_hi)
            units = Fraction(rng.randint(quote_lo, quote_hi))
            pays, gets = market.offer_amounts(pair, side, ticks, units)
            fields[sfTakerPays], fields[sfTakerGets] = pays, gets
        elif kind == "cross":
            counters["cross"] += 1
            sender = senders.next(("pair", pair), market.holders(pair))
            whole = rng.randint(take_lo, take_hi) - 1
            part = rng.randint(25, 75)
            units = Fraction(market.seed_units * (100 * whole + part), 100)
            pays, gets = market.offer_amounts(pair, side, -through, units)
            fields[sfTakerPays], fields[sfTakerGets] = pays, gets
            flags = 0
            if counters["cross"] % int(params["ioc_every"]) == 0:
                flags |= tfImmediateOrCancel
            if counters["cross"] % int(params["sell_every"]) == \
                    int(params["sell_every"]) // 2:
                flags |= tfSell
            if flags:
                fields[sfFlags] = flags
        elif kind == "xpay":
            tx_type = TxType.ttPAYMENT
            base, quote = market.pairs[pair]
            holders = market.holders(pair)
            sender = senders.next(("pair", pair), holders)
            units = Fraction(rng.randint(pay_lo, pay_hi))
            # a BID-side payment delivers the base and sends the quote
            # (it takes the asks), an ASK-side one the reverse
            cost = market.quote_units(pair, ASK, 0, units)
            if side == BID:
                deliver = market.amount(base, units)
                send = market.amount(quote, rounded(cost * (1 + slack)))
            else:
                deliver = market.amount(quote, cost)
                send = market.amount(base, rounded(units * (1 + slack)))
            fields[sfAmount], fields[sfSendMax] = deliver, send
            fields[sfDestination] = market.account_id(
                other_holder(("to", pair), holders, sender))
        elif kind == "ipay":
            tx_type = TxType.ttPAYMENT
            base = market.pairs[pair][0]
            holders = market.currency_holders(base)
            sender = senders.next(("pair", base), holders)
            fields[sfAmount] = market.iou(
                base, Fraction(rng.randint(pay_lo, pay_hi)))
            fields[sfDestination] = market.account_id(
                other_holder(("to", base), holders, sender))
        elif kind == "spay":
            tx_type = TxType.ttPAYMENT
            takers = market.takers()
            sender = senders.next("takers", takers)
            fields[sfAmount] = STAmount.from_drops(int(params["amount_drops"]))
            fields[sfDestination] = market.account_id(
                other_holder("to", takers, sender))

        seq = sequence(sender)
        if kind == "rest":
            placed[book][(sender, seq)] = None
        key = market.key(sender)
        tx = SerializedTransaction.build(
            tx_type, key.account_id, seq, fee, fields)
        tx.sign(key)
        blob = tx.serialize()
        senders.used(sender)
        entries.append((blob, False, kind, sender, tx.txid()))
        if k in planted_at:
            bad = workload.corrupt(blob, n_planted)
            n_planted += 1
            entries.append((
                bad, True, kind, sender,
                SerializedTransaction.from_bytes(bad).txid()))
    return entries
