"""The benchmark's own arithmetic over a ledger of the exchange
deployment, independent of the engine: one walk of the ledger's entries
(``snapshot``), then sums and comparisons in whole numbers.

- ``conservation``: for each currency the sum of all holders' balances
  equals what set-up issued, to the last digit (no transfer fee, so
  trading moves IOUs and makes none). The sums are exact integers in
  units of the finest balance's last digit. They can be exact because
  the deployment keeps every line's balance in one decade (funded
  500,000, trades of hundreds): an amount added to one line and taken
  from another is then cut to the same last digit on both.
- ``coins``: the account roots' STR add up to the header's total, and
  that is the genesis total less the fees of the applied transactions.
- ``owner_counts``: an account's owner count is its trust lines (those
  it set a limit on) plus its live offers.
- ``crossed_books``: in no pair does the best funded offer of one book
  cross the best funded offer of the opposite book (the product of the
  two prices, each TakerPays over TakerGets, is not under 1): a
  crossable pair left standing is a matching bug. An offer's price is
  the rate it was placed at, which its book directory's key carries
  (``rate_of``): the remainder of an order that crossed at better than
  its limit stays in the book at that limit while its two amounts, what
  it still wants and what it has left to give, imply another ratio.
- ``book_offers_in_order``: a book's offers by a direct walk of its
  directories (quality order, page order, entry order), for comparison
  with the RPC's answer.

Each check returns a list of problems, empty when it holds.
"""

from __future__ import annotations

from fractions import Fraction

CROSS_GUARD = Fraction(1, 10 ** 12)  # two roundings of 10^-16 are no cross


class Snapshot:
    """The entries of one ledger by kind."""

    def __init__(self):
        self.accounts: dict = {}  # id -> (drops, owner count)
        self.lines: list = []  # (low, high, currency, balance, lo lim, hi lim)
        # index -> (owner, pays, gets, sequence, book directory)
        self.offers: dict = {}
        self.dirs: dict = {}  # index -> (indexes, next page, is a book's)
        self.entries = 0
        self.coins = 0
        self.reserve_base = self.reserve_increment = 0


def snapshot(ledger) -> Snapshot:
    from stellard_tpu.protocol.formats import LedgerEntryType
    from stellard_tpu.protocol.sfields import (
        sfAccount, sfBalance, sfBookDirectory, sfExchangeRate, sfHighLimit,
        sfIndexNext,
        sfIndexes, sfLedgerEntryType, sfLowLimit, sfOwnerCount, sfSequence,
        sfTakerGets, sfTakerPays,
    )
    from stellard_tpu.protocol.stobject import STObject

    lt = LedgerEntryType
    snap = Snapshot()
    snap.coins = ledger.tot_coins
    snap.reserve_base = ledger.reserve_base
    snap.reserve_increment = ledger.reserve_increment
    for item in ledger.state_map.items():
        sle = STObject.from_bytes(item.data)
        kind = sle.get(sfLedgerEntryType)
        snap.entries += 1
        if kind == int(lt.ltACCOUNT_ROOT):
            snap.accounts[sle[sfAccount]] = (
                sle[sfBalance].drops(), sle.get(sfOwnerCount, 0))
        elif kind == int(lt.ltRIPPLE_STATE):
            low, high = sle[sfLowLimit], sle[sfHighLimit]
            snap.lines.append((low.issuer, high.issuer, low.currency,
                               sle[sfBalance], low, high))
        elif kind == int(lt.ltOFFER):
            snap.offers[item.tag] = (sle[sfAccount], sle[sfTakerPays],
                                     sle[sfTakerGets], sle[sfSequence],
                                     sle[sfBookDirectory])
        elif kind == int(lt.ltDIR_NODE):
            snap.dirs[item.tag] = (list(sle.get(sfIndexes, [])),
                                   sle.get(sfIndexNext, 0),
                                   sfExchangeRate in sle)
    return snap


def exact(amount) -> Fraction:
    """An amount's signed value as an exact rational (STR in drops)."""
    if amount.is_native:
        return Fraction(amount.drops())
    value = Fraction(amount.mantissa) * Fraction(10) ** amount.offset
    return -value if amount.negative else value


def rate_of(book_directory: bytes) -> Fraction:
    """The price a book directory's key carries in its last 8 bytes:
    TakerPays over TakerGets when the offer was placed, as one byte of
    exponent (plus 100) and 56 bits of mantissa."""
    packed = int.from_bytes(book_directory[-8:], "big")
    return Fraction(packed & ((1 << 56) - 1)) * Fraction(10) ** (
        (packed >> 56) - 100)


def holdings(snap: Snapshot) -> dict:
    """-> {(holder, currency, issuer): exact balance} for both ends of
    every line (the issuer's own end is the negative of its holder's)."""
    out: dict = {}
    for low, high, currency, balance, _lo, _hi in snap.lines:
        value = exact(balance)  # positive: the low account holds
        out[(low, currency, high)] = value
        out[(high, currency, low)] = -value
    return out


def conservation(snap: Snapshot, issued: dict) -> list[str]:
    """``issued``: {(currency, gateway): units paid out in set-up}."""
    problems = []
    sums = {key: Fraction(0) for key in issued}
    digits = {}
    for low, high, currency, balance, _lo, _hi in snap.lines:
        for issuer, sign in ((high, 1), (low, -1)):  # the other end holds
            key = (currency, issuer)
            if key in sums:
                sums[key] += sign * exact(balance)
                if balance.mantissa:
                    digits.setdefault(key, set()).add(balance.offset)
    for key, want in issued.items():
        if sums[key] != want:
            problems.append(
                f"currency {key[0][12:15].decode()}: holders hold "
                f"{sums[key]} = {float(sums[key])!r}, set-up issued {want} "
                f"(off by {float(sums[key] - want)!r}; balances end at "
                f"10^{sorted(digits.get(key, ()))})")
    return problems


def coins(snap: Snapshot, genesis_coins: int, fees_burned: int) -> list[str]:
    problems = []
    held = sum(drops for drops, _n in snap.accounts.values())
    if held != snap.coins:
        problems.append(f"account roots hold {held} drops, the header "
                        f"says {snap.coins}")
    if snap.coins != genesis_coins - fees_burned:
        problems.append(
            f"the header's total is {snap.coins}, genesis {genesis_coins} "
            f"less {fees_burned} of fees is {genesis_coins - fees_burned}")
    return problems


def owner_counts(snap: Snapshot) -> list[str]:
    owned = {account: 0 for account in snap.accounts}
    for low, high, _c, _b, lo_limit, hi_limit in snap.lines:
        if lo_limit.mantissa:
            owned[low] = owned.get(low, 0) + 1
        if hi_limit.mantissa:
            owned[high] = owned.get(high, 0) + 1
    for owner, *_rest in snap.offers.values():
        owned[owner] = owned.get(owner, 0) + 1
    return [
        f"account {account.hex()[:12]} owns {owned.get(account, 0)} lines "
        f"and offers, its owner count is {count}"
        for account, (_drops, count) in snap.accounts.items()
        if owned.get(account, 0) != count]


def book_key(pays, gets) -> tuple:
    zero = b"\x00" * 20
    return (pays.currency, zero if pays.is_native else pays.issuer,
            gets.currency, zero if gets.is_native else gets.issuer)


def book_counts(snap: Snapshot) -> dict:
    """-> {(pays currency, pays issuer, gets currency, gets issuer):
    offers standing in that book}."""
    out: dict = {}
    for _owner, pays, gets, _seq, _dir in snap.offers.values():
        key = book_key(pays, gets)
        out[key] = out.get(key, 0) + 1
    return out


def _funded(snap: Snapshot, held: dict, owner: bytes, gets) -> bool:
    if gets.is_native:
        drops, count = snap.accounts[owner]
        reserve = snap.reserve_base + count * snap.reserve_increment
        return drops - reserve > 0
    if owner == gets.issuer:
        return True
    return held.get((owner, gets.currency, gets.issuer), 0) > 0


def crossed_books(snap: Snapshot) -> list[str]:
    held = holdings(snap)
    best: dict = {}  # book -> (price, offer index)
    for index, (owner, pays, gets, _seq, directory) in snap.offers.items():
        if exact(gets) <= 0 or not _funded(snap, held, owner, gets):
            continue
        price = rate_of(directory)
        key = book_key(pays, gets)
        if key not in best or price < best[key][0]:
            best[key] = (price, index)
    problems = []
    for key, (price, index) in best.items():
        opposite = key[2:] + key[:2]
        if opposite < key or opposite not in best:
            continue
        other, other_index = best[opposite]
        if price * other < 1 - CROSS_GUARD:
            problems.append(
                f"offer {index.hex()[:12]} at {float(price)!r} and offer "
                f"{other_index.hex()[:12]} of the opposite book at "
                f"{float(other)!r} cross (product {float(price * other)!r}) "
                f"and both stand")
    return problems


def book_offers_in_order(snap: Snapshot, key: tuple) -> list[bytes]:
    """The offers of one book by a direct walk of its directories."""
    from stellard_tpu.state import indexes

    base = indexes.book_base(*key)
    end = indexes.quality_next(base)
    out = []
    for root in sorted(k for k, d in snap.dirs.items()
                       if d[2] and base <= k < end):
        page = 0
        while True:
            node = snap.dirs.get(indexes.dir_node_index(root, page))
            if node is None:
                break
            out.extend(i for i in node[0] if i in snap.offers)
            page = node[1]
            if not page:
                break
    return out


def depth_by_side(snap: Snapshot, market, pairs: list[int]) -> list[int]:
    """Offers standing on each side of each of ``pairs``: [ask, bid,
    ask, bid, ...]."""
    counts = book_counts(snap)
    out = []
    for pair in pairs:
        base, quote = market.pairs[pair]
        one = market.amount(base, Fraction(1))
        other = market.amount(quote, Fraction(1))
        out.append(counts.get(book_key(other, one), 0))  # asks: gets base
        out.append(counts.get(book_key(one, other), 0))
    return out
