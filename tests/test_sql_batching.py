"""A closed ledger's rows as a few multi-row statements (utils.sqlrows).

The writer must be the per-row statements it replaced, handed to SQLite
in fewer calls: the same rows through the plain per-row reference kept
HERE (the three ``executemany`` calls of the old ``_insert_tx_rows``; one
``execute`` an entry, ``store_entry`` / ``delete_entry`` as they were)
and through the new writer give identical tables, row for row in rowid
order, in both databases. And it must engage: a ledger of 2,048
transactions goes in a handful of statements, each one hand-over of the
interpreter lock where a row was one.
"""

from __future__ import annotations

import random
import sqlite3
from types import SimpleNamespace

import pytest

from stellard_tpu.node.txdb import TxDatabase
from stellard_tpu.protocol.formats import LedgerEntryType
from stellard_tpu.protocol.sfields import (
    sfAccount,
    sfBalance,
    sfFlags,
    sfHighLimit,
    sfIndexes,
    sfLedgerEntryType,
    sfLowLimit,
    sfOwnerCount,
    sfRegularKey,
    sfRootIndex,
    sfSequence,
    sfTakerGets,
    sfTakerPays,
)
from stellard_tpu.protocol.stamount import STAmount, currency_from_iso
from stellard_tpu.protocol.stobject import STObject
from stellard_tpu.state.clf import (
    K_LCL_CONTENT,
    K_LCL_HASH,
    CLFMirror,
    LedgerSqlDatabase,
)
from stellard_tpu.state.shamap import SHAMapItem
from stellard_tpu.utils.sqlrows import _rows_per_statement as rows_per_statement
from stellard_tpu.utils.sqlrows import write_rows

VARIABLES = sqlite3.SQLITE_LIMIT_VARIABLE_NUMBER
TX_TABLES = ("Ledgers", "Transactions", "AccountTransactions")
CLF_TABLES = ("StoreState", "accounts", "trustlines", "offers")


def h32(tag: str, i: int) -> bytes:
    return random.Random(f"{tag}-{i}").randbytes(32)


def acct(i: int) -> bytes:
    return random.Random(f"acct-{i}").randbytes(20)


def dump(conn, tables, rowid=False):
    cols = "rowid, *" if rowid else "*"
    return {t: conn.execute(f"SELECT {cols} FROM {t} ORDER BY rowid").fetchall()
            for t in tables}


# -- the txdb ----------------------------------------------------------------


def reference_insert_tx_rows(conn, rows):
    """``TxDatabase._insert_tx_rows`` as it was: three executemany calls."""
    tx_rows = []
    del_rows = []
    acct_rows = []
    for (txid, tx_type, account, seq, ledger_seq, status, raw, meta,
         affected, txn_seq) in rows:
        h = txid.hex()
        tx_rows.append((h, tx_type, account.hex(), seq, ledger_seq,
                        status, raw, meta))
        del_rows.append((h,))
        for a in affected:
            acct_rows.append((h, a.hex(), ledger_seq, txn_seq))
    cur = conn.cursor()
    cur.executemany(
        "INSERT OR REPLACE INTO Transactions VALUES (?,?,?,?,?,?,?,?)",
        tx_rows)
    cur.executemany(
        "DELETE FROM AccountTransactions WHERE TransID = ?", del_rows)
    cur.executemany(
        "INSERT INTO AccountTransactions VALUES (?,?,?,?)", acct_rows)


def tx_ledger(seq: int, n_tx: int, affected: int, salt: str = "") -> list:
    """save_transactions' row shape for one ledger."""
    rng = random.Random(f"ledger-{seq}-{salt}")
    return [
        (h32(f"tx-{seq}", i), "ttPAYMENT", acct(i), i + 1, seq, "tesSUCCESS",
         rng.randbytes(24), rng.randbytes(40),
         [acct(i * 31 + k) for k in range(affected)], i)
        for i in range(n_tx)
    ]


def fake_header(seq: int):
    return SimpleNamespace(
        hash=lambda: h32("ledger", seq), seq=seq,
        parent_hash=h32("ledger", seq - 1), tot_coins=10**17,
        close_time=seq * 10, parent_close_time=seq * 10 - 10,
        close_resolution=10, close_flags=0,
        account_hash=h32("state", seq), tx_hash=h32("txs", seq))


def reference_save_ledger(db: TxDatabase, ledger, rows) -> None:
    db._conn.execute(
        "INSERT OR REPLACE INTO Ledgers VALUES (?,?,?,?,?,?,?,?,?,?)",
        db._header_row(ledger))
    reference_insert_tx_rows(db._conn, rows)
    db._conn.commit()


def txdb_pair(limit):
    ref, new = TxDatabase(), TxDatabase()
    if limit is not None:
        new._conn.setlimit(VARIABLES, limit)
    # history that is there already, the same in both
    for db in (ref, new):
        reference_save_ledger(db, fake_header(2), tx_ledger(2, 7, 3))
    return ref, new


# Transactions rows carry 8 values, AccountTransactions rows 4: under a
# limit of 999 a full statement is 124 and 249 rows; under this
# library's own limit the writer's fixed 1,024 of either
TX_CASES = [
    ("no-rows", 0, 3, None),
    ("one-row", 1, 1, None),
    ("tx-chunk-less-1", 123, 2, 999),
    ("tx-chunk", 124, 2, 999),
    ("tx-chunk-plus-1", 125, 2, 999),
    ("acct-chunk-less-1", 124, 2, 999),      # 248 of 249
    ("acct-chunk", 83, 3, 999),              # 249
    ("acct-chunk-plus-1", 125, 2, 999),      # 250
    ("several-chunks", 700, 5, 999),
    ("exchange-ledger-2048x9", 2048, 9, None),
    ("exchange-ledger-2048x9-old-library", 2048, 9, 999),
    ("own-limit-chunk-less-1", 1023, 1, None),
    ("own-limit-chunk", 1024, 1, None),
    ("own-limit-chunk-plus-1", 1025, 1, None),
]


@pytest.mark.parametrize("n_tx,affected,limit",
                         [c[1:] for c in TX_CASES],
                         ids=[c[0] for c in TX_CASES])
def test_txdb_rows_equal_the_per_row_reference(n_tx, affected, limit):
    ref, new = txdb_pair(limit)
    rows = tx_ledger(3, n_tx, affected)
    reference_save_ledger(ref, fake_header(3), rows)
    bound, statements = new.save_ledger(fake_header(3), rows)
    # rowids too: every statement inserts in the reference's order
    assert dump(new._conn, TX_TABLES, rowid=True) == dump(
        ref._conn, TX_TABLES, rowid=True)
    assert bound == 1 + n_tx * (2 + affected)
    per = {cols: rows_per_statement(new._conn, cols) for cols in (8, 1, 4)}
    assert statements == 1 + sum(
        -(-n // per[cols])
        for cols, n in ((8, n_tx), (1, n_tx), (4, n_tx * affected)))
    assert not new._conn.in_transaction  # committed where it was
    for db in (ref, new):
        db.close()


@pytest.mark.parametrize("limit", [None, 999], ids=["own-limit", "999"])
def test_a_ledger_persisted_twice_leaves_no_duplicate_rows(limit):
    """The cleaner's repair path: the rows exist already; REPLACE and
    the DELETE before the INSERT keep one copy. The second pass carries
    other blobs and other affected accounts, so what stays is the
    second pass's."""
    ref, new = txdb_pair(limit)
    first, second = tx_ledger(3, 300, 4), tx_ledger(3, 300, 2, salt="again")
    for rows in (first, second):
        reference_save_ledger(ref, fake_header(3), rows)
        new.save_ledger(fake_header(3), rows)
    assert dump(new._conn, TX_TABLES, rowid=True) == dump(
        ref._conn, TX_TABLES, rowid=True)
    counts = new.counts()
    assert counts["transactions"] == 7 + 300
    assert counts["account_transactions"] == 7 * 3 + 300 * 2
    assert counts["ledgers"] == 2
    for db in (ref, new):
        db.close()


def test_save_transactions_goes_through_the_same_writer():
    """The archive's importer: rows without a header."""
    ref, new = txdb_pair(999)
    rows = tx_ledger(3, 200, 3)
    reference_insert_tx_rows(ref._conn, rows)
    ref._conn.commit()
    seen = []
    new._conn.set_trace_callback(seen.append)
    new.save_transactions(rows)
    new._conn.set_trace_callback(None)
    assert dump(new._conn, TX_TABLES, rowid=True) == dump(
        ref._conn, TX_TABLES, rowid=True)
    # 200 rows of 8, 200 ids, 600 rows of 4 under a limit of 999
    written = [s for s in seen if s.startswith(("INSERT", "DELETE"))]
    assert len(written) == 2 + 1 + 3
    for db in (ref, new):
        db.close()


def test_txdb_failure_in_the_last_chunk_leaves_what_it_left(tmp_path):
    """As ever: the error reaches the caller, nothing of the ledger is
    committed (another connection sees none of it), and the statements
    before the failing one stay pending in the connection's open
    transaction, for the caller to roll back or the next commit to
    carry. The per-row reference stopped at the failing ROW; the writer
    stops at the failing STATEMENT, whole chunks before it pending."""
    path = str(tmp_path / "tx.db")
    db = TxDatabase(path)
    db._conn.setlimit(VARIABLES, 999)
    rows = tx_ledger(3, 300, 2)
    bad = list(rows[-1])
    bad[6] = object()  # RawTxn of the last row: no SQLite type
    rows[-1] = tuple(bad)
    with pytest.raises(sqlite3.Error):
        db.save_ledger(fake_header(3), rows)
    assert db._conn.in_transaction
    other = sqlite3.connect(path)
    assert other.execute("SELECT COUNT(*) FROM Ledgers").fetchone()[0] == 0
    assert other.execute(
        "SELECT COUNT(*) FROM Transactions").fetchone()[0] == 0
    other.close()
    # the header and the two full statements of 124 rows are pending
    pending = db._conn.execute(
        "SELECT COUNT(*) FROM Transactions").fetchone()[0]
    assert pending == 248
    db._conn.rollback()
    assert db.counts() == {
        "transactions": 0, "account_transactions": 0, "ledgers": 0}
    db.close()


def test_an_exchange_ledger_is_a_handful_of_statements():
    """2,048 transactions x 9 affected accounts: 22,529 rows that were
    22,529 hand-overs of the interpreter lock."""
    db = TxDatabase()
    rows = tx_ledger(3, 2048, 9)
    seen = []
    db._conn.set_trace_callback(seen.append)
    bound, statements = db.save_ledger(fake_header(3), rows)
    db._conn.set_trace_callback(None)
    assert bound == 1 + 2048 * 11
    assert statements == sum(
        1 for s in seen if s.startswith(("INSERT", "DELETE")))
    # the header, 2 + 2 + 18 full statements, BEGIN and COMMIT
    assert len(seen) == 25
    assert db.counts() == {"transactions": 2048,
                           "account_transactions": 2048 * 9, "ledgers": 1}
    db.close()


# -- the writer itself ---------------------------------------------------------


@pytest.mark.parametrize("n", [0, 1, 332, 333, 334, 1000])
def test_write_rows_chunks_by_the_connections_own_limit(n):
    conn = sqlite3.connect(":memory:")
    conn.setlimit(VARIABLES, 999)
    conn.execute("CREATE TABLE t (a INTEGER, b TEXT, c BLOB)")
    rows = [(i, f"r{i}", bytes([i % 256])) for i in range(n)]
    seen = []
    conn.set_trace_callback(seen.append)
    statements = write_rows(conn, "INSERT INTO t VALUES ", 3, rows)
    conn.set_trace_callback(None)
    assert rows_per_statement(conn, 3) == 333
    assert statements == -(-n // 333)
    inserts = [s for s in seen if s.startswith("INSERT")]
    assert len(inserts) == statements
    assert conn.execute("SELECT * FROM t ORDER BY rowid").fetchall() == rows
    conn.close()


def test_full_statements_repeat_their_text_from_ledger_to_ledger():
    """What sqlite3's statement cache holds compiled: every full
    statement of a table is one text, whatever the ledger's size; only
    the remainder's text is the ledger's own."""
    texts = []

    class Recording(sqlite3.Connection):
        # the text as handed over (the trace callback's is expanded)
        def execute(self, sql, *args):
            texts.append(sql)
            return super().execute(sql, *args)

    conn = sqlite3.connect(":memory:", factory=Recording)
    conn.execute("CREATE TABLE t (a INTEGER, b TEXT)")
    per = rows_per_statement(conn, 2)
    assert per == 1024  # this library's variable limit is far above
    del texts[:]
    for n in (2 * per + 17, 3 * per + 400):
        write_rows(conn, "INSERT INTO t VALUES ",
                   2, [(i, str(i)) for i in range(n)])
    assert len(texts) == 3 + 4
    assert len(set(texts)) == 1 + 2
    conn.close()


# -- the CLF mirror ------------------------------------------------------------

USD = currency_from_iso("USD")
EUR = currency_from_iso("EUR")


def account_root(i: int, balance: int, seq: int = 1) -> STObject:
    sle = STObject()
    sle[sfLedgerEntryType] = int(LedgerEntryType.ltACCOUNT_ROOT)
    sle[sfAccount] = acct(i)
    sle[sfBalance] = STAmount.from_drops(balance)
    sle[sfSequence] = seq
    sle[sfOwnerCount] = i % 5
    sle[sfFlags] = 0
    if i % 7 == 0:
        sle[sfRegularKey] = acct(i + 10**6)
    return sle


def trust_line(i: int, balance: int) -> STObject:
    sle = STObject()
    sle[sfLedgerEntryType] = int(LedgerEntryType.ltRIPPLE_STATE)
    cur = USD if i % 2 else EUR
    sle[sfBalance] = STAmount.from_iou(cur, b"\x00" * 19 + b"\x01", balance, -2)
    sle[sfLowLimit] = STAmount.from_iou(cur, acct(i), 1_000_000, 0)
    sle[sfHighLimit] = STAmount.from_iou(cur, acct(i + 1), 0, 0)
    sle[sfFlags] = 0x10000
    return sle


def offer(i: int, gets: int) -> STObject:
    sle = STObject()
    sle[sfLedgerEntryType] = int(LedgerEntryType.ltOFFER)
    sle[sfAccount] = acct(i)
    sle[sfSequence] = i + 3
    sle[sfTakerPays] = STAmount.from_iou(USD, acct(0), 5 + i, 0)
    sle[sfTakerGets] = STAmount.from_drops(gets)
    sle[sfFlags] = 0
    return sle


def directory(i: int, value: int) -> STObject:
    sle = STObject()
    sle[sfLedgerEntryType] = int(LedgerEntryType.ltDIR_NODE)
    sle[sfIndexes] = [h32("member", value + k) for k in range(value % 3 + 1)]
    sle[sfRootIndex] = h32("dir", i)
    sle[sfFlags] = 0
    return sle


MAKERS = {"acct": account_root, "line": trust_line, "offer": offer,
          "dir": directory}


def item(kind: str, i: int, value: int) -> SHAMapItem:
    """A state item as the engine leaves it (the parsed entry pinned) or,
    every third one, as a load from disk leaves it (bytes only)."""
    sle = MAKERS[kind](i, value)
    it = SHAMapItem(h32(kind, i), sle.serialize())
    if i % 3:
        it.parsed = sle
    return it


class FakeMap:
    """What the mirror asks of a state map: ``items()`` and
    ``compare()``, the latter in a seeded shuffled order, as a walk of
    two trees interleaves the entry types."""

    def __init__(self, entries: dict):
        self.entries = entries

    def items(self):
        return iter(self.entries.values())

    def compare(self, other: "FakeMap") -> dict:
        tags = [t for t in set(self.entries) | set(other.entries)
                if self.entries.get(t) != other.entries.get(t)]
        tags.sort()
        random.Random(len(tags)).shuffle(tags)
        return {t: (self.entries.get(t), other.entries.get(t)) for t in tags}


def fake_ledger(seq: int, entries: dict):
    return SimpleNamespace(
        seq=seq, state_map=FakeMap(entries), hash=lambda: h32("ledger", seq),
        header_bytes=lambda: b"header" + h32("ledger", seq))


def reference_store_entry(conn, index: bytes, sle: STObject) -> None:
    """``LedgerSqlDatabase.store_entry`` as it was: one execute an entry."""
    letype = LedgerEntryType(sle[sfLedgerEntryType])
    if letype == LedgerEntryType.ltACCOUNT_ROOT:
        conn.execute(
            "INSERT OR REPLACE INTO accounts VALUES (?,?,?,?,?,?)",
            (sle[sfAccount].hex(), sle[sfBalance].drops(),
             sle.get(sfSequence, 0), sle.get(sfOwnerCount, 0),
             sle.get(sfFlags, 0), (sle.get(sfRegularKey) or b"").hex()))
    elif letype == LedgerEntryType.ltRIPPLE_STATE:
        low, high = sle[sfLowLimit], sle[sfHighLimit]
        conn.execute(
            "INSERT OR REPLACE INTO trustlines VALUES (?,?,?,?,?,?,?,?)",
            (index.hex(), low.issuer.hex(), high.issuer.hex(),
             low.currency.hex(), sle[sfBalance].value_text(),
             low.value_text(), high.value_text(), sle.get(sfFlags, 0)))
    elif letype == LedgerEntryType.ltOFFER:
        conn.execute(
            "INSERT OR REPLACE INTO offers VALUES (?,?,?,?,?,?)",
            (index.hex(), sle[sfAccount].hex(), sle.get(sfSequence, 0),
             repr(sle[sfTakerPays]), repr(sle[sfTakerGets]),
             sle.get(sfFlags, 0)))


def reference_delete_entry(conn, index: bytes, sle: STObject) -> None:
    """``LedgerSqlDatabase.delete_entry`` as it was."""
    letype = LedgerEntryType(sle[sfLedgerEntryType])
    if letype == LedgerEntryType.ltACCOUNT_ROOT:
        conn.execute("DELETE FROM accounts WHERE account_id=?",
                     (sle[sfAccount].hex(),))
    elif letype == LedgerEntryType.ltRIPPLE_STATE:
        conn.execute("DELETE FROM trustlines WHERE index_hex=?",
                     (index.hex(),))
    elif letype == LedgerEntryType.ltOFFER:
        conn.execute("DELETE FROM offers WHERE index_hex=?", (index.hex(),))


def _sle(it: SHAMapItem) -> STObject:
    return it.parsed if it.parsed is not None else STObject.from_bytes(it.data)


def reference_commit(db: LedgerSqlDatabase, new, prev) -> None:
    """``CLFMirror.commit_ledger_close`` / ``import_ledger_state`` as
    they were, entry by entry."""
    with db.transaction():
        if prev is None:
            db.drop_all_entries()
            for it in new.state_map.items():
                reference_store_entry(db._conn, it.tag, _sle(it))
        else:
            delta = new.state_map.compare(prev.state_map)
            for tag, (new_item, old_item) in delta.items():
                if new_item is not None:
                    reference_store_entry(db._conn, tag, _sle(new_item))
                elif old_item is not None:
                    reference_delete_entry(db._conn, tag, _sle(old_item))
        db.set_state(K_LCL_HASH, new.hash())
        db.set_state(K_LCL_CONTENT, new.header_bytes())


def exchange_state(n: int) -> dict:
    """n entries of each kind."""
    entries = {}
    for kind in MAKERS:
        for i in range(n):
            it = item(kind, i, 1000 + i)
            entries[it.tag] = it
    return entries


def exchange_close(entries: dict, n: int, changed: int) -> dict:
    """The next ledger: of each kind `changed` entries modified, as many
    deleted and as many created."""
    out = dict(entries)
    for kind in MAKERS:
        for i in range(changed):
            it = item(kind, i, 555_000 + i)                 # modified
            out[it.tag] = it
            del out[h32(kind, n - 1 - i)]                   # deleted
            it = item(kind, n + i, 7)                       # created
            out[it.tag] = it
    return out


CLF_CASES = [("no-change", 40, 0, None), ("one-of-each", 40, 1, None),
             ("chunk-less-1", 400, 123, 999), ("chunk", 400, 124, 999),
             ("chunk-plus-1", 400, 125, 999),
             ("a-busy-close", 3000, 700, None),
             ("a-busy-close-old-library", 3000, 700, 999)]


@pytest.mark.parametrize("n,changed,limit", [c[1:] for c in CLF_CASES],
                         ids=[c[0] for c in CLF_CASES])
def test_clf_rows_equal_the_per_entry_reference(n, changed, limit):
    """A fresh mirror (the whole-state import), then a delta with
    creations, modifications and deletions of account roots, trust lines
    and offers, and of directory nodes, which have no row."""
    ref_db, new_db = LedgerSqlDatabase(), LedgerSqlDatabase()
    if limit is not None:
        new_db._conn.setlimit(VARIABLES, limit)
    mirror = CLFMirror(new_db)
    first = fake_ledger(2, exchange_state(n))
    second = fake_ledger(3, exchange_close(first.state_map.entries, n, changed))

    # a delta has one verdict a key: none is both stored and deleted
    delta = second.state_map.compare(first.state_map)
    assert len(delta) == 4 * 3 * changed
    assert all((a is None) != (b is None) or a != b for a, b in delta.values())

    reference_commit(ref_db, first, None)
    rows, statements = mirror.commit_ledger_close(first, None)
    assert mirror.full_imports == 1
    assert rows == 3 * n + 2
    assert dump(new_db._conn, CLF_TABLES) == dump(ref_db._conn, CLF_TABLES)

    reference_commit(ref_db, second, first)
    rows, statements = mirror.commit_ledger_close(second, first)
    assert mirror.commits == 1
    # 2 stored and 1 deleted of each kind with a row, and the pointer's two
    assert rows == 3 * 3 * changed + 2
    assert dump(new_db._conn, CLF_TABLES) == dump(ref_db._conn, CLF_TABLES)
    assert new_db.count("accounts") == n
    assert mirror.last_closed_hash == second.hash()
    assert not new_db._conn.in_transaction
    for db in (ref_db, new_db):
        db.close()


def test_a_whole_state_import_is_written_a_batch_at_a_time(monkeypatch):
    """The once-a-checkout build of a million accounts must not hold the
    state's rows at once: a table's stored rows go out whenever a batch
    is full, and the mirror is what the per-entry import gives."""
    from stellard_tpu.state import clf

    monkeypatch.setattr(clf, "_IMPORT_BATCH", 64)
    ref_db, new_db = LedgerSqlDatabase(), LedgerSqlDatabase()
    ledger = fake_ledger(2, exchange_state(200))
    reference_commit(ref_db, ledger, None)
    rows, statements = CLFMirror(new_db).commit_ledger_close(ledger, None)
    assert dump(new_db._conn, CLF_TABLES) == dump(ref_db._conn, CLF_TABLES)
    assert rows == 3 * 200 + 2
    assert statements == 3 * 4 + 1  # 64, 64, 64 and 8 a table; the pointer
    for db in (ref_db, new_db):
        db.close()


def test_a_clf_delta_of_5000_entries_is_a_handful_of_statements():
    db = LedgerSqlDatabase()
    mirror = CLFMirror(db)
    first = fake_ledger(2, exchange_state(2000))
    mirror.commit_ledger_close(first, None)
    second = fake_ledger(
        3, exchange_close(first.state_map.entries, 2000, 417))
    assert len(second.state_map.compare(first.state_map)) == 5004
    seen = []
    db._conn.set_trace_callback(seen.append)
    rows, statements = mirror.commit_ledger_close(second, first)
    db._conn.set_trace_callback(None)
    assert rows == 9 * 417 + 2
    written = [s for s in seen if s.startswith(("INSERT", "DELETE"))]
    assert statements == len(written) == 7  # 3 upserts, 3 deletes, pointer
    assert len(seen) <= 24  # the SELECT of the pointer, BEGIN, COMMIT too
    db.close()


def test_clf_failure_in_the_last_chunk_rolls_the_whole_close_back():
    """As ever: the scoped transaction takes rows AND pointer back."""
    db = LedgerSqlDatabase()
    db._conn.setlimit(VARIABLES, 999)
    mirror = CLFMirror(db)
    first = fake_ledger(2, exchange_state(400))
    mirror.commit_ledger_close(first, None)
    before = dump(db._conn, CLF_TABLES)
    entries = exchange_close(first.state_map.entries, 400, 150)
    # the LAST offer stored is one whose row cannot be bound: its
    # statement is the odd one behind the full chunk of 166
    bad = offer(10**6, 1)
    bad[sfSequence] = 2**70
    it = SHAMapItem(b"\xff" * 32, b"")
    it.parsed = bad
    entries[it.tag] = it
    second = fake_ledger(3, entries)
    with pytest.raises((sqlite3.Error, OverflowError)):
        mirror.commit_ledger_close(second, first)
    assert not db._conn.in_transaction
    assert dump(db._conn, CLF_TABLES) == before
    assert mirror.last_closed_hash == first.hash()
    assert mirror.commits == 0
    db.close()
