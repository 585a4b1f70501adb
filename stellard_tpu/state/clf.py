"""The Stellar CLF layer: canonical-ledger persistence + typed SQL mirror.

Role parity with the reference's second (Stellar-specific) ledger plane
(/root/reference/src/ledger/): alongside the rippled-style NodeStore, every
ledger close is committed to a SQL database in one atomic transaction —

- ``StoreState``: the last-closed-ledger hash and its serialized header
  (LedgerDatabase.h:10-63 kLastClosedLedger/kLastClosedLedgerContent),
- typed row mirrors of the ledger entries: ``accounts`` / ``trustlines``
  / ``offers`` (AccountEntry/TrustLine/OfferEntry.cpp), updated from the
  SHAMap delta between the previous and new ledger (LedgerMaster::catchUp,
  LegacyCLF::getDeltaSince) or rebuilt from a full ledger walk
  (importLedgerState).

The scoped-transaction rule is the crash-safety contract
(LedgerDatabase.h ScopedTransaction): either the whole close lands (state
hash + rows) or none of it does, so a kill -9 mid-commit resumes from the
previous consistent ledger.
"""

from __future__ import annotations

import sqlite3
import threading
from typing import Optional

from ..protocol.formats import LedgerEntryType
from ..protocol.sfields import (
    sfAccount,
    sfBalance,
    sfFlags,
    sfHighLimit,
    sfLedgerEntryType as _LE_TYPE_FIELD,
    sfLowLimit,
    sfOwnerCount,
    sfRegularKey,
    sfSequence,
    sfTakerGets,
    sfTakerPays,
)
from ..protocol.stobject import STObject
from ..utils.sqlrows import write_rows

__all__ = ["LedgerSqlDatabase", "CLFMirror"]

_SCHEMA = [
    "PRAGMA journal_mode=WAL;",
    "PRAGMA synchronous=NORMAL;",
    """CREATE TABLE IF NOT EXISTS StoreState (
        StateName TEXT PRIMARY KEY,
        State     BLOB
    );""",
    """CREATE TABLE IF NOT EXISTS accounts (
        account_id  TEXT PRIMARY KEY,
        balance     INTEGER,
        sequence    INTEGER,
        owner_count INTEGER,
        flags       INTEGER,
        regular_key TEXT
    );""",
    """CREATE TABLE IF NOT EXISTS trustlines (
        index_hex   TEXT PRIMARY KEY,
        low_account  TEXT,
        high_account TEXT,
        currency    TEXT,
        balance_str TEXT,
        low_limit   TEXT,
        high_limit  TEXT,
        flags       INTEGER
    );""",
    """CREATE TABLE IF NOT EXISTS offers (
        index_hex   TEXT PRIMARY KEY,
        account_id  TEXT,
        sequence    INTEGER,
        taker_pays  TEXT,
        taker_gets  TEXT,
        flags       INTEGER
    );""",
    "CREATE INDEX IF NOT EXISTS offers_by_account ON offers(account_id);",
    "CREATE INDEX IF NOT EXISTS lines_by_low ON trustlines(low_account);",
    "CREATE INDEX IF NOT EXISTS lines_by_high ON trustlines(high_account);",
]

K_LCL_HASH = "LastClosedLedger"
K_LCL_CONTENT = "LastClosedLedgerContent"


# table -> (upsert head, columns, delete-by-key head); the order the
# tables are written in
_TABLES = {
    "accounts": ("INSERT OR REPLACE INTO accounts VALUES ", 6,
                 "DELETE FROM accounts WHERE account_id IN (VALUES "),
    "trustlines": ("INSERT OR REPLACE INTO trustlines VALUES ", 8,
                   "DELETE FROM trustlines WHERE index_hex IN (VALUES "),
    "offers": ("INSERT OR REPLACE INTO offers VALUES ", 6,
               "DELETE FROM offers WHERE index_hex IN (VALUES "),
}


def entry_row(index: bytes, sle: STObject) -> Optional[tuple]:
    """-> (table, the entry's mirror row), or None: directory, amendment
    and fee entries have no row mirror (reference LedgerEntry::makeEntry
    returns null for them too)."""
    letype = LedgerEntryType(sle[_LE_TYPE_FIELD])
    if letype == LedgerEntryType.ltACCOUNT_ROOT:
        return "accounts", (
            sle[sfAccount].hex(),
            sle[sfBalance].drops(),
            sle.get(sfSequence, 0),
            sle.get(sfOwnerCount, 0),
            sle.get(sfFlags, 0),
            (sle.get(sfRegularKey) or b"").hex(),
        )
    if letype == LedgerEntryType.ltRIPPLE_STATE:
        low = sle[sfLowLimit]
        high = sle[sfHighLimit]
        return "trustlines", (
            index.hex(),
            low.issuer.hex(),
            high.issuer.hex(),
            low.currency.hex(),
            sle[sfBalance].value_text(),
            low.value_text(),
            high.value_text(),
            sle.get(sfFlags, 0),
        )
    if letype == LedgerEntryType.ltOFFER:
        return "offers", (
            index.hex(),
            sle[sfAccount].hex(),
            sle.get(sfSequence, 0),
            repr(sle[sfTakerPays]),
            repr(sle[sfTakerGets]),
            sle.get(sfFlags, 0),
        )
    return None


def entry_key(index: bytes, sle: STObject) -> Optional[tuple]:
    """-> (table, the one-column key row a deleted entry's mirror row
    goes by), or None for an entry without a row."""
    letype = LedgerEntryType(sle[_LE_TYPE_FIELD])
    if letype == LedgerEntryType.ltACCOUNT_ROOT:
        return "accounts", (sle[sfAccount].hex(),)
    if letype == LedgerEntryType.ltRIPPLE_STATE:
        return "trustlines", (index.hex(),)
    if letype == LedgerEntryType.ltOFFER:
        return "offers", (index.hex(),)
    return None


# stored rows a table gathers before they are written: a whole-state
# import of a million accounts holds this many rows, not the state's
_IMPORT_BATCH = 32_768


class _EntryRows:
    """The mirror rows of one commit, gathered by table and written as
    multi-row statements, `_IMPORT_BATCH` rows at a time. A key is
    stored or deleted, never both: a SHAMap delta has one verdict a key
    and an import deletes nothing, so writing a table's stored rows
    before its deleted keys gives what the entries' own order gave.
    Within a table the stored rows keep that order (and with it their
    rowid order, which `offer_keys` hands the book index)."""

    def __init__(self, db: "LedgerSqlDatabase"):
        self.db = db
        self.stored: dict[str, list] = {t: [] for t in _TABLES}
        self.deleted: dict[str, list] = {t: [] for t in _TABLES}
        self.rows = 0
        self.statements = 0

    def store(self, item) -> None:
        got = entry_row(item.tag, _parsed(item))
        if got is not None:
            table, row = got
            pending = self.stored[table]
            pending.append(row)
            if len(pending) >= _IMPORT_BATCH:
                self._write_stored(table)

    def delete(self, item) -> None:
        got = entry_key(item.tag, _parsed(item))
        if got is not None:
            self.deleted[got[0]].append(got[1])

    def _write_stored(self, table: str) -> None:
        head, ncols, _delete = _TABLES[table]
        pending = self.stored[table]
        self.statements += self.db.write_rows(head, ncols, pending)
        self.rows += len(pending)
        pending.clear()

    def finish(self) -> None:
        for table, (_head, _ncols, delete) in _TABLES.items():
            self._write_stored(table)
            keys = self.deleted[table]
            self.statements += self.db.write_rows(delete, 1, keys, ")")
            self.rows += len(keys)
            keys.clear()


def _parsed(item) -> STObject:
    # the engine pinned a parsed mirror on every item it wrote
    # (Ledger.write_entry); reuse it — re-parsing every changed entry was
    # the commit's dominant Python cost, and on the close-pipeline worker
    # it stole GIL time from the next ledger's apply
    sle = item.parsed
    return sle if sle is not None else STObject.from_bytes(item.data)


class LedgerSqlDatabase:
    """SQLite CLF store with explicit scoped transactions."""

    def __init__(self, path: str = ":memory:"):
        self.path = path
        # autocommit mode: transaction boundaries are ONLY the explicit
        # BEGIN/COMMIT of the scoped transaction (python sqlite3's
        # implicit-BEGIN magic would otherwise fight the scope)
        self._conn = sqlite3.connect(
            path, check_same_thread=False, isolation_level=None
        )
        self._lock = threading.RLock()
        with self._lock:
            for stmt in _SCHEMA:
                self._conn.execute(stmt)

    # -- state store ------------------------------------------------------

    def get_state(self, name: str) -> Optional[bytes]:
        with self._lock:
            row = self._conn.execute(
                "SELECT State FROM StoreState WHERE StateName=?", (name,)
            ).fetchone()
        return row[0] if row else None

    def set_state(self, name: str, value: bytes) -> None:
        with self._lock:
            self._conn.execute(
                "INSERT OR REPLACE INTO StoreState (StateName, State) VALUES (?, ?)",
                (name, value),
            )

    # -- scoped transaction ----------------------------------------------

    def transaction(self):
        """`with db.transaction():` — commit on clean exit, rollback on
        exception (the reference ScopedTransaction contract)."""
        return _Scoped(self)

    # -- typed rows --------------------------------------------------------

    def write_rows(self, head: str, ncols: int, rows: list,
                   tail: str = "") -> int:
        """utils.sqlrows.write_rows on this connection, inside the
        caller's scoped transaction; -> statements executed."""
        with self._lock:
            return write_rows(self._conn, head, ncols, rows, tail)

    def drop_all_entries(self) -> None:
        with self._lock:
            for table in ("accounts", "trustlines", "offers"):
                self._conn.execute(f"DELETE FROM {table}")

    def count(self, table: str) -> int:
        with self._lock:
            return self._conn.execute(f"SELECT COUNT(*) FROM {table}").fetchone()[0]

    def query(self, sql: str, args: tuple = ()) -> list:
        with self._lock:
            return self._conn.execute(sql, args).fetchall()

    def close(self) -> None:
        with self._lock:
            self._conn.close()


class _Scoped:
    def __init__(self, db: LedgerSqlDatabase):
        self.db = db

    def __enter__(self):
        self.db._lock.acquire()
        self.db._conn.execute("BEGIN")
        return self.db

    def __exit__(self, exc_type, exc, tb):
        try:
            if exc_type is None:
                self.db._conn.commit()
            else:
                self.db._conn.rollback()
        finally:
            self.db._lock.release()
        return False


class CLFMirror:
    """The stellar::LedgerMaster role: keep the SQL mirror in lockstep
    with the closed-ledger chain."""

    def __init__(self, db: LedgerSqlDatabase):
        self.db = db
        self.commits = 0
        self.full_imports = 0

    @property
    def last_closed_hash(self) -> Optional[bytes]:
        raw = self.db.get_state(K_LCL_HASH)
        return raw if raw else None

    # -- close commit -------------------------------------------------------

    def commit_ledger_close(self, new_ledger,
                            prev_ledger=None) -> tuple[int, int]:
        """One atomic SQL transaction: entry-row delta + LCL state
        (reference: commitLedgerClose → catchUp → updateDBFromLedger).
        -> (rows bound to statements, a deleted entry's key among them;
        statements executed)."""
        stored = self.last_closed_hash
        if prev_ledger is None or stored != prev_ledger.hash():
            # mirror out of lockstep (fresh db, or we skipped ledgers):
            # rebuild from the full state walk
            return self.import_ledger_state(new_ledger)
        delta = new_ledger.state_map.compare(prev_ledger.state_map)
        with self.db.transaction():
            entries = _EntryRows(self.db)
            for new_item, old_item in delta.values():
                if new_item is not None:
                    entries.store(new_item)
                elif old_item is not None:
                    entries.delete(old_item)
            entries.finish()
            statements = entries.statements + self._write_lcl_state(new_ledger)
        self.commits += 1
        return entries.rows + 2, statements

    def import_ledger_state(self, ledger) -> tuple[int, int]:
        """Full rebuild (reference importLedgerState): drop rows, walk the
        whole state tree, then swap the LCL pointer — atomically."""
        with self.db.transaction():
            self.db.drop_all_entries()
            entries = _EntryRows(self.db)
            for item in ledger.state_map.items():
                entries.store(item)
            entries.finish()
            statements = entries.statements + self._write_lcl_state(ledger)
        self.full_imports += 1
        return entries.rows + 2, statements

    def _write_lcl_state(self, ledger) -> int:
        return self.db.write_rows(
            "INSERT OR REPLACE INTO StoreState (StateName, State) VALUES ", 2,
            [(K_LCL_HASH, ledger.hash()),
             (K_LCL_CONTENT, ledger.header_bytes())])

    # -- resume -------------------------------------------------------------

    def load_last_known(self, nodestore, hash_batch=None, lazy=False):
        """reference loadLastKnownCLF: resume the chain from the SQL state
        pointer, rebuilding the ledger from the NodeStore; returns the
        Ledger or None when there is nothing (or something broken) saved.
        `lazy` opens the trees with on-demand node faulting (O(1) boot
        regardless of state size, out-of-core plane)."""
        from .ledger import Ledger

        lkcl = self.last_closed_hash
        if not lkcl:
            return None
        try:
            led = Ledger.load(nodestore, lkcl, hash_batch=hash_batch,
                              lazy=lazy)
        except (KeyError, ValueError):
            return None
        return led

    def offer_keys(self, ledger) -> Optional[list]:
        """The state indexes of every offer of `ledger`, from the row
        mirror — or None unless the mirror is in lockstep with exactly
        that ledger (rows and the LCL pointer commit in one
        transaction, so an equal pointer means the rows are its)."""
        if self.last_closed_hash != ledger.hash():
            return None
        return [bytes.fromhex(r[0])
                for r in self.db.query("SELECT index_hex FROM offers")]

    def get_json(self) -> dict:
        return {
            "last_closed": (self.last_closed_hash or b"").hex(),
            "accounts": self.db.count("accounts"),
            "trustlines": self.db.count("trustlines"),
            "offers": self.db.count("offers"),
            "commits": self.commits,
            "full_imports": self.full_imports,
        }
