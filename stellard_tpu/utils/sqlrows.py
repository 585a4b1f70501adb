"""Many rows in one SQLite statement.

CPython's ``sqlite3`` gives the interpreter lock up around every
``sqlite3_step``, so ``executemany`` over n rows (or n ``execute``
calls) hands the lock over n times, and inside a busy node each
hand-over waits for whichever thread holds it to let go (up to the
switch interval when that thread is in pure Python; PERF.md section 6,
PR 31). ``write_rows`` sends the same rows, in the same order, as
``head (?,..),(?,..),... tail``: one hand-over a statement.

Needs Python 3.11 (``Connection.getlimit``).
"""

from __future__ import annotations

import sqlite3
from itertools import chain
from typing import Sequence

__all__ = ["write_rows"]

# rows of a full statement: a ledger of thousands of rows is still a few
# dozen statements, and a full statement's text is short enough (tens of
# KB) for sqlite3's statement cache to hold without weight
_FULL_ROWS = 1024


def _rows_per_statement(conn: sqlite3.Connection, ncols: int) -> int:
    """Rows of ``ncols`` values a full statement carries: `_FULL_ROWS`,
    or fewer where the connection's own bound-variable limit is tighter
    (SQLite's default is 32,766 variables from 3.32 and 999 before; a
    distribution may build it higher), never an option."""
    limit = conn.getlimit(sqlite3.SQLITE_LIMIT_VARIABLE_NUMBER)
    return max(1, min(_FULL_ROWS, limit // ncols))


def write_rows(conn: sqlite3.Connection, head: str, ncols: int,
               rows: Sequence[tuple], tail: str = "") -> int:
    """Execute ``head`` + one ``(?,..)`` group a row + ``tail`` over
    ``rows`` (tuples of ``ncols`` values), in order; -> the statements
    executed. A full statement carries a FIXED number of rows, so its
    text repeats from ledger to ledger and the connection's statement
    cache holds it compiled; the remainder goes as one odd statement.
    The caller owns the lock, the transaction and the commit."""
    if not rows:
        return 0
    per = _rows_per_statement(conn, ncols)
    group = "(" + ",".join("?" * ncols) + ")"
    full = head + ",".join([group] * per) + tail if len(rows) >= per else ""
    statements = 0
    for at in range(0, len(rows), per):
        chunk = rows[at:at + per]
        sql = full if len(chunk) == per else (
            head + ",".join([group] * len(chunk)) + tail)
        conn.execute(sql, tuple(chain.from_iterable(chunk)))
        statements += 1
    return statements
