"""A block's rows for one table as one sqlite statement.

On the commit path a table is touched by one statement a block, not one
step a row: the ``sqlite3`` module gives the interpreter lock away and
takes it back around every ``sqlite3_step``, and beside the launching,
prefetch and committer threads that hand-over costs more than the row
does (ROADMAP D13).  ``executemany`` is one step a row; a multi-row
``VALUES`` list is one step for all of them.
"""

from __future__ import annotations

import sqlite3
from itertools import chain


def row_statements(conn: sqlite3.Connection, rows, *, head: str, width: int,
                   tail: str = ""):
    """Yield ``(sql, flat_params)``: ``rows`` (sequences of ``width``
    values) as ``head (?,..),(?,..),... tail``, all of them in one
    statement, cut only where ``conn`` takes no more variables (32,766
    a statement since sqlite 3.32 unless the build says otherwise, 999
    before).  The values are passed through as they are (a
    ``memoryview`` stays one).  The text depends on the row count
    alone, so blocks of one size share one prepared statement in the
    connection's cache: the ``sqlite3`` module keeps 128, least recently
    used out first, at about 0.7 KB a row, and preparing one anew costs
    about what running it costs (3 ms at 1,000 rows)."""
    per = conn.getlimit(sqlite3.SQLITE_LIMIT_VARIABLE_NUMBER) // width
    group = "(" + ",".join("?" * width) + ")"
    for at in range(0, len(rows), per):
        part = rows[at:at + per]
        yield (f"{head} {','.join([group] * len(part))}{tail}",
               list(chain.from_iterable(part)))
