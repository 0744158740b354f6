"""The least a query must read: bytes of the columns its SQL names, once.

Rows come from the generator's own count of what it wrote, widths from the
generator's schema file (`lib/schema_<generator>.json`, Arrow in-memory
bytes a row), the columns from the SQL text: a column of the configuration's
tables is read when its name appears in the query. Nothing here asks the
program, so the number is the same whatever implements a stage. It is a floor,
not a count of traffic: a join that re-reads or a kernel that widens to 64-bit
lanes moves more, and that is what a roofline share is meant to show.
"""

from __future__ import annotations

import re


def columns_read(sql: str, tables: dict[str, list[str]]) -> dict[str, list[str]]:
    words = set(re.findall(r"[a-z_][a-z0-9_]*", sql.lower()))
    read = {t: [c for c in cols if c in words] for t, cols in tables.items()}
    return {t: cols for t, cols in read.items() if cols}


def query_bytes(sql: str, tables: dict[str, list[str]], rows: dict[str, int],
                schema: dict) -> float:
    return sum(rows[t] * schema["tables"][t][c]["bytes"]
               for t, cols in columns_read(sql, tables).items() for c in cols)
