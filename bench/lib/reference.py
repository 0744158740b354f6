"""The benchmark's plain reference: TPC-H queries written directly against
pandas, and the comparison that decides `correct`.

Each query's reference is `answer(tables)` in `bench/queries/<q>.py`, beside
its SQL (q1, q3, q5 and q6 are copies of the oracle in
`ballista_tpu/testing/reference.py`, which later PRs may edit; these they may
not). Nothing here imports the program or reads more than the generated
parquet files. The loader reads just the columns the configuration lists and
keeps strings dictionary-encoded: 60 M object strings would cost more than
the queries.

`precision="float32"` is the control, never the reference: the same queries
with every float column cast to float32 first — the step below the float64 /
exact-cents arithmetic the configurations state.
"""

from __future__ import annotations

import glob
import importlib.util
import os

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as pq

QUERIES_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "queries")


def load_tables(data_dir: str, tables: dict[str, list[str]],
                precision: str = "float64") -> dict[str, pd.DataFrame]:
    out = {}
    for t, cols in tables.items():
        files = sorted(glob.glob(os.path.join(data_dir, t, "*.parquet")))
        schema = pq.read_schema(files[0])
        strings = [c for c in cols if pa.types.is_string(schema.field(c).type)]
        table = pa.concat_tables(
            pq.read_table(f, columns=cols, read_dictionary=strings) for f in files)
        df = table.unify_dictionaries().to_pandas(date_as_object=False)
        if precision != "float64":
            floats = [c for c in cols if pa.types.is_floating(schema.field(c).type)]
            df = df.astype({c: precision for c in floats})
        out[t] = df
    return out


def query_answer(query: str):
    """`answer(tables)` of bench/queries/<query>.py: a query's reference is a
    file beside its SQL, so a new query is two new files."""
    path = os.path.join(QUERIES_DIR, f"{query}.py")
    spec = importlib.util.spec_from_file_location(f"bench_query_{query}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.answer


def answers(data_dir: str, tables: dict[str, list[str]], queries: list[str],
            precision: str = "float64") -> dict[str, pd.DataFrame]:
    """The reference's answer to each query over the generated files."""
    loaded = load_tables(data_dir, tables, precision)
    return {q: query_answer(q)(loaded) for q in dict.fromkeys(queries)}


# --------------------------------------------------------------- comparison
# What one answer is held to, row for row in the order it came (every query
# here has an ORDER BY or one row): `rows_off` rows too many or too few,
# `cells_off` cells of exact columns (keys, counts, dates, names) that differ,
# `rel_err` the widest relative gap of a float cell. A result that cannot be
# lined up (row or column count) reads rel_err 1.0.

def compare(got: pd.DataFrame, want: pd.DataFrame) -> dict[str, float]:
    if got.shape[1] != want.shape[1] or len(got) != len(want):
        return {"rows_off": abs(len(got) - len(want)) or 1,
                "cells_off": want.size, "rel_err": 1.0}
    cells_off, rel_err = 0, 0.0
    for i, col in enumerate(want.columns):
        a, b = got.iloc[:, i], want[col]
        if pd.api.types.is_float_dtype(b):
            a = a.to_numpy(dtype=np.float64, na_value=np.nan)
            b = b.to_numpy(dtype=np.float64, na_value=np.nan)
            gap = np.abs(a - b) / np.where(b == 0, 1.0, np.abs(b))
            gap = np.where(np.isnan(a) != np.isnan(b), 1.0, np.nan_to_num(gap))
            rel_err = max(rel_err, float(gap.max(initial=0.0)))
        elif pd.api.types.is_datetime64_any_dtype(b):
            same = (pd.to_datetime(a).to_numpy("datetime64[D]")
                    == pd.to_datetime(b).to_numpy("datetime64[D]"))
            cells_off += int((~same).sum())
        else:
            cells_off += int((a.astype(object).to_numpy() != b.astype(object).to_numpy()).sum())
    return {"rows_off": 0, "cells_off": cells_off, "rel_err": rel_err}


def worst(readings: list[dict[str, float]]) -> dict[str, float]:
    """A run's numbers from its answers': the widest gap, and the off rows
    and cells summed."""
    return {"rows_off": sum(r["rows_off"] for r in readings),
            "cells_off": sum(r["cells_off"] for r in readings),
            "rel_err": max((r["rel_err"] for r in readings), default=0.0)}
