"""Generator `h2o`: what a configuration with `"generator": "h2o"` gets — the
h2oai db-benchmark `groupby` table `x` from the seed, the reference's answers,
the comparison that decides `correct`, and the schema file that `lib/work.py`
reads widths from.

The recipe is `_data/groupby-datagen.R`'s, row for row (N rows, K = 100):

    id1, id2   sample(sprintf("id%03d", 1:K), N, TRUE)
    id3        sample(sprintf("id%010d", 1:(N/K)), N, TRUE)
    id4, id5   sample(K, N, TRUE)            int32
    id6        sample(N/K, N, TRUE)          int32: N/K groups, about K rows each
    v1, v2     sample(5, ...), sample(15, ...)   int32
    v3         round(runif(N, max=100), 6)   float64

with numpy's generators where R has its own: one seeded stream a column and
file, so a column's values do not depend on which columns are written. N is
`scale` x the configuration's `rows_per_scale`; K never changes, so a smaller
scale keeps the source's K rows a group of id6. No NA, unsorted
(`G1_<N>_1e2_0_0`).

The answers of this suite's queries promise no order (no ORDER BY), so
`compare` puts both sides into one canonical order — every column in turn,
the first ascending, the others descending — and only then compares row for
row: equal rows (peers of a window's ORDER BY, tied in every column that is
returned) stay equal wherever they land.
"""

from __future__ import annotations

import os
import sys
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as pq

from lib import reference

SCHEMA_FILE = "schema_h2o.json"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
COLUMNS = ("id1", "id2", "id3", "id4", "id5", "id6", "v1", "v2", "v3")
ROW_GROUP_ROWS = 256 * 1024


def _labels(fmt: str, codes: np.ndarray, count: int) -> pa.Array:
    """sample(sprintf(fmt, 1:count), ...) for the drawn `codes` (1-based)."""
    names = pa.array([fmt % i for i in range(1, count + 1)])
    return pa.DictionaryArray.from_arrays(pa.array(codes - 1), names).cast(pa.string())


def column(name: str, rows: int, n: int, k: int, seed: int, part: int):
    """`rows` values of column `name` of a table of N = `n` rows: the stream
    of (seed, column, file part)."""
    rng = np.random.default_rng([seed, COLUMNS.index(name), part])
    groups = max(n // k, 1)
    if name in ("id1", "id2"):
        return _labels("id%03d", rng.integers(1, k + 1, rows, dtype=np.int32), k)
    if name == "id3":
        return _labels("id%010d", rng.integers(1, groups + 1, rows, dtype=np.int32), groups)
    if name == "v3":
        return np.round(rng.uniform(0.0, 100.0, rows), 6)
    high = {"id4": k, "id5": k, "id6": groups, "v1": 5, "v2": 15}[name]
    return rng.integers(1, high + 1, rows, dtype=np.int32)


def refuse_unless_runnable(config: dict) -> None:
    """Exits 4, at once and before anything is generated, where the program
    beside bench/ lacks what the configuration says it `needs` (a file of the
    program -> a text it holds, read as text: this process imports nothing of
    the program). A configuration states a need where a program without it
    cannot run the cell inside a run's time, so that such a program fails
    cleanly and soon and does not hang a check."""
    for path, text in config.get("needs", {}).get("program", {}).items():
        try:
            with open(os.path.join(ROOT, path)) as f:
                found = text in f.read()
        except OSError:
            found = False
        if not found:
            print(f"bench: {config['name']} needs {text!r} in {path}, which this program lacks: "
                  f"{config['needs']['why']}", file=sys.stderr)
            sys.exit(4)


def generate(out_dir: str, config: dict, scale: float, seed: int,
             whole: bool = False) -> dict[str, int]:
    """Table `x` with the columns the configuration lists (`whole`: all nine)
    under out_dir/x/part-*.parquet; returns {"x": rows written}."""
    refuse_unless_runnable(config)
    (table, listed), = config["tables"].items()
    columns = list(COLUMNS) if whole else listed
    unknown = [c for c in columns if c not in COLUMNS]
    if table != "x" or unknown:
        raise ValueError(f"h2o groupby has the one table x, and no columns {unknown}")
    n, k, files = int(round(scale * config["rows_per_scale"])), config["k"], config["files"]
    d = os.path.join(out_dir, table)
    os.makedirs(d, exist_ok=True)
    step = -(-n // files)

    def write(part: int) -> int:
        rows = min(step, n - part * step)
        if rows <= 0:
            return 0
        t = pa.table({c: column(c, rows, n, k, seed, part) for c in columns})
        pq.write_table(t, os.path.join(d, f"part-{part:03d}.parquet"),
                       row_group_size=ROW_GROUP_ROWS, compression="zstd")
        return rows

    with ThreadPoolExecutor(max_workers=files) as pool:
        return {table: sum(pool.map(write, range(files)))}


def answers(data_dir: str, config: dict, queries: list[str],
            precision: str = "float64") -> dict:
    return reference.answers(data_dir, config["tables"], queries, precision)


def canonical(df: pd.DataFrame) -> pd.DataFrame:
    """The rows in the one order both sides are compared in: by every column,
    the first ascending, the rest descending."""
    if not len(df) or not df.shape[1]:
        return df
    keys = [df.iloc[:, 0].to_numpy()] + [-df.iloc[:, i].to_numpy(dtype=np.float64)
                                         for i in range(1, df.shape[1])]
    return df.iloc[np.lexsort(keys[::-1])].reset_index(drop=True)


def compare(got: pd.DataFrame, want: pd.DataFrame) -> dict[str, float]:
    if got.shape[1] != want.shape[1]:
        return reference.compare(got, want)
    return reference.compare(canonical(got), canonical(want))


worst = reference.worst
