"""h2oai db-benchmark, task groupby, question 10 "sum v3 count by id1:id6":
the plain reference in numpy / pandas. Imports nothing of the program.

The six keys are factorized each by itself (sorted), their codes folded into
one int64 key whose order is the keys' lexicographic order, and the groups
found by one stable sort of it; `sum(v3)` is a float64 sum in row order within
a group, `count(*)` an int64 count. String keys come back categorical. The
rows come back in the sort's order; the SQL promises no order, and the
comparison puts both sides into one (lib/generator_h2o_groupby.compare)."""

import numpy as np
import pandas as pd

KEYS = ("id1", "id2", "id3", "id4", "id5", "id6")


def answer(t):
    x = t["x"]
    codes, uniques = [], []
    key, span = np.zeros(len(x), dtype=np.int64), 1
    for k in KEYS:
        c, u = pd.factorize(x[k], sort=True)
        codes.append(c)
        uniques.append(u)
        if span * len(u) >= 1 << 63:  # re-rank the key so far: its order stays
            _, key = np.unique(key, return_inverse=True)
            span = int(key.max()) + 1
        key, span = key * len(u) + c, span * len(u)
    order = np.argsort(key, kind="stable")  # by id1, then id2, ... id6
    sorted_key = key[order]
    first = np.ones(len(order), dtype=bool)
    first[1:] = sorted_key[1:] != sorted_key[:-1]
    starts = np.flatnonzero(first)
    rows = order[starts]  # a group's first row
    out = {}
    for k, u, c in zip(KEYS, uniques, codes):
        values = np.asarray(u)
        out[k] = (values[c[rows]] if pd.api.types.is_numeric_dtype(values.dtype)
                  else pd.Categorical.from_codes(c[rows], values))
    v3 = x["v3"].to_numpy()[order]
    out["v3"] = np.add.reduceat(v3, starts) if len(starts) else v3[:0]
    out["count"] = np.diff(np.append(starts, len(order))).astype(np.int64)
    return pd.DataFrame(out)
