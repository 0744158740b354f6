"""h2oai db-benchmark, task groupby, question 8 "largest two v3 by id6": the
plain reference in numpy / pandas. Imports nothing of the program. The rows
come back ordered by (id6, v3 descending); the SQL promises no order, and the
comparison puts both sides into one (lib/generator_h2o.compare)."""

import numpy as np
import pandas as pd


def answer(t):
    x = t["x"]
    x = x[x["v3"].notna()]
    id6, v3 = x["id6"].to_numpy(), x["v3"].to_numpy()
    order = np.lexsort((-v3, id6))  # by id6, inside a group by v3 descending
    id6, v3 = id6[order], v3[order]
    at = np.arange(len(id6))
    first = np.ones(len(id6), dtype=bool)
    first[1:] = id6[1:] != id6[:-1]
    rank = at - np.maximum.accumulate(np.where(first, at, 0))  # 0-based row_number
    keep = rank < 2
    return pd.DataFrame({"id6": id6[keep], "largest2_v3": v3[keep]})
