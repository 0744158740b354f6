"""The plain reference's answer to bench/queries/q6.sql: `answer(t)` takes the
configuration's tables as pandas frames and returns the rows the SQL asks
for, in its ORDER BY order. A copy of `ballista_tpu/testing/reference.py`'s
q6; imports nothing of the program."""

import pandas as pd


def answer(t):
    l = t["lineitem"]
    number = l.l_discount.dtype.type  # the control's float32 constants stay float32
    m = (
        (l.l_shipdate >= pd.Timestamp("1994-01-01"))
        & (l.l_shipdate < pd.Timestamp("1995-01-01"))
        & (l.l_discount >= number(0.05))
        & (l.l_discount <= number(0.07))
        & (l.l_quantity < 24)
    )
    return pd.DataFrame({"revenue": [(l[m].l_extendedprice * l[m].l_discount).sum()]})
