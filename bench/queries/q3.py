"""The plain reference's answer to bench/queries/q3.sql: `answer(t)` takes the
configuration's tables as pandas frames and returns the rows the SQL asks
for, in its ORDER BY order. A copy of `ballista_tpu/testing/reference.py`'s
q3; imports nothing of the program."""

import pandas as pd


def answer(t):
    day = pd.Timestamp("1995-03-15")
    c = t["customer"][t["customer"].c_mktsegment == "BUILDING"]
    o = t["orders"][t["orders"].o_orderdate < day]
    l = t["lineitem"][t["lineitem"].l_shipdate > day].copy()
    x = c.merge(o, left_on="c_custkey", right_on="o_custkey").merge(
        l, left_on="o_orderkey", right_on="l_orderkey"
    )
    x["revenue"] = x.l_extendedprice * (1 - x.l_discount)
    g = x.groupby(["l_orderkey", "o_orderdate", "o_shippriority"], as_index=False)["revenue"].sum()
    g = g[["l_orderkey", "revenue", "o_orderdate", "o_shippriority"]]
    return g.sort_values(["revenue", "o_orderdate"], ascending=[False, True]).head(10).reset_index(drop=True)
