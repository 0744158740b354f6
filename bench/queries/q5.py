"""The plain reference's answer to bench/queries/q5.sql: `answer(t)` takes the
configuration's tables as pandas frames and returns the rows the SQL asks
for, in its ORDER BY order. A copy of `ballista_tpu/testing/reference.py`'s
q5; imports nothing of the program."""

import pandas as pd


def answer(t):
    r = t["region"][t["region"].r_name == "ASIA"]
    n = t["nation"].merge(r, left_on="n_regionkey", right_on="r_regionkey")
    o = t["orders"]
    o = o[(o.o_orderdate >= pd.Timestamp("1994-01-01")) & (o.o_orderdate < pd.Timestamp("1995-01-01"))]
    x = (
        t["customer"]
        .merge(o, left_on="c_custkey", right_on="o_custkey")
        .merge(t["lineitem"], left_on="o_orderkey", right_on="l_orderkey")
        .merge(t["supplier"], left_on="l_suppkey", right_on="s_suppkey")
    )
    x = x[x.c_nationkey == x.s_nationkey]
    x = x.merge(n, left_on="s_nationkey", right_on="n_nationkey")
    x["revenue"] = x.l_extendedprice * (1 - x.l_discount)
    x = x.astype({"n_name": str})
    g = x.groupby("n_name", as_index=False)["revenue"].sum()
    return g.sort_values("revenue", ascending=False).reset_index(drop=True)
