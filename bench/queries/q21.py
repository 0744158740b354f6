"""The plain reference's answer to bench/queries/q21.sql: `answer(t)` takes the
configuration's tables as pandas frames and returns the rows the SQL asks
for, in its ORDER BY order. It reads the SQL literally: EXISTS is l1 merged
with lineitem (l2) on `l_orderkey`, a pair whose `l_suppkey` differs making
the l1 row count; NOT EXISTS is the same merge over the late lines (l3), such
a pair striking the l1 row out. It is written apart from the program's own
oracle (`ballista_tpu/testing/reference.py`'s q21, which counts distinct
suppliers an order instead) and imports nothing of the program."""

import numpy as np


def answer(t):
    l = t["lineitem"]
    l1 = l[l.l_receiptdate > l.l_commitdate]
    l1 = l1.assign(row=np.arange(len(l1)))
    probe = l1[["row", "l_orderkey", "l_suppkey"]]
    # exists (l2.l_orderkey = l1.l_orderkey and l2.l_suppkey <> l1.l_suppkey)
    l2 = probe.merge(l[["l_orderkey", "l_suppkey"]], on="l_orderkey", suffixes=("", "_l2"))
    exists = l2.row[l2.l_suppkey_l2 != l2.l_suppkey].unique()
    # not exists (the same over l3, the lines received after their commit date)
    late = l[l.l_receiptdate > l.l_commitdate]
    l3 = probe.merge(late[["l_orderkey", "l_suppkey"]], on="l_orderkey", suffixes=("", "_l3"))
    struck = l3.row[l3.l_suppkey_l3 != l3.l_suppkey].unique()
    l1 = l1[l1.row.isin(exists) & ~l1.row.isin(struck)]

    o = t["orders"][t["orders"].o_orderstatus == "F"]
    n = t["nation"][t["nation"].n_name == "SAUDI ARABIA"]
    x = (t["supplier"].merge(l1, left_on="s_suppkey", right_on="l_suppkey")
         .merge(o, left_on="l_orderkey", right_on="o_orderkey")
         .merge(n, left_on="s_nationkey", right_on="n_nationkey"))
    # `s_name` comes dictionary-encoded (a categorical of every supplier):
    # observed=True, and plain strings before the sort, which must not follow
    # the dictionary's order
    g = x.groupby("s_name", observed=True).size().rename("numwait").reset_index()
    g = g.astype({"s_name": str, "numwait": np.int64})
    return g.sort_values(["numwait", "s_name"], ascending=[False, True],
                         kind="stable").head(100).reset_index(drop=True)
