"""The plain reference's answer to bench/queries/q18.sql: `answer(t)` takes the
configuration's tables as pandas frames and returns the rows the SQL asks
for, in its ORDER BY order. A copy of `ballista_tpu/testing/reference.py`'s
q18; imports nothing of the program."""


def answer(t):
    l = t["lineitem"]
    qty = l.groupby("l_orderkey")["l_quantity"].sum()
    big = qty[qty > 300].index
    o = t["orders"][t["orders"].o_orderkey.isin(big)]
    x = t["customer"].merge(o, left_on="c_custkey", right_on="o_custkey").merge(
        l, left_on="o_orderkey", right_on="l_orderkey"
    )
    # `c_name` comes dictionary-encoded (a categorical of every customer):
    # observed=True, or each key combination gets 150,000 empty groups
    g = x.groupby(["c_name", "c_custkey", "o_orderkey", "o_orderdate", "o_totalprice"],
                  as_index=False, observed=True)["l_quantity"].sum()
    g = g.rename(columns={"l_quantity": "total_quantity"}).astype({"c_name": str})
    # stable, as the SQL's sort is: rows equal in both keys (none among the few
    # dozen orders a run returns, whose prices are to the cent) would stay in
    # group-key order here, an order the SQL does not promise — the comparison
    # is row for row and would count them as cells off
    return g.sort_values(["o_totalprice", "o_orderdate"], ascending=[False, True],
                         kind="stable").head(100).reset_index(drop=True)
