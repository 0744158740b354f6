"""The plain reference's answer to bench/queries/q1.sql: `answer(t)` takes the
configuration's tables as pandas frames and returns the rows the SQL asks
for, in its ORDER BY order. A copy of `ballista_tpu/testing/reference.py`'s
q1; imports nothing of the program."""

import pandas as pd


def answer(t):
    li = t["lineitem"]
    keep = (li.l_shipdate <= pd.Timestamp("1998-09-02")).to_numpy()
    # filtered column by column, grouped once: at 60 M rows a whole-frame
    # boolean take and eight named aggregations cost five times as much
    df = pd.DataFrame({c: li[c].array[keep] for c in (
        "l_returnflag", "l_linestatus", "l_quantity", "l_extendedprice",
        "l_discount", "l_tax")}, copy=False)
    df["disc_price"] = df.l_extendedprice * (1 - df.l_discount)
    df["charge"] = df.disc_price * (1 + df.l_tax)
    g = df.groupby(["l_returnflag", "l_linestatus"], observed=True)
    s = g[["l_quantity", "l_extendedprice", "disc_price", "charge", "l_discount"]].sum()
    n = g.size()
    out = pd.DataFrame({
        "sum_qty": s.l_quantity, "sum_base_price": s.l_extendedprice,
        "sum_disc_price": s.disc_price, "sum_charge": s.charge,
        "avg_qty": s.l_quantity / n, "avg_price": s.l_extendedprice / n,
        "avg_disc": s.l_discount / n, "count_order": n}).reset_index()
    out = out.astype({"l_returnflag": str, "l_linestatus": str})
    return out.sort_values(["l_returnflag", "l_linestatus"]).reset_index(drop=True)
