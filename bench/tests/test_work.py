"""lib/work.py: bytes a query must read once, against hand-computed values."""

import json
import os

import pytest

from run import BENCH
from lib import work

SCHEMA = json.load(open(os.path.join(BENCH, "lib", "schema_tpch.json")))
ROWS = {"lineitem": 6_000_000, "orders": 1_500_000, "customer": 150_000,
        "supplier": 10_000, "nation": 25, "region": 5}
F64 = I64 = 8
DATE = 4
FLAG = 5  # one character + a 4-byte offset
HAND = {
    # q1: quantity, extendedprice, discount, tax, returnflag, linestatus, shipdate
    "q1": ("tpch_scan_1chip", 6_000_000 * (4 * F64 + 2 * FLAG + DATE)),
    # q6: extendedprice, discount, shipdate, quantity
    "q6": ("tpch_scan_1chip", 6_000_000 * (3 * F64 + DATE)),
    # q3: l_orderkey, l_extendedprice, l_discount, l_shipdate; o_orderkey, o_custkey,
    # o_orderdate, o_shippriority; c_custkey, c_mktsegment (mean 9.0 + 4)
    "q3": ("tpch_join_1chip", 6_000_000 * (I64 + 2 * F64 + DATE)
           + 1_500_000 * (3 * I64 + DATE) + 150_000 * (I64 + 13.0)),
}


def tables(config):
    return json.load(open(os.path.join(BENCH, "configs", f"{config}.json")))["tables"]


@pytest.mark.parametrize("query", sorted(HAND))
def test_bytes_read_once(query):
    config, want = HAND[query]
    sql = open(os.path.join(BENCH, "queries", f"{query}.sql")).read()
    got = work.query_bytes(sql, tables(config), ROWS, SCHEMA)
    assert got == pytest.approx(want, rel=1e-4)  # c_mktsegment's measured mean is 13.01


def test_q5_reads_every_join_table():
    sql = open(os.path.join(BENCH, "queries", "q5.sql")).read()
    read = work.columns_read(sql, tables("tpch_join_1chip"))
    assert read == {
        "lineitem": ["l_orderkey", "l_suppkey", "l_extendedprice", "l_discount"],
        "orders": ["o_orderkey", "o_custkey", "o_orderdate"],
        "customer": ["c_custkey", "c_nationkey"],
        "supplier": ["s_suppkey", "s_nationkey"],
        "nation": ["n_nationkey", "n_name", "n_regionkey"],
        "region": ["r_regionkey", "r_name"]}
    want = (6_000_000 * 4 * 8 + 1_500_000 * (16 + 4) + 150_000 * 16 + 10_000 * 16
            + 25 * (16 + SCHEMA["tables"]["nation"]["n_name"]["bytes"])
            + 5 * (8 + SCHEMA["tables"]["region"]["r_name"]["bytes"]))
    assert work.query_bytes(sql, tables("tpch_join_1chip"), ROWS, SCHEMA) == pytest.approx(want)
