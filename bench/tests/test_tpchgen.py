"""The benchmark's generator and reference: a column subset holds the same
values as a full generation from the same seed, and the reference agrees with
the program's own oracle on the same files."""

import glob
import json
import os

import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from run import BENCH
from lib import reference, tpchgen

SCALE, SEED = 0.01, 2**31 + 11  # seeds are wider than 32 signed bits


def read(data_dir, table):
    return pa.concat_tables(
        pq.read_table(f) for f in sorted(glob.glob(os.path.join(data_dir, table, "*.parquet"))))


@pytest.fixture(scope="module")
def full(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("full"))
    rows = tpchgen.generate_tpch(d, SCALE, SEED, files_per_table=2)
    return d, rows


@pytest.mark.parametrize("config", ["tpch_scan_1chip", "tpch_join_1chip"])
def test_subset_equals_full(config, full, tmp_path):
    tables = json.load(open(os.path.join(BENCH, "configs", f"{config}.json")))["tables"]
    rows = tpchgen.generate_tpch(str(tmp_path), SCALE, SEED, files_per_table=2, tables=tables)
    assert sorted(os.listdir(tmp_path)) == sorted(tables)
    for table, columns in tables.items():
        got = read(str(tmp_path), table)
        assert got.column_names == columns
        assert got.equals(read(full[0], table).select(columns))
        assert rows[table] == full[1][table] == got.num_rows


def test_shapes_and_types(full):
    li, orders = read(full[0], "lineitem"), read(full[0], "orders")
    assert li.column_names == tpchgen.SCHEMA["lineitem"]
    assert li.schema.field("l_extendedprice").type == pa.float64()
    assert li.schema.field("l_shipdate").type == pa.date32()
    assert li.schema.field("l_returnflag").type == pa.string()
    assert orders.num_rows == 15000 and 3.5 < li.num_rows / orders.num_rows < 4.5
    assert len(glob.glob(os.path.join(full[0], "lineitem", "*.parquet"))) == 8
    with pytest.raises(ValueError):
        tpchgen.generate_tpch(full[0], SCALE, SEED, tables={"lineitem": ["l_nosuch"]})


def test_dates_are_what_pyarrow_makes_of_datetime64():
    # the generator goes by way of int32 day numbers (tpchgen._date32 says why)
    import numpy as np

    days = np.datetime64("1992-01-01") + np.arange(-400, 2600, 7).astype("timedelta64[D]")
    made = tpchgen._date32(days)
    assert made.type == pa.date32() and made.equals(pa.array(days))


def test_another_seed_gives_other_data(full, tmp_path):
    tpchgen.generate_tpch(str(tmp_path), SCALE, SEED + 1, 2, tables={"lineitem": ["l_quantity"]})
    assert not read(str(tmp_path), "lineitem").equals(read(full[0], "lineitem").select(["l_quantity"]))


@pytest.mark.parametrize("q", [1, 3, 5, 6])
def test_reference_agrees_with_the_programs_oracle(q, full):
    from ballista_tpu.testing import reference as program_oracle

    theirs = program_oracle.run_reference(q, program_oracle.load_tables(full[0]))
    mine = reference.answers(full[0], {t: tpchgen.SCHEMA[t] for t in (
        "lineitem", "orders", "customer", "supplier", "nation", "region")}, [f"q{q}"])[f"q{q}"]
    assert len(mine) > 0
    assert reference.compare(mine, theirs) == {"rows_off": 0, "cells_off": 0, "rel_err": 0.0}
