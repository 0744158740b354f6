"""The benchmark's own TPC-H generator (dbgen-shaped, deterministic).

Taken from `ballista_tpu/testing/tpchgen.py` (which later PRs may edit; this
one they may not): the same schema, cardinalities, key relationships and value
distributions. What differs is how randomness is drawn: every column has a
random stream of its own, made from (seed, "table.column"), so a column has
the same values whether or not its neighbours are generated, and a cell
generates **only the tables and columns its queries read** — no draw, string
cast, compression or write for the rest (at SF10 the whole generator was
estimated at ~200 s a run; the seven columns q1 and q6 read take a fraction).
The data for a seed therefore differs from the program's generator's; answers
come from `bench/lib/reference.py` on the same files.

TPC-H types and widths are unchanged: keys and counts int64, quantities and
money float64 (money exact to the cent), dates date32, flags and names
strings. Not a bit-exact dbgen clone: comments and addresses are abbreviated.
"""

from __future__ import annotations

import os
import threading
import zlib
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.parquet as pq

STARTDATE = np.datetime64("1992-01-01")
ENDDATE = np.datetime64("1998-12-31")
CURRENTDATE = np.datetime64("1995-06-17")

REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
NATIONS = [
    ("ALGERIA", 0), ("ARGENTINA", 1), ("BRAZIL", 1), ("CANADA", 1),
    ("EGYPT", 4), ("ETHIOPIA", 0), ("FRANCE", 3), ("GERMANY", 3),
    ("INDIA", 2), ("INDONESIA", 2), ("IRAN", 4), ("IRAQ", 4),
    ("JAPAN", 2), ("JORDAN", 4), ("KENYA", 0), ("MOROCCO", 0),
    ("MOZAMBIQUE", 0), ("PERU", 1), ("CHINA", 2), ("ROMANIA", 3),
    ("SAUDI ARABIA", 4), ("VIETNAM", 2), ("RUSSIA", 3),
    ("UNITED KINGDOM", 3), ("UNITED STATES", 1),
]
SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"]
PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
SHIPMODES = ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"]
INSTRUCTS = ["DELIVER IN PERSON", "COLLECT COD", "NONE", "TAKE BACK RETURN"]
TYPE_S1 = ["STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY", "PROMO"]
TYPE_S2 = ["ANODIZED", "BURNISHED", "PLATED", "POLISHED", "BRUSHED"]
TYPE_S3 = ["TIN", "NICKEL", "BRASS", "STEEL", "COPPER"]
CONTAINER_1 = ["SM", "LG", "MED", "JUMBO", "WRAP"]
CONTAINER_2 = ["CASE", "BOX", "BAG", "JAR", "PKG", "PACK", "CAN", "DRUM"]
COLORS = [
    "almond", "antique", "aquamarine", "azure", "beige", "bisque", "black",
    "blanched", "blue", "blush", "brown", "burlywood", "burnished", "chartreuse",
    "chiffon", "chocolate", "coral", "cornflower", "cornsilk", "cream", "cyan",
    "dark", "deep", "dim", "dodger", "drab", "firebrick", "floral", "forest",
    "frosted", "gainsboro", "ghost", "goldenrod", "green", "grey", "honeydew",
    "hot", "indian", "ivory", "khaki", "lace", "lavender", "lawn", "lemon",
    "light", "lime", "linen", "magenta", "maroon", "medium", "metallic", "midnight",
    "mint", "misty", "moccasin", "navajo", "navy", "olive", "orange", "orchid",
    "pale", "papaya", "peach", "peru", "pink", "plum", "powder", "puff", "purple",
    "red", "rose", "rosy", "royal", "saddle", "salmon", "sandy", "seashell",
    "sienna", "sky", "slate", "smoke", "snow", "spring", "steel", "tan", "thistle",
    "tomato", "turquoise", "violet", "wheat", "white", "yellow",
]
WORDS = (
    "carefully regular instructions sleep blithely final deposits haggle quickly "
    "express packages cajole furiously silent requests boost even ideas nag ironic "
    "accounts wake slyly pending theodolites integrate daringly bold pinto beans "
    "above the unusual foxes detect along platelets across fluffily busy dependencies"
).split()

SCHEMA = {
    "region": ["r_regionkey", "r_name", "r_comment"],
    "nation": ["n_nationkey", "n_name", "n_regionkey", "n_comment"],
    "supplier": ["s_suppkey", "s_name", "s_address", "s_nationkey", "s_phone",
                 "s_acctbal", "s_comment"],
    "part": ["p_partkey", "p_name", "p_mfgr", "p_brand", "p_type", "p_size",
             "p_container", "p_retailprice", "p_comment"],
    "partsupp": ["ps_partkey", "ps_suppkey", "ps_availqty", "ps_supplycost",
                 "ps_comment"],
    "customer": ["c_custkey", "c_name", "c_address", "c_nationkey", "c_phone",
                 "c_acctbal", "c_mktsegment", "c_comment"],
    "orders": ["o_orderkey", "o_custkey", "o_orderstatus", "o_totalprice",
               "o_orderdate", "o_orderpriority", "o_clerk", "o_shippriority",
               "o_comment"],
    "lineitem": ["l_orderkey", "l_partkey", "l_suppkey", "l_linenumber",
                 "l_quantity", "l_extendedprice", "l_discount", "l_tax",
                 "l_returnflag", "l_linestatus", "l_shipdate", "l_commitdate",
                 "l_receiptdate", "l_shipinstruct", "l_shipmode", "l_comment"],
}
TPCH_TABLES = list(SCHEMA)


def _take(choices: list[str], idx: np.ndarray) -> pa.Array:
    return pa.DictionaryArray.from_arrays(
        pa.array(idx.astype(np.int32)), pa.array(choices)
    ).cast(pa.string())


def _date32(days: np.ndarray) -> pa.Array:
    """A datetime64[D] column as date32, by way of its int32 day numbers:
    `pa.array` over a datetime64 array, run beside numpy work on other
    threads, corrupted the heap (pyarrow 25.0.0, numpy 2.0.2: one generation
    of the whole orders table in about 150 died; PERF.md, PR 27)."""
    return pa.array(days.astype("datetime64[D]").astype(np.int32)).cast(pa.date32())


def _retail_cents(pk: np.ndarray) -> np.ndarray:
    return 90000 + ((pk // 10) % 20001) + 100 * (pk % 1000)


def _ps_suppkey(pk: np.ndarray, i, s_count: int) -> np.ndarray:
    # dbgen's formula: the i-th (0..3) supplier for part pk
    return (pk + i * (s_count // 4 + (pk - 1) // s_count)) % s_count + 1


def table_rows(scale: float) -> dict[str, int]:
    """Row counts the scale fixes (lineitem's is drawn: 1..7 lines an order,
    4 on average — `generate_tpch` returns the count it wrote)."""
    n_part = max(200, int(200_000 * scale))
    return {"region": 5, "nation": 25,
            "supplier": max(10, int(10_000 * scale)),
            "part": n_part, "partsupp": 4 * n_part,
            "customer": max(150, int(150_000 * scale)),
            "orders": max(1500, int(1_500_000 * scale))}


class _Columns:
    """Every column as a method named after it; values several columns share
    (lines an order, ship dates, ...) are memoised under a lock, so columns
    may be built from several threads."""

    def __init__(self, scale: float, seed: int):
        self.seed = int(seed)
        self.n = table_rows(scale)
        self._memo: dict[str, object] = {}
        self._locks: dict[str, threading.Lock] = {}
        self._guard = threading.Lock()

    def rng(self, name: str) -> np.random.Generator:
        return np.random.default_rng([zlib.crc32(name.encode()), self.seed])

    def shared(self, name: str):
        with self._guard:
            lock = self._locks.setdefault(name, threading.Lock())
        with lock:
            if name not in self._memo:
                self._memo[name] = getattr(self, "_" + name)()
            return self._memo[name]

    def ints(self, name: str, lo: int, hi: int, n: int, dtype=np.int64) -> np.ndarray:
        return self.rng(name).integers(lo, hi, n, dtype=dtype)

    def money(self, name: str, n: int, lo: float, hi: float) -> np.ndarray:
        cents = self.rng(name).integers(round(lo * 100), round(hi * 100) + 1, n)
        return cents / 100.0

    def comment(self, name: str, n: int, nwords: int, inject: str | None = None,
                inject_rate: float = 0.0) -> pa.Array:
        rng = self.rng(name)
        cols = [_take(WORDS, rng.integers(0, len(WORDS), n, dtype=np.int32))
                for _ in range(nwords)]
        out = pc.binary_join_element_wise(*cols, " ")
        if inject and inject_rate > 0:
            mask = rng.random(n) < inject_rate
            if mask.any():
                out = pc.if_else(pa.array(mask), pc.binary_join_element_wise(
                    out, pa.scalar(inject), " "), out)
        return out

    # ---- region / nation
    def r_regionkey(self): return pa.array(range(5), pa.int64())
    def r_name(self): return pa.array(REGIONS)
    def r_comment(self): return self.comment("region.r_comment", 5, 5)
    def n_nationkey(self): return pa.array(range(25), pa.int64())
    def n_name(self): return pa.array([n for n, _ in NATIONS])
    def n_regionkey(self): return pa.array([r for _, r in NATIONS], pa.int64())
    def n_comment(self): return self.comment("nation.n_comment", 25, 5)

    # ---- supplier
    def _sk(self): return np.arange(1, self.n["supplier"] + 1, dtype=np.int64)
    def s_suppkey(self): return self.shared("sk")
    def s_name(self): return pa.array([f"Supplier#{i:09d}" for i in self.shared("sk")])
    def s_address(self): return self.comment("supplier.s_address", self.n["supplier"], 2)
    def s_nationkey(self): return self.ints("supplier.s_nationkey", 0, 25, self.n["supplier"])

    def s_phone(self):
        return pa.array([f"{10 + i % 25}-{i % 900 + 100}-{i % 900 + 100}-{i % 9000 + 1000}"
                         for i in self.shared("sk")])

    def s_acctbal(self): return self.money("supplier.s_acctbal", self.n["supplier"], -999.99, 9999.99)

    def s_comment(self):
        return self.comment("supplier.s_comment", self.n["supplier"], 6,
                            "Customer Complaints", 0.0005)

    # ---- part
    def _pk(self): return np.arange(1, self.n["part"] + 1, dtype=np.int64)
    def _brand_m(self): return self.ints("part.brand_m", 1, 6, self.n["part"])
    def p_partkey(self): return self.shared("pk")

    def p_name(self):
        rng, n = self.rng("part.p_name"), self.n["part"]
        return pc.binary_join_element_wise(
            *[_take(COLORS, rng.integers(0, len(COLORS), n)) for _ in range(5)], " ")

    def p_mfgr(self): return pa.array([f"Manufacturer#{m}" for m in self.shared("brand_m")])

    def p_brand(self):
        brand_n = self.ints("part.brand_n", 1, 6, self.n["part"])
        return pa.array([f"Brand#{m}{n}" for m, n in zip(self.shared("brand_m"), brand_n)])

    def p_type(self):
        rng, n = self.rng("part.p_type"), self.n["part"]
        return pc.binary_join_element_wise(
            _take(TYPE_S1, rng.integers(0, len(TYPE_S1), n)),
            _take(TYPE_S2, rng.integers(0, len(TYPE_S2), n)),
            _take(TYPE_S3, rng.integers(0, len(TYPE_S3), n)), " ")

    def p_size(self): return self.ints("part.p_size", 1, 51, self.n["part"])

    def p_container(self):
        rng, n = self.rng("part.p_container"), self.n["part"]
        return pc.binary_join_element_wise(
            _take(CONTAINER_1, rng.integers(0, 5, n)),
            _take(CONTAINER_2, rng.integers(0, 8, n)), " ")

    def p_retailprice(self): return _retail_cents(self.shared("pk")) / 100.0
    def p_comment(self): return self.comment("part.p_comment", self.n["part"], 3)

    # ---- partsupp (4 suppliers per part, dbgen formula, ordered by part)
    def ps_partkey(self): return np.repeat(self.shared("pk"), 4)

    def ps_suppkey(self):
        pk = np.repeat(self.shared("pk"), 4)
        i = np.tile(np.arange(4, dtype=np.int64), self.n["part"])
        return _ps_suppkey(pk, i, self.n["supplier"])

    def ps_availqty(self): return self.ints("partsupp.ps_availqty", 1, 10_000, self.n["partsupp"])
    def ps_supplycost(self): return self.money("partsupp.ps_supplycost", self.n["partsupp"], 1.0, 1000.0)
    def ps_comment(self): return self.comment("partsupp.ps_comment", self.n["partsupp"], 4)

    # ---- customer
    def _ck(self): return np.arange(1, self.n["customer"] + 1, dtype=np.int64)
    def _c_nat(self): return self.ints("customer.c_nationkey", 0, 25, self.n["customer"])
    def c_custkey(self): return self.shared("ck")
    def c_name(self): return pa.array([f"Customer#{i:09d}" for i in self.shared("ck")])
    def c_address(self): return self.comment("customer.c_address", self.n["customer"], 2)
    def c_nationkey(self): return self.shared("c_nat")

    def c_phone(self):
        return pa.array([f"{10 + n}-{int(x) % 900 + 100}-{int(x) % 900 + 100}-{int(x) % 9000 + 1000}"
                         for n, x in zip(self.shared("c_nat"), self.shared("ck"))])

    def c_acctbal(self): return self.money("customer.c_acctbal", self.n["customer"], -999.99, 9999.99)

    def c_mktsegment(self):
        return _take(SEGMENTS, self.ints("customer.c_mktsegment", 0, 5, self.n["customer"]))

    def c_comment(self):
        return self.comment("customer.c_comment", self.n["customer"], 6, "special requests", 0.002)

    # ---- orders, and what lineitem shares with it
    def _ok(self):  # sparse keys like dbgen
        return (np.arange(1, self.n["orders"] + 1, dtype=np.int64) * 4) - 3

    def _o_date(self):
        span = int((ENDDATE - np.timedelta64(151, "D") - STARTDATE).astype("int64"))
        return STARTDATE + self.ints("orders.o_orderdate", 0, span + 1,
                                     self.n["orders"]).astype("timedelta64[D]")

    def _lines_per(self): return self.ints("orders.lines", 1, 8, self.n["orders"])
    def _n_li(self): return int(self.shared("lines_per").sum())
    def _l_odate(self): return np.repeat(self.shared("o_date"), self.shared("lines_per"))
    def _l_pk(self): return self.ints("lineitem.l_partkey", 1, self.n["part"] + 1, self.shared("n_li"))
    def _l_qty(self): return self.ints("lineitem.l_quantity", 1, 51, self.shared("n_li"))
    def _l_disc(self): return self.ints("lineitem.l_discount", 0, 11, self.shared("n_li")) / 100.0
    def _l_tax(self): return self.ints("lineitem.l_tax", 0, 9, self.shared("n_li")) / 100.0

    def _l_price(self):  # quantity x the part's retail price, exact to the cent
        return (self.shared("l_qty") * _retail_cents(self.shared("l_pk"))) / 100.0

    def _l_ship(self):
        return self.shared("l_odate") + self.ints(
            "lineitem.l_shipdate", 1, 122, self.shared("n_li")).astype("timedelta64[D]")

    def _l_receipt(self):
        return self.shared("l_ship") + self.ints(
            "lineitem.l_receiptdate", 1, 31, self.shared("n_li")).astype("timedelta64[D]")

    def _open_line(self): return self.shared("l_ship") > CURRENTDATE
    def _line_order(self): return np.repeat(np.arange(self.n["orders"]), self.shared("lines_per"))

    def o_orderkey(self): return self.shared("ok")

    def o_custkey(self):
        # only customers with custkey % 3 != 0 place orders (q13/q22 shape)
        ck = self.shared("ck")
        eligible = ck[ck % 3 != 0]
        return eligible[self.ints("orders.o_custkey", 0, len(eligible), self.n["orders"])]

    def o_orderstatus(self):
        open_lines = np.bincount(self.shared("line_order"), weights=self.shared("open_line"),
                                 minlength=self.n["orders"])
        code = np.where(open_lines == self.shared("lines_per"), 1, np.where(open_lines > 0, 2, 0))
        return _take(["F", "O", "P"], code)

    def o_totalprice(self):
        charge = np.rint(self.shared("l_price") * 100 * (1 + self.shared("l_tax"))
                         * (1 - self.shared("l_disc")))
        return np.bincount(self.shared("line_order"), weights=charge,
                           minlength=self.n["orders"]) / 100.0

    def o_orderdate(self): return _date32(self.shared("o_date"))

    def o_orderpriority(self):
        return _take(PRIORITIES, self.ints("orders.o_orderpriority", 0, 5, self.n["orders"]))

    def o_clerk(self):
        clerks = max(1, self.n["orders"] // 1000)
        return pa.array([f"Clerk#{int(c) + 1:09d}" for c in
                         self.ints("orders.o_clerk", 0, clerks, self.n["orders"])])

    def o_shippriority(self): return np.zeros(self.n["orders"], dtype=np.int64)

    def o_comment(self):
        return self.comment("orders.o_comment", self.n["orders"], 5, "special requests", 0.01)

    # ---- lineitem
    def l_orderkey(self): return np.repeat(self.shared("ok"), self.shared("lines_per"))
    def l_partkey(self): return self.shared("l_pk")

    def l_suppkey(self):
        which = self.ints("lineitem.l_suppkey", 0, 4, self.shared("n_li"))
        return _ps_suppkey(self.shared("l_pk"), which, self.n["supplier"])

    def l_linenumber(self):
        lines = self.shared("lines_per")
        first = np.repeat(np.cumsum(lines) - lines, lines)
        return np.arange(self.shared("n_li"), dtype=np.int64) - first + 1

    def l_quantity(self): return self.shared("l_qty").astype(np.float64)
    def l_extendedprice(self): return self.shared("l_price")
    def l_discount(self): return self.shared("l_disc")
    def l_tax(self): return self.shared("l_tax")

    def l_returnflag(self):
        returned = self.rng("lineitem.l_returnflag").random(self.shared("n_li")) < 0.5
        return _take(["R", "A", "N"], np.where(
            self.shared("l_receipt") <= CURRENTDATE, np.where(returned, 0, 1), 2))

    def l_linestatus(self): return _take(["F", "O"], self.shared("open_line"))
    def l_shipdate(self): return _date32(self.shared("l_ship"))

    def l_commitdate(self):
        return _date32(self.shared("l_odate") + self.ints(
            "lineitem.l_commitdate", 30, 91, self.shared("n_li")).astype("timedelta64[D]"))

    def l_receiptdate(self): return _date32(self.shared("l_receipt"))

    def l_shipinstruct(self):
        return _take(INSTRUCTS, self.ints("lineitem.l_shipinstruct", 0, 4, self.shared("n_li")))

    def l_shipmode(self):
        return _take(SHIPMODES, self.ints("lineitem.l_shipmode", 0, 7, self.shared("n_li")))

    def l_comment(self): return self.comment("lineitem.l_comment", self.shared("n_li"), 3)


def generate_tpch(out_dir: str, scale: float = 0.01, seed: int = 42,
                  files_per_table: int = 1, row_group_rows: int = 256 * 1024,
                  tables: dict[str, list[str]] | None = None) -> dict[str, int]:
    """Write parquet under out_dir/<table>/part-*.parquet and return
    {table: rows written}. `tables` maps each table to write to its columns;
    None writes all eight tables whole. lineitem is split over four times
    `files_per_table` files, the tables from supplier up over
    `files_per_table`, region and nation are one file each."""
    wanted = {t: list(SCHEMA[t]) for t in TPCH_TABLES} if tables is None else dict(tables)
    for t, cols in wanted.items():
        unknown = [c for c in cols if c not in SCHEMA.get(t, ())]
        if unknown or not cols:
            raise ValueError(f"TPC-H has no {t} columns {unknown or cols}")
    gen = _Columns(scale, seed)
    os.makedirs(out_dir, exist_ok=True)
    written: dict[str, int] = {}
    with ThreadPoolExecutor(max_workers=8) as pool:
        writes = []
        for t in TPCH_TABLES:  # small tables first, lineitem last
            if t not in wanted:
                continue
            built = list(pool.map(lambda c: getattr(gen, c)(), wanted[t]))
            table = pa.table(dict(zip(wanted[t], built)))
            del built
            d = os.path.join(out_dir, t)
            os.makedirs(d, exist_ok=True)
            nfiles = {"region": 1, "nation": 1,
                      "lineitem": 4 * files_per_table}.get(t, files_per_table)
            nfiles = max(1, min(nfiles, table.num_rows))
            step = -(-table.num_rows // nfiles)
            for i in range(nfiles):
                sl = table.slice(i * step, step)
                if sl.num_rows:
                    writes.append(pool.submit(
                        pq.write_table, sl, os.path.join(d, f"part-{i:03d}.parquet"),
                        row_group_size=row_group_rows, compression="zstd"))
            written[t] = table.num_rows
        for w in writes:
            w.result()
    return written
