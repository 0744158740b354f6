"""Operator / kernel unit tests: hashing contract, join matching, config."""

import numpy as np
import pyarrow as pa
import pytest

from ballista_tpu.config import BallistaConfig, EXECUTOR_ENGINE, DEFAULT_SHUFFLE_PARTITIONS
from ballista_tpu.errors import ConfigurationError
from ballista_tpu.ops.cpu.join_kernel import match_pairs
from ballista_tpu.ops.hashing import hash_arrays, partition_indices


def test_hash_deterministic_across_types():
    a = pa.array([1, 2, 3, 2**40], pa.int64())
    h1 = hash_arrays([a])
    h2 = hash_arrays([a.cast(pa.int32(), safe=False)])  # 2**40 wraps; ignore last
    assert (h1[:3] == h2[:3]).all()
    d = pa.array([0, 1, 2], pa.int32()).cast(pa.date32())
    hd = hash_arrays([d])
    assert len(set(hd.tolist())) == 3


def test_hash_strings_and_nulls():
    s = pa.array(["abc", "abd", None, "abc"])
    h = hash_arrays([s])
    assert h[0] == h[3] and h[0] != h[1]
    # null has its own stable hash
    h2 = hash_arrays([pa.array([None], pa.string())])
    assert h[2] == h2[0]


def test_partition_indices_range():
    a = pa.array(np.arange(1000), pa.int64())
    p = partition_indices([a], 7)
    assert p.min() >= 0 and p.max() < 7
    # roughly uniform
    counts = np.bincount(p, minlength=7)
    assert counts.min() > 80


def test_match_pairs_duplicates_and_nulls():
    build = [pa.array([1, 2, 2, None, 5], pa.int64())]
    probe = [pa.array([2, 5, 7, None], pa.int64())]
    bi, pi = match_pairs(build, probe)
    pairs = sorted(zip(pi.tolist(), bi.tolist()))
    # probe row 0 (val 2) matches build rows 1 and 2; probe row 1 (val 5) matches build 4
    assert pairs == [(0, 1), (0, 2), (1, 4)]


def test_match_pairs_multi_key():
    build = [pa.array([1, 1, 2]), pa.array(["a", "b", "a"])]
    probe = [pa.array([1, 2]), pa.array(["b", "a"])]
    bi, pi = match_pairs(build, probe)
    assert sorted(zip(pi.tolist(), bi.tolist())) == [(0, 1), (1, 2)]


def test_config_validation():
    c = BallistaConfig()
    assert c.get(DEFAULT_SHUFFLE_PARTITIONS) == 16
    c.set(DEFAULT_SHUFFLE_PARTITIONS, "8")
    assert c.get(DEFAULT_SHUFFLE_PARTITIONS) == 8
    with pytest.raises(ConfigurationError):
        c.set("ballista.unknown.key", 1)
    with pytest.raises(ConfigurationError):
        c.set(EXECUTOR_ENGINE, "gpu")
    pairs = c.to_key_value_pairs()
    c2 = BallistaConfig.from_key_value_pairs(pairs)
    assert c2.get(DEFAULT_SHUFFLE_PARTITIONS) == 8


@pytest.mark.parametrize("key", [
    "ballista.tpu.pallas.enabled",
    "ballista.tpu.fusion.enabled",
    "ballista.tpu.fusion.mode",
    "ballista.tpu.fusion.min.rows",
    "ballista.tpu.fusion.pallas.max.groups",
    "ballista.tpu.fusion.pallas.max.probe.rows",
    "ballista.tpu.sort.pallas.max.rows",
    "ballista.tpu.topk.enabled",
    "ballista.tpu.topk.max.k",
    "ballista.tpu.hash.table.load.factor",
    "ballista.tpu.allow.f32.money",
])
def test_removed_engine_keys_are_unknown(key):
    """The keys that steered the lowerings that went (and two nothing ever
    read) are not registered: setting one is an error like any unknown key,
    at construction and at `set`, with no alias that swallows it."""
    from ballista_tpu.config import VALID_ENTRIES

    assert key not in VALID_ENTRIES
    with pytest.raises(ConfigurationError, match="unknown config key"):
        BallistaConfig({key: "1"})
    with pytest.raises(ConfigurationError, match="unknown config key"):
        BallistaConfig().set(key, "1")


def test_config_docs_generation():
    from ballista_tpu.config import generate_config_docs

    docs = generate_config_docs()
    assert "ballista.executor.engine" in docs
    assert "ballista.tpu.shape.buckets" in docs


def test_config_docs_file_is_fresh():
    """docs-as-code means the COMMITTED file tracks the registry — the
    generator only returns a string, so nothing else catches drift."""
    import os

    from ballista_tpu.config import generate_config_docs

    path = os.path.join(os.path.dirname(__file__), "..", "docs", "configs.md")
    with open(path) as f:
        on_disk = f.read()
    assert on_disk == generate_config_docs(), (
        "docs/configs.md is stale; regenerate with "
        "python -c \"from ballista_tpu.config import generate_config_docs; "
        "open('docs/configs.md','w').write(generate_config_docs())\"")


def test_hash_nullable_columns_match_clean_columns():
    """Wire contract under nulls: a nullable column's VALID slots must hash
    identically to the same values in a null-free column (and to the native
    C++ hasher). Regression for the float64 to_numpy round-trip that
    mis-hashed every row of nullable date32/bool columns and lost precision
    on nullable int64 > 2^53."""
    from ballista_tpu.ops import native

    big = 2**60 + 12345  # would corrupt through float64
    cases = [
        (pa.array([1, None, big, -7], pa.int64()),
         pa.array([1, 0, big, -7], pa.int64())),
        (pa.array([3, None, 20000], pa.int32()).cast(pa.date32()),
         pa.array([3, 0, 20000], pa.int32()).cast(pa.date32())),
        (pa.array([True, None, False], pa.bool_()),
         pa.array([True, False, False], pa.bool_())),
        (pa.array([1.5, None, -2.25], pa.float64()),
         pa.array([1.5, 0.0, -2.25], pa.float64())),
    ]
    for nullable, clean in cases:
        hn = hash_arrays([nullable])
        hc = hash_arrays([clean])
        valid = np.asarray(nullable.is_valid())
        assert (hn[valid] == hc[valid]).all(), nullable.type
        # null slots get the stable null tag, distinct from the filled value
        assert (hn[~valid] != hc[~valid]).all(), nullable.type
        nat = native.hash_arrays_native([nullable])
        if nat is not None:
            assert (hn == nat).all(), nullable.type


def test_hash_date64_columns():
    """date64 repartition keys must hash (ms-int64 direct cast) and agree
    with the equivalent date32 values where representable."""
    from ballista_tpu.ops import native

    ms = pa.array([86_400_000, None, 172_800_000], pa.int64()).cast(pa.date64())
    h = hash_arrays([ms])
    assert len(set(h.tolist())) == 3
    nat = native.hash_arrays_native([ms])
    if nat is not None:
        assert (h == nat).all()


def test_decimal_parquet_exact_policy(tmp_path):
    """decimal128 parquet (what the reference's TPC-H generators emit) and
    decimal arrow tables keep EXACT decimal semantics end-to-end: sums widen
    to decimal128(38,s) like DataFusion's, min/max preserve the input type,
    nulls flow, and no float rounding touches the money lane."""
    import decimal

    import pyarrow.parquet as pq

    from ballista_tpu.client.context import SessionContext

    D = decimal.Decimal
    tbl = pa.table({
        "g": pa.array(["a", "b", "a"]),
        "price": pa.array([D("10.25"), None, D("7.75")], pa.decimal128(15, 2)),
    })
    pq.write_table(tbl, tmp_path / "d.parquet")
    ctx = SessionContext()
    ctx.register_parquet("d", str(tmp_path / "d.parquet"))
    assert ctx.catalog.get("d").arrow_schema().field("price").type == pa.decimal128(15, 2)
    out = ctx.sql("SELECT sum(price) s, min(price) mn, count(price) c FROM d").collect()
    assert out.schema.field("s").type == pa.decimal128(38, 2)
    assert out.schema.field("mn").type == pa.decimal128(15, 2)
    r = out.to_pandas()
    assert r.s[0] == D("18.00") and r.mn[0] == D("7.75") and int(r.c[0]) == 2
    ctx.register_arrow_table("m", tbl)
    r2 = ctx.sql("SELECT g, sum(price) s FROM m GROUP BY g ORDER BY g").collect()
    assert r2.column("s").to_pylist() == [D("18.00"), None]
