"""TPU cold-path tests (jax CPU backend via conftest env).

- pipelined / chunked device fill is byte-identical to the strict serial
  fill (the defaults-off safety property)
- compile/fill overlap actually overlaps: a q1 run with artificially slow
  encode+upload reports compile_overlap_s > 0 and still returns correct rows
- the persistent XLA compile cache round-trips: after clearing every
  in-process cache, the recompile is served from disk (cache_hits grows)
- LruDict bounds the module caches (entry cap, byte budget, clear)
- RUN_STATS keeps concurrent stage runs isolated
"""

import threading
import time

import numpy as np
import pyarrow as pa
import pytest

from ballista_tpu.config import (
    BallistaConfig,
    EXECUTOR_ENGINE,
    TPU_COMPILE_OVERLAP,
    TPU_FILL_CHUNK_ROWS,
    TPU_FILL_THREADS,
    TPU_MIN_ROWS,
)
from ballista_tpu.plan.physical import MemoryScanExec, TaskContext
from ballista_tpu.plan.schema import DFSchema

from .conftest import tpch_query


def _mixed_table(n: int = 5_000) -> pa.Table:
    rng = np.random.default_rng(11)
    price = np.round(rng.uniform(1, 1000, n), 2)
    qty = rng.integers(1, 50, n).astype(np.int64)
    flag = rng.choice(["A", "N", "R"], n)
    day = rng.integers(8000, 11000, n).astype(np.int32)
    weight = rng.uniform(0.0, 1.0, n)
    ok = rng.random(n) > 0.5
    nullable = pa.array(
        [None if i % 7 == 0 else int(v) for i, v in enumerate(qty)], pa.int64()
    )
    return pa.table({
        "qty": qty,
        "price": price,                       # money lane (2-decimal f64)
        "flag": flag,                         # dictionary codes + LUT
        "day": pa.array(day, pa.date32()),
        "weight": weight,                     # true f64
        "ok": ok,
        "maybe": nullable,                    # validity plane
    })


def _scan(tbl: pa.Table, partitions: int = 3) -> MemoryScanExec:
    batches = tbl.to_batches(max_chunksize=max(1, tbl.num_rows // (partitions * 2)))
    return MemoryScanExec(DFSchema.from_arrow(tbl.schema), batches, partitions)


def _load(scan, **kw):
    import ballista_tpu.ops.tpu.stage_compiler as sc

    ctx = TaskContext(BallistaConfig({}))
    return sc.DEVICE_CACHE._load(scan, [1 << 12, 1 << 14, 1 << 16], ctx, None, **kw)


def _assert_tables_identical(a, b):
    assert a.kinds == b.kinds
    assert a.scales == b.scales
    assert a.dicts == b.dicts
    assert a.part_rows == b.part_rows
    assert a.nbytes == b.nbytes
    assert np.array_equal(np.asarray(a.mask), np.asarray(b.mask))
    for ca, cb in zip(a.cols, b.cols):
        assert ca.dtype == cb.dtype
        assert np.array_equal(np.asarray(ca), np.asarray(cb))
    for va, vb in zip(a.valids, b.valids):
        assert (va is None) == (vb is None)
        if va is not None:
            assert np.array_equal(np.asarray(va), np.asarray(vb))


def test_pipelined_fill_byte_identical_to_serial():
    tbl = _mixed_table()
    serial = _load(_scan(tbl), fill_threads=1)
    piped = _load(_scan(tbl), fill_threads=4)
    _assert_tables_identical(serial, piped)


def test_chunked_upload_byte_identical():
    tbl = _mixed_table()
    whole = _load(_scan(tbl), fill_threads=1)
    chunked = _load(_scan(tbl), fill_threads=4, chunk_rows=7)
    _assert_tables_identical(whole, chunked)


def test_fill_records_encode_upload_split():
    rec: dict = {}
    _load(_scan(_mixed_table()), fill_threads=2, stats=rec)
    assert rec["encode_s"] >= 0
    assert rec["upload_s"] >= 0


def test_on_spec_fires_with_full_compile_metadata():
    """The spec table must carry everything the compile key reads (kinds,
    scales, dict sizes, dtypes, valid slots, P, N) before uploads drain."""
    fired: list = []
    tbl = _mixed_table()
    dt = _load(_scan(tbl), fill_threads=4, on_spec=fired.append)
    assert len(fired) == 1
    spec = fired[0]
    assert spec.kinds == dt.kinds
    assert spec.scales == dt.scales
    assert spec.dicts == dt.dicts
    assert spec.part_rows == dt.part_rows
    assert spec.shape == dt.shape
    for sc_, dc in zip(spec.cols, dt.cols):
        assert sc_.shape == tuple(dc.shape)
        assert np.dtype(sc_.dtype) == np.dtype(dc.dtype)
    for sv, dv in zip(spec.valids, dt.valids):
        assert (sv is None) == (dv is None)


def test_unencodable_column_raises_unsupported_in_pipeline():
    from ballista_tpu.ops.tpu.kernels import Unsupported

    tbl = pa.table({
        "a": np.arange(100, dtype=np.int64),
        "bad": pa.array([[1, 2]] * 100, pa.list_(pa.int64())),
    })
    with pytest.raises(Unsupported):
        _load(_scan(tbl), fill_threads=4)


@pytest.fixture()
def tpu_ctx(tpch_dir):
    from ballista_tpu.client.context import SessionContext
    from ballista_tpu.testing.tpchgen import register_tpch

    cfg = BallistaConfig({EXECUTOR_ENGINE: "tpu", TPU_MIN_ROWS: 0})
    ctx = SessionContext(cfg)
    register_tpch(ctx, tpch_dir)
    return ctx


def test_compile_overlaps_slow_fill(tpu_ctx, monkeypatch):
    """With encode and upload artificially slowed, the compile worker must
    start (and make progress) under the fill: compile_overlap_s > 0."""
    import ballista_tpu.ops.tpu.columnar as columnar
    import ballista_tpu.ops.tpu.stage_compiler as sc

    sc.clear_device_caches()
    sc.RUN_STATS.clear()

    real_encode = columnar.encode_column
    real_put = sc._put_chunked

    def slow_encode(arr):
        time.sleep(0.02)
        return real_encode(arr)

    def slow_put(mesh, arr, spec=None, chunk_rows=0):
        time.sleep(0.05)
        return real_put(mesh, arr, spec, chunk_rows)

    monkeypatch.setattr(columnar, "encode_column", slow_encode)
    monkeypatch.setattr(sc, "_put_chunked", slow_put)

    out = tpu_ctx.sql(tpch_query(1)).collect()
    assert out.to_pandas().shape[0] > 0
    stats = sc.RUN_STATS.snapshot()
    assert stats.get("compile_overlap_s", 0.0) > 0.0
    # the legacy total is still reported alongside the split
    assert stats["compile_s"] >= stats.get("trace_s", 0.0)
    assert stats["fill_s"] >= stats["upload_s"] > 0.0


def test_overlap_off_is_serial_and_correct(tpch_dir, tpch_ref_tables):
    from ballista_tpu.client.context import SessionContext
    from ballista_tpu.testing.reference import compare_results, run_reference
    from ballista_tpu.testing.tpchgen import register_tpch
    import ballista_tpu.ops.tpu.stage_compiler as sc

    sc.clear_device_caches()
    cfg = BallistaConfig({
        EXECUTOR_ENGINE: "tpu", TPU_MIN_ROWS: 0,
        TPU_COMPILE_OVERLAP: False, TPU_FILL_THREADS: 1,
    })
    ctx = SessionContext(cfg)
    register_tpch(ctx, tpch_dir)
    eng = ctx.sql(tpch_query(6)).collect()
    ref = run_reference(6, tpch_ref_tables)
    problems = compare_results(eng, ref, 6)
    assert not problems, "\n".join(problems)


def test_persistent_cache_roundtrip(tpch_dir):
    """Simulated restart: clear every in-process cache, rerun the same
    stage — the XLA recompile must be served from the on-disk cache, which
    lives where JAX_COMPILATION_CACHE_DIR (set by conftest) says."""
    from ballista_tpu.client.context import SessionContext
    from ballista_tpu.ops.tpu import runtime
    from ballista_tpu.testing.tpchgen import register_tpch
    import ballista_tpu.ops.tpu.stage_compiler as sc

    import os

    cache_dir = os.environ[runtime.CACHE_DIR_ENV]
    cfg = BallistaConfig({EXECUTOR_ENGINE: "tpu", TPU_MIN_ROWS: 0})
    ctx = SessionContext(cfg)
    register_tpch(ctx, tpch_dir)

    sc.clear_device_caches()
    ctx.sql(tpch_query(6)).collect()
    cold = runtime.compile_cache_stats()
    assert cold["dir"] == cache_dir
    assert cold["requests"] > 0
    assert os.listdir(cache_dir), "persistent cache wrote nothing"

    # "restart": drop the in-process compile/LUT/build/device caches so the
    # stage re-traces and re-invokes backend compile from scratch
    sc.clear_device_caches()
    ctx2 = SessionContext(cfg)
    register_tpch(ctx2, tpch_dir)
    ctx2.sql(tpch_query(6)).collect()
    warm = runtime.compile_cache_stats()
    assert warm["hits"] > cold["hits"], (
        f"warm run missed the persistent cache: {cold} -> {warm}")


def test_lru_dict_entry_cap_and_bytes():
    from ballista_tpu.ops.tpu.stage_compiler import LruDict

    d = LruDict(3)
    for i in range(5):
        d[i] = i * 10
    assert len(d) == 3
    assert d.evictions == 2
    assert 0 not in d and 1 not in d
    assert d.get(4) == 40
    # LRU order: touching 2 protects it from the next eviction
    assert d[2] == 20
    d[5] = 50
    assert 2 in d and 3 not in d

    b = LruDict(100, max_bytes=100, sizer=lambda v: v)
    b["a"] = 60
    b["b"] = 60  # over budget: "a" evicted
    assert "a" not in b and "b" in b
    assert b.nbytes() == 60
    b.clear()
    assert len(b) == 0 and b.nbytes() == 0


def test_module_caches_are_bounded():
    import ballista_tpu.ops.tpu.final_stage as fs
    import ballista_tpu.ops.tpu.stage_compiler as sc

    for cache in (sc._COMPILE_CACHE, sc._LUT_CACHE, sc._BUILD_CACHE,
                  fs._FINAL_COMPILE_CACHE):
        assert isinstance(cache, sc.LruDict)
        assert cache.max_entries >= 1


def test_run_stats_isolation_across_concurrent_stages():
    from ballista_tpu.ops.tpu.stage_compiler import RunStats

    rs = RunStats()
    barrier = threading.Barrier(2)

    def stage(tag, key, value):
        with rs.run(tag) as rec:
            barrier.wait()
            rs.set(key, value, rec=rec)
            time.sleep(0.01)
            # thread-local routing: a bare set() lands in THIS run
            rs.set(f"{key}_tls", value + 1)

    t1 = threading.Thread(target=stage, args=("stage_a", "fill_s", 1.0))
    t2 = threading.Thread(target=stage, args=("stage_b", "exec_s", 2.0))
    t1.start(); t2.start(); t1.join(); t2.join()

    stages = rs.stages()
    assert stages["stage_a"] == {"fill_s": 1.0, "fill_s_tls": 2.0,
                                 "dispatches": 1}
    assert stages["stage_b"] == {"exec_s": 2.0, "exec_s_tls": 3.0,
                                 "dispatches": 1}
    merged = rs.snapshot()
    assert merged["fill_s"] == 1.0 and merged["exec_s"] == 2.0
    # legacy surfaces: Mapping view and item assignment outside a run scope
    assert dict(rs)["fill_s"] == 1.0
    rs["device_bytes"] = 7
    assert rs["device_bytes"] == 7
    # a stage's later dispatch merges over its record, keeping the cold keys
    with rs.run("stage_a") as rec:
        rec["exec_s"] = 0.5
    assert rs.stages()["stage_a"] == {"fill_s": 1.0, "fill_s_tls": 2.0,
                                      "exec_s": 0.5, "dispatches": 2}
    rs.clear()
    assert not rs.snapshot() and not rs.stages()


def test_fill_and_cache_knobs_registered():
    cfg = BallistaConfig({})
    assert int(cfg.get(TPU_FILL_THREADS)) == 0
    assert int(cfg.get(TPU_FILL_CHUNK_ROWS)) == 0
    assert bool(cfg.get(TPU_COMPILE_OVERLAP)) is True
    # the compile cache has no key: it is placed from outside the program
    from ballista_tpu.config import _ENTRIES

    assert not [e.name for e in _ENTRIES if "compile.cache" in e.name]


def test_compile_cache_placed_from_outside(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set the cache lives there and the
    program names no directory of its own; unset, it is one fixed path
    inside the checkout — never a temp name, pid or time."""
    import os
    import subprocess
    import sys

    from ballista_tpu.ops.tpu import runtime

    code = ("import jax\n"
            "from ballista_tpu.ops.tpu import runtime\n"
            "runtime.ensure_jax()\n"
            "print(runtime.compile_cache_dir())\n"
            "print(jax.config.jax_compilation_cache_dir)\n")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def run(env):
        r = subprocess.run([sys.executable, "-c", code], cwd=root, env=env,
                           capture_output=True, text=True, timeout=120)
        assert r.returncode == 0, r.stderr[-2000:]
        return r.stdout.split()

    placed = str(tmp_path / "placed")
    env = dict(os.environ, **{runtime.CACHE_DIR_ENV: placed})
    assert run(env) == [placed, placed]
    env.pop(runtime.CACHE_DIR_ENV)
    fixed = os.path.join(root, ".jax_compile_cache")
    assert run(env) == [fixed, fixed]
    assert run(env) == [fixed, fixed]  # same path on every start


def test_estimate_stage_matches_actual_device_bytes():
    """The admission planner trusts estimate_stage byte-for-byte: its
    table_bytes must equal the filled DeviceTable's nbytes (data stacks +
    validity planes + row mask), and dictionary-coded string columns must
    price their device LUTs in dict_bytes rather than undercounting to the
    4-byte code plane alone."""
    from ballista_tpu.ops.tpu import fusion

    tbl = _mixed_table()
    scan = _scan(tbl)
    dt = _load(scan, fill_threads=1)
    est = fusion.estimate_stage([], None, dt, [])
    assert est.table_bytes == dt.nbytes
    # "flag" is dictionary-encoded: the LUT rows must be priced
    assert any(d for d in dt.dicts)
    assert est.dict_bytes > 0
    # the full working set the planner admits against is estimate-exact
    assert est.table_bytes + est.dict_bytes >= dt.nbytes
