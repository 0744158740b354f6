"""Out-of-core TPU execution tests (jax CPU backend via conftest env).

- admission boundary decisions: plan_stage's ladder (run_whole /
  spill_colds / grace_split / cpu_demote) at exact budget boundaries
- host spill pool: put → pop byte parity across the host and disk tiers,
  tmp+rename discipline, counters
- device-table spill → touch → re-upload byte parity through the cache
- grace-join vs CPU-engine oracle on skewed keys with nulls + strings,
  byte-identical to the unconstrained device run
- grace recursion-depth cap → CPU-engine demotion (still correct)
- chaos hbm_oom e2e: TPC-H q3 under a forced sub-working-set budget
  completes byte-identical via grace with nonzero counters; an injected
  RESOURCE_EXHAUSTED is absorbed by the spill+retry rung
"""

import os
import re

import numpy as np
import pyarrow as pa
import pytest

from ballista_tpu.config import (
    BallistaConfig,
    CHAOS_ENABLED,
    CHAOS_MODE,
    EXECUTOR_ENGINE,
    TPU_HBM_BUDGET_BYTES,
    TPU_HBM_GRACE_DEPTH,
    TPU_MIN_ROWS,
)
from ballista_tpu.ops.tpu import hbm
from ballista_tpu.ops.tpu.fusion import StageEstimate

from .conftest import tpch_query
from .test_tpu_fill import _assert_tables_identical, _mixed_table, _scan


def _est(table=1000, dicts=0, build=4000, jidx=0, has_mult=False):
    return StageEstimate(
        has_mult=has_mult, table_bytes=table, dict_bytes=dicts,
        build_bytes=build, max_build_bytes=build, max_build_jidx=jidx)


def _plan(est, budget, **kw):
    kw.setdefault("grace_eligible", True)
    kw.setdefault("grace_fanout", 4)
    kw.setdefault("grace_max_depth", 2)
    return hbm.plan_stage(est, budget, **kw)


class TestAdmission:
    def test_exact_fit_runs_whole(self):
        p = _plan(_est(), 5000)  # working set == budget
        assert p.decision == hbm.RUN_WHOLE
        assert p.working_set == 5000

    def test_one_byte_over_grace_splits(self):
        p = _plan(_est(), 4999)
        assert p.decision == hbm.GRACE_SPLIT
        assert p.grace_depth == 1
        assert p.grace_buckets == 4
        assert p.split_jidx == 0

    def test_unbudgeted_runs_whole(self):
        assert _plan(_est(), 0).decision == hbm.RUN_WHOLE

    def test_cold_residents_force_spill(self):
        p = _plan(_est(), 6000, resident_other=2000)
        assert p.decision == hbm.SPILL_COLDS
        p = _plan(_est(), 8000, resident_other=2000)  # both fit: no spill
        assert p.decision == hbm.RUN_WHOLE

    def test_depth_escalation(self):
        # depth 1 (1000 + 4000/4 = 2000) misses, depth 2 (1000 + 250) fits
        p = _plan(_est(), 1500)
        assert p.decision == hbm.GRACE_SPLIT
        assert p.grace_depth == 2
        assert p.grace_buckets == 16

    def test_depth_cap_demotes_to_cpu(self):
        p = _plan(_est(), 1010)  # even 16 buckets: 1000 + 250 > 1010
        assert p.decision == hbm.CPU_DEMOTE
        assert "depth cap" in p.reason

    def test_fixed_bytes_over_budget_demote(self):
        p = _plan(_est(), 900)  # non-splittable 1000 B alone exceed budget
        assert p.decision == hbm.CPU_DEMOTE
        assert "non-splittable" in p.reason

    def test_ineligible_join_demotes(self):
        p = _plan(_est(), 4999, grace_eligible=False)
        assert p.decision == hbm.CPU_DEMOTE
        p = _plan(_est(build=0, jidx=-1), 999)  # no build at all
        assert p.decision == hbm.CPU_DEMOTE

    def test_grace_disabled_demotes(self):
        p = _plan(_est(), 4999, grace_max_depth=0)
        assert p.decision == hbm.CPU_DEMOTE
        assert "disabled" in p.reason

    def test_post_oom_hint_prefers_grace(self):
        p = _plan(_est(), 10_000, force_grace=True)
        assert p.decision == hbm.GRACE_SPLIT
        assert "post-OOM" in p.reason

    def test_post_oom_hint_without_grace_reruns_whole(self):
        # the evict+spill freed the device: a joinless stage's one retry
        # re-attempts the device run instead of demoting straight to CPU
        p = _plan(_est(build=0, jidx=-1), 10_000, force_grace=True)
        assert p.decision == hbm.RUN_WHOLE
        assert "re-running whole after spill" in p.reason
        p = _plan(_est(), 10_000, force_grace=True, grace_max_depth=0)
        assert p.decision == hbm.RUN_WHOLE

    def test_observed_bytes_floor_the_estimate(self):
        # AQE-observed input volume overrides an optimistic build estimate
        p = _plan(_est(build=10), 2000, observed_bytes=5000)
        assert p.working_set == 6000
        assert p.decision == hbm.GRACE_SPLIT


def test_grace_bucket_of_covers_and_is_deterministic():
    keys = np.array([0, 1, 5, -3, 1 << 40, 7, 7, 123456789], dtype=np.int64)
    b1 = hbm.grace_bucket_of(keys, 4)
    b2 = hbm.grace_bucket_of(keys, 4)
    assert (b1 == b2).all()
    assert ((b1 >= 0) & (b1 < 4)).all()
    # equal keys always share a bucket (the correctness invariant)
    assert b1[5] == b1[6]
    # a spread of keys lands in more than one bucket
    many = hbm.grace_bucket_of(np.arange(1000, dtype=np.int64), 4)
    assert len(np.unique(many)) == 4


class TestGracePostconditions:
    def _report(self, **over):
        kw = dict(stage_tag="s", n_buckets=4, fanout=4, depth=1, max_depth=2,
                  buckets_run=[0, 1, 3], buckets_empty=[2])
        kw.update(over)
        return hbm.GraceReport(**kw)

    def test_good_report_passes(self):
        from ballista_tpu.analysis.plan_check import check_grace

        assert check_grace(self._report()) == []

    def test_missing_bucket_flags_cover(self):
        from ballista_tpu.analysis.plan_check import check_grace

        v = check_grace(self._report(buckets_run=[0, 1], buckets_empty=[2]))
        assert any("grace-cover" == x.code for x in v)

    def test_overlap_flags_cover(self):
        from ballista_tpu.analysis.plan_check import check_grace

        v = check_grace(self._report(buckets_run=[0, 1, 2, 3],
                                     buckets_empty=[2]))
        assert any("grace-cover" == x.code for x in v)

    def test_non_producer_order_merge_flags(self):
        from ballista_tpu.analysis.plan_check import check_grace

        v = check_grace(self._report(merge="bucket-major-shuffled"))
        assert any("grace-order" == x.code for x in v)

    def test_depth_over_cap_flags(self):
        from ballista_tpu.analysis.plan_check import check_grace

        v = check_grace(self._report(depth=3, max_depth=2, n_buckets=64,
                                     buckets_run=list(range(64)),
                                     buckets_empty=[]))
        assert any("grace-depth" == x.code for x in v)

    def test_bucket_fanout_mismatch_flags(self):
        from ballista_tpu.analysis.plan_check import check_grace

        v = check_grace(self._report(n_buckets=5,
                                     buckets_run=[0, 1, 2, 3, 4],
                                     buckets_empty=[]))
        assert any("grace-depth" == x.code for x in v)


class TestHostSpillPool:
    def test_host_tier_roundtrip_preserves_none_slots(self):
        pool = hbm.HostSpillPool(max_host_bytes=1 << 20)
        arrays = [np.arange(10, dtype=np.int64), None,
                  np.ones((3, 3), dtype=bool)]
        nb = sum(a.nbytes for a in arrays if a is not None)
        pool.put(("k",), ("meta", 1), arrays, nb)
        st = pool.stats()
        assert st["spill_events"] == 1 and st["spill_bytes"] == nb
        assert st["host_bytes"] == nb
        meta, back = pool.pop(("k",))
        assert meta == ("meta", 1)
        assert back[1] is None
        assert np.array_equal(back[0], arrays[0])
        assert np.array_equal(back[2], arrays[2])
        assert pool.stats()["reupload_events"] == 1
        assert pool.pop(("k",)) is None

    def test_disk_tier_tmp_rename_discipline(self, tmp_path):
        pool = hbm.HostSpillPool(max_host_bytes=0, spill_dir=str(tmp_path))
        arrays = [np.arange(100, dtype=np.float64), None]
        pool.put(("d",), "m", arrays, arrays[0].nbytes)
        files = os.listdir(tmp_path)
        assert len(files) == 1 and files[0].endswith(".npz")
        assert not any(f.endswith(".tmp") for f in files)
        meta, back = pool.pop(("d",))
        assert meta == "m"
        assert back[1] is None
        assert np.array_equal(back[0], arrays[0])
        assert os.listdir(tmp_path) == []  # consumed

    def test_host_overflow_demotes_coldest_to_disk(self, tmp_path):
        pool = hbm.HostSpillPool(max_host_bytes=100, spill_dir=str(tmp_path))
        a1 = [np.zeros(10, dtype=np.int64)]  # 80 B
        a2 = [np.ones(10, dtype=np.int64)]
        pool.put(("one",), "m1", a1, 80)
        pool.put(("two",), "m2", a2, 80)
        assert len(pool) == 2
        assert pool.stats()["host_bytes"] <= 100
        assert len(os.listdir(tmp_path)) == 1  # the cold entry hit disk
        _, b1 = pool.pop(("one",))
        _, b2 = pool.pop(("two",))
        assert np.array_equal(b1[0], a1[0]) and np.array_equal(b2[0], a2[0])

    def test_clear_removes_disk_files(self, tmp_path):
        pool = hbm.HostSpillPool(max_host_bytes=0, spill_dir=str(tmp_path))
        pool.put(("x",), "m", [np.arange(5)], 40)
        assert os.listdir(tmp_path)
        pool.clear()
        assert os.listdir(tmp_path) == []
        assert len(pool) == 0


def test_device_table_spill_touch_reupload_parity(tmp_path):
    """A cached device table demoted to the pool and re-fetched on the next
    touch must be byte-identical — through the host tier AND the disk tier."""
    import ballista_tpu.ops.tpu.stage_compiler as sc
    from ballista_tpu.plan.physical import TaskContext

    for host_bytes in (1 << 30, 0):  # host tier, then forced disk tier
        sc.clear_device_caches()
        pool = hbm.HostSpillPool(max_host_bytes=host_bytes,
                                 spill_dir=str(tmp_path))
        ctx = TaskContext(BallistaConfig({}))
        scan = _scan(_mixed_table())
        buckets = [1 << 12, 1 << 14, 1 << 16]
        dt = sc.DEVICE_CACHE.get(scan, buckets, ctx, 1 << 30, None,
                                 spill_pool=pool)
        freed = sc.DEVICE_CACHE.ensure_headroom(0, None, pool)
        assert freed == dt.nbytes
        assert pool.stats()["spill_events"] == 1
        assert sc.DEVICE_CACHE.resident_bytes() == 0
        dt2 = sc.DEVICE_CACHE.get(scan, buckets, ctx, 1 << 30, None,
                                  spill_pool=pool)
        assert pool.stats()["reupload_events"] == 1
        _assert_tables_identical(dt, dt2)
    sc.clear_device_caches()


# ---------------------------------------------------------------------------
# e2e: grace-partitioned join vs CPU-engine oracle


def _skewed_tables():
    """Skewed join keys (70% in 10 hot keys), NULL probe keys, dictionary
    strings on both sides, money-lane amounts, and probe keys with no dim
    match (unmatched masking)."""
    rng = np.random.default_rng(7)
    n = 30_000
    keys = np.where(rng.random(n) < 0.7,
                    rng.integers(0, 10, n),
                    rng.integers(0, 1200, n)).astype(np.int64)
    key_arr = pa.array(
        [None if i % 23 == 0 else int(k) for i, k in enumerate(keys)],
        pa.int64())
    fact = pa.table({
        "k": key_arr,
        "flag": pa.array(rng.choice(["x", "y", "z", "w"], n)),
        "amount": np.round(rng.uniform(0, 100, n), 2),
    })
    dk = np.arange(1000, dtype=np.int64)  # keys 1000..1199 unmatched
    dim = pa.table({
        "dk": dk,
        "name": pa.array([f"seg{int(v) % 5}" for v in dk]),
    })
    return fact, dim


_ORACLE_SQL = (
    "select f.flag, d.name, count(*) c, sum(f.amount) s "
    "from fact f join dim d on f.k = d.dk "
    "group by f.flag, d.name order by f.flag, d.name")


def _join_stage_rec(stages: dict) -> dict:
    """The per-stage record of the budget-relevant join stage: the one whose
    admission reason states a working set (the final stage states its own
    `final stage fits` reason and would shadow it in the merged snapshot)."""
    recs = [r for r in stages.values()
            if re.search(r"working set (\d+) B", r.get("hbm_plan_reason", ""))]
    assert recs, f"no admission-planned stage in {list(stages)}"
    return max(recs, key=lambda r: int(
        re.search(r"working set (\d+) B", r["hbm_plan_reason"]).group(1)))


def _working_set(rec: dict) -> int:
    return int(re.search(r"working set (\d+) B",
                         rec["hbm_plan_reason"]).group(1))


def _run_oracle(cfg_over: dict) -> tuple[pa.Table, dict]:
    import ballista_tpu.ops.tpu.stage_compiler as sc
    from ballista_tpu.client.context import SessionContext

    sc.clear_device_caches()
    sc.RUN_STATS.clear()
    fact, dim = _skewed_tables()
    ctx = SessionContext(BallistaConfig(cfg_over))
    ctx.register_arrow_table("fact", fact, partitions=3)
    ctx.register_arrow_table("dim", dim, partitions=2)
    out = ctx.sql(_ORACLE_SQL).collect()
    return out, sc.RUN_STATS.stages()


def _assert_same_values(got: pa.Table, ref: pa.Table):
    assert got.num_rows == ref.num_rows
    for col in ("flag", "name", "c"):
        assert got.column(col).to_pylist() == ref.column(col).to_pylist()
    g = np.asarray(got.column("s").to_pylist(), dtype=np.float64)
    r = np.asarray(ref.column("s").to_pylist(), dtype=np.float64)
    assert np.allclose(g, r, rtol=0, atol=1e-6), (g, r)


def test_grace_join_matches_cpu_oracle():
    ref, _ = _run_oracle({})  # CPU engine oracle

    whole, stages = _run_oracle({EXECUTOR_ENGINE: "tpu", TPU_MIN_ROWS: 0})
    rec = _join_stage_rec(stages)
    assert rec["hbm_plan"] == hbm.RUN_WHOLE
    _assert_same_values(whole, ref)
    working = _working_set(rec)

    graced, stages = _run_oracle({
        EXECUTOR_ENGINE: "tpu", TPU_MIN_ROWS: 0,
        TPU_HBM_BUDGET_BYTES: working - 1,
    })
    rec = _join_stage_rec(stages)
    assert rec["hbm_plan"] == hbm.GRACE_SPLIT, rec["hbm_plan_reason"]
    assert rec["grace_splits"] >= 2
    _assert_same_values(graced, ref)
    # byte-identity against the unconstrained device run: producer-order
    # reunification makes the grace output literally the same table
    assert graced.equals(whole)


def test_grace_depth_cap_demotes_to_cpu_engine():
    ref, _ = _run_oracle({})
    _, stages = _run_oracle({EXECUTOR_ENGINE: "tpu", TPU_MIN_ROWS: 0})
    working = _working_set(_join_stage_rec(stages))

    # budget below the working set with grace disabled: the only rung left
    # is the CPU engine — the stage must decline, not crash, and the CPU
    # fallback must serve the exact oracle result
    demoted, stages = _run_oracle({
        EXECUTOR_ENGINE: "tpu", TPU_MIN_ROWS: 0,
        TPU_HBM_BUDGET_BYTES: working - 1, TPU_HBM_GRACE_DEPTH: 0,
    })
    rec = _join_stage_rec(stages)
    assert rec["hbm_plan"] == hbm.CPU_DEMOTE
    _assert_same_values(demoted, ref)


# ---------------------------------------------------------------------------
# e2e: chaos hbm_oom on TPC-H q3


@pytest.fixture()
def _chaos_cleanup():
    yield
    hbm.disarm_chaos()


def _run_q3_standalone(tpch_dir, cfg_over: dict):
    import ballista_tpu.ops.tpu.stage_compiler as sc
    from ballista_tpu.client.context import SessionContext
    from ballista_tpu.testing.tpchgen import register_tpch

    sc.clear_device_caches()
    sc.RUN_STATS.clear()
    cfg = BallistaConfig({EXECUTOR_ENGINE: "tpu", TPU_MIN_ROWS: 0, **cfg_over})
    ctx = SessionContext.standalone(cfg, num_executors=1, vcores=2)
    register_tpch(ctx, tpch_dir)
    try:
        out = ctx.sql(tpch_query(3)).collect()
    finally:
        ctx.shutdown()
    return out, sc.RUN_STATS.stages()


def test_chaos_hbm_oom_q3_grace_byte_identical(tpch_dir, tpch_ref_tables,
                                               monkeypatch, _chaos_cleanup):
    """TPC-H q3 whose join stage exceeds a chaos-forced HBM budget must
    complete byte-identical via the grace rung (nonzero grace_splits), not
    raise RESOURCE_EXHAUSTED or silently leave the device engine."""
    from ballista_tpu.testing.reference import compare_results, run_reference

    baseline, stages = _run_q3_standalone(tpch_dir, {})
    working = _working_set(_join_stage_rec(stages))

    monkeypatch.setenv("BALLISTA_CHAOS_HBM_BUDGET", str(working - 1))
    chaotic, stages = _run_q3_standalone(
        tpch_dir, {CHAOS_ENABLED: True, CHAOS_MODE: "hbm_oom"})

    rec = _join_stage_rec(stages)
    assert rec["hbm_budget_bytes"] == working - 1
    assert rec["hbm_plan"] == hbm.GRACE_SPLIT, rec["hbm_plan_reason"]
    assert rec.get("grace_splits", 0) > 0
    assert chaotic.equals(baseline), "grace q3 diverges from device baseline"
    problems = compare_results(chaotic, run_reference(3, tpch_ref_tables), 3)
    assert not problems, "\n".join(problems)


def test_chaos_injected_oom_spill_retry_converges(tpch_dir, tpch_ref_tables,
                                                  monkeypatch, _chaos_cleanup):
    """An injected RESOURCE_EXHAUSTED on a device upload is absorbed by the
    evict+spill+retry rung: the stage re-runs on device and the query is
    still correct (hbm_oom_retries recorded)."""
    from ballista_tpu.testing.reference import compare_results, run_reference

    monkeypatch.setenv("BALLISTA_CHAOS_HBM_BUDGET", str(1 << 30))
    monkeypatch.setenv("BALLISTA_CHAOS_HBM_OOM_N", "1")
    out, stages = _run_q3_standalone(
        tpch_dir, {CHAOS_ENABLED: True, CHAOS_MODE: "hbm_oom"})

    assert any(r.get("hbm_oom_retries", 0) >= 1 for r in stages.values()), \
        {t: r.get("hbm_oom_retries") for t, r in stages.items()}
    problems = compare_results(out, run_reference(3, tpch_ref_tables), 3)
    assert not problems, "\n".join(problems)
