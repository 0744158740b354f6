"""The recorder of the served path (ballista_tpu/tracing.py): spans, their
parents, the one record a job leaves in RUN_STATS.stages(), the trace
annotations, and the names the device side carries. CPU backend only: no
number here is a timing claim."""

import concurrent.futures as fut
import contextlib
import glob
import threading
import time

import pytest

from ballista_tpu.tracing import (
    MAX_SPANS_PER_JOB,
    RUN_STATS,
    RunStats,
    critical_path,
    job_path,
    join_job_parts,
)

from .conftest import tpch_query

NAME, ID, PARENT, START, END, STAGE, TASK, NUMBERS = range(8)


def job_records(stats: RunStats) -> dict:
    return {t: r for t, r in stats.stages().items() if t.startswith("job_")}


def by_name(spans: list, name: str) -> list:
    return [s for s in spans if s[NAME] == name]


def self_seconds(spans: list, span: list) -> float:
    """Duration minus what the children cover (children here never overlap)."""
    kids = [s for s in spans if s[PARENT] == span[ID]]
    return (span[END] - span[START]) - sum(k[END] - k[START] for k in kids)


def assert_a_tree_under(spans: list, root: list) -> None:
    """Every span reaches `root` through its parents and lies inside its
    parent's interval."""
    index = {s[ID]: s for s in spans}
    for s in spans:
        hops, cur = 0, s
        while cur is not root:
            parent = index.get(cur[PARENT])
            assert parent is not None, f"{s[NAME]} hangs under nothing: {cur}"
            assert parent[START] <= cur[START] and cur[END] <= parent[END], (cur, parent)
            cur, hops = parent, hops + 1
            assert hops < 64


# ------------------------------------------------------------- the recorder


def test_parent_child_and_self_time_on_a_hand_built_tree():
    stats = RunStats()
    with stats.span("bt.client.collect", root=True) as root:
        with stats.span("bt.client.submit") as submit:
            time.sleep(0.002)
            with stats.span("bt.sched.plan", job="j1", plan_cache_hit=1):
                time.sleep(0.003)
            root.set(job="j1")
        with stats.span("bt.client.wait"):
            time.sleep(0.002)
        assert root.seconds >= 0.007 and submit.seconds >= 0.005
    (tag, rec), = job_records(stats).items()
    assert tag == "job_j1" and rec["spans_dropped"] == 0 and set(rec) == {"spans", "spans_dropped"}
    spans = rec["spans"]
    collect, = by_name(spans, "bt.client.collect")
    submit, = by_name(spans, "bt.client.submit")
    plan, = by_name(spans, "bt.sched.plan")
    wait, = by_name(spans, "bt.client.wait")
    assert collect[PARENT] is None
    assert submit[PARENT] == collect[ID] == wait[PARENT] and plan[PARENT] == submit[ID]
    assert plan[NUMBERS] == {"plan_cache_hit": 1}
    assert_a_tree_under(spans, collect)
    assert self_seconds(spans, plan) >= 0.003
    assert 0.002 <= self_seconds(spans, submit) < submit[END] - submit[START] - 0.003 + 1e-6
    # the root's self time is what neither submit nor wait covers
    assert self_seconds(spans, collect) < 0.002
    # nothing of the counters' keys, and nothing through the dispatch count
    assert "dispatches" not in rec and "exec_s" not in rec
    assert stats.snapshot() == {}


def test_a_span_on_a_pool_thread_hangs_under_its_stage_and_job_by_ids():
    stats = RunStats()

    def task(task_id: int, stage: int) -> None:
        with stats.span("bt.task.run", job="j2", stage=stage, task=task_id):
            with stats.span("bt.stage.dispatch"):  # ids come from the task
                time.sleep(0.001)

    with fut.ThreadPoolExecutor(max_workers=2) as pool, \
            stats.span("bt.client.collect", root=True, job="j2"):
        with stats.span("bt.client.wait"):
            t0 = time.perf_counter_ns()
            list(pool.map(task, [1, 2], [1, 1]))
            stats.add_span("bt.sched.stage", t0, job="j2", stage=1)
            t1 = time.perf_counter_ns()
            pool.submit(task, 3, 2).result()
            # a task of another job in between: not this record's
            pool.submit(lambda: stats.span("bt.task.run", job="other", task=9).__enter__()
                        .__exit__(None, None, None)).result()
            stats.add_span("bt.sched.stage", t1, job="j2", stage=2)
    spans = job_records(stats)["job_j2"]["spans"]
    stage1, stage2 = sorted(by_name(spans, "bt.sched.stage"), key=lambda s: s[STAGE])
    wait, = by_name(spans, "bt.client.wait")
    assert stage1[PARENT] == wait[ID] == stage2[PARENT]
    runs = {s[TASK]: s for s in by_name(spans, "bt.task.run")}
    assert set(runs) == {1, 2, 3}
    assert runs[1][PARENT] == runs[2][PARENT] == stage1[ID] and runs[3][PARENT] == stage2[ID]
    for d in by_name(spans, "bt.stage.dispatch"):
        assert d[PARENT] == runs[d[TASK]][ID] and d[STAGE] == runs[d[TASK]][STAGE]
    assert_a_tree_under(spans, by_name(spans, "bt.client.collect")[0])
    assert [s.name for s in stats.job_spans("other")] == ["bt.task.run"]


def test_a_helper_thread_attaches_to_the_span_it_works_for():
    stats = RunStats()
    with stats.span("bt.client.collect", root=True, job="j3"):
        with stats.span("bt.stage.dispatch", stage=4, task=2) as dispatch:
            def helper():
                with stats.attach(dispatch), stats.span("bt.compile.trace"):
                    pass
                assert stats.current_span() is None

            t = threading.Thread(target=helper)
            t.start()
            t.join(timeout=10)
            assert not t.is_alive()
    spans = job_records(stats)["job_j3"]["spans"]
    trace, = by_name(spans, "bt.compile.trace")
    assert trace[PARENT] == by_name(spans, "bt.stage.dispatch")[0][ID]
    assert (trace[STAGE], trace[TASK]) == (4, 2)


def test_the_cap_counts_what_it_drops_and_keeps_the_root():
    stats = RunStats()
    with stats.span("bt.client.collect", root=True, job="big"):
        for _ in range(MAX_SPANS_PER_JOB + 10):
            with stats.span("bt.decode"):
                pass
    rec = job_records(stats)["job_big"]
    assert rec["spans_dropped"] == 10
    assert len(rec["spans"]) == MAX_SPANS_PER_JOB + 1
    assert len(by_name(rec["spans"], "bt.client.collect")) == 1


def test_spans_outside_any_job_ride_in_the_next_record_and_survive_clear():
    stats = RunStats()
    with stats.span("bt.device.fill", bytes=7):
        pass
    stats.clear()
    assert job_records(stats) == {}
    with stats.span("bt.client.collect", root=True) as root:
        with stats.span("bt.client.submit"):  # closes before the job has an id
            pass
        root.set(job="j4")
    spans = job_records(stats)["job_j4"]["spans"]
    assert {s[NAME] for s in spans} == {"bt.device.fill", "bt.client.submit", "bt.client.collect"}
    fill, = by_name(spans, "bt.device.fill")
    assert fill[PARENT] is None and fill[NUMBERS] == {"bytes": 7}
    assert by_name(spans, "bt.client.submit")[0][PARENT] == by_name(spans, "bt.client.collect")[0][ID]
    stats.clear()
    assert stats.stages() == {}


def test_job_records_keep_the_counters_apart():
    """`runstats_sum` of the benchmark sums a key over every tag: the job's
    record must hold none, and a stage's dispatches still count one each."""
    stats = RunStats()
    with stats.span("bt.client.collect", root=True, job="j5"):
        for _ in range(3):
            with stats.run("stage_aa") as rec, stats.span("bt.stage.dispatch"):
                rec["exec_s"] = 0.5
    stages = stats.stages()
    assert stages["stage_aa"] == {"exec_s": 0.5, "dispatches": 3}
    assert set(stages["job_j5"]) == {"spans", "spans_dropped"}
    assert sum(r.get("dispatches", 0) for r in stages.values()) == 3
    assert len(by_name(stages["job_j5"]["spans"], "bt.stage.dispatch")) == 3


def test_concurrent_spans_lose_nothing():
    """More threads than cores, a short switch interval: every span closed is
    in the record or counted as dropped, and ids are unique."""
    import sys

    stats = RunStats()
    n_threads, per_thread = 16, 200
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        with stats.span("bt.client.collect", root=True, job="j6"):
            def work(i):
                for _ in range(per_thread):
                    with stats.span("bt.task.run", job="j6", task=i):
                        with stats.span("bt.decode"):
                            pass

            threads = [threading.Thread(target=work, args=(i,)) for i in range(n_threads)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    rec = job_records(stats)["job_j6"]
    assert len(rec["spans"]) + rec["spans_dropped"] == 2 * n_threads * per_thread + 1
    assert len({s[ID] for s in rec["spans"]}) == len(rec["spans"])


def test_a_closed_span_is_cheap():
    """A trip-wire for a regression (a lock held long, an import in the
    path), not a timing claim: the target is a few microseconds."""
    stats = RunStats()
    n = 20_000
    best = float("inf")
    for _ in range(5):
        with stats.span("bt.client.collect", root=True, job="cheap"):
            t0 = time.perf_counter()
            for _ in range(n):
                with stats.span("bt.decode"):
                    pass
            best = min(best, (time.perf_counter() - t0) / n)
    assert best < 20e-6, f"{best * 1e6:.1f} us a span"


def test_the_old_names_still_import_from_stage_compiler():
    import ballista_tpu.ops.tpu.stage_compiler as sc
    import ballista_tpu.tracing as tracing

    assert sc.RUN_STATS is tracing.RUN_STATS and sc.STAGE_OUTCOMES is tracing.STAGE_OUTCOMES
    assert sc.RunStats is tracing.RunStats and sc.StageOutcomes is tracing.StageOutcomes


def test_tracing_imports_without_jax():
    """A scheduler or client process records spans and never loads jax."""
    import subprocess
    import sys

    code = ("import sys; import ballista_tpu.tracing as t\n"
            "with t.RUN_STATS.span('bt.client.collect', root=True, job='x'): pass\n"
            "assert 'job_x' in t.RUN_STATS.stages()\n"
            "assert 'jax' not in sys.modules and 'numpy' not in sys.modules\n")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=60)


# ------------------------------------------------- one query, end to end

# every name of the table in docs/tpu_engine.md#spans that a two-stage
# aggregate over parquet takes in the standalone topology
SERVED = {"bt.client.collect", "bt.client.submit", "bt.sched.plan", "bt.client.wait",
          "bt.sched.stage", "bt.task.queued", "bt.task.run", "bt.task.prepare",
          "bt.shuffle.write", "bt.shuffle.commit", "bt.shuffle.read", "bt.task.report",
          "bt.client.fetch_results"}
DEVICE = {"bt.stage.dispatch", "bt.device.exec", "bt.device.fetch", "bt.decode"}


@pytest.fixture(scope="module", params=["cpu", "tpu"])
def standalone(request, tpch_dir):
    from ballista_tpu.client.context import SessionContext
    from ballista_tpu.config import EXECUTOR_ENGINE, BallistaConfig
    from ballista_tpu.testing.tpchgen import register_tpch

    import ballista_tpu.ops.tpu.stage_compiler as sc

    # the first collect below must be cold whatever this process ran before
    # (tables and programs of the same files stay resident across tests)
    sc.clear_device_caches()
    ctx = SessionContext.standalone(BallistaConfig({EXECUTOR_ENGINE: request.param}),
                                    num_executors=1)
    register_tpch(ctx, tpch_dir)
    yield request.param, ctx
    ctx.shutdown()


def test_one_collect_leaves_one_job_record_covering_the_served_path(standalone):
    engine, ctx = standalone
    ctx.sql(tpch_query(1)).collect()  # fill, compile: the cold names are checked below
    cold = {s[NAME] for r in job_records(RUN_STATS).values() for s in r["spans"]}
    RUN_STATS.clear()
    out = ctx.sql(tpch_query(1)).collect()
    assert out.num_rows == 4
    stages = RUN_STATS.stages()
    (tag, rec), = job_records(RUN_STATS).items()
    spans = rec["spans"]
    names = {s[NAME] for s in spans}
    assert SERVED <= names, SERVED - names
    root, = by_name(spans, "bt.client.collect")
    assert_a_tree_under(spans, root)  # one job: every span reaches its root
    assert rec["spans_dropped"] == 0 and set(rec) == {"spans", "spans_dropped"}
    assert by_name(spans, "bt.sched.plan")[0][NUMBERS]["plan_cache_hit"] == 1
    tasks = by_name(spans, "bt.task.run")
    assert len(tasks) == len(by_name(spans, "bt.task.queued")) == len(by_name(spans, "bt.task.report"))
    assert {s[STAGE] for s in by_name(spans, "bt.sched.stage")} == {s[STAGE] for s in tasks}
    dispatches = [s for s in by_name(spans, "bt.stage.dispatch")
                  if s[NUMBERS].get("family") == "partial"]
    counted = sum(r.get("dispatches", 0) for t, r in stages.items() if not t.startswith("job_"))
    if engine == "tpu":
        assert DEVICE <= names and {"bt.device.fill", "bt.compile.trace", "bt.compile.xla"} <= cold
        # one span a dispatch, one dispatch a device stage: the scheduler
        # hands the stage's eight partitions to the one executor as one task
        assert len(dispatches) == counted == 1
        assert sorted(t[NUMBERS]["partitions"] for t in tasks)[-1] == 8
        for d in dispatches:
            kids = {s[NAME] for s in spans if s[PARENT] == d[ID]}
            assert {"bt.device.exec", "bt.device.fetch", "bt.decode"} <= kids
    else:
        assert not (DEVICE & names) and counted == 0


@pytest.fixture(scope="module")
def window_query():
    """A window query served once through `SessionContext.standalone` with
    the TPU engine: two window functions over one PARTITION BY / ORDER BY
    (one ordering, two scans) and a third over an ordering of its own."""
    import numpy as np
    import pyarrow as pa

    from ballista_tpu.client.context import SessionContext
    from ballista_tpu.config import EXECUTOR_ENGINE, TPU_MIN_ROWS, BallistaConfig
    from ballista_tpu.ops.tpu import sort_window as sw

    rng = np.random.default_rng(34)
    n = 5000
    t = pa.table({"g": pa.array(rng.integers(0, 40, n), pa.int32()),
                  "v": pa.array(np.round(rng.uniform(0, 100, n), 6)),
                  "w": pa.array(rng.integers(0, 1000, n), pa.int64())})
    ctx = SessionContext.standalone(BallistaConfig({EXECUTOR_ENGINE: "tpu", TPU_MIN_ROWS: 0}),
                                    num_executors=1)
    try:
        ctx.register_arrow_table("t", t, partitions=2)
        sql = ("SELECT g, row_number() OVER (PARTITION BY g ORDER BY v DESC) rn, "
               "max(w) OVER (PARTITION BY g ORDER BY v DESC) mw, "
               "rank() OVER (PARTITION BY g ORDER BY w) rk FROM t")
        sw._CALLED.clear()  # whatever this process ran before: every program is new to it
        ctx.sql(sql).collect()  # the first call of a program is a `bt.compile.xla`
        cold = RUN_STATS.stages()
        RUN_STATS.clear()
        out = ctx.sql(sql).collect()
        stages = RUN_STATS.stages()
    finally:
        ctx.shutdown()
    (_, rec), = ((t_, r) for t_, r in stages.items() if t_.startswith("job_"))
    return n, out, rec["spans"], {t_: r for t_, r in stages.items() if t_.startswith("window_")}, cold


def test_the_window_familys_spans_nest_under_its_dispatch(window_query):
    n, out, spans, _, _ = window_query
    assert out.num_rows == n
    dispatches = [s for s in by_name(spans, "bt.stage.dispatch")
                  if s[NUMBERS].get("family") == "window"]
    assert dispatches
    assert_a_tree_under(spans, by_name(spans, "bt.client.collect")[0])
    rows = 0
    for d in dispatches:
        under = [s for s in spans if s[PARENT] == d[ID]]
        keys = [s for s in under if s[NAME] == "bt.window.keys"]
        emits = [s for s in under if s[NAME] == "bt.window.emit"]
        execs = [s for s in under if s[NAME] == "bt.device.exec"]
        # two distinct orderings: keys and ONE device call (the frame program: order,
        # boundaries, the ranking function's scan and scatter) once each; three
        # functions: an emit each, the aggregate's value scans inside its own
        assert len(keys) == 2 and len(execs) == 2 and len(emits) == 3
        assert {s[NAME] for s in under} == {"bt.window.keys", "bt.window.emit", "bt.device.exec"}
        assert all(k[NUMBERS]["key_lanes"] == 2 and k[NUMBERS]["rows"] > 0 for k in keys)
        assert sorted(e[NUMBERS]["kernel"] for e in execs) == ["segscan_rank", "segscan_row_number"]
        for e in execs:
            nums = e[NUMBERS]
            assert nums["lanes"] >= nums["rows"] > 0 and 0 < nums["partitions"] <= 40
            assert nums["lanes"] & (nums["lanes"] - 1) == 0 and nums["bytes"] >= 12 * nums["lanes"]
        assert sorted(e[NUMBERS]["func"] for e in emits) == ["max", "rank", "row_number"]
        scans = [s for s in spans if s[NAME] == "bt.device.exec"
                 and s[PARENT] in {e[ID] for e in emits}]
        # max: the count's sum scan and the max scan; row_number and rank: none
        assert sorted(s[NUMBERS]["kernel"] for s in scans) == ["segscan_max", "segscan_sum"]
        assert all(s[NUMBERS]["bytes"] == 9 * s[NUMBERS]["lanes"] for s in scans)
        rows += keys[0][NUMBERS]["rows"]
    assert rows == n


def test_a_window_task_leaves_a_record_of_its_own(window_query):
    n, _, spans, records, cold = window_query
    dispatches = [s for s in by_name(spans, "bt.stage.dispatch")
                  if s[NUMBERS].get("family") == "window"]
    assert len(records) == len(dispatches) >= 1
    assert sum(r["window_rows"] for r in records.values()) == n
    for rec in records.values():
        assert rec["dispatches"] == 1 and rec["exec_s"] > 0 and rec["device_bytes"] > 0
        # two orderings of the task's rows, padded, each by a frame program; 40 groups
        # found by each; the aggregate's two scans beside them
        assert rec["window_lanes"] >= 2 * rec["window_rows"]
        assert 0 < rec["window_segments"] <= 2 * 40
        assert rec["window_frames_fused"] == 2 and rec["window_scans"] == 2
        assert "xla_compile_s" not in rec  # every program had been called before
    # the first query's first calls were the compiles: named so, and counted on the record
    compiles = [s for r in cold.values() for s in r.get("spans", ()) if s[NAME] == "bt.compile.xla"]
    assert {s[NUMBERS]["kernel"] for s in compiles} == {
        "segscan_row_number", "segscan_rank", "segscan_sum", "segscan_max"}
    assert sum(r.get("xla_compile_s", 0) for t, r in cold.items() if t.startswith("window_")) > 0


def test_the_profiler_trace_holds_the_programs_spans(standalone, tmp_path):
    import jax
    from jax.profiler import ProfileData

    engine, ctx = standalone
    ctx.sql(tpch_query(6)).collect()
    RUN_STATS.clear()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation("q6"):
            ctx.sql(tpch_query(6)).collect()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = [e for plane in ProfileData.from_file(path).planes if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events if e.name.startswith("bt.")]
    found = {e.name for e in events}
    want = {"bt.client.collect", "bt.client.wait", "bt.task.run", "bt.shuffle.write"}
    if engine == "tpu":
        want |= {"bt.stage.dispatch", "bt.device.exec"}
    assert want <= found, want - found
    # the hand-over intervals are recorded after the fact: no annotation
    assert not {"bt.task.queued", "bt.sched.stage", "bt.shuffle.read"} & found
    tag, = job_records(RUN_STATS)
    jobs = {dict(e.stats).get("job") for e in events}
    assert jobs == {tag[len("job_"):]}, jobs  # every one carries the job's id


# ------------------------------------------------- names on the device side


def _first_stage(q: int, tpch_dir):
    import ballista_tpu.ops.tpu.stage_compiler as sc
    from ballista_tpu.client.context import SessionContext
    from ballista_tpu.config import EXECUTOR_ENGINE, BallistaConfig
    from ballista_tpu.engine.tpu_engine import maybe_compile_tpu
    from ballista_tpu.plan.physical import HashJoinExec, TaskContext
    from ballista_tpu.testing.tpchgen import register_tpch

    from .conftest import iter_plan

    cfg = BallistaConfig({EXECUTOR_ENGINE: "tpu"})
    ctx = SessionContext(cfg)
    register_tpch(ctx, tpch_dir)
    phys = maybe_compile_tpu(ctx.create_physical_plan(ctx.sql(tpch_query(q)).plan), cfg)
    stage = next(n for n in iter_plan(phys) if isinstance(n, sc.TpuStageExec))
    tc = TaskContext(cfg)
    dt = sc.DEVICE_CACHE.get(stage.scan, stage.buckets, tc, 1 << 34)
    table_key = sc.DEVICE_CACHE.key_of(stage.scan)
    builds = [stage._prepare_build(op, j, tc, table_key)
              for j, op in enumerate(o for o in stage.ops if isinstance(o, HashJoinExec))]
    return stage, dt, builds


def _lower(stage, dt, builds):
    P, N = dt.shape
    _, _, meta, lowered = stage._compile(dt, list(zip(dt.kinds, dt.scales)), dt.dicts, P, N,
                                         builds)
    return meta, lowered


@pytest.mark.parametrize("q, module, scopes", [
    (1, "jit_stage_partial_direct_fused_xla", {"scan_decode", "filter", "project", "partial_agg"}),
    (3, "jit_stage_partial_sorted_fused_xla",
     {"scan_decode", "filter", "project", "join_probe_0", "sorted_agg"}),
])
def test_named_scopes_change_metadata_only(q, module, scopes, tpch_dir, monkeypatch):
    """The stage's program with the operator scopes is the program without
    them, but for locations and names: same StableHLO text."""
    import jax

    stage, dt, builds = _first_stage(q, tpch_dir)
    meta, scoped = _lower(stage, dt, builds)
    text = scoped.as_text()
    assert f"module @{module}" in text and "jit_raw" not in text
    located = scoped.as_text(debug_info=True)
    for scope in scopes:
        assert f"{scope}/" in located, scope
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    _, plain = _lower(stage, dt, builds)
    assert "partial_agg/" not in plain.as_text(debug_info=True)
    assert plain.as_text() == text


# the partial-stage, sort and window programs by the names a trace shows;
# tests/test_tpu_compile.py compiles each for the chip
JITTED_STAGE_FAMILIES = (
    "stage_partial_direct_fused_xla", "stage_partial_sorted_fused_xla",
    "sort_lex_order", "window_segscan_sum", "window_segscan_min",
    "window_segscan_max", "window_segscan_row_number")


def test_every_jitted_stage_function_has_a_name_of_its_own():
    """No `jit_raw`, no `jit__lambda_`: a trace names the stage family (the
    two partial-stage names are checked on q1 and q3 above)."""
    import jax.numpy as jnp

    from ballista_tpu.ops.tpu import sort_window as sw

    keys = jnp.arange(8, dtype=jnp.int32)
    lowered = {"sort_lex_order": sw._lex_order_jit().lower(keys).as_text()}
    for func in ("sum", "min", "max"):
        lowered[f"window_segscan_{func}"] = \
            sw._segscan_jit(func).lower(keys, keys > 3).as_text()
    frame = sw._frame_jit((("int", True, False, False, True),), ("row_number",), False, 8)
    lowered["window_segscan_row_number"] = frame.lower(jnp.int32(8), keys).as_text()
    assert set(lowered) == set(JITTED_STAGE_FAMILIES[2:])
    for name, text in lowered.items():
        assert f"module @jit_{name}" in text


# -------------------------------------------------------- the critical path
#
# hand-made records, plain rows: [name, id, parent, start_s, end_s, stage, task, numbers]


def _row(name, sid, parent, start, end, stage=None, task=None, **numbers):
    return [name, sid, parent, start, end, stage, task, numbers]


def _one_thread_nested():
    """A query on one thread: every span the child of the one around it."""
    return [
        _row("bt.client.collect", 1, None, 0.0, 10.0),
        _row("bt.client.submit", 2, 1, 0.0, 1.0),
        _row("bt.sched.plan", 3, 2, 0.2, 0.8),
        _row("bt.client.wait", 4, 1, 1.0, 9.0),
        _row("bt.sched.stage", 5, 4, 1.0, 8.5, 1),
        _row("bt.task.run", 6, 5, 1.5, 8.0, 1, 1),
        _row("bt.shuffle.write", 7, 6, 2.0, 7.5, 1, 1),
        _row("bt.stage.dispatch", 8, 7, 2.5, 6.0, 1, 1),
        _row("bt.device.exec", 9, 8, 3.0, 5.0, 1, 1),
        _row("bt.shuffle.commit", 10, 7, 6.5, 7.0, 1, 1),
        _row("bt.client.fetch_results", 11, 1, 9.0, 9.9),
    ], {"bt.client.collect": 0.1, "bt.client.submit": 0.4, "bt.sched.plan": 0.6,
        "bt.client.wait": 0.5, "bt.sched.stage": 1.0, "bt.task.run": 1.0,
        "bt.shuffle.write": 1.5, "bt.stage.dispatch": 1.5, "bt.device.exec": 2.0,
        "bt.shuffle.commit": 0.5, "bt.client.fetch_results": 0.9}


def _four_slots_three_waves():
    """Twelve tasks of one stage on four slots: a slot's next task starts when
    its last one ends. The path takes one task a wave — the one whose end
    freed the slot the next one on the path ran in — never a neighbour that
    was still running."""
    rows = [_row("bt.client.collect", 1, None, 0.0, 40.0),
            _row("bt.client.wait", 2, 1, 0.0, 40.0),
            _row("bt.sched.stage", 3, 2, 0.0, 40.0, 1)]
    ends = {0: [10.0, 21.0, 33.0], 1: [11.0, 22.0, 34.0],
            2: [12.0, 20.0, 40.0], 3: [13.0, 26.0, 31.0]}
    sid = 10
    for slot, stops in ends.items():
        start = 0.0
        for wave, stop in enumerate(stops):
            rows.append(_row("bt.task.run", sid, 3, start, stop, 1, 4 * wave + slot))
            start, sid = stop, sid + 1
    # the last to end is slot 2's third task (20 -> 40); before it slot 2's
    # second (12 -> 20) and first (0 -> 12): 40 s of bt.task.run, no wait
    return rows, {"bt.task.run": 40.0}, [(0.0, 12.0), (12.0, 20.0), (20.0, 40.0)]


def _first_stage_beside_the_wait():
    """A query's first stage becomes runnable inside bt.client.submit, so its
    bt.sched.stage hangs under bt.client.collect BESIDE bt.client.wait, which
    covers it: its tasks are on the path all the same, and the hand-over
    between its tasks goes to the stage, not to the client's wait."""
    return [
        _row("bt.client.collect", 1, None, 0.0, 20.0),
        _row("bt.client.submit", 2, 1, 0.0, 1.0),
        _row("bt.sched.stage", 3, 1, 0.5, 9.0, 1),       # hung beside the wait
        _row("bt.client.wait", 4, 1, 1.0, 19.0),
        _row("bt.task.run", 5, 3, 1.0, 5.0, 1, 1),
        _row("bt.task.run", 6, 3, 5.5, 8.5, 1, 2),
        _row("bt.sched.stage", 7, 4, 9.5, 18.0, 2),
        _row("bt.task.run", 8, 7, 10.0, 18.0, 2, 3),
        _row("bt.client.fetch_results", 9, 1, 19.0, 20.0),
    ], {"bt.client.submit": 1.0, "bt.task.run": 15.0,
        "bt.sched.stage": 0.5 + 0.5 + 0.5,   # 5–5.5 and 8.5–9 in stage 1, 9.5–10 in stage 2
        "bt.client.wait": 0.5 + 1.0,         # 9–9.5 between the stages, 18–19 the poll
        "bt.client.fetch_results": 1.0}


def _task_clipped_by_its_stage():
    """A task's report ends after its stage has closed, and a task begins a
    little before its stage says it was runnable (another process's clock):
    both are clipped to what encloses them, and nothing is counted twice."""
    return [
        _row("bt.client.collect", 1, None, 0.0, 10.0),
        _row("bt.client.wait", 2, 1, 0.5, 9.5),
        _row("bt.sched.stage", 3, 2, 1.0, 6.0, 1),
        _row("bt.task.run", 4, 3, 0.8, 5.0, 1, 1),       # starts before its stage
        _row("bt.task.report", 5, 3, 5.0, 6.4, 1, 1),    # ends after it
        _row("bt.sched.stage", 6, 2, 6.0, 9.0, 2),
        _row("bt.task.run", 7, 6, 6.4, 9.0, 2, 2),
    ], {"bt.client.collect": 1.0, "bt.client.wait": 0.8, "bt.task.run": 4.2 + 2.6,
        "bt.task.report": 1.4}


def _six_processes():
    """The same query as six processes recorded it (ids collide, clocks differ,
    no parent crosses a process), joined into one record."""
    S = 1_000_000_000

    def part(process, offset_s, rows):
        shifted = [[r[0], r[1], r[2], r[3] + offset_s, r[4] + offset_s, *r[5:]] for r in rows]
        return {"process": process, "clock": [int((100 + offset_s) * S), 1000 * S],
                "spans": shifted, "spans_dropped": 0}

    parts = [
        part("client", 0.0, [
            _row("bt.client.collect", 1, None, 0.0, 20.0),
            _row("bt.client.submit", 2, 1, 0.0, 1.0),
            _row("bt.client.wait", 3, 1, 1.0, 19.0),
            _row("bt.client.fetch_results", 4, 1, 19.0, 20.0)]),
        part("scheduler:s0", 500.25, [
            _row("bt.sched.plan", 1, None, 0.2, 0.8),
            _row("bt.sched.stage", 2, None, 1.05, 9.0, 1),
            _row("bt.task.launch", 3, None, 1.05, 1.2, 1, 1, tasks=2),
            _row("bt.task.launch", 4, None, 1.05, 1.3, 1, 2, tasks=2),
            _row("bt.sched.stage", 5, None, 9.2, 18.5, 2),
            _row("bt.task.launch", 6, None, 9.2, 9.5, 2, 5, tasks=1)]),
        part("executor:e0", -77.5, [
            _row("bt.task.run", 1, None, 1.2, 6.0, 1, 1),
            _row("bt.shuffle.write", 2, 1, 1.3, 5.9, 1, 1)]),
        part("executor:e1", 3.0, [
            _row("bt.task.run", 1, None, 1.3, 8.8, 1, 2),
            _row("bt.shuffle.write", 2, 1, 1.4, 8.7, 1, 2)]),
        part("executor:e2", 1e6, [
            _row("bt.task.run", 1, None, 9.5, 18.2, 2, 5),
            _row("bt.device.exec", 2, 1, 10.0, 17.0, 2, 5)]),
        part("executor:e3", 0.125, []),
    ]
    return join_job_parts("j", parts)["spans"], {
        "bt.client.submit": 0.2 + 0.2, "bt.sched.plan": 0.6, "bt.task.launch": 0.25 + 0.3,
        "bt.task.run": 0.1 + 0.1 + 0.5 + 1.2, "bt.shuffle.write": 7.3,
        "bt.device.exec": 7.0, "bt.sched.stage": 0.2 + 0.3,
        "bt.client.wait": 0.05 + 0.2 + 0.5, "bt.client.fetch_results": 1.0}


PATH_CASES = {
    "one_thread_nested": _one_thread_nested,
    "four_slots_three_waves": lambda: _four_slots_three_waves()[:2],
    "first_stage_beside_the_wait": _first_stage_beside_the_wait,
    "task_clipped_by_its_stage": _task_clipped_by_its_stage,
    "six_processes_joined": _six_processes,
}


@pytest.mark.parametrize("case", sorted(PATH_CASES))
def test_the_critical_path_charges_each_span_what_the_client_waited_for(case):
    rows, want = PATH_CASES[case]()
    path = critical_path(rows)
    got = {name: round(s, 6) for name, s in path["seconds"].items() if round(s, 6)}
    assert got == {name: pytest.approx(s, abs=2e-6) for name, s in want.items()}, got


@pytest.mark.parametrize("case", sorted(PATH_CASES))
def test_the_paths_segments_tile_the_root_span(case):
    """The identity: no gap, no overlap, no negative segment, and the seconds
    by name are the segments' and sum to the root's duration."""
    rows, _ = PATH_CASES[case]()
    path = critical_path(rows)
    root = next(r for r in rows if r[ID] == path["root"])
    segments = path["segments"]
    assert segments[0][0] == root[START] and segments[-1][1] == root[END]
    ids = {r[ID]: r[NAME] for r in rows}
    for (a, b, sid, name), nxt in zip(segments, segments[1:] + [None]):
        assert b > a and ids[sid] == name
        assert nxt is None or nxt[0] == b  # the next starts where this one ends, exactly
    assert sum(path["seconds"].values()) == pytest.approx(root[END] - root[START], abs=1e-9)
    by_name_s: dict = {}
    for a, b, _, name in segments:
        by_name_s[name] = by_name_s.get(name, 0.0) + (b - a)
    assert by_name_s == path["seconds"]


def test_with_four_slots_the_path_takes_the_task_whose_end_freed_the_slot():
    rows, _, want = _four_slots_three_waves()
    segments = critical_path(rows)["segments"]
    assert [(a, b) for a, b, _, name in segments if name == "bt.task.run"] == want
    # a neighbour still running at that moment is no predecessor: slot 3's
    # second task (13 -> 26) overlaps the path's last task and is not on it
    neighbour = next(r[ID] for r in rows if r[START] == 13.0 and r[END] == 26.0)
    assert neighbour not in {sid for _, _, sid, _ in segments}


def test_a_waiting_span_never_hides_the_work_beside_it():
    """The walk that trusts the tree would charge all of stage 1 to
    bt.client.wait, which covers it; looked through, wherever the stage was
    hung the path is the same."""
    rows, want = _first_stage_beside_the_wait()
    mended = [list(r) for r in rows]
    next(r for r in mended if r[ID] == 3)[PARENT] = 4  # the stage hung UNDER the wait
    assert critical_path(mended)["seconds"] == critical_path(rows)["seconds"]
    assert critical_path(rows)["seconds"]["bt.client.wait"] == pytest.approx(1.5)
    # not looked through, the wait swallows what ran beside and inside it
    blind = critical_path(rows, waiting=())["seconds"]
    assert blind["bt.client.wait"] == pytest.approx(9.5) and blind["bt.task.run"] == 8.0


def test_a_span_that_ends_a_little_late_is_still_a_predecessor():
    """A hand-over between threads or processes: the report says it began
    0.2 ms before the task's end came (another clock). Within the tolerance
    the task is the predecessor; a span running well past `t` is not."""
    rows = [_row("bt.client.collect", 1, None, 0.0, 2.0),
            _row("bt.task.run", 2, 1, 0.0, 1.0002, 1, 1),
            _row("bt.task.report", 3, 1, 1.0, 2.0, 1, 1)]
    assert critical_path(rows)["seconds"] == {
        "bt.task.run": pytest.approx(1.0), "bt.task.report": pytest.approx(1.0)}
    rows[1][END] = 1.5  # still running half a second into the report: a neighbour
    assert critical_path(rows)["seconds"] == {
        "bt.client.collect": pytest.approx(1.0), "bt.task.report": pytest.approx(1.0)}


def test_a_record_without_a_root_has_no_path():
    rows, _ = _task_clipped_by_its_stage()
    assert critical_path([r for r in rows if r[NAME] != "bt.client.collect"]) is None
    assert job_path([]) is None


def test_the_joined_record_gives_the_path_of_the_one_process_record():
    """Six processes' parts joined, and the same spans as one process would
    have recorded them (parents in place): the same seconds by name."""
    joined, want = _six_processes()
    ids = {(r[NAME], r[STAGE], r[TASK], round(r[START], 3)): r[ID] for r in joined}
    one = [list(r) for r in joined]
    stage_of = {r[STAGE]: r[ID] for r in one if r[NAME] == "bt.sched.stage"}
    wait = next(r[ID] for r in one if r[NAME] == "bt.client.wait")
    for r in one:  # hang everything as the in-process recorder does
        if r[NAME] == "bt.sched.stage":
            r[PARENT] = wait
        elif r[NAME] in ("bt.task.run", "bt.task.launch"):
            r[PARENT] = stage_of[r[STAGE]]
    a, b = critical_path(joined)["seconds"], critical_path(one)["seconds"]
    assert a == pytest.approx(b) and len(ids) == len(joined)
    assert {"bt.task.launch", "bt.sched.stage", "bt.client.wait"} <= set(a)


def test_job_path_names_the_ten_longest_segments_with_stage_and_task():
    rows, want = _first_stage_beside_the_wait()
    path = job_path(rows)
    assert path["root_s"] == 20.0
    assert list(path["seconds"])[0] == "bt.task.run" and path["seconds"]["bt.task.run"] == 15.0
    assert sum(path["seconds"].values()) == pytest.approx(20.0)
    a, b, sid, name, stage, task = path["longest"][0]
    assert (a, b, name, stage, task) == (10.0, 18.0, "bt.task.run", 2, 3)
    assert len(path["longest"]) == min(10, len(critical_path(rows)["segments"]))
    assert [s[1] - s[0] for s in path["longest"]] == sorted(
        (s[1] - s[0] for s in path["longest"]), reverse=True)


# ------------------------------------- what the catch-all spans say they hold


def test_the_writers_span_names_the_operators_it_pulled(standalone):
    """`bt.shuffle.write` states the pull, the partitioning and the plan's
    operators; `bt.shuffle.read` the reading inside it."""
    engine, ctx = standalone
    ctx.sql(tpch_query(3)).collect()
    RUN_STATS.clear()
    ctx.sql(tpch_query(3)).collect()
    (_, rec), = job_records(RUN_STATS).items()
    spans = rec["spans"]
    writes = by_name(spans, "bt.shuffle.write")
    assert writes
    for w in writes:
        n = w[NUMBERS]
        assert n["pull_ms"] >= 0 and n["partition_ms"] >= 0
        assert n["pull_ms"] + n["partition_ms"] <= 1e3 * (w[END] - w[START]) + 0.01, w
        assert n["ops"] and all(len(o) == 6 for o in n["ops"])
        assert [o[0] for o in n["ops"]][0] == 1  # the writer's input, depth 1
        counted = sum(o[2] for o in n["ops"] if o[5] in ("", "clamped"))
        assert n["ops_ms"] == pytest.approx(counted, abs=0.001 * len(n["ops"]))
        assert all(o[5] == "span" for o in n["ops"]
                   if o[1] in ("ShuffleReaderExec", "TpuStageExec", "TpuFinalStageExec"))
    flagged = {o[1] for w in writes for o in w[NUMBERS]["ops"] if o[5] == "span"}
    assert "ShuffleReaderExec" in flagged
    if engine == "tpu":
        assert "TpuStageExec" in flagged
        device = next(w for w in writes
                      if any(o[1] == "TpuStageExec" for o in w[NUMBERS]["ops"]))
        # a device stage's operator is listed, flagged and not summed: its
        # dispatch is a span of its own inside the pull
        assert device[NUMBERS]["ops_ms"] < 1.0 < device[NUMBERS]["pull_ms"]
    scans = [o for w in writes for o in w[NUMBERS]["ops"] if o[1] == "ParquetScanExec" and o[3]]
    assert scans and all(o[5] == "" and o[2] > 0 for o in scans)
    # the operators in `collect_metrics` order: the scheduler's copy of the
    # same harvest, a task of the stage, names the same operators
    sched = ctx._cluster.scheduler
    with sched._jobs_lock:
        g = list(sched.jobs.values())[-1]
    for w in writes:
        per_task = g.stage_metrics[w[STAGE]]
        first = per_task[:len(w[NUMBERS]["ops"]) + 1]  # one task's harvest, the writer first
        assert [m["depth"] for m in first[1:]] == [o[0] for o in w[NUMBERS]["ops"]]
        assert [m["name"].split(":")[0] for m in first[1:]] == [o[1] for o in w[NUMBERS]["ops"]]
        assert all("self_ns" in m and m["self_ns"] <= max(m["elapsed_ns"], 0) for m in per_task)
    reads = by_name(spans, "bt.shuffle.read")
    assert reads
    for r in reads:
        assert 0 <= r[NUMBERS]["read_ms"] <= 1e3 * (r[END] - r[START]) + 0.01, r


def test_a_tasks_operator_metrics_are_its_own_where_tasks_share_a_plan(tmp_path):
    """In-process the tasks of a stage share the stage's plan objects: what a
    task reports, and what its write span says, is what ITS thread pulled."""
    import pyarrow as pa

    from ballista_tpu.config import BallistaConfig
    from ballista_tpu.executor.executor import Executor, ExecutorMetadata
    from ballista_tpu.plan.expressions import Column
    from ballista_tpu.plan.physical import MemoryScanExec
    from ballista_tpu.plan.schema import DFSchema
    from ballista_tpu.scheduler.state.execution_graph import TaskDescription
    from ballista_tpu.shuffle.writer import ShuffleWriterExec

    table = pa.table({"k": list(range(2000)), "v": [float(i) for i in range(2000)]})
    # batch i goes to partition i % 2: ten batches of 100 rows a partition
    scan = MemoryScanExec(DFSchema.from_arrow(table.schema),
                          table.to_batches(max_chunksize=100), partitions=2)
    plan = ShuffleWriterExec(scan, "job-m", 1, 4, [Column("k")])
    ex = Executor(str(tmp_path), ExecutorMetadata(id="ex-m"))
    stats_before = {t for t in RUN_STATS.stages()}
    results = []
    for task_id, partition in ((1, 0), (2, 1)):
        task = TaskDescription(job_id="job-m", stage_id=1, stage_attempt=0, task_id=task_id,
                               partitions=[partition], plan=plan, session_id="s")
        results.append(ex.execute_task(task, BallistaConfig()))
    assert [r.state for r in results] == ["success", "success"]
    for r in results:  # each task saw its own 1,000 rows, not the plan's 2,000
        scan_m = r.metrics[1]
        assert scan_m["name"].startswith("MemoryScanExec") and scan_m["output_rows"] == 1000
        assert scan_m["output_batches"] == 10 and scan_m["self_ns"] == scan_m["elapsed_ns"]
    assert scan.metrics.output_rows == 2000  # the shared operator still adds up
    writes = [s for s in RUN_STATS.job_spans("job-m") if s.name == "bt.shuffle.write"]
    assert len(writes) == 2 and stats_before == set(RUN_STATS.stages())
    for s in writes:
        assert s.attrs["ops"] == [[1, "MemoryScanExec", pytest.approx(s.attrs["ops_ms"]),
                                   1000, 10, ""]]
    RUN_STATS.take_job_spans("job-m")


@pytest.mark.parametrize("mode", ["local", "standalone"])
def test_explain_analyze_prints_an_operators_own_time_beside_the_inclusive(mode, tpch_dir):
    """`self_ms` is `elapsed_ms` less the operator's inputs: what the write
    span's `ops` carry, printed where a user reads operator times."""
    import re

    from ballista_tpu.client.context import SessionContext
    from ballista_tpu.config import EXECUTOR_ENGINE, BallistaConfig
    from ballista_tpu.testing.tpchgen import register_tpch

    config = BallistaConfig({EXECUTOR_ENGINE: "cpu"})
    ctx = SessionContext.standalone(config, num_executors=1) if mode == "standalone" \
        else SessionContext(config)
    try:
        register_tpch(ctx, tpch_dir)
        out = ctx.sql("explain analyze " + tpch_query(6)).collect()
        plans = dict(zip(out.column("plan_type").to_pylist(), out.column("plan").to_pylist()))
        body = next(v for k, v in plans.items() if k.startswith("analyzed_plan"))
        pairs = re.findall(r"elapsed_ms=([0-9.]+) self_ms=([0-9.]+)", body)
        assert len(pairs) == len(body.strip().splitlines()) - body.count("stage ")
        assert all(float(own) <= float(whole) + 0.01 for whole, own in pairs), body
        scan = next(l for l in body.splitlines() if "ParquetScanExec" in l)
        whole, own = re.search(r"elapsed_ms=([0-9.]+) self_ms=([0-9.]+)", scan).groups()
        assert whole == own and float(own) > 0  # a leaf's time is its own
    finally:
        ctx.shutdown()
