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

from ballista_tpu.tracing import MAX_SPANS_PER_JOB, RUN_STATS, RunStats

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
        # two distinct orderings: keys, the ordering and the boundaries once each; three
        # functions: an emit each, the scan's device call inside it
        assert len(keys) == 2 and len(execs) == 2 and len(emits) == 2 + 3
        assert {s[NAME] for s in under} == {"bt.window.keys", "bt.window.emit", "bt.device.exec"}
        assert all(k[NUMBERS]["key_lanes"] == 2 and k[NUMBERS]["rows"] > 0 for k in keys)
        for e in execs:
            nums = e[NUMBERS]
            assert nums["kernel"] == "lex_order" and nums["lanes"] >= nums["rows"] > 0
            assert nums["lanes"] & (nums["lanes"] - 1) == 0 and nums["bytes"] >= 12 * nums["lanes"]
        frames = [e for e in emits if "partitions" in e[NUMBERS]]
        funcs = [e for e in emits if "func" in e[NUMBERS]]
        assert len(frames) == 2 and all(0 < f[NUMBERS]["partitions"] <= 40 for f in frames)
        assert sorted(f[NUMBERS]["func"] for f in funcs) == ["max", "rank", "row_number"]
        scans = [s for s in spans if s[NAME] == "bt.device.exec"
                 and s[PARENT] in {f[ID] for f in funcs}]
        # row_number: a sum scan; max: the count's sum scan and the max scan; rank: a max scan
        assert sorted(s[NUMBERS]["kernel"] for s in scans) == [
            "segscan_max", "segscan_max", "segscan_sum", "segscan_sum"]
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
        # two orderings of the task's rows, padded; 40 groups found by each; four scans
        assert rec["window_lanes"] >= 2 * rec["window_rows"]
        assert 0 < rec["window_segments"] <= 2 * 40 and rec["window_scans"] == 4
        assert "xla_compile_s" not in rec  # every program had been called before
    # the first query's first calls were the compiles: named so, and counted on the record
    compiles = [s for r in cold.values() for s in r.get("spans", ()) if s[NAME] == "bt.compile.xla"]
    assert {s[NUMBERS]["kernel"] for s in compiles} == {"lex_order", "segscan_sum", "segscan_max"}
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
    "window_segscan_max")


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
    assert set(lowered) == set(JITTED_STAGE_FAMILIES[2:])
    for name, text in lowered.items():
        assert f"module @jit_{name}" in text
