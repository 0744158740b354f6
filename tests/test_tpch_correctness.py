"""TPC-H correctness: engine output vs the independent pandas oracle.

Reference analog: benchmarks `verify expected results` CI leg
(.github/workflows/rust.yml) and the SF10 distributed matrix (tpch.yml).
"""

import pytest

from ballista_tpu.testing.reference import compare_results, run_reference

from .conftest import tpch_query


@pytest.mark.parametrize("q", list(range(1, 23)))
def test_tpch_local_cpu(q, tpch_ctx, tpch_ref_tables):
    eng = tpch_ctx.sql(tpch_query(q)).collect()
    ref = run_reference(q, tpch_ref_tables)
    problems = compare_results(eng, ref, q)
    assert not problems, "\n".join(problems)


@pytest.mark.parametrize("breakage", ["none", "mismatch", "raises"])
def test_benchmark_runner_exit_code_tells_failure(breakage, tpch_dir, monkeypatch):
    """`benchmarks/tpch.py run --verify` (the README's quick start) must not
    exit 0 over a failed query or an oracle mismatch."""
    import importlib.util
    import os

    import ballista_tpu.testing.reference as reference
    from ballista_tpu.client.context import DataFrame

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "bench_tpch", os.path.join(root, "benchmarks", "tpch.py"))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    if breakage == "mismatch":
        monkeypatch.setattr(reference, "compare_results",
                            lambda *a, **k: ["q6: injected mismatch"])
    elif breakage == "raises":
        def boom(self):
            raise RuntimeError("injected query failure")

        monkeypatch.setattr(DataFrame, "collect", boom)
    argv = ["run", "--data", tpch_dir, "--query", "6", "--iterations", "1",
            "--verify"]
    if breakage == "none":
        bench.main(argv)  # returns: exit code 0
    else:
        with pytest.raises(SystemExit) as e:
            bench.main(argv)
        assert e.value.code == 1
