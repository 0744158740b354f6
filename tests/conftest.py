import atexit
import os
import shutil
import tempfile

# Tests run on the CPU backend, on a virtual 8-device mesh for the
# sharding/parallelism tests. Set before anything imports jax: it reads
# these at import.
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
os.environ["JAX_PLATFORMS"] = "cpu"

# The engine's persistent compile cache is always on and, unplaced, lives
# inside the checkout; the suite's xdist workers must not share (or litter)
# that directory, so each test process places its cache the way a deployment
# would — through the environment — in a temp directory of its own.
if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
    _cache_dir = tempfile.mkdtemp(prefix="ballista_tpu_xla_cache_")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = _cache_dir
    atexit.register(shutil.rmtree, _cache_dir, ignore_errors=True)

import pytest


@pytest.fixture(scope="session")
def tpch_dir(tmp_path_factory):
    """Session-scoped TPC-H SF0.01 parquet directory."""
    from ballista_tpu.testing.tpchgen import generate_tpch

    d = tmp_path_factory.mktemp("tpch") / "sf001"
    generate_tpch(str(d), scale=0.01, seed=42, files_per_table=2)
    return str(d)


@pytest.fixture()
def tpch_ctx(tpch_dir):
    from ballista_tpu.client.context import SessionContext
    from ballista_tpu.testing.tpchgen import register_tpch

    ctx = SessionContext()
    register_tpch(ctx, tpch_dir)
    return ctx


@pytest.fixture(scope="session")
def tpch_ref_tables(tpch_dir):
    from ballista_tpu.testing.reference import load_tables

    return load_tables(tpch_dir)


def tpch_query(n: int) -> str:
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "tpch", "queries", f"q{n}.sql")) as f:
        return f.read()


def iter_plan(node):
    """Depth-first walk of a physical plan (shared by plan-shape tests)."""
    yield node
    for c in node.children():
        yield from iter_plan(c)
