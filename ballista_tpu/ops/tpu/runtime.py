"""JAX runtime bootstrap for the TPU engine.

x64 is required: join keys are int64 and money arithmetic is int64 scaled
(ops/tpu/columnar.py). On TPU, f64 falls back to XLA software emulation —
acceptable because the hot paths (masks, money, codes) are integer.

Nothing here guesses. A backend that cannot be asked for its devices is an
error that propagates: a silent "cpu" answer would run a TPU deployment on
the host with exit code 0.
"""

from __future__ import annotations

import contextlib
import logging
import os
import sys
import threading

log = logging.getLogger(__name__)

_lock = threading.Lock()
_ready = False

# -------------------------------------------------- persistent compile cache
# JAX's on-disk compilation cache: compiled XLA programs keyed by (HLO,
# compile options, backend) survive process restarts, so a re-admitted or
# redeployed executor skips recompiles entirely. It is always on. Placement
# is decided from OUTSIDE the program: where JAX_COMPILATION_CACHE_DIR is set
# jax itself reads it and no code here names a directory; where it is not,
# the cache lives at one fixed path inside the checkout (the path is part of
# the cache key, so a directory that moves never hits). Hits/misses are
# observed through jax's monitoring events (the cache never surfaces them).
CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_compile_cache")

_cc_lock = threading.Lock()
_cc_dir: str | None = None
_cc_counts = {"requests": 0, "hits": 0}


def ensure_jax():
    global _ready
    with _lock:
        import jax

        if not _ready:
            jax.config.update("jax_enable_x64", True)
            _init_compile_cache(jax)
            _ready = True
    return jax


def _cc_on_event(event: str, **kwargs) -> None:
    # recorded by jax._src.compiler around every backend_compile: one
    # *_use_cache request per compilation attempt, one cache_hits when the
    # persistent entry was found (misses = requests - hits)
    if event == "/jax/compilation_cache/compile_requests_use_cache":
        with _cc_lock:
            _cc_counts["requests"] += 1
    elif event == "/jax/compilation_cache/cache_hits":
        with _cc_lock:
            _cc_counts["hits"] += 1


def _init_compile_cache(jax) -> None:
    """Turn the persistent XLA compilation cache on (once, from ensure_jax).
    Thresholds are zeroed so even sub-second stage compiles persist — a
    query engine's compile population is small and every warm-start second
    counts."""
    global _cc_dir
    cache_dir = os.environ.get(CACHE_DIR_ENV)
    if not cache_dir:
        cache_dir = DEFAULT_CACHE_DIR
        try:
            os.makedirs(cache_dir, exist_ok=True)
        except OSError as e:
            # a read-only install: run uncached rather than not at all
            log.warning("persistent compile cache off: cannot create %s (%s)",
                        cache_dir, e)
            return
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        # jax latches cache initialization on the FIRST backend compile: a
        # compile that ran before this call (the embedding program's own
        # jax use) leaves the cache off for the process. Reset so the
        # directory takes.
        from jax.experimental.compilation_cache import compilation_cache

        compilation_cache.reset_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.monitoring.register_event_listener(_cc_on_event)
    with _cc_lock:
        _cc_dir = cache_dir


def compile_cache_dir() -> str | None:
    """Active persistent-cache directory (None before ensure_jax, or when
    the fixed directory could not be created)."""
    with _cc_lock:
        return _cc_dir


def compile_cache_stats() -> dict:
    """Snapshot of persistent-cache effectiveness for this process."""
    with _cc_lock:
        return {
            "dir": _cc_dir,
            "requests": _cc_counts["requests"],
            "hits": _cc_counts["hits"],
            "misses": _cc_counts["requests"] - _cc_counts["hits"],
        }


def process_rusage() -> dict:
    """Resource-usage snapshot of THIS process for post-mortem artifacts
    (the device daemon's crash report): peak RSS and CPU split. jax-free
    and never raises — diagnostics must not add failure modes."""
    try:
        import resource

        ru = resource.getrusage(resource.RUSAGE_SELF)
        return {
            "max_rss_kb": int(ru.ru_maxrss),
            "user_s": round(ru.ru_utime, 3),
            "system_s": round(ru.ru_stime, 3),
        }
    except Exception:  # noqa: BLE001 — platform without getrusage
        return {}


# ---------------------------------------------------------------- binding
# Per-chip executor pinning (SURVEY §7 step 7: one executor per chip,
# scheduler slot = chip; reference analog: the vcore slot model in
# executor/src/executor_process.rs:261). A chip belongs to ONE process at a
# time. Two layers:
#
#  * process level — on TPU hardware a chip is claimed exclusively at
#    backend init, so a pinned process must filter visibility BEFORE the
#    backend initialises (bind_process_ordinal, called from
#    executor_process.main and the device daemon);
#  * dispatch level — where one process does see several devices (the CPU
#    test mesh, an in-process standalone cluster, one process driving a
#    whole host) each stage commits its arrays via jax.default_device
#    (device_scope, threaded through TaskContext.device_ordinal), and every
#    platform / memory question is asked of THAT device (current_device).

# libtpu's slice-builder port for a one-chip process; each pinned process
# on a host takes its own (base + ordinal)
_TPU_PROCESS_PORT_BASE = 8476


def bind_process_ordinal(ordinal: int) -> None:
    """Restrict this PROCESS to one TPU chip of its host. Must run before
    jax's backend initialises: afterwards the process already holds every
    chip it could see, and carrying on "unpinned" would take its
    neighbours' chips — so that is an error, not a no-op."""
    if ordinal is None or ordinal < 0:
        raise ValueError(f"device ordinal must be >= 0, got {ordinal}")
    if "jax" in sys.modules:
        from jax._src import xla_bridge

        if xla_bridge.backends_are_initialized():
            raise RuntimeError(
                f"cannot pin this process to device ordinal {ordinal}: the "
                "jax backend is already initialised and holds every visible "
                "chip. Bind before anything touches jax.devices().")
    # Read by libtpu when the backend initialises (names as jax's own
    # multi-process launcher writes them for this installation): the chip
    # this process may see, "one chip per process, one process in the
    # slice", and a slice-builder port of its own so several one-chip
    # processes can share a host. The explicit ordinal wins over any
    # inherited host-wide value: setdefault would leave several executors
    # seeing (and exclusively claiming) each other's chips. Harmless on CPU.
    port = _TPU_PROCESS_PORT_BASE + ordinal
    os.environ["TPU_VISIBLE_CHIPS"] = str(ordinal)
    os.environ["TPU_CHIPS_PER_PROCESS_BOUNDS"] = "1,1,1"
    os.environ["TPU_PROCESS_BOUNDS"] = "1,1,1"
    os.environ["TPU_PROCESS_ADDRESSES"] = f"localhost:{port}"
    os.environ["TPU_PROCESS_PORT"] = str(port)
    os.environ["CLOUD_TPU_TASK_ID"] = "0"


def backend_is_up() -> bool:
    """Whether this process already runs a jax backend (and so holds the
    chips it can see). Never imports jax and never initialises one."""
    if "jax" not in sys.modules:
        return False
    from jax._src import xla_bridge

    return xla_bridge.backends_are_initialized()


def bound_device(ordinal: int):
    """Resolve the jax.Device for an ordinal. After process-level filtering
    only one device is visible and it wins regardless of ordinal; otherwise
    index the local device list."""
    if ordinal is None or ordinal < 0:
        return None
    jax = ensure_jax()
    devs = jax.local_devices()
    if len(devs) == 1:
        return devs[0]
    if ordinal >= len(devs):
        # never alias a misconfigured ordinal onto someone else's chip: the
        # slot=chip model requires disjoint placement, so fail loudly (the
        # stage dispatcher counts this as an error fallback)
        raise ValueError(
            f"device ordinal {ordinal} out of range: {len(devs)} local devices")
    return devs[ordinal]


def device_scope(ordinal: int):
    """Context manager committing jax ops to the pinned device (no-op when
    unpinned). Wrap every device dispatch path in this."""
    dev = bound_device(ordinal)
    if dev is None:
        return contextlib.nullcontext()
    jax = ensure_jax()
    return jax.default_device(dev)


def current_device():
    """The device jax ops on THIS thread dispatch to: the device_scope pin
    when one is active, else the default backend's first local device.
    Raises what jax raises when the backend cannot initialise."""
    jax = ensure_jax()
    dev = jax.config.jax_default_device
    if dev is None:
        return jax.local_devices()[0]
    if isinstance(dev, str):  # a platform name is also a legal value
        return jax.local_devices(backend=dev)[0]
    return dev


# ---------------------------------------------------------------- profiler
# One jax.profiler session a process, started and stopped from outside (the
# `Profile` rpc): the process that holds a chip is the only one that can
# trace it. `python_tracer_level = 0` and no HLO protos: the file holds the
# device's operations and the program's own `bt.*` annotations.

_profile_lock = threading.Lock()
_profile_dir: str | None = None


def start_profile(trace_dir: str) -> dict:
    """Start this process's profiler session into `trace_dir` (made if it
    is not there). One session at a time: a second start is an error."""
    global _profile_dir
    jax = ensure_jax()
    with _profile_lock:
        if _profile_dir is not None:
            raise RuntimeError(f"a profiler session into {_profile_dir} is already running")
        os.makedirs(trace_dir, exist_ok=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.enable_hlo_proto = False
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        _profile_dir = trace_dir
    return {"dir": trace_dir}


def stop_profile() -> dict:
    """End the session; the `.xplane.pb` is complete when this returns."""
    global _profile_dir
    import glob

    with _profile_lock:
        if _profile_dir is None:
            raise RuntimeError("no profiler session is running")
        trace_dir, _profile_dir = _profile_dir, None
        sys.modules["jax"].profiler.stop_trace()
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    return {"dir": trace_dir, "files": sorted(files)}
