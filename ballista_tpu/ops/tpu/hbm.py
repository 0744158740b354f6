"""Out-of-core TPU execution: the per-stage HBM memory plan.

Theseus-style discipline (arXiv:2508.05029) transplanted onto the TPU
path: device memory is a *planned* resource, not a crash surface. Three
rungs, every decision recorded in RUN_STATS as `hbm_plan` /
`hbm_plan_reason` in the demotion-ladder style of `mesh_mode_reason`:

- **admission** (`plan_stage`): before dispatch, the stage's working-set
  bytes — probe table + dictionary LUTs + join build tables, all
  derivable from `fusion.estimate_stage`'s encode metadata — are checked
  against a configurable budget (`ballista.tpu.hbm.budget.bytes`,
  default a fraction of detected device memory). Outcomes: `run_whole`,
  `spill_colds` (the stage fits but cold cache residents must demote
  first), `grace_split`, or `cpu_demote`.
- **spill** (`HostSpillPool`): cold `DeviceTableCache` entries demote to
  host buffers instead of being dropped, re-uploading transparently on
  the next touch; past the host budget they demote again to disk files
  written with the CPU spill pool's attempt-unique tmp+rename discipline
  (shuffle/writer.py). A runtime `RESOURCE_EXHAUSTED` from XLA evicts +
  spills and retries the stage ONCE before demoting.
- **grace fallback**: a hash-join working set over budget re-splits the
  build side by a secondary hash (a re-mixed splitmix64 of the combined
  join key — independent of the PR 7 exchange routing hash, which routes
  on the UN-mixed key) into `buckets^depth` sub-buckets executed
  sequentially on device. Probe rows are never permuted: each sub-run
  sees the full [P, N] stacks in producer row order and a probe row
  matches only in the sub-bucket its key hashes to, so the concatenated
  partial-aggregate outputs are exactly the unconstrained run's partials
  re-bucketed — the downstream final aggregate merges them identically.
  Recursion depth is bounded; past the cap the stage demotes to the CPU
  engine, the always-correct final rung.

Everything here is pure host logic: jax is imported lazily inside the
few functions that need it, so the module can be imported by chaos
injection and the analysis passes without pulling in a backend.
"""

from __future__ import annotations

import contextlib
import logging
import os
import tempfile
import threading
from dataclasses import dataclass, field

log = logging.getLogger(__name__)

# secondary-hash salt for grace sub-bucketing. The PR 7 exchange routes on
# `hash_arrays(keys) % n_devices`; grace buckets on a re-mixed image of the
# combined int64 join key so the two splits stay independent (a partition
# that landed on this chip BY key hash still spreads across sub-buckets).
GRACE_SALT = 0xA5A5_5A5A_C3C3_3C3C

RUN_WHOLE = "run_whole"
SPILL_COLDS = "spill_colds"
GRACE_SPLIT = "grace_split"
CPU_DEMOTE = "cpu_demote"


class InjectedResourceExhausted(RuntimeError):
    """Chaos mode hbm_oom's synthetic device OOM. The message carries the
    literal RESOURCE_EXHAUSTED tag so `is_resource_exhausted` classifies it
    exactly like the real XlaRuntimeError."""


def is_resource_exhausted(exc: BaseException) -> bool:
    """Classify a device-path exception as an out-of-memory condition.
    XLA surfaces HBM exhaustion as XlaRuntimeError with a
    RESOURCE_EXHAUSTED status string; chaos injects the same tag."""
    if isinstance(exc, InjectedResourceExhausted):
        return True
    return "RESOURCE_EXHAUSTED" in f"{type(exc).__name__}: {exc}"


# ---------------------------------------------------------------------------
# chaos arming (executor-local; see ballista.chaos.mode = hbm_oom)

_CHAOS_LOCK = threading.Lock()
_CHAOS = {"armed": False, "budget": 0, "oom_n": 0, "puts": 0}


def arm_chaos(budget_bytes: int, oom_n: int = 0) -> None:
    """Arm the hbm_oom chaos override: the resolved budget shrinks to
    `budget_bytes`, and (oom_n > 0) the oom_n-th device upload raises a
    synthetic RESOURCE_EXHAUSTED — once, so the spill+retry rung can be
    observed converging."""
    with _CHAOS_LOCK:
        _CHAOS["armed"] = True
        _CHAOS["budget"] = int(budget_bytes)
        _CHAOS["oom_n"] = int(oom_n)
        _CHAOS["puts"] = 0


def disarm_chaos() -> None:
    with _CHAOS_LOCK:
        _CHAOS["armed"] = False
        _CHAOS["budget"] = 0
        _CHAOS["oom_n"] = 0
        _CHAOS["puts"] = 0


def chaos_budget() -> int:
    """The armed chaos budget, or 0 when chaos is not steering the plan."""
    with _CHAOS_LOCK:
        return _CHAOS["budget"] if _CHAOS["armed"] else 0


def maybe_chaos_oom() -> None:
    """Call on every device upload. When armed with oom_n > 0, the N-th
    upload raises a synthetic RESOURCE_EXHAUSTED exactly once."""
    with _CHAOS_LOCK:
        if not _CHAOS["armed"] or _CHAOS["oom_n"] <= 0:
            return
        _CHAOS["puts"] += 1
        if _CHAOS["puts"] < _CHAOS["oom_n"]:
            return
        _CHAOS["oom_n"] = 0  # fire once: the retry after spill must succeed
    raise InjectedResourceExhausted(
        "RESOURCE_EXHAUSTED: chaos hbm_oom injected device OOM on upload")


# ---------------------------------------------------------------------------
# budget resolution

def detect_device_memory_bytes() -> int:
    """Bytes of device memory on the chip this thread dispatches to, via its
    memory_stats. 0 only where the backend itself reports none (the CPU
    backend's memory_stats() is None); a backend that cannot be asked
    raises."""
    from ballista_tpu.ops.tpu import runtime

    stats = runtime.current_device().memory_stats() or {}
    return int(stats.get("bytes_limit", 0))


# per-session HBM quota (device daemon multi-tenancy): the daemon wraps
# each attached session's stage execution in session_quota(q), and the
# budget resolver clamps to it — every downstream admission decision
# (plan_stage's spill/grace/demote ladder) becomes quota-aware without
# the ladder itself knowing sessions exist.
_QUOTA_TLS = threading.local()


@contextlib.contextmanager
def session_quota(quota_bytes: int):
    """Scope a per-session ceiling over resolve_hbm_budget(). 0 = no
    ceiling. Nests (inner scope wins); thread-local, matching the daemon's
    one-handler-thread-per-request execution model."""
    prev = getattr(_QUOTA_TLS, "quota", 0)
    _QUOTA_TLS.quota = int(quota_bytes)
    try:
        yield
    finally:
        _QUOTA_TLS.quota = prev


def active_session_quota() -> int:
    return int(getattr(_QUOTA_TLS, "quota", 0) or 0)


def resolve_hbm_budget(config) -> int:
    """The per-stage HBM budget in bytes. Precedence: armed chaos override,
    then the explicit knob, then fraction x detected device memory, then
    fraction x ballista.tpu.max.device.bytes (CPU-jax fallback). An active
    session_quota() clamps whatever the ladder produced (chaos included:
    a quota-ed tenant must not dodge its ceiling via a chaos knob)."""
    from ballista_tpu.config import (
        TPU_HBM_BUDGET_BYTES,
        TPU_HBM_BUDGET_FRACTION,
        TPU_MAX_DEVICE_BYTES,
    )

    def _clamp(budget: int) -> int:
        quota = active_session_quota()
        return max(1, min(budget, quota)) if quota > 0 else budget

    forced = chaos_budget()
    if forced > 0:
        return _clamp(forced)
    explicit = int(config.get(TPU_HBM_BUDGET_BYTES))
    if explicit > 0:
        return _clamp(explicit)
    frac = float(config.get(TPU_HBM_BUDGET_FRACTION))
    base = detect_device_memory_bytes() or int(config.get(TPU_MAX_DEVICE_BYTES))
    return _clamp(max(1, int(base * frac)))


# ---------------------------------------------------------------------------
# OOM hints: a stage that hit RESOURCE_EXHAUSTED pre-plans grace on retry

_HINT_LOCK = threading.Lock()
# analysis: ignore[bounded-cache] self-draining: consume_oom_hint discards on read; one entry per in-flight OOM-retried stage
_OOM_HINTS: set[str] = set()


_OOM_RETRIES = [0]  # cumulative, process-wide (mirrored into RUN_STATS like
#                     the spill counters: a later clean re-run of the same
#                     stage tag must not erase the evidence that a retry ran)


def note_oom(fingerprint: str) -> None:
    with _HINT_LOCK:
        _OOM_HINTS.add(fingerprint)
        _OOM_RETRIES[0] += 1


def oom_retry_count() -> int:
    with _HINT_LOCK:
        return _OOM_RETRIES[0]


def consume_oom_hint(fingerprint: str) -> bool:
    with _HINT_LOCK:
        return fingerprint in _OOM_HINTS and (_OOM_HINTS.discard(fingerprint) or True)


# ---------------------------------------------------------------------------
# admission

@dataclass(frozen=True)
class HbmPlan:
    """One stage's admission decision (RUN_STATS hbm_plan/_reason)."""

    decision: str  # run_whole | spill_colds | grace_split | cpu_demote
    reason: str
    budget: int
    working_set: int
    grace_buckets: int = 0  # total sub-buckets (fanout ** depth)
    grace_depth: int = 0
    split_jidx: int = -1  # which join's build side the grace split targets


def plan_stage(est, budget: int, *, grace_eligible: bool, grace_fanout: int,
               grace_max_depth: int, resident_other: int = 0,
               observed_bytes: int = 0, force_grace: bool = False) -> HbmPlan:
    """Admission: check the stage's working-set estimate against the budget.

    `est` is a fusion.StageEstimate carrying table_bytes / dict_bytes /
    build_bytes (all derivable from encode metadata, so the decision is
    computable from a spec table during compile/fill overlap) and
    program_bytes (what the traced program holds beside them: the sorted
    path's [C] output lanes and ordering scratch, which grow with its row
    slots — no constant caps its group capacity, the budget does).
    `resident_other` is the device-cache residency NOT owned by this stage
    (cold entries spillable to make room). `observed_bytes` is the AQE
    seam's observed input volume for a resolved/retried stage — a floor
    under the build estimate. `force_grace` is the post-OOM hint: the
    estimate said "fits" once already and the device disagreed."""
    fixed_bytes = int(est.table_bytes) + int(est.dict_bytes) + int(est.program_bytes)
    working = fixed_bytes + int(est.build_bytes)
    observed_extra = 0
    if observed_bytes > 0:
        floored = fixed_bytes + int(observed_bytes)
        if floored > working:
            # the AQE seam observed more input volume than the estimate
            # priced: the excess is build-side data the grace split can
            # partition, so it rides the splittable term, not the fixed one
            observed_extra = floored - working
            working = floored
    if budget <= 0:
        return HbmPlan(RUN_WHOLE, "unbudgeted (hbm budget <= 0)", budget, working)
    over = working > budget or force_grace
    if not over:
        if resident_other > 0 and resident_other + working > budget:
            return HbmPlan(
                SPILL_COLDS,
                f"stage fits ({working} <= {budget} B) but {resident_other} B "
                f"of cold residents must spill to host first",
                budget, working)
        return HbmPlan(RUN_WHOLE, f"working set {working} B <= budget {budget} B",
                       budget, working)
    # over budget: try the grace rung, then the CPU rung. A stage that is
    # only "over" because of the post-OOM hint (its estimate fits; the
    # device disagreed once) prefers grace but falls back to re-running
    # whole when no grace rung exists — the evict+spill freed the device,
    # and that retry is the contract; a SECOND runtime OOM demotes for real.
    nominally_fits = working <= budget
    why = (f"post-OOM pre-plan (estimate {working} B, budget {budget} B)"
           if force_grace and nominally_fits
           else f"working set {working} B > budget {budget} B")
    split = int(est.max_build_bytes)
    if split > 0 and est.max_build_jidx >= 0:
        split += observed_extra
    if not grace_eligible or est.max_build_jidx < 0 or split <= 0:
        if nominally_fits:
            return HbmPlan(RUN_WHOLE, why + "; no grace-splittable inner-join "
                           "build — re-running whole after spill", budget, working)
        return HbmPlan(CPU_DEMOTE, why + "; no grace-splittable inner-join build",
                       budget, working)
    if grace_max_depth <= 0:
        if nominally_fits:
            return HbmPlan(RUN_WHOLE, why + "; grace disabled (max depth 0) — "
                           "re-running whole after spill", budget, working)
        return HbmPlan(CPU_DEMOTE, why + "; grace disabled (max depth 0)",
                       budget, working)
    fixed = working - split
    if fixed > budget:
        return HbmPlan(
            CPU_DEMOTE,
            why + f"; non-splittable bytes ({fixed} B) alone exceed the budget",
            budget, working)
    fanout = max(2, int(grace_fanout))
    for depth in range(1, int(grace_max_depth) + 1):
        buckets = fanout ** depth
        if fixed + -(-split // buckets) <= budget:
            return HbmPlan(
                GRACE_SPLIT,
                why + f"; grace-splitting build {est.max_build_jidx} "
                f"({split} B) into {buckets} sub-buckets (depth {depth})",
                budget, working, grace_buckets=buckets, grace_depth=depth,
                split_jidx=int(est.max_build_jidx))
    return HbmPlan(
        CPU_DEMOTE,
        why + f"; grace depth cap {grace_max_depth} (fanout {fanout}) still "
        f"over budget — demoting to the CPU engine",
        budget, working)


def grace_bucket_of(key_np, n_buckets: int):
    """Secondary-hash sub-bucket of each combined int64 join key: the
    splitmix64 finalizer (ops/hashing.py — the bit-exact twin of the
    device hash64) over the salted key. Deterministic, engine-independent,
    and independent of the exchange's primary routing hash."""
    import numpy as np

    from ballista_tpu.ops.hashing import splitmix64

    salted = (key_np.astype(np.int64).view(np.uint64)
              ^ np.uint64(GRACE_SALT))
    return (splitmix64(salted) % np.uint64(n_buckets)).astype(np.int64)


# ---------------------------------------------------------------------------
# grace verification record (consumed by analysis/plan_check.py)

@dataclass
class GraceReport:
    """What a grace-split execution actually did — checked by
    plan_check.verify_grace after every grace run (the postconditions the
    static verifier owns: sub-buckets cover the partition, the merge kept
    producer row order, recursion stayed under the cap)."""

    stage_tag: str
    n_buckets: int
    fanout: int
    depth: int
    max_depth: int
    buckets_run: list = field(default_factory=list)
    buckets_empty: list = field(default_factory=list)  # empty sub-build: no-op
    # how sub-runs merged: "producer-order" = probe rows were never permuted
    # (each sub-run masks non-bucket matches in place) and per-partition
    # outputs concatenate in bucket order
    merge: str = "producer-order"


# ---------------------------------------------------------------------------
# host spill pool

_SEQ_LOCK = threading.Lock()
_SEQ = [0]


def _next_seq() -> int:
    with _SEQ_LOCK:
        _SEQ[0] += 1
        return _SEQ[0]


class SpilledEntry:
    """One demoted cache entry: metadata + either host numpy arrays or a
    disk-tier npz path (never both)."""

    def __init__(self, meta, arrays, nbytes: int, path: str | None = None):
        self.meta = meta  # opaque to the pool; the cache reconstructs from it
        self.arrays = arrays  # list[np.ndarray | None] | None when on disk
        self.nbytes = int(nbytes)
        self.path = path

    @property
    def on_disk(self) -> bool:
        return self.path is not None


class HostSpillPool:
    """Demotion target for cold device-cache entries.

    Two tiers: host buffers up to `max_host_bytes` (LRU), then disk files
    under `spill_dir` written with the shuffle writer's attempt-unique
    tmp+rename discipline (write `<name>.tmp`, fsync-free `os.replace`;
    a crashed writer leaves only a .tmp that never shadows a committed
    file). Counters are cumulative gauges mirrored into RUN_STATS by the
    stage compiler: spill_bytes / spill_events / reupload_events."""

    def __init__(self, max_host_bytes: int = 2 * 1024**3, spill_dir: str = ""):
        import collections

        self.max_host_bytes = int(max_host_bytes)
        self.spill_dir = spill_dir
        self._entries: "collections.OrderedDict[tuple, SpilledEntry]" = \
            collections.OrderedDict()
        self._lock = threading.Lock()
        self.host_bytes = 0
        self.spill_bytes = 0  # cumulative bytes demoted (host + disk tiers)
        self.spill_events = 0
        self.reupload_events = 0
        # disk-pressure gate (docs/lifecycle.md#watermark-ladder): a
        # callable returning False sheds DISK demotions — cold entries stay
        # in the host tier (overcommitting it) instead of filling the last
        # of the disk. None = disk always allowed.
        self.spill_gate = None

    def configure(self, max_host_bytes: int, spill_dir: str, spill_gate=None) -> None:
        with self._lock:
            self.max_host_bytes = int(max_host_bytes)
            self.spill_dir = spill_dir
            self.spill_gate = spill_gate

    def _disk_tier_allowed(self) -> bool:
        gate = self.spill_gate
        if gate is None:
            return True
        try:
            return bool(gate())
        except Exception:  # noqa: BLE001 — a broken gate must not block demotion
            return True

    def _dir(self) -> str:
        d = self.spill_dir or os.path.join(tempfile.gettempdir(), "ballista-hbm-spill")
        os.makedirs(d, exist_ok=True)
        return d

    def put(self, key: tuple, meta, arrays, nbytes: int) -> None:
        """Demote one entry (host numpy arrays). Entries past the host
        budget immediately take the disk tier; host-tier overflow demotes
        the coldest host entries to disk too."""
        entry = SpilledEntry(meta, arrays, nbytes)
        with self._lock:
            old = self._entries.pop(key, None)
            if old is not None:
                self._drop_locked(old)
            if nbytes > self.max_host_bytes and self._disk_tier_allowed():
                self._to_disk_locked(key, entry)
            else:
                self.host_bytes += entry.nbytes
                while (self.host_bytes > self.max_host_bytes and
                       self._disk_tier_allowed() and
                       any(not e.on_disk and e is not entry
                           for e in self._entries.values())):
                    ck, cold = next((k, e) for k, e in self._entries.items()
                                    if not e.on_disk)
                    self._entries.pop(ck)
                    self.host_bytes -= cold.nbytes
                    self._to_disk_locked(ck, cold)
            self._entries[key] = entry
            self.spill_bytes += entry.nbytes
            self.spill_events += 1

    def _to_disk_locked(self, key: tuple, entry: SpilledEntry) -> None:
        import numpy as np

        name = f"hbm-{os.getpid()}-{_next_seq()}-{abs(hash(key)) & 0xFFFFFFFF:08x}.npz"
        path = os.path.join(self._dir(), name)
        live = {f"a{i}": a for i, a in enumerate(entry.arrays) if a is not None}
        mask = [a is not None for a in entry.arrays]
        tmp = path + ".tmp"
        try:
            with open(tmp, "wb") as f:
                np.savez(f, __mask__=np.asarray(mask, dtype=bool), **live)
            os.replace(tmp, path)
        except OSError as e:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            from ballista_tpu.executor.disk import wrap_enospc

            typed = wrap_enospc(e, "hbm spill demotion")
            if typed is not None:
                raise typed from e
            raise
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
        self._entries[key] = entry
        self._entries[key].path = path
        self._entries[key].arrays = None

    def pop(self, key: tuple):
        """Take a demoted entry for re-upload: returns (meta, arrays) or
        None. The entry (and any disk file) is consumed."""
        with self._lock:
            entry = self._entries.pop(key, None)
            if entry is None:
                return None
            if not entry.on_disk:
                self.host_bytes -= entry.nbytes
            self.reupload_events += 1
        if not entry.on_disk:
            return entry.meta, entry.arrays
        import numpy as np

        try:
            with np.load(entry.path) as z:
                mask = z["__mask__"]
                arrays = [z[f"a{i}"] if present else None
                          for i, present in enumerate(mask)]
        finally:
            try:
                os.unlink(entry.path)
            except OSError:
                pass
        return entry.meta, arrays

    def _drop_locked(self, entry: SpilledEntry) -> None:
        if entry.on_disk:
            try:
                os.unlink(entry.path)
            except OSError:
                pass
        else:
            self.host_bytes -= entry.nbytes

    def drop(self, key: tuple) -> None:
        with self._lock:
            entry = self._entries.pop(key, None)
            if entry is not None:
                self._drop_locked(entry)

    def clear(self) -> None:
        with self._lock:
            for entry in self._entries.values():
                self._drop_locked(entry)
            self._entries.clear()
            self.host_bytes = 0

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def stats(self) -> dict:
        with self._lock:
            return {
                "entries": len(self._entries),
                "host_bytes": self.host_bytes,
                "spill_bytes": self.spill_bytes,
                "spill_events": self.spill_events,
                "reupload_events": self.reupload_events,
            }


SPILL_POOL = HostSpillPool()
