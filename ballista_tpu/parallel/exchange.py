"""Intra-slice collective exchange: the ICI data plane.

The reference's data plane is Arrow-IPC-over-Flight between executor
processes (SURVEY.md §2.5). On a TPU pod slice, co-scheduled stages can
exchange partitions over ICI instead of files: this module implements the
stage patterns as jittable collectives under `shard_map` over a Mesh:

- `partial_then_psum`: per-device partial aggregation merged with psum —
  the collective form of partial-agg → shuffle(1) → final-agg.
- `hash_exchange_all_to_all`: rows routed by the engine-wide key hash
  (bit-identical twin of ops/hashing.py) into fixed-capacity per-device
  buckets, exchanged with all_to_all — the collective form of
  ShuffleWriter(hash K) → ShuffleReader. Fixed capacity keeps shapes
  static for XLA; overflow falls back to the file shuffle path (the
  capacity check happens host-side before dispatch).

The file-based Flight shuffle remains the general path (elasticity, retry,
cross-host); gated by `ballista.tpu.collective.exchange`.
"""

from __future__ import annotations

from functools import partial

import numpy as np


class ExchangeCapacityExceeded(Exception):
    """A fixed-capacity collective exchange cannot hold the routed rows.

    The device kernel's per-(sender, destination) buckets have `capacity`
    slots; at least one pair needs `required` of them. Raised by the
    host-side gate BEFORE any device dispatch, so no row is ever silently
    truncated — the caller demotes the stage to the per-partition
    file-shuffle path and logs the reason."""

    def __init__(self, required: int, capacity: int, n_devices: int):
        self.required = required
        self.capacity = capacity
        self.n_devices = n_devices
        super().__init__(
            f"collective exchange needs {required} slots per (sender, dest) "
            f"pair but capacity is {capacity} ({n_devices} devices); "
            "demote to the file shuffle path"
        )


def make_mesh(n_devices: int | None = None, axis: str = "part"):
    """1-D device mesh over the partition axis (data parallel over rows),
    built from the DEFAULT backend's devices only. A backend with fewer
    devices than asked for is an error: a mesh that quietly borrowed CPU
    devices would run the "device" exchange on the host."""
    import jax
    from jax.sharding import Mesh

    devs = jax.devices()
    n = n_devices or len(devs)
    if len(devs) < n:
        raise RuntimeError(
            f"need {n} devices, the {devs[0].platform} backend has "
            f"{len(devs)} (on the CPU backend set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=N)"
        )
    return Mesh(np.array(devs[:n]), (axis,))


def partial_then_psum(values, gmask_fn, num_groups: int, mesh, axis: str = "part"):
    """Group-aggregate values sharded by rows across the mesh; returns the
    globally-merged per-group (sums, counts) replicated on every device.

    values: [rows] array sharded on `axis`; gmask_fn(local_rows) -> bool
    masks [num_groups, local_rows].
    """
    from ballista_tpu.ops.tpu.runtime import ensure_jax

    jax = ensure_jax()
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from jax.experimental.shard_map import shard_map

    def local(vals):
        gm = gmask_fn(vals)
        sums = jnp.stack([jnp.where(gm[g], vals, 0).sum() for g in range(num_groups)])
        cnts = jnp.stack([gm[g].sum() for g in range(num_groups)])
        sums = jax.lax.psum(sums, axis)
        cnts = jax.lax.psum(cnts, axis)
        return sums, cnts

    return shard_map(local, mesh=mesh, in_specs=(P(axis),), out_specs=(P(), P()))(values)


def required_exchange_capacity(key_arrays, n_devices: int, *, prehashed: bool = False) -> int:
    """Slots per (sending device, destination) pair the routed rows need:
    the max bucket fill over every pair. `key_arrays` is the per-device list
    of host arrays — raw int64 keys hashed with the engine-wide key hash
    (ops/hashing.py splitmix64, bit-exact twin of the device hash64), or,
    with `prehashed`, already-combined uint64 row hashes (the multi-column
    `hash_arrays` form that `hash_exchange_table` routes on)."""
    from ballista_tpu.ops.hashing import splitmix64

    worst = 0
    for k in key_arrays:
        k = np.asarray(k)
        if prehashed:
            h = k.astype(np.uint64)
        else:
            h = splitmix64(k.astype(np.uint64))
        dest = h % np.uint64(n_devices)
        counts = np.bincount(dest.astype(np.int64), minlength=n_devices)
        worst = max(worst, int(counts.max(initial=0)))
    return worst


def exchange_capacity_fits(key_arrays, n_devices: int, capacity: int,
                           *, prehashed: bool = False) -> bool:
    """Host-side capacity check (the gate the docstring above promises):
    True iff, for every (sending device, destination) pair, the number of
    rows routed there fits in `capacity` slots. Rows beyond capacity would
    be dropped by the fixed-shape kernel, so a False verdict must route the
    exchange down the file-shuffle path instead."""
    return required_exchange_capacity(key_arrays, n_devices, prehashed=prehashed) <= capacity


def require_exchange_capacity(key_arrays, n_devices: int, capacity: int,
                              *, prehashed: bool = False) -> int:
    """The raising form of `exchange_capacity_fits`: returns the required
    per-pair slot count when it fits, raises the typed
    `ExchangeCapacityExceeded` when it does not (silent truncation is never
    an option — the executor catches the error and demotes the stage to the
    per-partition path)."""
    required = required_exchange_capacity(key_arrays, n_devices, prehashed=prehashed)
    if required > capacity:
        raise ExchangeCapacityExceeded(required, capacity, n_devices)
    return required


def hash_exchange_all_to_all(keys, payload, mesh, axis: str = "part", capacity: int | None = None):
    """Route (key, payload) rows to device hash(key) % n via all_to_all.

    keys/payload: [rows] int64 sharded on `axis`. Every device receives the
    rows whose key hashes to it, in fixed-capacity slots:
    returns (keys_out, payload_out, valid_out) with per-device shape
    [n_dev * capacity] where valid marks real rows.

    Overflow rows (more than `capacity` for one destination) land in a
    dump slot that is sliced away before the exchange — they can NEVER
    clobber a valid row. Callers gate dispatch with
    `exchange_capacity_fits` and fall back to the file shuffle when the
    data does not fit.
    """
    from ballista_tpu.ops.tpu.runtime import ensure_jax

    jax = ensure_jax()  # x64: the key hash works on uint64 lanes
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from jax.experimental.shard_map import shard_map

    from ballista_tpu.ops.tpu.kernels import hash64

    n = mesh.devices.size
    local_rows = keys.shape[0] // n
    cap = capacity or local_rows  # worst case: all rows to one bucket

    def local(k, v):
        dest = (hash64(k.astype(jnp.uint64)) % jnp.uint64(n)).astype(jnp.int32)
        # stable slot assignment per destination bucket
        slot = jnp.zeros_like(dest)
        for d in range(n):
            is_d = dest == d
            slot = jnp.where(is_d, jnp.cumsum(is_d) - 1, slot)
        # scatter into [n, cap+1] send buffers: slot `cap` is a write-only
        # dump for overflow rows (duplicate-index .at[].set ordering is
        # unspecified, so overflow must never share a slot with valid data)
        ok = slot < cap
        slot_w = jnp.where(ok, slot, cap)
        send_k = jnp.zeros((n, cap + 1), dtype=k.dtype).at[dest, slot_w].set(k)
        send_v = jnp.zeros((n, cap + 1), dtype=v.dtype).at[dest, slot_w].set(v)
        send_ok = jnp.zeros((n, cap + 1), dtype=bool).at[dest, slot_w].set(ok)
        rk = jax.lax.all_to_all(send_k[:, :cap], axis, 0, 0, tiled=True)
        rv = jax.lax.all_to_all(send_v[:, :cap], axis, 0, 0, tiled=True)
        ro = jax.lax.all_to_all(send_ok[:, :cap], axis, 0, 0, tiled=True)
        return rk.reshape(-1), rv.reshape(-1), ro.reshape(-1)

    return shard_map(
        local, mesh=mesh, in_specs=(P(axis), P(axis)), out_specs=(P(axis), P(axis), P(axis))
    )(keys, payload)


def hash_exchange_table(hashes, lanes, live, mesh, axis: str = "part",
                        capacity: int | None = None):
    """Route a whole table's rows to device hash % n via one all_to_all
    routing decision shared by every column.

    The single-payload form above hashes raw keys on device; real stage
    output rows carry multi-column (possibly string/dictionary) keys, so
    here the caller ships the PRE-combined row hash (`ops/hashing.py
    hash_arrays`, uint64 bit-cast to int64) and the device only takes
    `% n_devices` — host gate and device routing are the same hash by
    construction.

    hashes: [rows] int64 (bit-cast uint64 row hash), sharded on `axis`.
    lanes:  list of [rows] int64 payload lanes (every column of the table
            encoded to one or more int64 lanes by the caller).
    live:   [rows] bool — padding rows (added to make rows divisible by the
            device count) carry False and are never routed.

    Returns (hashes_out, lanes_out, valid_out), each with per-device shape
    [n_dev * capacity] (global [n_dev² * capacity]); `valid_out` marks real
    rows. Callers MUST gate with `require_exchange_capacity(...,
    prehashed=True)` first: rows beyond `capacity` for one (sender, dest)
    pair land in a write-only dump slot and are dropped, exactly like the
    single-payload kernel."""
    from ballista_tpu.ops.tpu.runtime import ensure_jax

    jax = ensure_jax()  # x64: routing works on uint64 lanes
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from jax.experimental.shard_map import shard_map

    n = mesh.devices.size
    local_rows = hashes.shape[0] // n
    cap = capacity or local_rows

    def local(h, lv, *ls):
        dest = (h.astype(jnp.uint64) % jnp.uint64(n)).astype(jnp.int32)
        # stable slot assignment per destination bucket; dead (padding) rows
        # never claim a slot
        slot = jnp.zeros_like(dest)
        for d in range(n):
            is_d = (dest == d) & lv
            slot = jnp.where(is_d, jnp.cumsum(is_d) - 1, slot)
        ok = lv & (slot < cap)
        # slot `cap` is a write-only dump for overflow + padding rows
        # (duplicate-index .at[].set ordering is unspecified, so they must
        # never share a slot with valid data)
        slot_w = jnp.where(ok, slot, cap)
        outs = []
        for a in (h,) + ls:
            send = jnp.zeros((n, cap + 1), dtype=a.dtype).at[dest, slot_w].set(a)
            outs.append(jax.lax.all_to_all(send[:, :cap], axis, 0, 0, tiled=True).reshape(-1))
        send_ok = jnp.zeros((n, cap + 1), dtype=bool).at[dest, slot_w].set(ok)
        ro = jax.lax.all_to_all(send_ok[:, :cap], axis, 0, 0, tiled=True).reshape(-1)
        return outs[0], tuple(outs[1:]), ro

    spec = P(axis)
    out = shard_map(
        local, mesh=mesh,
        in_specs=(spec, spec) + (spec,) * len(lanes),
        out_specs=(spec, tuple(spec for _ in lanes), spec),
    )(hashes, live, *lanes)
    return out
