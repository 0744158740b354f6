"""Pallas kernel family for the fused stage-execution hot path.

Two kernels back `fusion_mode=fused_pallas` on a TPU; the chip's compiler
accepts both (tests/test_tpu_compile.py compiles them for a described v5e at
SF10 stage shapes) and chip_smoke.py checks them against their XLA forms on
the chip:

- `masked_group_reduce`: per-(partition, group) masked (sum, count) over
  [P, N] value lanes. Each (8-row, block_n-lane) block builds, per row, a
  [group-tile, block_n] membership mask (group id == sublane index, AND the
  stage mask) and reduces it along the lanes on the VPU — exact f32 adds, no
  MXU pass, so the result does not depend on matmul precision. Group
  domains beyond one 128-group tile run on a multi-tile grid axis (G up to
  MAX_GROUPS).
- `dict_filter`: string predicates (eq / prefix / LIKE-literal) as a
  boolean LUT over dictionary codes, fused with the incoming predicate
  mask. The LUT sits in VMEM as [T/128, 128] rows and every 128-lane vreg of
  codes is answered by in-register lane gathers, one per LUT row; LUTs past
  MAX_DICT_LUT entries take the plain XLA gather.

The rest run ONLY in the CPU backend's Pallas interpreter — the TPU lowering
refuses them, fusion.py never selects them on a TPU (`fusion.TPU_KERNELS`),
and they stay for the CPU parity tests until a chip cell decides their
fate (ROADMAP S6/D2):

- `hash_probe`: direct-mode join probe as one `table[keys]` gather from a
  whole VMEM-resident table; the TPU has no such gather past one vreg.
- `segmented_sort` / `topk_select`: the ORDER BY family over the int64
  lane encoding (ints/dates widened, floats bit-twiddled order-preserving,
  strings as lexicographic-rank dictionary codes, validity as a leading
  null-rank operand) as a bitonic network of reshape + compare-exchange
  passes over the lexicographic triple (key, tiebreak, position).
  `topk_select` never materializes the full sort: chunks of C = pow2(≥k)
  lanes sort locally, then pairs fold with the elementwise-min bitonic
  trick, log2(N/C) rounds down to one sorted chunk. 64-bit operands cannot
  cross into a TPU kernel at all.
- `segmented_scan`: inclusive segmented sum/min/max over [P, N] lanes with
  boundary resets (Hillis-Steele with flag propagation), int64/f64 lanes.

Blocks follow the TPU rule: the last two block dimensions are multiples of
(8, 128) or span the whole array (`_tile`). Reduction outputs are revisited
across row blocks and accumulated in place (pallas_guide.md). Every scalar
inside a kernel is 32-bit by construction — the engine runs with x64 on, and
a weak Python literal or an index-map `0` would otherwise enter as 64-bit,
which the TPU lowering refuses.

Scope follows TPU arithmetic reality: f32 sums + i32 counts (the VPU's
native widths). The exact int64-cents money path stays on the XLA
reduction. Mode selection lives in ops/tpu/fusion.py (cost model).
"""

from __future__ import annotations

import functools

LANES = 128  # vreg lane width: every lane block is a multiple of it
GROUP_LANES = 128  # group-tile height ceiling (groups ride sublanes)
MAX_GROUP_TILES = 32
MAX_GROUPS = GROUP_LANES * MAX_GROUP_TILES  # multi-tile grid ceiling


def _on_cpu() -> bool:
    """Interpret mode is for the CPU backend only: on any other platform a
    selected kernel runs compiled or fails to compile, loudly."""
    from ballista_tpu.ops.tpu import runtime

    return runtime.platform() == "cpu"


def _jit_named(jax, fn, name: str):
    """`fn` jitted under the kernel's own name: the profiler's trace then
    reads jit_<kernel>(..)/<kernel>, not jit_wrapped."""
    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn)


def _tile(P: int, N: int, block_n: int) -> tuple[int, int, int]:
    """(padded P, padded N, lane block) for a [P, N] operand under the TPU
    block rule. Up to 8 partitions ride as one whole-axis row block; more
    are padded to a multiple of 8 and blocked by 8 (builders take
    min(P, 8)). Lanes pad to a multiple of 128 (a no-op for the engine's
    shape buckets) and block by the largest multiple of 128 that divides
    them and is at most block_n."""
    Pp = P if P <= 8 else -(-P // 8) * 8
    Np = -(-N // LANES) * LANES
    k = max(1, min(block_n, Np) // LANES)
    while (Np // LANES) % k:
        k -= 1
    return Pp, Np, k * LANES


def _pad2(x, Pp: int, Np: int):
    """Zero-pad a [P, N] operand up to [Pp, Np] (zero mask = dead lanes)."""
    import jax.numpy as jnp

    P, N = x.shape
    if (P, N) == (Pp, Np):
        return x
    return jnp.pad(x, ((0, Pp - P), (0, Np - N)))


@functools.lru_cache(maxsize=32)
def _build_group_reduce(P: int, N: int, block_n: int, G: int, interpret: bool):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    Pb = min(P, 8)
    # groups ride the sublane axis: a tile is as tall as the domain needs
    # (rounded to the 8-sublane vreg), 128 at most
    Gt = min(GROUP_LANES, -(-G // 8) * 8)
    n_tiles = -(-G // Gt)

    def kernel(vals_ref, gid_ref, mask_ref, sums_ref, cnts_ref):
        gt = pl.program_id(1)
        j = pl.program_id(2)

        @pl.when(j == 0)
        def _init():
            sums_ref[...] = jnp.zeros_like(sums_ref)
            cnts_ref[...] = jnp.zeros_like(cnts_ref)

        groups = gt * Gt + jax.lax.broadcasted_iota(jnp.int32, (Gt, 1), 0)
        for r in range(Pb):
            v = vals_ref[r:r + 1, :]  # [1, block_n]
            g = gid_ref[r:r + 1, :]
            m = mask_ref[r:r + 1, :] != 0
            hit = (g == groups) & m  # [Gt, block_n]: group on sublanes
            sums_ref[r] += jnp.sum(jnp.where(hit, v, jnp.zeros_like(v)),
                                   axis=1, keepdims=True, dtype=jnp.float32)
            cnts_ref[r] += jnp.sum(hit.astype(jnp.int32),
                                   axis=1, keepdims=True, dtype=jnp.int32)

    in_spec = pl.BlockSpec((Pb, block_n), lambda i, gt, j: (i, j))
    # [P, groups, 1]: a tile's sums land as one sublane column per row
    # (an int32 zero made inside the index map: a bare 0 enters as int64)
    out_spec = pl.BlockSpec((Pb, Gt, 1),
                            lambda i, gt, j: (i, gt, jnp.int32(0)))
    fn = pl.pallas_call(
        kernel,
        name="masked_group_reduce",
        grid=(P // Pb, n_tiles, N // block_n),
        in_specs=[in_spec, in_spec, in_spec],
        out_specs=(out_spec, out_spec),
        out_shape=(
            jax.ShapeDtypeStruct((P, n_tiles * Gt, 1), jnp.float32),
            jax.ShapeDtypeStruct((P, n_tiles * Gt, 1), jnp.int32),
        ),
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )
    return _jit_named(jax, fn, "masked_group_reduce")


def masked_group_reduce(vals, gid, mask, num_groups: int, block_n: int = 2048):
    """Per-(partition, group) masked (sum, count) over [P, N] lanes.

    vals: f32 [P, N]; gid: i32 [P, N]; mask: bool [P, N].
    Returns (sums f32 [P, G], counts i32 [P, G]).
    """
    import jax.numpy as jnp

    if num_groups > MAX_GROUPS:
        raise ValueError(f"num_groups {num_groups} > {MAX_GROUPS}")
    P, N = vals.shape
    Pp, Np, bn = _tile(P, N, block_n)
    fn = _build_group_reduce(Pp, Np, bn, num_groups, interpret=_on_cpu())
    sums, cnts = fn(
        _pad2(vals.astype(jnp.float32), Pp, Np),
        _pad2(gid.astype(jnp.int32), Pp, Np),
        _pad2(mask.astype(jnp.int32), Pp, Np),
    )
    return sums[:P, :num_groups, 0], cnts[:P, :num_groups, 0]


@functools.lru_cache(maxsize=32)
def _build_hash_probe(P: int, N: int, block_n: int, T: int, interpret: bool):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def kernel(keys_ref, mask_ref, table_ref, row_ref, match_ref):
        k = keys_ref[0, :]
        m = mask_ref[0, :] != 0
        table = table_ref[...]  # full [T] lookup table, VMEM-resident
        rows = table[k]
        matched = m & (rows >= 0)
        # fused downstream predicate mask: unmatched probes clamp to row 0
        # (the gather index contract of the XLA finder, bit-for-bit)
        row_ref[0, :] = jnp.where(matched, rows, 0)
        match_ref[0, :] = matched.astype(jnp.int8)

    grid = (P, N // block_n)
    fn = pl.pallas_call(
        kernel,
        name="hash_probe",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_n), lambda i, j: (i, j)),
            pl.BlockSpec((1, block_n), lambda i, j: (i, j)),
            pl.BlockSpec((T,), lambda i, j: (0,)),
        ],
        out_specs=(
            pl.BlockSpec((1, block_n), lambda i, j: (i, j)),
            pl.BlockSpec((1, block_n), lambda i, j: (i, j)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((P, N), jnp.int32),
            jax.ShapeDtypeStruct((P, N), jnp.int8),
        ),
        interpret=interpret,
    )
    return _jit_named(jax, fn, "hash_probe")


def hash_probe(keys, table, mask, block_n: int = 2048):
    """Direct-mode join probe: rows = table[keys], fused with the probe
    predicate mask.

    keys: i32 [P, N], pre-clamped into [0, T); table: i32 [T] (key → build
    row, -1 absent); mask: bool [P, N] (in-range AND probe-key-valid).
    Returns (rows i32 [P, N] — 0 where unmatched, matching the XLA
    finder's clamped gather index — and matched bool [P, N]).
    """
    import jax.numpy as jnp

    P, N = keys.shape
    bn = min(block_n, N)
    while N % bn:
        bn //= 2
    fn = _build_hash_probe(P, N, bn, int(table.shape[0]), interpret=_on_cpu())
    rows, matched = fn(
        keys.astype(jnp.int32), mask.astype(jnp.int32), table.astype(jnp.int32)
    )
    return rows, matched != 0


# ---------------------------------------------------------------------------
# segmented sort / top-k (ORDER BY family)
# ---------------------------------------------------------------------------

MAX_SORT_LANES = 1 << 20  # absolute ceiling; the cost model caps lower


def _cx3(jnp, lax, a, b, p, k: int, j: int):
    """One bitonic compare-exchange pass over the last axis (length n,
    pow2) of the lexicographic triple (a, b, p). Partner pairs at XOR
    distance j are materialized by a reshape to [..., n/(2j), 2, j] — no
    gathers, so the pass is pure VPU select traffic. Direction follows the
    classic (index & k) == 0 rule; with k == n this is the all-ascending
    merge of a bitonic sequence."""
    sh = a.shape
    n = sh[-1]
    m = n // (2 * j)
    s3 = sh[:-1] + (m, 2, j)
    a3, b3, p3 = a.reshape(s3), b.reshape(s3), p.reshape(s3)
    la, ha = a3[..., 0, :], a3[..., 1, :]
    lb, hb = b3[..., 0, :], b3[..., 1, :]
    lp, hp = p3[..., 0, :], p3[..., 1, :]
    blk = lax.broadcasted_iota(jnp.int32, (m, j), 0)
    up = ((blk * (2 * j)) & k) == 0
    gt = (la > ha) | ((la == ha) & ((lb > hb) | ((lb == hb) & (lp > hp))))
    sw = jnp.where(up, gt, ~gt)

    def put(lo, hi):
        return jnp.stack([jnp.where(sw, hi, lo), jnp.where(sw, lo, hi)],
                         axis=-2).reshape(sh)

    return put(la, ha), put(lb, hb), put(lp, hp)


def _bitonic_sort3(jnp, lax, a, b, p):
    """Full bitonic sort of each last-axis row, ascending by (a, b, p)."""
    n = a.shape[-1]
    k = 2
    while k <= n:
        j = k // 2
        while j >= 1:
            a, b, p = _cx3(jnp, lax, a, b, p, k, j)
            j //= 2
        k *= 2
    return a, b, p


@functools.lru_cache(maxsize=32)
def _build_segmented_sort(P: int, N: int, interpret: bool):
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl

    def kernel(a_ref, b_ref, p_ref, oa_ref, ob_ref, op_ref):
        a, b, p = a_ref[0, :], b_ref[0, :], p_ref[0, :]
        a, b, p = _bitonic_sort3(jnp, lax, a, b, p)
        oa_ref[0, :] = a
        ob_ref[0, :] = b
        op_ref[0, :] = p

    spec = pl.BlockSpec((1, N), lambda i: (i, 0))
    fn = pl.pallas_call(
        kernel,
        name="segmented_sort",
        grid=(P,),
        in_specs=[spec, spec, spec],
        out_specs=(spec, spec, spec),
        out_shape=(
            jax.ShapeDtypeStruct((P, N), jnp.int64),
            jax.ShapeDtypeStruct((P, N), jnp.int64),
            jax.ShapeDtypeStruct((P, N), jnp.int32),
        ),
        interpret=interpret,
    )
    return _jit_named(jax, fn, "segmented_sort")


def segmented_sort(a, b, pos):
    """Sort each row of [P, N] ascending by the triple (a, b, pos).

    a, b: i64 lanes (b is the tiebreak operand — zeros for single-key
    sorts, the null-rank plane for nullable keys); pos: i32 original
    positions. N must be a power of two; pad with (i64 max, i64 max,
    i32 max) sentinels, which sort strictly after every real row.
    Returns the sorted triple; the permutation is the pos output.
    """
    import jax.numpy as jnp

    P, N = a.shape
    if N & (N - 1):
        raise ValueError(f"segmented_sort needs pow2 lanes, got {N}")
    fn = _build_segmented_sort(P, N, interpret=_on_cpu())
    return fn(a.astype(jnp.int64), b.astype(jnp.int64), pos.astype(jnp.int32))


@functools.lru_cache(maxsize=32)
def _build_topk(P: int, N: int, C: int, interpret: bool):
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl

    def kernel(a_ref, b_ref, p_ref, oa_ref, ob_ref, op_ref):
        nc = N // C
        a = a_ref[0, :].reshape(nc, C)
        b = b_ref[0, :].reshape(nc, C)
        p = p_ref[0, :].reshape(nc, C)
        # round 0: every C-lane chunk sorts locally (ascending)
        a, b, p = _bitonic_sort3(jnp, lax, a, b, p)
        # fold rounds: pair chunks, keep the C smallest of each 2C via the
        # elementwise-min bitonic trick, re-merge (k=C ascending merge) —
        # the full N-lane sort is never materialized
        while a.shape[0] > 1:
            ea, eb, ep = a[0::2], b[0::2], p[0::2]
            oa, ob, op = a[1::2, ::-1], b[1::2, ::-1], p[1::2, ::-1]
            lt = (ea < oa) | ((ea == oa) & ((eb < ob) | ((eb == ob) & (ep < op))))
            a = jnp.where(lt, ea, oa)
            b = jnp.where(lt, eb, ob)
            p = jnp.where(lt, ep, op)
            j = C // 2
            while j >= 1:
                a, b, p = _cx3(jnp, lax, a, b, p, C, j)
                j //= 2
        oa_ref[0, :] = a.reshape(C)
        ob_ref[0, :] = b.reshape(C)
        op_ref[0, :] = p.reshape(C)

    in_spec = pl.BlockSpec((1, N), lambda i: (i, 0))
    out_spec = pl.BlockSpec((1, C), lambda i: (i, 0))
    fn = pl.pallas_call(
        kernel,
        name="topk_select",
        grid=(P,),
        in_specs=[in_spec, in_spec, in_spec],
        out_specs=(out_spec, out_spec, out_spec),
        out_shape=(
            jax.ShapeDtypeStruct((P, C), jnp.int64),
            jax.ShapeDtypeStruct((P, C), jnp.int64),
            jax.ShapeDtypeStruct((P, C), jnp.int32),
        ),
        interpret=interpret,
    )
    return _jit_named(jax, fn, "topk_select")


def topk_select(a, b, pos, k: int):
    """Per-row k smallest triples of [P, N] lanes, in sorted order —
    ORDER BY ... LIMIT without the full sort. Same operand contract and
    sentinel padding as segmented_sort. Returns [P, k] triples."""
    import jax.numpy as jnp

    P, N = a.shape
    if N & (N - 1):
        raise ValueError(f"topk_select needs pow2 lanes, got {N}")
    C = 1
    while C < max(k, 1):
        C *= 2
    C = min(max(C, 128), N)  # chunk floor keeps the fold shallow
    fn = _build_topk(P, N, C, interpret=_on_cpu())
    sa, sb, sp = fn(a.astype(jnp.int64), b.astype(jnp.int64),
                    pos.astype(jnp.int32))
    return sa[:, :k], sb[:, :k], sp[:, :k]


# ---------------------------------------------------------------------------
# segmented scans (window-aggregate primitive)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=32)
def _build_seg_scan(P: int, N: int, func: str, dtype_name: str, interpret: bool):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    dtype = jnp.dtype(dtype_name)
    floating = jnp.issubdtype(dtype, jnp.floating)
    # python scalars, not jnp arrays: the kernel must not capture tracers
    if func == "sum":
        ident = 0
        op = jnp.add
    elif func == "min":
        ident = float("inf") if floating else int(jnp.iinfo(dtype).max)
        op = jnp.minimum
    else:  # max
        ident = float("-inf") if floating else int(jnp.iinfo(dtype).min)
        op = jnp.maximum

    def kernel(v_ref, f_ref, o_ref):
        v = v_ref[0, :]
        f = f_ref[0, :] != 0
        d = 1
        # Hillis-Steele with boundary-flag OR-propagation: shifted-out
        # positions read the identity under a True flag (the implicit
        # segment boundary at lane 0)
        while d < N:
            pv = jnp.concatenate([jnp.full((d,), ident, dtype), v[:-d]])
            pf = jnp.concatenate([jnp.ones((d,), jnp.bool_), f[:-d]])
            v = jnp.where(f, v, op(v, pv))
            f = f | pf
            d *= 2
        o_ref[0, :] = v

    spec = pl.BlockSpec((1, N), lambda i: (i, 0))
    fn = pl.pallas_call(
        kernel,
        name="segmented_scan",
        grid=(P,),
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((P, N), dtype),
        interpret=interpret,
    )
    return _jit_named(jax, fn, "segmented_scan")


def segmented_scan(vals, boundary, func: str):
    """Inclusive segmented sum/min/max over each [P, N] row: the scan
    resets wherever boundary is True (row 0 is an implicit boundary).
    N must be a power of two; pad the tail with boundary=True lanes."""
    import jax.numpy as jnp

    P, N = vals.shape
    if N & (N - 1):
        raise ValueError(f"segmented_scan needs pow2 lanes, got {N}")
    fn = _build_seg_scan(P, N, func, str(vals.dtype), interpret=_on_cpu())
    return fn(vals, boundary.astype(jnp.int32))


# ---------------------------------------------------------------------------
# dictionary-code string predicates
# ---------------------------------------------------------------------------


MAX_DICT_LUT = 1024  # 8 lane-gather rounds per vreg of codes


@functools.lru_cache(maxsize=32)
def _build_dict_filter(P: int, N: int, block_n: int, T: int, interpret: bool):
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    Pb = min(P, 8)
    n_rows = T // LANES
    # the one gather the TPU lowering takes: per-row, within one 128-lane
    # vreg, int32 indices (jnp.take_along_axis would widen them to int64)
    lane_gather = lax.GatherDimensionNumbers(
        offset_dims=(), collapsed_slice_dims=(1,), start_index_map=(1,),
        operand_batching_dims=(0,), start_indices_batching_dims=(0,))

    def kernel(codes_ref, mask_ref, lut_ref, keep_ref):
        for c in range(block_n // LANES):
            sl = slice(c * LANES, (c + 1) * LANES)
            codes = codes_ref[:, sl]  # [Pb, 128]
            hit = jnp.zeros_like(codes)
            for k in range(n_rows):
                row = jnp.broadcast_to(lut_ref[k:k + 1, :], codes.shape)
                local = codes - jnp.int32(k * LANES)
                here = (local >= 0) & (local < LANES)
                got = lax.gather(
                    row, jnp.where(here, local, jnp.zeros_like(local))[..., None],
                    lane_gather, slice_sizes=(1, 1),
                    mode=lax.GatherScatterMode.PROMISE_IN_BOUNDS)
                hit = jnp.where(here, got, hit)
            keep_ref[:, sl] = (
                (mask_ref[:, sl] != 0) & (hit != 0)).astype(jnp.int32)

    spec = pl.BlockSpec((Pb, block_n), lambda i, j: (i, j))
    fn = pl.pallas_call(
        kernel,
        name="dict_filter",
        grid=(P // Pb, N // block_n),
        in_specs=[spec, spec,
                  pl.BlockSpec((n_rows, LANES),
                               lambda i, j: (jnp.int32(0), jnp.int32(0)))],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((P, N), jnp.int32),
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
    )
    return _jit_named(jax, fn, "dict_filter")


def dict_filter(codes, lut, mask, block_n: int = 2048):
    """String predicate over dictionary codes: keep = mask & lut[codes].

    codes: i32 [P, N] dictionary indices (pre-clamped into [0, T));
    lut: bool [T] host-compiled predicate truth table (eq / prefix /
    LIKE-literal evaluated per dictionary entry, pow2-padded); mask:
    bool [P, N]. Returns keep bool [P, N] — for LUTs of up to MAX_DICT_LUT
    entries the gather and the mask conjunction never round-trip through
    HBM; larger dictionaries take the XLA gather."""
    import jax.numpy as jnp

    T = int(lut.shape[0])
    if T > MAX_DICT_LUT:
        return mask & lut[codes]
    P, N = codes.shape
    Pp, Np, bn = _tile(P, N, block_n)
    Tp = -(-T // LANES) * LANES
    lut2 = jnp.pad(lut.astype(jnp.int32), (0, Tp - T)).reshape(Tp // LANES, LANES)
    fn = _build_dict_filter(Pp, Np, bn, Tp, interpret=_on_cpu())
    keep = fn(_pad2(codes.astype(jnp.int32), Pp, Np),
              _pad2(mask.astype(jnp.int32), Pp, Np), lut2)
    return keep[:P, :N] != 0
