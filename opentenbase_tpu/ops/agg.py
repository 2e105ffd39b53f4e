"""Grouped and scalar aggregation kernels.

The reference's nodeAgg.c (6,331 LoC) builds a per-group hash table and
advances transition states tuple-by-tuple. The TPU-native formulation is
sort-based: stable-sort rows by the group keys, detect segment boundaries,
then compute every aggregate as a segment reduction (`jax.ops.segment_*`) —
one fused scatter-reduce per aggregate, no serial hash probing.

Two-stage shape handling (SURVEY.md §7 "two-pass size estimation"):
``group_ids`` sorts + labels and returns the group count; the executor
buckets that count to a static ``num_groups`` and calls ``group_reduce``.
Both stages are jitted; the intermediate stays on device.

Distributed 2-phase aggregation maps exactly onto this: each shard runs
group_reduce (partial), the coordinator (or a psum/all_gather collective)
re-runs group_reduce over concatenated partials with merge ops — the
equivalent of make_remotesubplan's agg split
(src/backend/optimizer/plan/createplan.c:1852).
"""

from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from opentenbase_tpu.obs.trace import scope

_I64_MAX = np.int64(2**62)  # sentinels safely inside int64
_I64_MIN = np.int64(-(2**62))


def float_key_parts(d) -> list:
    """Equality-preserving int32 views of a float column for grouping and
    join keys. -0.0 folds into +0.0 and every NaN collapses to one bit
    pattern (SQL groups NaNs together). float64 cannot be bitcast on TPU
    (the x64 rewrite lacks 64-bit bitcast), so it is split double-float
    style into hi+lo f32 parts — exact discrimination down to ~2^-48
    relative difference, far below SQL-visible precision."""
    d = jnp.where(d == 0, jnp.zeros((), d.dtype), d)
    d = jnp.where(jnp.isnan(d), jnp.full((), jnp.nan, d.dtype), d)
    if d.dtype == jnp.float64:
        hi = d.astype(jnp.float32)
        lo = (d - hi.astype(jnp.float64)).astype(jnp.float32)
        lo = jnp.where(jnp.isfinite(d), lo, jnp.zeros((), jnp.float32))
        return [
            jax.lax.bitcast_convert_type(hi, jnp.int32),
            jax.lax.bitcast_convert_type(lo, jnp.int32),
        ]
    return [jax.lax.bitcast_convert_type(d.astype(jnp.float32), jnp.int32)]


def _key_parts(keys):
    """Flatten (data, valid) group keys into comparable integer parts.
    Floats are bitcast so exact equality grouping matches SQL GROUP BY."""
    parts = []
    for data, valid in keys:
        d = data
        if jnp.issubdtype(d.dtype, jnp.floating):
            for piece in float_key_parts(d):
                if valid is not None:
                    piece = jnp.where(valid, piece, 0)
                parts.append((piece, valid))
            continue
        if jnp.issubdtype(d.dtype, jnp.bool_):
            d = d.astype(jnp.int32)
        if valid is not None:
            d = jnp.where(valid, d, 0)  # canonicalize NULL payloads
            parts.append((d, valid))
        else:
            parts.append((d, None))
    return parts


def _hash_slot_ids(keys, mask, cap: int):
    """Row -> slot in [0, cap) by mixing the key parts; invisible rows
    get slot == cap. Returns (slot, int64 key parts, visibility)."""
    assert cap & (cap - 1) == 0, "group capacity must be a power of two"
    parts = _key_parts(keys)
    n = parts[0][0].shape[0] if parts else mask.shape[0]
    # 64-bit FNV-style mix over parts + validity bits
    h = jnp.full(n, 1469598103934665603, dtype=jnp.int64)
    p64: list = []
    for d, v in parts:
        d64 = d.astype(jnp.int64)
        p64.append(d64)
        h = (h ^ d64) * jnp.int64(1099511628211)
        if v is not None:
            p64.append(v.astype(jnp.int64))
            h = (h ^ v.astype(jnp.int64)) * jnp.int64(1099511628211)
    h = h ^ (h >> 29)  # finalize: low bits must feel the high bits
    slot = jnp.bitwise_and(h, cap - 1).astype(jnp.int32)
    vis = mask if mask is not None else jnp.ones(n, dtype=jnp.bool_)
    return jnp.where(vis, slot, jnp.int32(cap)), p64, vis


def _hash_slots_impl(keys, mask, cap: int):
    """Hash-addressed grouping: map each visible row straight to a slot in
    [0, cap) by mixing its key parts — NO sort. The TPU-native replacement
    for the multi-pass argsort labeling of ``_group_ids_impl`` on the hot
    fused path: hashing is one linear VPU pass, while argsort is
    O(n log^2 n) on device.

    Exactness: a slot may receive two distinct keys (hash collision, or
    more than ``cap`` distinct groups). Per slot we keep the minimum of
    every key part and flag any visible row that disagrees with its
    slot's representative — the caller falls back to the sort path when
    ``collision`` is true, so results are never silently wrong.

    Returns (slot, ngroups, collision): ``slot[i]`` in [0, cap) for
    visible rows and == cap for invisible ones (the overflow bin
    ``_group_reduce_impl`` already clamps to), ``ngroups`` the used-slot
    count, ``collision`` a 0-d bool.

    ``cap`` must be a power of two (slot = hash & (cap-1)).
    """
    slot, p64, vis = _hash_slot_ids(keys, mask, cap)
    # exact collision detection against per-slot representatives
    collision = jnp.asarray(False)
    for p in p64:
        rep = jax.ops.segment_min(
            jnp.where(vis, p, _I64_MAX), slot, num_segments=cap + 1
        )
        collision = collision | jnp.any(
            vis & (p != jnp.take(rep, slot, axis=0))
        )
    used = (
        jax.ops.segment_sum(
            vis.astype(jnp.int32), slot, num_segments=cap + 1
        )[:cap]
        > 0
    )
    ngroups = jnp.sum(used, dtype=jnp.int32)
    return slot, ngroups, collision


_MXU_BLOCK = 4096  # rows per one-hot matmul block
# 8-bit limbs: every limb value (< 256) is exactly representable in
# bf16, so the MXU's bf16 multiply passes are exact and the f32
# accumulator holds block sums <= 4096*255 < 2^24 exactly. (12-bit
# limbs are NOT bf16-exact — the TPU computes "f32" matmuls as bf16
# product passes.)
_LIMB_BITS = 8
_LIMB_MASK = (1 << _LIMB_BITS) - 1
# a bound at or above this is not trusted to size limbs: it comes from
# float interval arithmetic (ops/pallas_scan.bound), exact on integers
# only below 2^53
_BOUND_TRUSTED = float(1 << 52)


def limbs_for_bound(bound, nonneg: bool = False):
    """``(n_limbs, signed)`` holding every integer v with |v| <= bound in
    8-bit limbs — the top limb carries the sign by arithmetic shift,
    unless ``nonneg`` proves there is none to carry (one bit less).
    None when the bound is unknown or too large to trust: the caller
    then takes the width of the value's dtype."""
    if bound is None or not 0 <= bound < _BOUND_TRUSTED:
        return None
    bits = int(math.ceil(bound)).bit_length() + (0 if nonneg else 1)
    return max(-(-bits // _LIMB_BITS), 1), not nonneg


def limbs_for_range(rng):
    """``limbs_for_bound`` of a column's host-known ``(min, max)``."""
    if rng is None:
        return None
    return limbs_for_bound(max(abs(rng[0]), abs(rng[1])), rng[0] >= 0)


def _dtype_limbs(dtype):
    """The limbs that hold ANY value of an integer dtype (bool: one)."""
    dtype = jnp.dtype(dtype)
    return dtype.itemsize, not jnp.issubdtype(dtype, jnp.unsignedinteger)


class MxuBounds(NamedTuple):
    """What the host certifies about a grouped reduction's keys and
    arguments (executor/fused.py, from the device table's column
    statistics): the quantised half of the lane plan, and as such part
    of the compiled program's key. ``None`` where nothing is known."""

    key_limbs: tuple  # per group key: limbs_for_bound() or None
    arg_ids: tuple  # per spec: its distinct argument, None for count(*)
    arg_limbs: tuple  # per distinct argument: limbs_for_bound() or None


class MxuLanePlan(NamedTuple):
    """The K lanes of the one-hot matmul and who reads which."""

    # per lane: ("limb", raw, l, masked) | ("f32", raw) | ("ones",)
    lanes: tuple
    raws: tuple  # per raw column: (source, index, n_limbs or None=validity)
    key_slices: tuple  # per key: (first lane, n_limbs)
    key_valid: tuple  # per key: validity lane or None
    arg_ids: tuple  # per spec: its distinct argument, None for count(*)
    arg_slices: tuple  # per distinct argument: (first lane, n_limbs) or None
    arg_valid: tuple  # per distinct argument: validity lane or None
    ones: int  # the count(*) lane


def mxu_lane_plan(key_cols, specs, arg_cols, bounds: Optional[MxuBounds]):
    """Lay out the lanes from what each value can hold, not from what
    its dtype could: ``key_cols`` / ``arg_cols`` give ``(dtype,
    nullable)`` per group key / per spec (None for count(*)).

    - only lanes that are read: a ``count(x)`` reads x's validity lane
      when x has one and the ones lane otherwise, never limbs;
    - each distinct argument once (``bounds.arg_ids``): sum(x) and
      avg(x)'s sum share one raw column and one set of limbs;
    - as many limbs as the certified bound needs, never more than the
      dtype's; no bound (``bounds`` None, or None inside it) gives the
      dtype's width, so the unbounded plan is this same plan.

    The host calls this with the types it knows to report K
    (fused.bind), the trace with the arrays it has."""
    if bounds is None:
        ids, nxt = [], 0
        for spec in specs:
            ids.append(None if spec == "count_star" else nxt)
            nxt += spec != "count_star"
        bounds = MxuBounds((None,) * len(key_cols), tuple(ids), (None,) * nxt)
    lanes: list = []
    raws: list = []

    def width(dtype, certified):
        nl, signed = _dtype_limbs(dtype)
        if certified is not None and certified[0] < nl:
            nl, signed = certified
        return nl, signed

    def add_limbs(source, index, nl, signed):
        start = len(lanes)
        raws.append((source, index, nl))
        lanes.extend(
            ("limb", len(raws) - 1, l, not (signed and l == nl - 1))
            for l in range(nl)
        )
        return start, nl

    def add_valid(source, index):
        raws.append((source, index, None))
        lanes.append(("f32", len(raws) - 1))
        return len(lanes) - 1

    key_slices, key_valid = [], []
    for i, ((dtype, nullable), certified) in enumerate(
        zip(key_cols, bounds.key_limbs)
    ):
        key_slices.append(add_limbs("key", i, *width(dtype, certified)))
        key_valid.append(add_valid("key", i) if nullable else None)
    arg_slices: list = [None] * len(bounds.arg_limbs)
    arg_valid: list = [None] * len(bounds.arg_limbs)
    seen: set = set()
    for i, (spec, aid) in enumerate(zip(specs, bounds.arg_ids)):
        if aid is None:
            continue
        dtype, nullable = arg_cols[i]
        if aid not in seen:
            seen.add(aid)
            if nullable:
                arg_valid[aid] = add_valid("arg", i)
        if spec == "sum" and arg_slices[aid] is None:
            arg_slices[aid] = add_limbs(
                "arg", i, *width(dtype, bounds.arg_limbs[aid])
            )
    lanes.append(("ones",))
    return MxuLanePlan(
        tuple(lanes), tuple(raws), tuple(key_slices), tuple(key_valid),
        bounds.arg_ids, tuple(arg_slices), tuple(arg_valid), len(lanes) - 1,
    )


def mxu_lanes_dtype_wide(key_cols, specs, arg_cols) -> int:
    """K of the plan sized by dtype alone (the one this module cut
    before it read bounds): every key at 4 or 8 limbs, every sum and
    count argument at 8, nothing shared. Kept as the yardstick the
    ``lanes_full`` span argument reports against."""
    k = 1
    for dtype, nullable in key_cols:
        k += (4 if jnp.dtype(dtype).itemsize <= 4 else 8) + bool(nullable)
    for spec, col in zip(specs, arg_cols):
        if spec != "count_star":
            k += 8 + bool(col[1])
    return k


def _superblocks(n: int, most: int) -> tuple:
    """``n`` rows as scan steps of ``sb`` blocks of ``_MXU_BLOCK``:
    (sb, rows a step, steps, rows padded to). At most ``most`` blocks a
    step and never more than the rows need: a shard with one block of
    rows must not pad to (and one-hot-matmul over) a full superblock of
    zeros."""
    sb = max(1, min(most, -(-n // _MXU_BLOCK)))
    super_rows = sb * _MXU_BLOCK
    ns = max(-(-n // super_rows), 1)
    return sb, super_rows, ns, ns * super_rows


def _mxu_group_reduce_impl(
    keys, vals, slot, num_groups: int, specs: tuple,
    bounds: Optional[MxuBounds] = None,
):
    """Grouped reduction on the MXU: one-hot(slot) matmuls instead of
    segment scatters — XLA's TPU scatter/sort are orders of magnitude
    slower than a systolic-array pass for cap-bounded grouping.

    Exactness: every accumulated quantity is integer-valued and split
    into 8-bit limbs (``mxu_lane_plan``; sum_l limb_l << 8l is the
    value); each 4096-row block's one-hot matmul sums each limb exactly
    in f32 (<= 2^24), per-block partials convert to int64 and sum
    exactly. ``bounds`` only ever drops limbs that are zero (or pure
    sign) on every row the statistics cover. Group keys are recovered
    by division (all rows in a slot share one key, or the collision
    flag is set): khat = sum(key)/count, checked per row via a
    gather-compare — which doubles as exact hash-collision detection.

    Eligibility (caller-enforced): integer-typed keys/vals, specs in
    sum/count/count_star. Returns (out_keys, out_vals, gvalid, ngroups,
    collision) matching the segment path's contract."""
    cap = num_groups
    n = slot.shape[0]
    # two-level blocking: superblocks scanned with an int64 accumulator
    # so the per-block f32 partials ([sb, cap, K]) stay a few MB instead
    # of materializing an [nblocks, cap, K] tensor proportional to the
    # whole table
    # superblock height adapts to the data: a shard with one block of
    # rows must not pad to (and one-hot-matmul over) 256 blocks of
    # zeros — the fixed floor made every small GROUP BY pay a
    # million-row scan
    # per-step f32 partials: [sb, cap, K]
    sb, super_rows, ns, padded = _superblocks(n, 256)
    if padded != n:
        slot = jnp.pad(slot, (0, padded - n), constant_values=cap)

    def pad0(x):
        return jnp.pad(x, (0, padded - n)) if padded != n else x

    plan = mxu_lane_plan(
        [(d.dtype, v is not None) for d, v in keys],
        specs,
        [None if val is None else (val[0].dtype, val[1] is not None)
         for val in vals],
        bounds,
    )
    # raw columns ride through the scan, limbs are cut per superblock.
    # A column whose limbs fit 32 bits rides as int32 (native shifts,
    # half the carry); a wrapping cast keeps the low 32 bits, which is
    # all its limbs read.
    raw = []  # padded [ns, super_rows] arrays carried by the scan
    for source, index, nl in plan.raws:
        data, valid = (keys if source == "key" else vals)[index]
        if nl is None:
            x = valid.astype(jnp.float32)
        else:
            if valid is not None:  # canonical NULL payload: zero
                data = jnp.where(valid, data, jnp.zeros((), data.dtype))
            x = data.astype(jnp.int32 if nl <= 4 else jnp.int64)
        raw.append(pad0(x).reshape(ns, super_rows))

    K = len(plan.lanes)
    slot_b = slot.reshape(ns, sb, _MXU_BLOCK)

    def step(acc, xs):
        sl = xs[0].reshape(sb, _MXU_BLOCK)
        cols = xs[1:]
        lane_arrays = []
        with scope("agg/limbs"):
            for lane in plan.lanes:
                if lane[0] == "ones":
                    lane_arrays.append(
                        jnp.ones((sb, _MXU_BLOCK), dtype=jnp.float32)
                    )
                    continue
                v = cols[lane[1]].reshape(sb, _MXU_BLOCK)
                if lane[0] == "limb":
                    _kind, _ri, l, masked = lane
                    v = jnp.right_shift(v, _LIMB_BITS * l)
                    if masked:  # the unmasked top limb carries the sign
                        v = jnp.bitwise_and(v, _LIMB_MASK)
                    v = v.astype(jnp.float32)
                lane_arrays.append(v)
            lb = jnp.stack(lane_arrays, axis=-1)  # [sb, B, K]
        # masked/invisible rows carry slot == cap: their one-hot row is
        # all zero, so they contribute nothing (incl. the count column)
        with scope("agg/onehot"):
            onehot = (
                sl[..., None] == jnp.arange(cap, dtype=slot.dtype)
            ).astype(jnp.float32)
            part = jnp.einsum(
                "sbc,sbk->sck", onehot, lb,
                preferred_element_type=jnp.float32,
            )
            return acc + jnp.sum(part.astype(jnp.int64), axis=0), None

    # the init carry derives from ``slot`` so its varying-manual-axes
    # match inside shard_map (a plain zeros init is replicated and the
    # scan body's output — computed from sharded operands — is varying)
    acc0 = jnp.zeros((cap, K), dtype=jnp.int64) + (
        slot_b[0, 0, 0] * 0
    ).astype(jnp.int64)
    totals, _ = jax.lax.scan(
        step,
        acc0,
        (slot_b, *raw),
    )  # [cap, K]
    with scope("agg/recombine"):
        return _mxu_recombine(totals, plan, keys, slot, cap, pad0, specs)


def _mxu_recombine(totals, plan, keys, slot, cap, pad0, specs):
    """The limb totals of ``_mxu_group_reduce_impl`` back to keys, sums
    and the exact collision verdict."""
    cnt = totals[:, plan.ones]
    got = cnt > 0
    safe_cnt = jnp.maximum(cnt, 1)

    out_keys = []
    khats = []
    for (start, nl), vidx, (data, valid) in zip(
        plan.key_slices, plan.key_valid, keys
    ):
        khat = _recombine_limbs(totals, start, nl) // safe_cnt
        khats.append(khat)
        d = khat.astype(data.dtype)
        if vidx is None:
            v = got
        else:
            v = (totals[:, vidx] // safe_cnt > 0) & got
        out_keys.append((d, v))

    # collision / correctness check: every visible row's key must equal
    # its slot's division-recovered key (a mixed slot makes khat garbage
    # and the equality fails) — one gather per key, no scatter
    vis = slot < cap
    collision = jnp.asarray(False)
    gslot = jnp.minimum(slot, cap - 1)
    for khat, (orig_data, orig_valid) in zip(khats, keys):
        d = orig_data
        if orig_valid is not None:
            d = jnp.where(orig_valid, d, jnp.zeros((), d.dtype))
        d = pad0(d).astype(jnp.int64)
        collision = collision | jnp.any(
            vis & (d != jnp.take(khat, gslot, axis=0))
        )

    out_vals = _mxu_recombine_vals(totals, plan, specs, cnt, got)
    ngroups = jnp.sum(got, dtype=jnp.int32)
    return out_keys, out_vals, got, ngroups, collision


def _recombine_limbs(totals, start: int, nl: int):
    """The value whose ``nl`` limb totals start at lane ``start``."""
    acc = totals[:, start + nl - 1]
    for l in range(nl - 2, -1, -1):
        acc = jnp.left_shift(acc, _LIMB_BITS) + totals[:, start + l]
    return acc


def _mxu_recombine_vals(totals, plan, specs, cnt, got) -> list:
    """Each spec's (value, valid) from the ``[cap, K]`` lane totals."""
    out_vals = []
    for spec, aid in zip(specs, plan.arg_ids):
        if spec == "count_star":
            out_vals.append((cnt.astype(jnp.int64), got))
            continue
        vidx = plan.arg_valid[aid]
        nonnull = cnt if vidx is None else totals[:, vidx]
        if spec == "count":
            out_vals.append((nonnull.astype(jnp.int64), got))
            continue
        out_vals.append((
            _recombine_limbs(totals, *plan.arg_slices[aid]),
            (nonnull > 0) & got,
        ))
    return out_vals


def mxu_group_eligible(keys, vals, specs) -> bool:
    """Integer-typed keys and sum/count vals only (floats keep the
    segment path: float sums are not limb-splittable exactly)."""
    for spec in specs:
        if spec not in ("sum", "count", "count_star"):
            return False
    for data, _v in keys:
        if jnp.issubdtype(data.dtype, jnp.floating):
            return False
    for spec, val in zip(specs, vals):
        if spec == "sum" and val is not None:
            if jnp.issubdtype(val[0].dtype, jnp.floating):
                return False
    return True


# Direct-addressed grouping (the DAG's grouped final): the slot IS the
# packed key, so the only question is the capacity.
# low slots a row of the second-level one-hot: slot = hi * L + lo (32:
# 69 ms for 67.1M rows at 8,192 slots on a v5e, 365 ms at 128, 493 ms
# flat; PERF.md, PR 34)
_DIRECT_LOW = 32
# one-hot and operand elements a scan step may hold ([sb, B, H + K*L]):
# the superblock's height follows the capacity
_DIRECT_STEP_ELEMS = 1 << 26


def direct_group_eligible(specs, arg_dtypes) -> bool:
    """``mxu_group_eligible``'s rule on what the host knows before any
    array exists: sum / count / count(*) only, sums over integers."""
    for spec, dtype in zip(specs, arg_dtypes):
        if spec not in ("sum", "count", "count_star"):
            return False
        if spec == "sum" and not np.issubdtype(dtype, np.integer):
            return False
    return True


def _direct_lanes(plan: MxuLanePlan, cols, shape: tuple) -> list:
    """The plan's lanes of one step as ``shape`` bf16 arrays (every
    limb, flag and one is an integer of at most 8 bits: exact). A
    64-bit column is cut from its two 32-bit words, so no shift runs
    on an emulated int64."""
    words: dict = {}

    def word(ri, l):
        v = cols[ri]
        if v.dtype != jnp.int64:
            return v, l
        if (ri, l // 4) not in words:
            words[ri, l // 4] = (
                jnp.right_shift(v, 32) if l >= 4 else v
            ).astype(jnp.int32)
        return words[ri, l // 4], l % 4

    lanes = []
    for lane in plan.lanes:
        if lane[0] == "ones":
            lanes.append(jnp.ones(shape, dtype=jnp.bfloat16))
        elif lane[0] == "f32":
            lanes.append(cols[lane[1]].reshape(shape).astype(jnp.bfloat16))
        else:
            _kind, ri, l, masked = lane
            v, l = word(ri, l)
            v = jnp.right_shift(v.reshape(shape), _LIMB_BITS * l)
            if masked:  # the unmasked top limb carries the sign
                v = jnp.bitwise_and(v, _LIMB_MASK)
            lanes.append(v.astype(jnp.bfloat16))
    return lanes


def _direct_group_reduce_impl(
    vals, slot, cap: int, specs: tuple, low: Optional[int] = None,
):
    """Grouped sums and counts where the group's slot is known without a
    sort or a hash: ``slot[i]`` in [0, cap) is row i's group (injective
    by the caller's construction), ``cap`` (a power of two) for a row
    that counts nowhere. One-hot matmuls over exact 8-bit limbs, the
    blocks, the contraction and the exactness argument of
    ``_mxu_group_reduce_impl`` word for word (limbs under 2^8 are
    bf16-exact, a 4096-row block's f32 sums stay under 2^24, blocks add
    up in int64), but with no key lanes, no collision check, and a
    one-hot that does not grow with the capacity:

    the slot splits into hi = slot // L and lo = slot mod L; a block
    forms ``A[b, k*L + lo] = [lo_b = lo] * x_k[b]`` and multiplies
    ``onehot_hi^T [H, B] . A [B, K*L]``. The one-hot work a row is
    H + L*K compares instead of ``cap``, the MXU gets L*K columns
    instead of K, and the superblock's height follows H + L*K, so
    nothing of capacity x superblock rows exists.

    Returns (out_vals, got, ngroups), lengths ``cap``."""
    assert cap & (cap - 1) == 0, "slot capacity must be a power of two"
    L = min(_DIRECT_LOW if low is None else low, cap)
    H = cap // L
    n = slot.shape[0]
    plan = mxu_lane_plan(
        [], specs,
        [None if val is None else (val[0].dtype, val[1] is not None)
         for val in vals],
        None,
    )
    K = len(plan.lanes)
    sb, super_rows, ns, padded = _superblocks(
        n, min(256, _DIRECT_STEP_ELEMS // (_MXU_BLOCK * (H + K * L)))
    )
    slot = slot.astype(jnp.int32)
    if padded != n:
        slot = jnp.pad(slot, (0, padded - n), constant_values=cap)
    raw = []
    for _source, index, nl in plan.raws:
        data, valid = vals[index]
        if nl is None:
            x = valid
        else:
            if valid is not None:  # canonical NULL payload: zero
                data = jnp.where(valid, data, jnp.zeros((), data.dtype))
            x = data.astype(jnp.int32 if nl <= 4 else jnp.int64)
        if padded != n:
            x = jnp.pad(x, (0, padded - n))
        raw.append(x.reshape(ns, super_rows))
    shift = L.bit_length() - 1
    zero = jnp.zeros((), jnp.bfloat16)

    def step(acc, xs):
        sl = xs[0].reshape(sb, _MXU_BLOCK)
        with scope("final/grouped/limbs"):
            lb = jnp.stack(
                _direct_lanes(plan, xs[1:], (sb, _MXU_BLOCK)), axis=-1
            )  # [sb, B, K]
        with scope("final/grouped/onehot"):
            # a dead row's slot is cap: hi == H matches no column of the
            # one-hot, so it adds nothing (the count lane included)
            hi = jnp.right_shift(sl, shift)
            lo = jnp.bitwise_and(sl, L - 1)
            oh_hi = (
                hi[..., None] == jnp.arange(H, dtype=jnp.int32)
            ).astype(jnp.bfloat16)
            oh_lo = lo[..., None] == jnp.arange(L, dtype=jnp.int32)
            a = jnp.concatenate(
                [jnp.where(oh_lo, lb[..., k:k + 1], zero) for k in range(K)],
                axis=-1,
            ) if L > 1 else lb  # [sb, B, K * L]
            part = jnp.einsum(
                "sbh,sbm->shm", oh_hi, a,
                preferred_element_type=jnp.float32,
            )  # every cell an exact integer under 2^24
            # (at most 256 blocks of at most 4096 * 255 a cell, 2.7e8:
            # the superblock's sums fit int32, one int64 convert a step)
            return acc + jnp.sum(
                part.astype(jnp.int32), axis=0
            ).astype(jnp.int64), None

    # (the carry derives from ``slot`` so that it varies over the mesh
    # axis inside shard_map, as _mxu_group_reduce_impl's does)
    acc0 = jnp.zeros((H, K * L), dtype=jnp.int64) + (
        slot[0] * 0
    ).astype(jnp.int64)
    totals, _ = jax.lax.scan(
        step, acc0, (slot.reshape(ns, super_rows), *raw)
    )
    with scope("final/grouped/recombine"):
        totals = (
            totals.reshape(H, K, L).transpose(0, 2, 1).reshape(cap, K)
        )
        cnt = totals[:, plan.ones]
        got = cnt > 0
        out_vals = _mxu_recombine_vals(totals, plan, specs, cnt, got)
        # otb_lint: ignore[int32-width] -- a count of at most cap slots
        return out_vals, got, jnp.sum(got, dtype=jnp.int32)


def _group_ids_impl(keys, mask):
    """Sort rows by keys (+validity), label segments.

    keys: list of (data, valid_or_None); mask: visible-row bool mask or None.
    Returns (perm, seg, ngroups): ``perm`` the sort permutation,
    ``seg[i]`` the group id of sorted row i (== ngroups for invisible rows),
    ``ngroups`` the number of distinct visible groups (0-d int32).
    """
    parts = _key_parts(keys)
    n = parts[0][0].shape[0] if parts else (mask.shape[0] if mask is not None else 0)
    perm = jnp.arange(n, dtype=jnp.int32)
    for d, v in reversed(parts):
        order = jnp.argsort(jnp.take(d, perm, axis=0), stable=True)
        perm = jnp.take(perm, order, axis=0)
        if v is not None:
            order = jnp.argsort(~jnp.take(v, perm, axis=0), stable=True)
            perm = jnp.take(perm, order, axis=0)
    if mask is not None:
        dead = ~jnp.take(mask, perm, axis=0)
        order = jnp.argsort(dead.astype(jnp.int32), stable=True)
        perm = jnp.take(perm, order, axis=0)
        vis = jnp.take(mask, perm, axis=0)
    else:
        vis = jnp.ones(n, dtype=jnp.bool_)

    boundary = jnp.zeros(n, dtype=jnp.bool_).at[0].set(True)
    for d, v in parts:
        ds = jnp.take(d, perm, axis=0)
        diff = jnp.concatenate([jnp.ones(1, jnp.bool_), ds[1:] != ds[:-1]])
        boundary = boundary | diff
        if v is not None:
            vs = jnp.take(v, perm, axis=0)
            vdiff = jnp.concatenate([jnp.ones(1, jnp.bool_), vs[1:] != vs[:-1]])
            boundary = boundary | vdiff
    boundary = boundary & vis
    seg = jnp.cumsum(boundary.astype(jnp.int32)) - 1
    ngroups = jnp.sum(boundary, dtype=jnp.int32)
    # Invisible rows get a sentinel far above any real group id so that
    # group_reduce's clamp routes them to its overflow bin no matter how
    # the caller buckets num_groups.
    seg = jnp.where(vis, seg, jnp.int32(2**30))
    return perm, seg, ngroups


def _group_reduce_impl(keys, vals, perm, seg, num_groups: int, specs: tuple):
    """Segment reductions with static group capacity.

    keys/vals: lists of (data, valid_or_None) in *unsorted* row order.
    specs: per-val tuple of op strings: 'sum' | 'count' | 'min' | 'max' |
    'count_star' (val entry may be None) | 'any' (first value — used to
    carry grouped expressions). Rows whose seg == num_groups-overflow bin
    are dropped via clamping to an extra scratch segment.

    Returns (out_keys, out_vals, group_valid) where each out is a list of
    (data, valid) arrays of length num_groups, and group_valid[g] marks
    groups < ngroups.
    """
    nseg = num_groups + 1  # +1 overflow bin for invisible rows
    seg = jnp.minimum(seg, nseg - 1)

    # representative row per group (first sorted row = segment start)
    n = perm.shape[0]
    first_sorted = jax.ops.segment_min(
        jnp.arange(n, dtype=jnp.int32), seg, num_segments=nseg
    )
    got = first_sorted < n
    first_row = jnp.take(perm, jnp.minimum(first_sorted, n - 1), axis=0)

    out_keys = []
    for data, valid in keys:
        d = jnp.take(data, first_row, axis=0)[:num_groups]
        if valid is None:
            v = got[:num_groups]
        else:
            v = (jnp.take(valid, first_row, axis=0) & got)[:num_groups]
        out_keys.append((d, v))

    # segment id per *unsorted* row
    seg_unsorted = jnp.zeros(n, dtype=jnp.int32).at[perm].set(seg)

    out_vals = []
    for spec, val in zip(specs, vals):
        if spec == "count_star":
            ones = jnp.where(seg_unsorted < num_groups, 1, 0)
            c = jax.ops.segment_sum(ones, seg_unsorted, num_segments=nseg)
            out_vals.append((c[:num_groups].astype(jnp.int64), got[:num_groups]))
            continue
        data, valid = val
        live = seg_unsorted < num_groups
        vvalid = live if valid is None else (live & valid)
        if spec == "count":
            c = jax.ops.segment_sum(
                vvalid.astype(jnp.int64), seg_unsorted, num_segments=nseg
            )
            out_vals.append((c[:num_groups], got[:num_groups]))
            continue
        if spec == "sum":
            # segment_sum preserves dtype: widen narrow ints so TPC-H
            # scale sums don't wrap in int32
            if jnp.issubdtype(data.dtype, jnp.integer):
                data = data.astype(jnp.int64)
            zero = jnp.zeros((), dtype=data.dtype)
            d = jnp.where(vvalid, data, zero)
            s = jax.ops.segment_sum(d, seg_unsorted, num_segments=nseg)
            c = jax.ops.segment_sum(
                vvalid.astype(jnp.int32), seg_unsorted, num_segments=nseg
            )
            out_vals.append((s[:num_groups], (c > 0)[:num_groups]))
            continue
        if spec in ("min", "max"):
            if jnp.issubdtype(data.dtype, jnp.floating):
                sent = jnp.inf if spec == "min" else -jnp.inf
            elif data.dtype == jnp.bool_:
                data = data.astype(jnp.int32)
                sent = 2 if spec == "min" else -1
            elif jnp.dtype(data.dtype).itemsize < 8:
                # an int64 sentinel WRAPS when cast into a narrower
                # lane (e.g. int32 text codes -> -1), poisoning every
                # group's min with the wrapped value
                info = jnp.iinfo(data.dtype)
                sent = info.max if spec == "min" else info.min
            else:
                sent = _I64_MAX if spec == "min" else _I64_MIN
            d = jnp.where(vvalid, data, jnp.asarray(sent, dtype=data.dtype))
            red = jax.ops.segment_min if spec == "min" else jax.ops.segment_max
            m = red(d, seg_unsorted, num_segments=nseg)
            c = jax.ops.segment_sum(
                vvalid.astype(jnp.int32), seg_unsorted, num_segments=nseg
            )
            out_vals.append((m[:num_groups], (c > 0)[:num_groups]))
            continue
        if spec == "any":
            d = jnp.take(data, first_row, axis=0)[:num_groups]
            if valid is None:
                v = got[:num_groups]
            else:
                v = (jnp.take(valid, first_row, axis=0) & got)[:num_groups]
            out_vals.append((d, v))
            continue
        raise ValueError(f"unknown agg spec {spec}")

    return out_keys, out_vals, got[:num_groups]


def _scalar_reduce_impl(vals, mask, specs: tuple):
    """Ungrouped aggregation over one batch (returns per-agg (0-d, valid)).
    Same specs as group_reduce. sum keeps a (sum, count) pair internally so
    partials merge correctly."""
    out = []
    for spec, val in zip(specs, vals):
        if spec == "count_star":
            # callers materialize the mask (a None mask would lose the
            # batch's row count here)
            c = jnp.sum(mask, dtype=jnp.int64)
            out.append((c, jnp.asarray(True)))
            continue
        data, valid = val
        vvalid = valid
        if mask is not None:
            vvalid = mask if valid is None else (mask & valid)
        n = data.shape[0]
        if vvalid is None:
            vvalid = jnp.ones(n, dtype=jnp.bool_)
        cnt = jnp.sum(vvalid, dtype=jnp.int64)
        if spec == "count":
            out.append((cnt, jnp.asarray(True)))
        elif spec == "sum":
            if jnp.issubdtype(data.dtype, jnp.integer):
                data = data.astype(jnp.int64)
            zero = jnp.zeros((), dtype=data.dtype)
            s = jnp.sum(jnp.where(vvalid, data, zero))
            out.append((s, cnt > 0))
        elif spec in ("min", "max"):
            d = data
            if jnp.issubdtype(d.dtype, jnp.floating):
                sent = jnp.inf if spec == "min" else -jnp.inf
            elif d.dtype == jnp.bool_:
                d = d.astype(jnp.int32)
                sent = 2 if spec == "min" else -1
            elif jnp.dtype(d.dtype).itemsize < 8:
                # same wrap hazard as group_reduce: narrow-lane casts
                # of the int64 sentinel flip its sign
                info = jnp.iinfo(d.dtype)
                sent = info.max if spec == "min" else info.min
            else:
                sent = _I64_MAX if spec == "min" else _I64_MIN
            dd = jnp.where(vvalid, d, jnp.asarray(sent, dtype=d.dtype))
            r = jnp.min(dd) if spec == "min" else jnp.max(dd)
            out.append((r, cnt > 0))
        else:
            raise ValueError(f"unknown scalar agg {spec}")
    return out


# Jitted entry points for operator-at-a-time execution (executor/local.py).
# The fused mesh executor calls the _impl functions directly instead —
# nesting jit inside a traced shard_map program defeats XLA fusion and
# adds per-call dispatch overhead.
group_ids = partial(jax.jit)(_group_ids_impl)
group_reduce = partial(jax.jit, static_argnames=("num_groups", "specs"))(
    _group_reduce_impl
)
scalar_reduce = partial(jax.jit, static_argnames=("specs",))(_scalar_reduce_impl)
