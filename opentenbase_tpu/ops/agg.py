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

from functools import partial

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


def _int_limbs(v, n_limbs: int):
    """Split an integer column into ``n_limbs`` radix-4096 limbs (f32
    arrays, each value < 4096; the top limb carries the sign via
    arithmetic shift). Exact recombination: sum_l limb_l << 12l."""
    v = v.astype(jnp.int64)
    out = []
    for l in range(n_limbs - 1):
        out.append(
            jnp.bitwise_and(
                jnp.right_shift(v, _LIMB_BITS * l), _LIMB_MASK
            ).astype(jnp.float32)
        )
    out.append(
        jnp.right_shift(v, _LIMB_BITS * (n_limbs - 1)).astype(jnp.float32)
    )
    return out


def _limbs_needed(dtype) -> int:
    return 4 if jnp.dtype(dtype).itemsize <= 4 else 8


def _mxu_group_reduce_impl(keys, vals, slot, num_groups: int, specs: tuple):
    """Grouped reduction on the MXU: one-hot(slot) matmuls instead of
    segment scatters — XLA's TPU scatter/sort are orders of magnitude
    slower than a systolic-array pass for cap-bounded grouping.

    Exactness: every accumulated quantity is integer-valued and
    limb-split (radix 4096); each 4096-row block's one-hot matmul sums
    each limb exactly in f32 (<= 2^24), per-block partials convert to
    int64 and sum exactly. Group keys are recovered by division
    (all rows in a slot share one key, or the collision flag is set):
    khat = sum(key)/count, checked per row via a gather-compare — which
    doubles as exact hash-collision detection.

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
    nb_needed = max(-(-n // _MXU_BLOCK), 1)
    sb = min(256, nb_needed)  # per-step f32 partials: [sb, cap, K]
    super_rows = sb * _MXU_BLOCK
    ns = max(-(-n // super_rows), 1)
    padded = ns * super_rows
    nb = padded // _MXU_BLOCK
    if padded != n:
        slot = jnp.pad(slot, (0, padded - n), constant_values=cap)

    def pad0(x):
        return jnp.pad(x, (0, padded - n)) if padded != n else x

    # Plan the accumulated lane layout without materializing anything:
    # raw columns ride through the scan, limbs are cut per superblock.
    # Entry kinds: ("limbs", raw_idx, nl) | ("f32", raw_idx).
    raw: list = []  # padded [ns, super_rows] arrays carried by the scan

    def add_raw(x):
        raw.append(pad0(x).reshape(ns, super_rows))
        return len(raw) - 1

    lanes: list = []  # lane plan, len = K
    key_slices: list = []  # (start, n_limbs) per key DATA column
    kvalid_idx: list = []  # lane index of the validity column (or None)
    for data, valid in keys:
        nl = _limbs_needed(data.dtype)
        d = data
        if valid is not None:
            d = jnp.where(valid, d, jnp.zeros((), d.dtype))
        key_slices.append((len(lanes), nl))
        ri = add_raw(d.astype(jnp.int64))
        lanes.extend(("limbs", ri, nl, l) for l in range(nl))
        if valid is not None:
            kvalid_idx.append(len(lanes))
            lanes.append(("f32", add_raw(valid.astype(jnp.float32)),
                          0, 0))
        else:
            kvalid_idx.append(None)
    val_slices: list = []  # per spec: (start, n_limbs, vstart) or None
    for spec, val in zip(specs, vals):
        if spec == "count_star":
            val_slices.append(None)
            continue
        data, valid = val
        vstart = None
        if valid is not None:
            vstart = len(lanes)
            lanes.append(("f32", add_raw(valid.astype(jnp.float32)),
                          0, 0))
        nl = 8  # sums are widened to int64
        d = data
        if valid is not None:
            d = jnp.where(valid, d, jnp.zeros((), d.dtype))
        val_slices.append((len(lanes), nl, vstart))
        ri = add_raw(d.astype(jnp.int64))
        lanes.extend(("limbs", ri, nl, l) for l in range(nl))
    cnt_idx = len(lanes)
    lanes.append(("ones", 0, 0, 0))

    K = len(lanes)
    slot_b = slot.reshape(ns, sb, _MXU_BLOCK)

    def step(acc, xs):
        sl = xs[0].reshape(sb, _MXU_BLOCK)
        cols = xs[1:]
        lane_arrays = []
        with scope("agg/limbs"):
            for kind, ri, nl, l in lanes:
                if kind == "ones":
                    lane_arrays.append(
                        jnp.ones((sb, _MXU_BLOCK), dtype=jnp.float32)
                    )
                elif kind == "f32":
                    lane_arrays.append(
                        cols[ri].reshape(sb, _MXU_BLOCK)
                    )
                else:  # one limb of an int64 raw column
                    v = cols[ri].reshape(sb, _MXU_BLOCK)
                    if l == nl - 1:
                        lane_arrays.append(
                            jnp.right_shift(
                                v, _LIMB_BITS * l
                            ).astype(jnp.float32)
                        )
                    else:
                        lane_arrays.append(
                            jnp.bitwise_and(
                                jnp.right_shift(v, _LIMB_BITS * l),
                                _LIMB_MASK,
                            ).astype(jnp.float32)
                        )
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
        return _mxu_recombine(
            totals, cnt_idx, key_slices, kvalid_idx, keys, slot, cap,
            pad0, specs, vals, val_slices,
        )


def _mxu_recombine(
    totals, cnt_idx, key_slices, kvalid_idx, keys, slot, cap, pad0,
    specs, vals, val_slices,
):
    """The limb totals of ``_mxu_group_reduce_impl`` back to keys, sums
    and the exact collision verdict."""
    cnt = totals[:, cnt_idx]
    got = cnt > 0
    safe_cnt = jnp.maximum(cnt, 1)

    def recombine(start, nl):
        acc = totals[:, start + nl - 1]
        for l in range(nl - 2, -1, -1):
            acc = jnp.left_shift(acc, _LIMB_BITS) + totals[:, start + l]
        return acc

    out_keys = []
    khats = []
    for (start, nl), vidx, (data, valid) in zip(
        key_slices, kvalid_idx, keys
    ):
        khat = recombine(start, nl) // safe_cnt
        khats.append((khat, data))
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
    for (khat, _data), (orig_data, orig_valid) in zip(khats, keys):
        d = orig_data
        if orig_valid is not None:
            d = jnp.where(orig_valid, d, jnp.zeros((), d.dtype))
        d = pad0(d).astype(jnp.int64)
        collision = collision | jnp.any(
            vis & (d != jnp.take(khat, gslot, axis=0))
        )

    out_vals = []
    for spec, val, sl in zip(specs, vals, val_slices):
        if spec == "count_star":
            out_vals.append((cnt.astype(jnp.int64), got))
            continue
        data, valid = val
        start, nl, vstart = sl
        if spec == "count":
            c = (
                totals[:, vstart]
                if vstart is not None
                else cnt
            )
            out_vals.append((c.astype(jnp.int64), got))
            continue
        # sum
        s = recombine(start, nl)
        nonnull = totals[:, vstart] if vstart is not None else cnt
        out_vals.append((s, (nonnull > 0) & got))

    ngroups = jnp.sum(got, dtype=jnp.int32)
    return out_keys, out_vals, got, ngroups, collision


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
