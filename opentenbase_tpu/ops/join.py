"""Equi-join kernels.

The reference's hash join (src/backend/executor/nodeHash.c +
nodeHashjoin.c) builds a bucketed hash table and probes tuple-at-a-time.
A serial-probe hash table is hostile to the TPU's vector units, so the
device formulation is sort + binary search:

1. ``encode_keys``: both sides' key tuples are jointly sorted and replaced
   by dense int32 *group ids* — equal tuples (across sides) get equal ids,
   NULLs get non-matching sentinels. This removes multi-key/width issues
   entirely; a single int32 id is what searchsorted sees.
2. ``match_counts``: sort build ids; per probe row, searchsorted left/right
   gives the contiguous match range [lo, hi). (= hash-bucket lookup, but
   branch-free and O(log n) vectorized.)
3. ``emit_pairs(out_size)``: expand ranges into (probe_idx, build_idx)
   pairs at a static padded size — the host rounds total match count up to
   a bucket, the two-pass sizing strategy of SURVEY.md §7.

Outer/semi/anti variants derive from the same counts: LEFT emits one
null-extended row when count==0; SEMI keeps probe rows with count>0; ANTI
keeps count==0. (RIGHT joins are planned as flipped LEFT joins.)

RADIX PATH. For the common single-integer-key join the encode step is
pure overhead: raw key values compare directly, so the sort-based
pipeline's two wide sorts (the joint encode sort over nb+np rows, then
the build sort) collapse into ONE build-side sort plus a bucket-padded
radix hash table — nodeHash.c's bucketed table, shapes kept static by
the bucket quantum (SURVEY §7 hard part #1):

1. ``build_radix_table``: hash build keys into P (power of two) radix
   partitions, sort the build side ONCE by (partition, key, row), and
   scatter rows into a [P, B] bucket-padded table (B slots per bucket,
   rounded to a quantum so repeat queries at similar scale reuse their
   compiled program). Occupancy overflow raises a flag — the caller
   grows B or falls back to the sort path; results are never wrong.
2. ``probe_radix_bounds``: per probe row, a vectorized binary search
   over its B-slot bucket (depth log2(B), vs log2(nb) for the full
   searchsorted) yields the same contiguous [lo, lo+count) match range
   contract as ``match_counts`` — ``emit_pairs`` is shared verbatim, so
   radix and sort-merge outputs are byte-identical by construction.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

# plain ints, not jnp constants: module import must never dispatch to a
# backend (an eager jnp op here would initialise JAX — and claim the
# chip — at import); they become traced int32 inside the jitted fns
_NO_MATCH_A = -2  # build-side NULL key
_NO_MATCH_B = -3  # probe-side NULL key


def JOIN_MODE() -> str:
    """Host-executor join formulation override: 'auto' (radix for
    eligible single-int-key shapes), 'radix', or 'sortmerge'. The fused
    device path takes the same choice from the ``join_mode`` GUC; the
    host executor has no session handle, so the env var is the knob
    (tests force both paths through it)."""
    import os

    return os.environ.get("OTB_JOIN_MODE", "auto").lower()


@partial(jax.jit)
def encode_keys(build_keys, probe_keys, build_mask, probe_mask):
    """Jointly encode key tuples as dense int32 ids.

    build_keys/probe_keys: lists of (data, valid_or_None), equal arity and
    compatible dtypes pairwise. masks: visible-row masks or None.
    Returns (build_ids, probe_ids) where invisible/NULL rows get distinct
    negative sentinels that can never match.
    """
    nb = build_keys[0][0].shape[0]
    npr = probe_keys[0][0].shape[0]
    from opentenbase_tpu.ops.agg import float_key_parts

    parts = []
    for (bd, bv), (pd, pv) in zip(build_keys, probe_keys):
        if jnp.issubdtype(bd.dtype, jnp.floating) or jnp.issubdtype(
            pd.dtype, jnp.floating
        ):
            # exact float views without 64-bit bitcasts (TPU-safe)
            target = jnp.promote_types(bd.dtype, pd.dtype)
            bparts = float_key_parts(bd.astype(target))
            pparts = float_key_parts(pd.astype(target))
        else:
            bparts, pparts = [bd], [pd]
        if bv is None and pv is None:
            v = None
        else:
            bvv = jnp.ones(nb, jnp.bool_) if bv is None else bv
            pvv = jnp.ones(npr, jnp.bool_) if pv is None else pv
            v = jnp.concatenate([bvv, pvv])
        for bpart, ppart in zip(bparts, pparts):
            d = jnp.concatenate(
                [bpart.astype(jnp.int64), ppart.astype(jnp.int64)]
            )
            parts.append((d, v))

    n = nb + npr
    perm = jnp.arange(n, dtype=jnp.int32)
    for d, v in reversed(parts):
        order = jnp.argsort(jnp.take(d, perm, axis=0), stable=True)
        perm = jnp.take(perm, order, axis=0)
    boundary = jnp.zeros(n, dtype=jnp.bool_).at[0].set(True)
    for d, v in parts:
        ds = jnp.take(d, perm, axis=0)
        boundary = boundary | jnp.concatenate(
            [jnp.ones(1, jnp.bool_), ds[1:] != ds[:-1]]
        )
    seg = jnp.cumsum(boundary.astype(jnp.int32)) - 1
    ids = jnp.zeros(n, dtype=jnp.int32).at[perm].set(seg)

    build_ids, probe_ids = ids[:nb], ids[nb:]
    # NULL in any key column -> never matches
    bnull = jnp.zeros(nb, jnp.bool_)
    pnull = jnp.zeros(npr, jnp.bool_)
    for (bd, bv), (pd, pv) in zip(build_keys, probe_keys):
        if bv is not None:
            bnull = bnull | ~bv
        if pv is not None:
            pnull = pnull | ~pv
    if build_mask is not None:
        bnull = bnull | ~build_mask
    if probe_mask is not None:
        pnull = pnull | ~probe_mask
    build_ids = jnp.where(bnull, _NO_MATCH_A, build_ids)
    probe_ids = jnp.where(pnull, _NO_MATCH_B, probe_ids)
    return build_ids, probe_ids


@partial(jax.jit)
def match_counts(build_ids, probe_ids):
    """Sort build ids; per probe row compute [lo, hi) match range.
    Returns (build_order, lo, counts, total)."""
    build_order = jnp.argsort(build_ids, stable=True).astype(jnp.int32)
    sorted_ids = jnp.take(build_ids, build_order, axis=0)
    lo = jnp.searchsorted(sorted_ids, probe_ids, side="left").astype(jnp.int32)
    hi = jnp.searchsorted(sorted_ids, probe_ids, side="right").astype(jnp.int32)
    counts = hi - lo
    total = jnp.sum(counts.astype(jnp.int64))
    return build_order, lo, counts, total


@partial(jax.jit, static_argnames=("out_size", "outer"))
def emit_pairs(build_order, lo, counts, out_size: int, outer: bool = False):
    """Expand match ranges to row-index pairs at static ``out_size``.

    Returns (probe_idx, build_idx, matched, valid):
      - probe_idx/build_idx: gather indices into the original (uncompacted)
        probe/build batches; build_idx is 0 where matched is False.
      - matched[j]: the pair is a real key match (False for the
        null-extended rows LEFT join emits when outer=True).
      - valid[j]: lane j is a real output row (False = padding).
    """
    # static empty edges: jnp.take from a zero-length axis raises, and
    # padded production batches are never empty — but the radix table's
    # contract tests (and any future caller) deserve the honest answer
    if counts.shape[0] == 0 or build_order.shape[0] == 0:
        z32 = jnp.zeros(out_size, jnp.int32)
        zb = jnp.zeros(out_size, jnp.bool_)
        if counts.shape[0] > 0 and outer:
            # no build rows: every probe row still null-extends once
            probe_idx = jnp.clip(
                jnp.arange(out_size, dtype=jnp.int32),
                0, counts.shape[0] - 1,
            )
            valid = jnp.arange(out_size) < counts.shape[0]
            return probe_idx, z32, zb, valid
        return z32, z32, zb, zb
    eff = jnp.maximum(counts, 1) if outer else counts
    # int64 prefix sums: an int32 cumsum wraps negative past 2^31
    # emitted pairs, silently truncating the join output (match_counts
    # already totals in int64 for the same reason)
    eff = eff.astype(jnp.int64)
    offsets = jnp.cumsum(eff) - eff  # exclusive prefix sum
    total = offsets[-1] + eff[-1] if counts.shape[0] > 0 else jnp.int64(0)

    j = jnp.arange(out_size, dtype=jnp.int64)
    # probe row for output lane j: last i with offsets[i] <= j
    probe_idx = (
        jnp.searchsorted(offsets, j, side="right").astype(jnp.int32) - 1
    )
    probe_idx = jnp.clip(probe_idx, 0, counts.shape[0] - 1)
    k = j - jnp.take(offsets, probe_idx, axis=0)
    cnt_j = jnp.take(counts, probe_idx, axis=0).astype(jnp.int64)
    matched = k < cnt_j
    pos = jnp.take(lo, probe_idx, axis=0) + jnp.minimum(
        k, jnp.maximum(cnt_j - 1, 0)
    ).astype(jnp.int32)
    pos = jnp.clip(pos, 0, build_order.shape[0] - 1)
    build_idx = jnp.take(build_order, pos, axis=0)
    build_idx = jnp.where(matched, build_idx, 0)
    valid = j < total
    return probe_idx, build_idx, matched, valid


# ---------------------------------------------------------------------------
# Bucket-padded radix hash join (single integer-family key fast path)
# ---------------------------------------------------------------------------


def radix_parts(keys, partitions: int):
    """Radix partition of each key: murmur-mixed before masking so dense
    AND strided key spaces both spread evenly over the power-of-two
    partition count (nodeHash.c buckets via ExecHashGetHashValue)."""
    from opentenbase_tpu.utils.hashing import hash32_jnp

    h = hash32_jnp(keys)
    return (h & jnp.uint32(partitions - 1)).astype(jnp.int32)


@partial(jax.jit, static_argnames=("partitions", "bucket"))
def build_radix_table(build_key, build_real, partitions: int, bucket: int):
    """Bucket-padded hash table over the build side.

    ``build_key``: integer-family key column (any int dtype);
    ``build_real``: row participates (visible AND key non-NULL).
    Returns (tkeys [P*B+1] int64, tvalid [P*B+1] bool,
    tbidx [P*B+1] int32, dup 0-d bool, overflow 0-d bool):

    - slot p*B+r holds the r-th smallest real key of partition p (ONE
      build-side sort by (partition, key, row) fills ranks in key order,
      ties in original row order — match emission order is identical to
      the stable sort-merge path);
    - the trailing slot is a dump for dead/overflowed rows;
    - ``dup``: two real build rows share a key (exact — equal keys land
      adjacent in the sort);
    - ``overflow``: some partition holds more than ``bucket`` real rows;
      results would drop matches, so the caller MUST retry (bigger
      bucket / sort path) when it fires. Empty slots are marked invalid
      rather than sentinel-valued, so the full int64 key domain is
      joinable."""
    nb = build_key.shape[0]
    P, B = partitions, bucket
    key64 = build_key.astype(jnp.int64)
    part = jnp.where(
        build_real, radix_parts(key64, P), jnp.int32(P)
    )  # dead rows route past every real partition
    idx = jnp.arange(nb, dtype=jnp.int32)
    spart, skey, sidx = jax.lax.sort(
        (part, key64, idx), num_keys=3, is_stable=False
    )
    sreal = spart < P
    # rank within partition = position - partition run start
    start = jnp.searchsorted(spart, spart, side="left").astype(jnp.int32)
    rank = idx - start
    if nb > 1:
        dup = jnp.any(
            sreal[1:] & sreal[:-1]
            & (spart[1:] == spart[:-1]) & (skey[1:] == skey[:-1])
        )
    else:
        dup = jnp.asarray(False)
    overflow = jnp.any(sreal & (rank >= B))
    slot_ok = sreal & (rank < B)
    pos = jnp.where(slot_ok, spart * B + rank, jnp.int32(P * B))
    tkeys = jnp.zeros(P * B + 1, jnp.int64).at[pos].set(skey)
    tvalid = jnp.zeros(P * B + 1, jnp.bool_).at[pos].set(slot_ok)
    tbidx = jnp.zeros(P * B + 1, jnp.int32).at[pos].set(sidx)
    return tkeys, tvalid, tbidx, dup, overflow


def _bucket_bound(tkeys, tvalid, base, key, bucket: int, side: str):
    """Vectorized in-bucket binary search: per probe row, the first slot
    offset in [0, bucket] whose key is >= (side='left') / > ('right')
    the probe key. Invalid (padding) slots compare as +infinity — they
    only ever trail the real slots, so ordering stays total. Depth is
    log2(bucket) gather rounds instead of log2(nb)."""
    n = key.shape[0]
    lo = jnp.zeros(n, jnp.int32)
    hi = jnp.full(n, bucket, jnp.int32)
    for _ in range(max(int(bucket).bit_length(), 1)):
        active = lo < hi
        mid = (lo + hi) >> 1
        at = base + mid
        v = jnp.take(tkeys, at)
        ok = jnp.take(tvalid, at)
        go = ok & ((v < key) if side == "left" else (v <= key))
        lo = jnp.where(active & go, mid + 1, lo)
        hi = jnp.where(active & ~go, mid, hi)
    return lo


@partial(jax.jit, static_argnames=("partitions", "bucket"))
def probe_radix_bounds(
    tkeys, tvalid, probe_key, probe_real, partitions: int, bucket: int
):
    """Per probe row, the contiguous table range [lo, lo+count) of
    matching build slots — the same contract ``match_counts`` returns
    over the sorted build, so ``emit_pairs`` consumes either verbatim."""
    P, B = partitions, bucket
    key64 = probe_key.astype(jnp.int64)
    base = radix_parts(key64, P) * B
    lo_rel = _bucket_bound(tkeys, tvalid, base, key64, B, "left")
    hi_rel = _bucket_bound(tkeys, tvalid, base, key64, B, "right")
    counts = jnp.where(probe_real, hi_rel - lo_rel, 0)
    return base + lo_rel, counts


@partial(jax.jit, static_argnames=("partitions", "bucket"))
def probe_radix_first(
    tkeys, tvalid, tbidx, probe_key, probe_real, partitions: int,
    bucket: int,
):
    """Existence probe for a unique build side: (matched [np] bool,
    bidx [np] int32 position into the TABLE's original build rows).
    One lower-bound search + two gathers — the fused DAG's radix join
    primitive (its inner joins verify build uniqueness via the dup
    flag, so the first match is the only match)."""
    P, B = partitions, bucket
    key64 = probe_key.astype(jnp.int64)
    base = radix_parts(key64, P) * B
    lo_rel = _bucket_bound(tkeys, tvalid, base, key64, B, "left")
    at = jnp.minimum(base + lo_rel, P * B)  # lo_rel==B: bucket full miss
    hit = (
        (lo_rel < B)
        & jnp.take(tvalid, at)
        & (jnp.take(tkeys, at) == key64)
        & probe_real
    )
    return hit, jnp.take(tbidx, at)


def radix_match_counts(
    build_key, build_real, probe_key, probe_real, partitions: int,
    bucket: int,
):
    """Radix counterpart of ``encode_keys`` + ``match_counts`` for a
    single integer-family key: returns (build_order, lo, counts, total,
    overflow). ``build_order``/``lo``/``counts`` feed ``emit_pairs``
    unchanged; ``overflow`` True means a bucket overfilled and the
    result is UNUSABLE — retry with a bigger bucket or the sort path."""
    tkeys, tvalid, tbidx, _dup, overflow = build_radix_table(
        build_key, build_real, partitions, bucket
    )
    lo, counts = probe_radix_bounds(
        tkeys, tvalid, probe_key, probe_real, partitions, bucket
    )
    total = jnp.sum(counts.astype(jnp.int64))
    return tbidx, lo, counts, total, overflow
