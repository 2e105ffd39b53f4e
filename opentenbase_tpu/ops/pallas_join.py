"""Pallas TPU kernel: bucket-padded radix hash-join probe.

The serial half of a hash join — walking a bucket per probe tuple
(nodeHashjoin.c ExecScanHashBucket) — is hostile to the TPU's vector
units: Mosaic has no per-lane gather from an arbitrary VMEM table. This
kernel recasts the bucket walk as an MXU one-hot contraction, the same
trick the engine's grouped aggregation plays (ops/agg.py superblock):

- the (small) build side is packed OUTSIDE the kernel into a
  bucket-padded radix table (ops/join.build_radix_table): P power-of-two
  partitions x B quantum-padded slots, so the table shape is static
  across batches;
- probe rows stream HBM -> VMEM in blocks on the LANE axis; each block
  builds a one-hot [P, block] partition-selector and ONE ``jnp.dot`` of
  the resident table against it gathers every slot of every probe
  row's bucket — a gather-free bucket lookup at MXU rate;
- exactness: Pallas TPU compute is f32, so 64-bit keys ride as
  radix-4096 limb planes (12 bits per limb, 6 limbs — each limb value
  < 2^12 is trivially f32-exact, and a one-hot row selects exactly one
  partition, so the contraction result IS the limb, not a rounded sum).
  A slot matches iff every limb plane matches. Build row indices stay
  below 2^24 (the eligibility gate enforces it), so they ride a single
  exact f32 plane;
- build words: a 32-bit build column the joined row reads can ride the
  contractions that gather the bucket (``pack_words``: two exact 16-bit
  halves of its bit pattern; the first word in the limb group's two pad
  rows, which are contracted anyway, every other word and a 0/1
  validity plane where a word is nullable stacked under the index
  plane), so the probe returns the column at the probe's rows and no
  XLA gather at the probe's width reads it afterwards
  (executor/fused_dag._build_join). The kernel's time follows the rows
  it contracts (the star cell's ``dates`` probe: 481.8 ms a statement
  for 400 rows, 568.5 with a word's 80 rows under the index plane, on
  TPU v5e), so the first word costs no row.

The XLA probe (ops/join.probe_radix_first) remains the reference
semantics; this kernel is the device fast path for small dimension
tables (P <= 4096 keeps the one-hot block in VMEM), and ``eligible``
is also the DAG's shape rule: under ``join_mode = auto`` a radix table
is built only where this kernel could probe it, every wider build
takes sort-merge (executor/fused_dag._lookup_radix). Tested in
interpreter mode on CPU (tests/test_join_device.py) and compiled by
Mosaic on the chip (chip_smoke.py's radix join over a small build
side). The kernel is compiled with the rest of its DAG program, so a
lowering or runtime failure there FAILS THE PROGRAM: the statement is
re-answered by the host executor as a counted, logged fused->host
demotion (engine._try_fused_inner; ``demoted`` in pg_stat_fused,
which chip_smoke.py and benchmarks/run.py refuse). There is no quiet
switch to the XLA probe.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from opentenbase_tpu.obs.trace import scope

LIMB_BITS = 12
LIMB_MASK = (1 << LIMB_BITS) - 1
LIMBS = 6  # 6 x 12 = 72 bits >= the full int64 key domain
# limb planes ride the SUBLANE axis, padded to one f32 tile (8 rows) so
# every bucket slot's limb group is a tile-aligned slice; the two pad
# rows are zero on both sides and always compare equal
LIMB_ROWS = 8
BLOCK = 256  # probe rows (lanes) per grid step
MAX_PARTITIONS = 4096  # one-hot sublane bound (VMEM) — dimension tables
MAX_BUILD = 1 << 24  # build row indices must be f32-exact
# P=4096: the resident [B*8, P] limb table (double-buffered) plus the
# [P, BLOCK] one-hot and its iota need ~22 MiB — over the v5e's 16 MiB
# default scoped VMEM, well inside its 128 MiB physical VMEM
VMEM_LIMIT_BYTES = 64 << 20


def eligible(nb: int, partitions: int, bucket: int) -> bool:
    """Static gate: table shapes this kernel can hold in VMEM with
    exact f32 index planes."""
    return (
        0 < nb < MAX_BUILD
        and partitions <= MAX_PARTITIONS
        and bucket * LIMBS <= 512
    )


def split_limbs(key64):
    """[n] int64 -> [LIMB_ROWS, n] f32 radix-4096 limb planes (equality
    on all limbs == equality on the key; each limb < 2^12 is f32-exact;
    rows past LIMBS are zero padding)."""
    u = key64.astype(jnp.int64).astype(jnp.uint64)
    zero = jnp.zeros(u.shape, jnp.float32)
    return jnp.stack(
        [
            ((u >> jnp.uint64(LIMB_BITS * i)) & jnp.uint64(LIMB_MASK))
            .astype(jnp.float32)
            for i in range(LIMBS)
        ]
        + [zero] * (LIMB_ROWS - LIMBS),
        axis=0,
    )


def pack_table(tkeys, tvalid, tbidx, partitions: int, bucket: int):
    """ops/join radix table -> the kernel's f32 planes, partitions on
    the LANE axis: (limbs [B*LIMB_ROWS, P], valid [B, P], bidx [B, P])."""
    P, B = partitions, bucket
    limbs = (
        split_limbs(tkeys[: P * B])  # [LIMB_ROWS, P*B]
        .reshape(LIMB_ROWS, P, B)
        .transpose(2, 0, 1)
        .reshape(B * LIMB_ROWS, P)
    )
    valid = tvalid[: P * B].astype(jnp.float32).reshape(P, B).T
    bidx = tbidx[: P * B].astype(jnp.float32).reshape(P, B).T
    return limbs, valid, bidx


def pack_words(tlimbs, tbidxf, words, partitions: int, bucket: int):
    """(tlimbs, tbidx) of ``pack_table`` with the build words in: each
    word's low and high 16-bit halves of its ``uint32`` bit pattern
    (each half is f32-exact) as planes [B, P], the first word's in the
    limb group's two pad rows, every other word's and then one 0/1
    plane per nullable word stacked under the row index, in the order
    ``words`` gives. ``words``: ((data [P*B+1] 32-bit, valid [P*B+1]
    bool or None), ..) laid out like ``tbidx``, one value a slot."""
    P, B = partitions, bucket

    def plane(x):
        return x[: P * B].astype(jnp.float32).reshape(P, B).T

    halves, valids = [], []
    for d, v in words:
        u = jax.lax.bitcast_convert_type(d, jnp.uint32)
        halves += [plane(u & jnp.uint32(0xFFFF)), plane(u >> jnp.uint32(16))]
        if v is not None:
            valids.append(plane(v))
    if halves:
        tlimbs = (
            tlimbs.reshape(B, LIMB_ROWS, P)
            .at[:, LIMBS:].set(jnp.stack(halves[:2], axis=1))
            .reshape(B * LIMB_ROWS, P)
        )
    under = halves[2:] + valids
    if under:
        tbidxf = jnp.concatenate([tbidxf] + under, axis=0)
    return tlimbs, tbidxf


def build_probe(
    partitions: int, bucket: int, block: int = BLOCK,
    interpret: bool = False, words: int = 0, valids: int = 0,
):
    """fn(tlimbs [B*R, P] f32, tvalid [B, P] f32, tbidx [B*(1+K), P]
    f32, part [1, n] i32, plimbs [R, n] f32) -> (matched [n] f32,
    bidx [n] f32, *planes [n] i32), R = LIMB_ROWS,
    K = 2*(words-1)+valids (``valids`` alone without words).

    ``words`` build words and ``valids`` validity planes ride the
    contractions that gather the bucket (``pack_words``): the first
    word's halves in each slot's limb group's two pad rows (the key
    then compares the group's ``LIMBS`` rows alone), the rest under the
    row index; the slot that hits selects them as it selects the row
    index. They come back as ``words`` int32 words (the halves joined
    into the bit pattern) and then ``valids`` 0/1 planes; on a row that
    matched nothing each reads 0. With neither the kernel is the plain
    probe's.

    Probe rows ride the LANE axis end to end (every operand and both
    outputs are lane-dense 2-D blocks — Mosaic has no cheap 1-D vectors
    or single-column extracts). ``part`` is the probe row's radix
    partition (ops/join.radix_parts, computed outside — it needs the
    murmur mix, which wants 64-bit integer ops); NULL/dead probe rows
    carry part = -1 and match nothing."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    P, B = partitions, bucket
    R = LIMB_ROWS
    K = 2 * max(words - 1, 0) + valids  # planes under the row index
    exact = jax.lax.Precision.HIGHEST  # one-hot gathers must not round

    def kernel(tl_ref, tv_ref, ti_ref, part_ref, pl_ref, m_ref, b_ref,
               *w_refs):
        sub = jax.lax.broadcasted_iota(jnp.int32, (P, block), 0)
        onehot = (sub == part_ref[...]).astype(jnp.float32)  # [P, block]
        # ONE MXU contraction per plane gathers the whole bucket for the
        # block: column j of each result is probe row j's bucket
        bucket_l = jnp.dot(
            tl_ref[...], onehot, precision=exact,
            preferred_element_type=jnp.float32,
        )  # [B*R, block]
        bucket_v = jnp.dot(
            tv_ref[...], onehot, precision=exact,
            preferred_element_type=jnp.float32,
        )  # [B, block]
        bucket_i = jnp.dot(
            ti_ref[...], onehot, precision=exact,
            preferred_element_type=jnp.float32,
        )  # [B*(1+K), block]
        plimbs = pl_ref[...]  # [R, block]
        matched = jnp.zeros((1, block), jnp.float32)
        bidx = jnp.zeros((1, block), jnp.float32)
        picked = [
            jnp.zeros((1, block), jnp.float32)
            for _ in range(2 * words + valids)
        ]
        if words:  # the pad rows carry the first word, not the key
            pad = jax.lax.broadcasted_iota(jnp.int32, (R, block), 0) >= LIMBS
        for b in range(B):
            group = bucket_l[b * R:(b + 1) * R, :]
            eq = group == plimbs
            if words:
                eq = eq | pad
            eq = eq.astype(jnp.float32)
            hit = jnp.min(eq, axis=0, keepdims=True) * (
                bucket_v[b:b + 1, :] > 0.5
            ).astype(jnp.float32)
            # build keys are unique (the dup flag fired otherwise), so
            # at most one slot hits: max keeps the result exact even on
            # the flagged-and-discarded duplicate run
            matched = jnp.maximum(matched, hit)
            bidx = jnp.maximum(bidx, hit * bucket_i[b:b + 1, :])
            rows = [
                group[LIMBS:LIMBS + 1, :], group[LIMBS + 1:LIMBS + 2, :]
            ] if words else []
            rows += [
                bucket_i[B * (1 + k) + b:B * (1 + k) + b + 1, :]
                for k in range(K)
            ]
            for k, row in enumerate(rows):
                picked[k] = jnp.maximum(picked[k], hit * row)
        m_ref[...] = matched
        b_ref[...] = bidx
        for j in range(words):
            lo = picked[2 * j].astype(jnp.int32)
            hi = picked[2 * j + 1].astype(jnp.int32)
            w_refs[j][...] = (hi << 16) | lo
        for j in range(valids):
            w_refs[words + j][...] = picked[2 * words + j].astype(jnp.int32)

    def run(tlimbs, tvalid, tbidx, part, plimbs):
        n = part.shape[1]
        grid = max((n + block - 1) // block, 1)
        padded = grid * block
        if padded != n:
            part = jnp.pad(
                part, ((0, 0), (0, padded - n)), constant_values=-1
            )
            plimbs = jnp.pad(plimbs, ((0, 0), (0, padded - n)))
        # inside a shard_map the outputs vary over the mesh axes the
        # probe rows vary over (check_vma needs it said)
        vma = jax.typeof(part).vma
        # the engine runs in global x64 mode; this kernel is pure
        # f32/i32 (see ops/pallas_scan.py for the Mosaic i64-scalar
        # rationale)
        planes = words + valids
        with jax.enable_x64(False), scope("kernel"):
            matched, bidx, *got = pl.pallas_call(
                kernel,
                grid=(grid,),
                in_specs=[
                    pl.BlockSpec((B * R, P), lambda i: (0, 0)),
                    pl.BlockSpec((B, P), lambda i: (0, 0)),
                    pl.BlockSpec((B * (1 + K), P), lambda i: (0, 0)),
                    pl.BlockSpec((1, block), lambda i: (0, i)),
                    pl.BlockSpec((R, block), lambda i: (0, i)),
                ],
                out_specs=[
                    pl.BlockSpec((1, block), lambda i: (0, i)),
                    pl.BlockSpec((1, block), lambda i: (0, i)),
                ] + [
                    pl.BlockSpec((1, block), lambda i: (0, i))
                ] * planes,
                out_shape=[
                    jax.ShapeDtypeStruct((1, padded), jnp.float32, vma=vma),
                    jax.ShapeDtypeStruct((1, padded), jnp.float32, vma=vma),
                ] + [
                    jax.ShapeDtypeStruct((1, padded), jnp.int32, vma=vma)
                ] * planes,
                compiler_params=pltpu.CompilerParams(
                    vmem_limit_bytes=VMEM_LIMIT_BYTES
                ),
                interpret=interpret,
            )(tlimbs, tvalid, tbidx, part, plimbs)
        return (matched[0, :n], bidx[0, :n], *(g[0, :n] for g in got))

    return run


def probe_radix_pallas(
    tkeys, tvalid, tbidx, probe_key, probe_real, partitions: int,
    bucket: int, interpret: bool = False, words=(),
):
    """ops/join.probe_radix_first over the same radix table, probing
    through the Pallas kernel: (matched [np] bool, bidx [np] int32,
    picked). ``words``: build words laid out like ``tbidx``, each
    (data [P*B+1] 32-bit, valid [P*B+1] bool or None); ``picked`` holds
    each at the probe's rows as (int32 [np], bool [np] or None), bit
    for bit ``take(data, bidx)`` on a matched row (0 on the others)."""
    from opentenbase_tpu.ops.join import radix_parts

    key64 = probe_key.astype(jnp.int64)
    part = jnp.where(
        probe_real, radix_parts(key64, partitions), jnp.int32(-1)
    ).astype(jnp.int32)[None, :]
    tlimbs, tvalidf, tbidxf = pack_table(
        tkeys, tvalid, tbidx, partitions, bucket
    )
    if words:
        tlimbs, tbidxf = pack_words(
            tlimbs, tbidxf, words, partitions, bucket
        )
    nw = len(words)
    nv = sum(v is not None for _d, v in words)
    matched, bidx, *got = build_probe(
        partitions, bucket, interpret=interpret, words=nw, valids=nv,
    )(tlimbs, tvalidf, tbidxf, part, split_limbs(key64))
    valid = iter(got[nw:])
    picked = tuple(
        (w, None if v is None else next(valid) > 0)
        for w, (_d, v) in zip(got[:nw], words)
    )
    return matched > 0.5, bidx.astype(jnp.int32), picked
