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
  exact f32 plane.

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


def build_probe(
    partitions: int, bucket: int, block: int = BLOCK,
    interpret: bool = False,
):
    """fn(tlimbs [B*R, P] f32, tvalid [B, P] f32, tbidx [B, P] f32,
    part [1, n] i32, plimbs [R, n] f32) -> (matched [n] f32,
    bidx [n] f32), R = LIMB_ROWS.

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
    exact = jax.lax.Precision.HIGHEST  # one-hot gathers must not round

    def kernel(tl_ref, tv_ref, ti_ref, part_ref, pl_ref, m_ref, b_ref):
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
        )  # [B, block]
        plimbs = pl_ref[...]  # [R, block]
        matched = jnp.zeros((1, block), jnp.float32)
        bidx = jnp.zeros((1, block), jnp.float32)
        for b in range(B):
            eq = (bucket_l[b * R:(b + 1) * R, :] == plimbs).astype(
                jnp.float32
            )
            hit = jnp.min(eq, axis=0, keepdims=True) * (
                bucket_v[b:b + 1, :] > 0.5
            ).astype(jnp.float32)
            # build keys are unique (the dup flag fired otherwise), so
            # at most one slot hits: max keeps the result exact even on
            # the flagged-and-discarded duplicate run
            matched = jnp.maximum(matched, hit)
            bidx = jnp.maximum(bidx, hit * bucket_i[b:b + 1, :])
        m_ref[...] = matched
        b_ref[...] = bidx

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
        with jax.enable_x64(False), scope("kernel"):
            matched, bidx = pl.pallas_call(
                kernel,
                grid=(grid,),
                in_specs=[
                    pl.BlockSpec((B * R, P), lambda i: (0, 0)),
                    pl.BlockSpec((B, P), lambda i: (0, 0)),
                    pl.BlockSpec((B, P), lambda i: (0, 0)),
                    pl.BlockSpec((1, block), lambda i: (0, i)),
                    pl.BlockSpec((R, block), lambda i: (0, i)),
                ],
                out_specs=[
                    pl.BlockSpec((1, block), lambda i: (0, i)),
                    pl.BlockSpec((1, block), lambda i: (0, i)),
                ],
                out_shape=[
                    jax.ShapeDtypeStruct((1, padded), jnp.float32, vma=vma),
                    jax.ShapeDtypeStruct((1, padded), jnp.float32, vma=vma),
                ],
                compiler_params=pltpu.CompilerParams(
                    vmem_limit_bytes=VMEM_LIMIT_BYTES
                ),
                interpret=interpret,
            )(tlimbs, tvalid, tbidx, part, plimbs)
        return matched[0, :n], bidx[0, :n]

    return run


def probe_radix_pallas(
    tkeys, tvalid, tbidx, probe_key, probe_real, partitions: int,
    bucket: int, interpret: bool = False,
):
    """Drop-in for ops/join.probe_radix_first over the same radix table,
    probing through the Pallas kernel. Same contract:
    (matched [np] bool, bidx [np] int32)."""
    from opentenbase_tpu.ops.join import radix_parts

    key64 = probe_key.astype(jnp.int64)
    part = jnp.where(
        probe_real, radix_parts(key64, partitions), jnp.int32(-1)
    ).astype(jnp.int32)[None, :]
    tlimbs, tvalidf, tbidxf = pack_table(
        tkeys, tvalid, tbidx, partitions, bucket
    )
    matched, bidx = build_probe(
        partitions, bucket, interpret=interpret
    )(tlimbs, tvalidf, tbidxf, part, split_limbs(key64))
    return matched > 0.5, bidx.astype(jnp.int32)
