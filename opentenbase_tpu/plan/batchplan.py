"""Spill-aware batch planner: size device join/scan batches against HBM.

SURVEY §7 ranks "dynamic shapes on XLA" (#1) and "spill/memory — HBM is
small" (#5) as the hard parts, and the reference solves the second with
work_mem batching: a hash join whose build side outgrows its memory
budget splits into batches and probes in passes
(src/backend/executor/nodeHash.c ExecHashIncreaseNumBatches,
ExecChooseHashTableSize). This module is the device-side analog: every
data-dependent device allocation — radix hash-join tables, exchange
buffers, streamed probe windows — is sized HERE, from estimated row
widths × cardinalities against one HBM budget, BEFORE any program
traces. Oversized build sides become multi-pass probes; oversized
anything-else falls back to the host path loudly instead of crashing
the TPU worker (an in-process OOM on the remote chip is unrecoverable).

The budget is the ``device_memory_limit`` GUC (bytes; 0 = unset) or,
without it, the constant below for that op.
"""

from __future__ import annotations

from dataclasses import dataclass

# An exchange holds, a device, the bucketing sort's operands going in and
# coming out, the (D, cap) slab it sends and the (D, cap) result it
# receives (exchange_bytes). Beyond its budget the DAG bails to the host
# path instead of crashing the TPU worker on HBM exhaustion (observed at
# TPC-H SF10 Q3 on one 16GB v5e). Radix tables borrow the same budget.
DEFAULT_EXCHANGE_BUDGET = 4_000_000_000
DEFAULT_WINDOW_BUDGET = 6_000_000_000
# a radix hash table is transient (freed after its join): allow it a
# fraction of the budget so probe/build residency still fits beside it
RADIX_TABLE_FRACTION = 4
RADIX_MAX_PASSES = 8
RADIX_TARGET_LOAD = 16  # average real keys per bucket the sizing aims at
RADIX_BUCKET_QUANTUM = 8  # bucket slots round up to a multiple of this


def next_pow2(n: int, floor: int = 1) -> int:
    p = max(int(floor), 1)
    while p < n:
        p <<= 1
    return p


def resolve_budget(device_memory_limit: int, default: int) -> int:
    """One budget in bytes: the GUC when set, else the op's constant."""
    if device_memory_limit and device_memory_limit > 0:
        return int(device_memory_limit)
    return int(default)


@dataclass(frozen=True)
class RadixPlan:
    """Static shape parameters for one bucket-padded radix hash join.

    ``partitions`` (power of two) × ``bucket`` slots is one pass's table;
    ``passes`` > 1 splits the build side into chunks probed one after
    another (multi-pass probe — nodeHash.c's nbatch, device-style:
    same probe residency, one transient table per pass)."""

    partitions: int
    bucket: int
    passes: int
    table_bytes: int  # per-pass footprint (keys + validity + indices)

    @property
    def slots(self) -> int:
        return self.partitions * self.bucket


def plan_radix_join(
    build_rows: int,
    probe_rows: int,
    budget: int,
    key_bytes: int = 8,
    idx_bytes: int = 4,
    quantum: int = RADIX_BUCKET_QUANTUM,
    target_load: int = RADIX_TARGET_LOAD,
    max_passes: int = RADIX_MAX_PASSES,
):
    """Size the radix table for a build side of ``build_rows`` (padded
    device width) against ``budget`` bytes. Returns a RadixPlan, or None
    when even ``max_passes`` passes can't fit a table — the caller keeps
    the sort-merge formulation (O(1) extra memory) instead.

    The bucket quantum keeps shapes static across batches: occupancy
    moves with the data, the table shape only moves in quantum steps, so
    repeat queries at similar scale reuse their compiled program."""
    if build_rows <= 0:
        return None
    slot_bytes = key_bytes + idx_bytes + 1  # +1: slot-validity plane
    cap = max(budget // RADIX_TABLE_FRACTION, 1)
    for passes in range(1, max_passes + 1):
        chunk = -(-build_rows // passes)
        partitions = next_pow2(max(chunk // target_load, 1))
        # headroom over the average load follows the balls-in-bins max
        # (~avg + sqrt(2 avg ln P)): avg + 4*sqrt(avg) + 8 keeps the
        # overflow flag a cold path for uniformly hashed keys at every
        # scale, rounded up to the quantum for shape reuse
        load = max(-(-chunk // partitions), 1)
        bucket = -(-int(load + 4 * load**0.5 + 8) // quantum) * quantum
        table_bytes = (partitions * bucket + 1) * slot_bytes
        if table_bytes <= cap:
            return RadixPlan(partitions, bucket, passes, table_bytes)
    return None


def exchange_row_bytes(schema) -> int:
    """Estimated wire bytes per exchanged row (data + validity)."""
    import numpy as np

    return sum(
        np.dtype(c.type.np_dtype).itemsize + 1 for c in schema
    )


def exchange_bytes(
    cap: int, row_bytes: int, devices: int, rows: int = 0
) -> int:
    """ONE device's footprint of an exchange whose buckets hold ``cap``
    rows: the operands of the bucketing sort going in and coming out
    extended to ``rows + cap`` (``rows``: the fragment's padded rows a
    device; 0 for a broadcast, which compacts ``cap`` rows by a take and
    sorts no payload), the ``(D, cap)`` slab it sends and the ``(D,
    cap)`` result of the collective. Every device holds the same, so
    this is what a one-device budget is held against."""
    return (2 * (rows + cap) + 2 * devices * cap) * row_bytes


def probe_window_width(
    rows_per_shard: int, per_row_bytes: int, shards: int, budget: int,
    floor: int = 1024,
) -> int:
    """Power-of-two window width (dividing the power-of-two shard
    capacity) for streaming a bigger-than-budget probe side: halve until
    the window's sort operands fit, never below ``floor`` rows."""
    width = rows_per_shard
    while (
        shards * width * per_row_bytes > budget
        and width % 2 == 0 and width > floor
    ):
        width //= 2
    return width
