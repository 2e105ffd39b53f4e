"""Fused mesh executor: whole plan fragments as ONE shard_map program.

The general path (executor/dist.py) runs each datanode's fragment as a
separate LocalExecutor call with host-mediated motions — correct, but it
round-trips HBM per operator and serializes datanodes. This module is the
TPU-native fast path the SURVEY §7 design calls for: all shards of a table
live stacked on the device mesh ([S, Rmax] per column, sharded over the
'dn' axis), and an eligible fragment (scan → filter → project → partial
aggregate) compiles to a single jitted shard_map program. XLA fuses the
filter/projection into the aggregation scatter; the only inter-device
traffic is the gather of [S, cap] partials (an all_gather when merged
in-program), riding ICI instead of the reference's DataPump sockets
(src/backend/pgxc/squeue/squeue.c).

Eligibility (v1): single sharded/roundrobin/replicated base table, chain of
Filter/Project between Scan and one Aggregate, no DISTINCT aggs. Everything
else falls back to the general executor. Grouped results use a static group
capacity; overflow is detected post-hoc and falls back too.

The same machinery drives the multichip dry-run: a Mesh over N devices,
one shard per device, partial aggregation + all_gather + an all_to_all
hash redistribution — the dp/sp collective pattern of the scaling-book
recipe (mesh → shardings → XLA inserts collectives).
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from functools import partial
from typing import NamedTuple, Optional

import numpy as np

import opentenbase_tpu.ops  # noqa: F401  (x64)
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from opentenbase_tpu.fault import FAULT
from opentenbase_tpu.obs import statements as _stmtobs
from opentenbase_tpu.obs.trace import compile_window, scope, span as _span
from opentenbase_tpu.ops import agg as agg_ops
from opentenbase_tpu.ops import filter as filt_ops
from opentenbase_tpu.ops.expr import ExprCompiler, resolve_param
from opentenbase_tpu.plan import logical as L
from opentenbase_tpu.plan.distribute import Fragment
from opentenbase_tpu.plan.skey import plan_skey
from opentenbase_tpu.storage.column import Column
from opentenbase_tpu.storage.table import ColumnBatch

DEFAULT_GROUP_CAP = 1024

import logging

_log = logging.getLogger("opentenbase_tpu.fused")


# ---------------------------------------------------------------------------
# The statement path's device calls: named programs, one launch helper,
# one fetch helper (spans + ledger counters, obs/trace.span)
# ---------------------------------------------------------------------------


def named_program(fn, name: str):
    """``jax.jit(fn)`` under a static name: the XLA module is
    ``jit_<name>``, so a device trace says which program ran. Names
    start with ``program`` (the benchmark's rooflines match
    ``^jit_program``) and carry no literal or hash."""
    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn)


def fetch(tree, what: str, **args):
    """THE host wait on the device for the statement path: every
    device->host read goes through here, so no sync exists without its
    ``fused.wait`` span and its ``device_syncs`` count. One batched
    ``jax.device_get`` of the whole tree, never per-array reads."""
    with _span(
        None, "fused.wait", "device_wait_ms", "device_syncs",
        cat="fused", what=what, **args,
    ) as sp:
        out = jax.device_get(tree)
        if sp.listening:
            sp.set(d2h_bytes=sum(
                int(getattr(x, "nbytes", 0)) for x in jax.tree.leaves(out)
            ))
    return out


class _CacheSpan(_span):
    """The ``fused.cache`` span of one DeviceCache lookup (``cache_ms``):
    hit or refresh, and what the refresh shipped, read from the cache's
    counters across it."""

    def __init__(self, cache, table: str):
        super().__init__(
            None, "fused.cache", "cache_ms", cat="fused", table=table
        )
        self._cache = cache

    def _marks(self) -> tuple:
        st = self._cache.stats
        return (
            st["full_uploads"] + st["delta_uploads"]
            + st.get("window_uploads", 0),
            st["h2d_bytes"], st["delta_tail_rows"],
        )

    def __enter__(self):
        super().__enter__()
        if self.listening:
            self._before = self._marks()
        return self

    def __exit__(self, *exc):
        if self.listening:
            now = self._marks()
            before = self._before
            self.set(
                hit=now[0] == before[0],
                h2d_bytes=now[1] - before[1],
                delta_tail_rows=now[2] - before[2],
            )
        return super().__exit__(*exc)


class Launcher:
    """Calls jitted programs for one FusedExecutor: a ``fused.launch``
    span from the call to its return (the enqueue, argument uploads
    included), ``device_launches``/``launch_ms`` on the ledger (compile
    time, which a first call spends inside, stays ``compile_ms``'s),
    and the retry note a flagged run leaves for the launch that
    re-answers it."""

    def __init__(self):
        self.attempt = 0  # launches of the current statement
        self.programs: list[str] = []  # their names, in order
        self._retry = None  # (program that raised the flag, reason)

    def begin(self) -> None:
        """A statement enters the device path."""
        self.attempt = 0
        self.programs = []
        self._retry = None

    def note_retry(self, reason: str) -> None:
        """The last launch's answer was refused (a flag, an overflow):
        the next launch carries ``retry_of``/``reason``."""
        self._retry = (self.programs[-1] if self.programs else "", reason)
        led = _stmtobs.current()
        if led is not None:
            led.fused_retries += 1

    def __call__(self, program, build_args, late=None, **args):
        """``program(*build_args())``; ``build_args`` makes the small
        uploads (``jnp.asarray`` of row counts, the snapshot) inside
        the span. ``late()`` gives the args only the call itself
        settles (what a first call's trace chose), read after it."""
        name = program.__name__
        self.attempt += 1
        self.programs.append(name)
        retry, self._retry = self._retry, None
        if retry is not None:
            args["retry_of"], args["reason"] = retry
        with _span(
            None, "fused.launch", "launch_ms", "device_launches",
            cat="fused", program=name, attempt=self.attempt, **args,
        ) as sp:
            with compile_window() as cw:
                outs = program(*build_args())
            if cw.ms:
                sp.set(compile_ms=round(cw.ms, 3))
                sp.exclude(cw.ms)
            if late is not None and sp.listening:
                sp.set(**late())
        return outs


# ---------------------------------------------------------------------------
# Device table cache: stacked shards on the mesh
# ---------------------------------------------------------------------------


@dataclass
class DeviceTable:
    """All shards of one table stacked: column name -> [S, Rmax] array
    (sharded over the mesh 'dn' axis), plus validity and MVCC columns."""

    columns: dict[str, jax.Array]
    validity: dict[str, Optional[jax.Array]]
    xmin: jax.Array  # [S, Rmax]
    xmax: jax.Array
    nrows: np.ndarray  # [S] live row count per shard (host)
    rmax: int
    versions: tuple[int, ...]
    node_order: tuple[int, ...]
    # host-side |max| per column (None where unknown/not numeric):
    # feeds the pallas certifier (ops/pallas_scan.certify_*)
    col_maxabs: dict[str, Optional[float]] = None
    # host-side [min, max] per integer column (None elsewhere): sizes the
    # static group-key domain for the grouped pallas kernel
    col_range: dict[str, Optional[tuple[int, int]]] = None
    # per-shard sync state for incremental refresh:
    # {nrows, structure, mvcc_seq} aligned with node_order
    sync: list = None


class DeviceCache:
    """Uploads/refreshes stacked shard columns; keyed by store versions.

    The buffer-manager analog, incremental since round 2: appends upload
    only the new row tail (columns are append-only, storage/table.py) and
    MVCC stamps replay from the store's compact stamp log as targeted
    device scatters. A full re-upload happens only when row positions
    were rewritten (vacuum, schema change — ``structure_version``), the
    padded row capacity is outgrown, or a column's NULL-mask presence
    flips. The reference analog: buffer-manager page replacement vs WAL
    redo of individual tuples.
    """

    def __init__(self, mesh: Mesh):
        self.mesh = mesh
        self._tables: dict[str, DeviceTable] = {}
        # concurrent readers may both miss and upload; the map itself
        # must never be mutated mid-iteration (window eviction iterates)
        import threading as _threading

        self._mu = _threading.RLock()
        self.stats = {
            "hits": 0,
            "full_uploads": 0,
            "column_uploads": 0,
            "delta_uploads": 0,
            "delta_rows": 0,
            "mvcc_replays": 0,
            # scannable delta plane: refreshes whose appended tail was
            # served straight from pending DeltaBatch segments (no
            # fold), and the delta-resident rows those tails carried
            "delta_tail_uploads": 0,
            "delta_tail_rows": 0,
            # host->device transfer volume (every device_put this cache
            # issued, data + validity + MVCC planes + delta tails): the
            # per-statement ledger snapshots before/after deltas of this
            # under the fused gate (engine._try_fused)
            "h2d_bytes": 0,
        }
        # enable_delta_scan = off: refreshes fold
        # stores before reading and keep the legacy per-entry MVCC
        # replay with its flat >8 full-plane cutoff — the pre-delta-
        # plane behavior on the same binary
        self.legacy_fold = False

    def _put(self, arr, sharding):
        """jax.device_put with transfer accounting: every byte this
        cache ships host->device lands in ``stats["h2d_bytes"]`` (the
        per-statement ledger reads before/after deltas of it under the
        fused gate)."""
        self.stats["h2d_bytes"] += int(getattr(arr, "nbytes", 0) or 0)
        return jax.device_put(arr, sharding)

    def get(
        self, name: str, meta, node_stores: dict[int, dict], nodes=None,
        columns=None,
    ) -> DeviceTable:
        """``nodes`` overrides which stores to stack (a replicated table
        reads ONE replica; default = every owning node). ``columns``
        restricts which columns must be device-resident — columns upload
        LAZILY on first use, so a query touching 4 of 7 columns never
        pays HBM transfer for the other 3 (physical-tlist, columnar
        style)."""
        nodes = tuple(meta.node_indices) if nodes is None else tuple(nodes)
        want = tuple(columns) if columns is not None else tuple(meta.schema)
        stores = [node_stores[n][name] for n in nodes]
        versions = tuple(s.version for s in stores)
        with _CacheSpan(self, name), self._mu:
            return self._get_locked(
                name, meta, stores, nodes, want, versions
            )

    def _get_locked(
        self, name, meta, stores, nodes, want, versions
    ) -> DeviceTable:
        cached = self._tables.get((name, nodes))
        if cached is not None and cached.versions == versions and (
            cached.node_order == nodes
        ):
            self.stats["hits"] += 1
            self._ensure_columns(cached, stores, meta, want)
            return cached
        if cached is not None and cached.node_order == nodes:
            updated = self._try_delta(cached, stores, meta, versions)
            if updated is not None:
                self._ensure_columns(updated, stores, meta, want)
                return updated
        self.stats["full_uploads"] += 1
        S = _pad_shards(len(stores), self.mesh.shape["dn"])
        # ONE coherent capture per store (ScanView): nrows, planes,
        # mvcc_seq and structure are one moment — concurrent appends
        # advance nrows after writing rows, so every plane slices the
        # same prefix, and the sync record can't claim stamps newer
        # than what was read. Reads never fold: delta-resident rows
        # assemble from their batches (the scannable delta plane).
        views = self._store_views(stores)
        for s, v in zip(stores, views):
            s.note_delta_read(v.delta_rows())  # whole-store upload
        totals = [v.nrows for v in views]
        seqs = [v.mvcc_seq for v in views]
        structs = [v.structure_version for v in views]
        xmins = [v.xmin() for v in views]
        xmaxs = [v.xmax() for v in views]
        rmax = filt_ops.bucket_size(max(max(totals, default=0), 1))
        sharding = NamedSharding(self.mesh, P("dn"))
        # COMPACT visibility: after a bulk load every row of a shard
        # carries the same (xmin, xmax), so the two MVCC planes upload
        # as [S, 1] per-shard constants instead of 16 bytes/row — the
        # visibility compare broadcasts on device for free. Any
        # non-uniform shard falls back to the full planes. (The
        # reference pays this with per-tuple xmin/xmax in the heap
        # header, src/include/access/htup_details.h.)
        uniform = True
        for xm, xx, nr in zip(xmins, xmaxs, totals):
            if nr == 0:
                continue
            if xm[0] != xm[-1] or xx[0] != xx[-1] or not (
                np.all(xm == xm[0]) and np.all(xx == xx[0])
            ):
                uniform = False
                break
        if uniform:
            xmin = np.full((S, 1), 2**62, dtype=np.int64)
            xmax = np.zeros((S, 1), dtype=np.int64)
            nrows = np.zeros(S, dtype=np.int64)
            for i in range(len(stores)):
                if totals[i]:
                    xmin[i, 0] = xmins[i][0]
                    xmax[i, 0] = xmaxs[i][0]
                nrows[i] = totals[i]
        else:
            xmin = np.full((S, rmax), 2**62, dtype=np.int64)
            xmax = np.zeros((S, rmax), dtype=np.int64)
            nrows = np.zeros(S, dtype=np.int64)
            for i in range(len(stores)):
                nr = totals[i]
                xmin[i, :nr] = xmins[i]
                xmax[i, :nr] = xmaxs[i]
                nrows[i] = nr
        dt = DeviceTable(
            {},
            {},
            self._put(xmin, sharding),
            self._put(xmax, sharding),
            nrows,
            rmax,
            versions,
            nodes,
            {},
            {},
            [
                {
                    "nrows": totals[i],
                    "structure": structs[i],
                    "mvcc_seq": seqs[i],
                }
                for i in range(len(stores))
            ],
        )
        self._ensure_columns(dt, stores, meta, want, totals, views)
        self._tables[(name, nodes)] = dt
        return dt

    def _store_views(self, stores):
        """One coherent non-folding ScanView per store. Under
        ``legacy_fold`` (enable_delta_scan = off) pending deltas are
        compacted FIRST — reproducing the fold-on-read read path on
        the same binary."""
        if self.legacy_fold:
            for s in stores:
                if getattr(s, "pending_delta_rows", 0):
                    s.compact()
        # fold-avoided accounting happens at the USE sites (tail
        # upload / full upload / window) with the rows actually read
        return [s.scan_view() for s in stores]

    def register_external(
        self, name: str, meta, nodes, columns: dict, nrows,
        versions=None,
    ) -> DeviceTable:
        """Register a DEVICE-RESIDENT table whose columns never lived in
        host stores — e.g. benchmark data generated on-chip with
        jax.random (SF100-scale tables never exist on the host;
        on-chip threefry is deterministic across backends, so a CPU
        baseline regenerates identical data locally). ``columns``: {name: [S, rmax] array}
        covering every column queries will touch (there is no host
        backing to lazy-load more). Visibility is compact all-visible
        planes; rmax may be ANY row count (not bucket-padded).
        Pair with stub stores exposing .nrows/.version so planner
        estimates and version checks keep working."""
        nodes = tuple(nodes)
        first = next(iter(columns.values()))
        S, rmax = first.shape
        sharding = NamedSharding(self.mesh, P("dn"))
        xmin = np.zeros((S, 1), dtype=np.int64)
        xmax = np.full((S, 1), 2**62, dtype=np.int64)
        nr = np.zeros(S, dtype=np.int64)
        nr[: len(nrows)] = nrows
        cols = {}
        col_range: dict = {}
        col_maxabs: dict = {}
        mins = {}
        maxs = {}
        # the live-row stats are full-width eager ops: keep them on
        # the mesh beside the columns (host-side arrays default to the
        # CPU backend, ops/__init__.py)
        with jax.default_device(self.mesh.devices.flat[0]):
            nr_dev = jnp.asarray(nr)
            for cname, arr in columns.items():
                cols[cname] = self._put(arr, sharding)
                if jnp.issubdtype(arr.dtype, jnp.integer):
                    # stats over LIVE rows only — padding garbage would
                    # widen the range and disable narrow-operand paths
                    live = (
                        jnp.arange(rmax)[None, :] < nr_dev[:S, None]
                    )
                    info = jnp.iinfo(arr.dtype)
                    mins[cname] = jnp.min(
                        jnp.where(live, cols[cname], info.max)
                    )
                    maxs[cname] = jnp.max(
                        jnp.where(live, cols[cname], info.min)
                    )
            fetched = fetch((mins, maxs), "column stats")
        for cname in columns:
            if cname in fetched[0]:
                lo = int(fetched[0][cname])
                hi = int(fetched[1][cname])
                col_range[cname] = (lo, hi)
                col_maxabs[cname] = float(max(abs(lo), abs(hi)))
            else:
                col_range[cname] = None
                col_maxabs[cname] = None
        if versions is None:
            versions = (1,) * len(nodes)
        dt = DeviceTable(
            cols,
            {c: None for c in cols},
            self._put(xmin, sharding),
            self._put(xmax, sharding),
            nr,
            rmax,
            tuple(versions),
            nodes,
            col_maxabs,
            col_range,
            [
                {"nrows": int(n), "structure": 0, "mvcc_seq": 0}
                for n in nr[: len(nodes)]
            ],
        )
        with self._mu:
            self._tables[(name, nodes)] = dt
        return dt

    def get_window(
        self, name: str, meta, node_stores: dict[int, dict], nodes,
        columns, start: int, length: int,
    ) -> DeviceTable:
        """A DeviceTable over row window [start, start+length) of every
        shard — the streaming unit for tables bigger than the HBM
        budget. Only the MOST RECENT window of a table stays resident
        (sequential scans revisit windows in order, and keeping more
        would defeat the point of chunking). Any full-table residency
        for the same table is evicted first."""
        nodes = tuple(nodes)
        want = tuple(sorted(columns))
        stores = [node_stores[n][name] for n in nodes]
        versions = tuple(s.version for s in stores)
        wkey = (name, nodes, "win", start, length, want)
        with _CacheSpan(self, name), self._mu:
            return self._get_window_locked(
                wkey, name, meta, stores, nodes, want, versions,
                start, length,
            )

    def _get_window_locked(
        self, wkey, name, meta, stores, nodes, want, versions,
        start, length,
    ) -> DeviceTable:
        cached = self._tables.get(wkey)
        if cached is not None and cached.versions == versions:
            self.stats["hits"] += 1
            return cached
        # evict every other residency of this table (full or windowed)
        for k in [
            k for k in self._tables
            if k[0] == name and k[1] == nodes and k != wkey
        ]:
            del self._tables[k]
        self.stats["window_uploads"] = (
            self.stats.get("window_uploads", 0) + 1
        )
        S = _pad_shards(len(stores), self.mesh.shape["dn"])
        W = filt_ops.bucket_size(max(length, 1))
        sharding = NamedSharding(self.mesh, P("dn"))
        xmin = np.full((S, W), 2**62, dtype=np.int64)
        xmax = np.zeros((S, W), dtype=np.int64)
        nrows = np.zeros(S, dtype=np.int64)
        # ONE coherent capture per store (non-folding ScanView): every
        # plane and column slices the same consistent prefix even under
        # concurrent appends, and the sync record can't claim stamps
        # newer than the planes just read
        views = self._store_views(stores)
        totals = [v.nrows for v in views]
        seqs = [v.mvcc_seq for v in views]
        structs = [v.structure_version for v in views]
        for i, v in enumerate(views):
            n = max(min(totals[i] - start, length), 0)
            if n:
                xmin[i, :n] = v.xmin(start, start + n)
                xmax[i, :n] = v.xmax(start, start + n)
                stores[i].note_delta_read(
                    v.delta_rows(start, start + n)
                )
            nrows[i] = n
        cols: dict = {}
        valids: dict = {}
        for cname in want:
            ty = meta.schema[cname]
            stack = np.zeros((S, W), dtype=ty.np_dtype)
            vstack = None
            for i, v in enumerate(views):
                n = int(nrows[i])
                if not n:
                    continue
                stack[i, :n] = v.col(cname, start, start + n)
                vm = v.validity(cname, start, start + n)
                if vm is not None:
                    if vstack is None:
                        vstack = np.ones((S, W), dtype=np.bool_)
                    vstack[i, :n] = vm
            cols[cname] = self._put(stack, sharding)
            valids[cname] = (
                None if vstack is None
                else self._put(vstack, sharding)
            )
        dt = DeviceTable(
            cols,
            valids,
            self._put(xmin, sharding),
            self._put(xmax, sharding),
            nrows,
            W,
            versions,
            nodes,
            {},
            {},
            [
                {
                    "nrows": totals[i],
                    "structure": structs[i],
                    "mvcc_seq": seqs[i],
                }
                for i in range(len(stores))
            ],
        )
        self._tables[wkey] = dt
        return dt

    def _ensure_columns(
        self, dt: DeviceTable, stores, meta, want, totals=None,
        views=None,
    ) -> None:
        """Upload any of ``want`` not yet device-resident. Row bounds
        come from ``totals`` (the caller's one-shot capture) or, absent
        that, from dt.sync — NEVER from a fresh nrows read, which a
        concurrent append could have advanced past the MVCC planes
        already on device. Store reads go through non-folding
        ScanViews, built lazily: the all-resident fast path (incl.
        register_external stub stores) never touches a store."""
        if all(cname in dt.columns for cname in want):
            return
        S = _pad_shards(len(stores), self.mesh.shape["dn"])
        sharding = NamedSharding(self.mesh, P("dn"))
        if views is None:
            views = self._store_views(stores)
        bounds = [
            min(
                totals[i] if totals is not None
                else dt.sync[i]["nrows"],
                dt.rmax,
                views[i].nrows,
            )
            for i in range(len(stores))
        ]
        for cname in want:
            if cname in dt.columns:
                continue
            ty = meta.schema[cname]
            stack = np.zeros((S, dt.rmax), dtype=ty.np_dtype)
            vstack = None
            reals = []
            for i, v in enumerate(views):
                n0 = bounds[i]
                real = v.col(cname, 0, n0)
                reals.append(real)
                stack[i, :n0] = real
                vm = v.validity(cname, 0, n0)
                if vm is not None:
                    if vstack is None:
                        vstack = np.ones((S, dt.rmax), dtype=np.bool_)
                    vstack[i, :n0] = vm
            if np.issubdtype(stack.dtype, np.integer):
                # stats over REAL rows only: the zero padding would
                # inflate the range (e.g. year keys 1992..1998 -> domain
                # 1999) and disqualify small-domain group keys
                lo = hi = ma = None
                for real in reals:
                    if real.size == 0:
                        continue
                    rlo, rhi = int(real.min()), int(real.max())
                    lo = rlo if lo is None else min(lo, rlo)
                    hi = rhi if hi is None else max(hi, rhi)
                    ma = max(ma or 0.0, float(max(abs(rlo), abs(rhi))))
                dt.col_maxabs[cname] = ma if ma is not None else 0.0
                dt.col_range[cname] = None if lo is None else (lo, hi)
            else:
                dt.col_maxabs[cname] = None
                dt.col_range[cname] = None
            dt.columns[cname] = self._put(stack, sharding)
            dt.validity[cname] = (
                None if vstack is None else self._put(vstack, sharding)
            )
            self.stats["column_uploads"] = (
                self.stats.get("column_uploads", 0) + 1
            )

    def _try_delta(
        self, dt: DeviceTable, stores, meta, versions
    ) -> Optional[DeviceTable]:
        """Refresh ``dt`` in place with append-tail uploads + MVCC stamp
        replay (device-RESIDENT columns only; absent columns upload lazily
        with current data). Returns None when only a full rebuild is
        sound.

        The tail read goes through non-folding ScanViews, so a fresh
        INSERT burst becomes a tail ``.at[].set()`` served STRAIGHT
        from pending DeltaBatch segments — no host fold, no
        ``full_uploads`` rebuild (delta batches are device-appendable;
        global positions map 1:1 onto the [S, rmax] planes). MVCC
        stamps on delta rows ride the existing ``mvcc_seq`` replay
        log; stamps that landed inside the freshly-read tail are
        already reflected in the tail planes and are skipped, and the
        remainder coalesces into ONE de-duplicated device scatter per
        plane sized against the rows actually touched — a 10-row stamp
        burst on a million-row shard never pays a full-plane refresh
        (the old flat >8-entry cutoff did exactly that)."""
        present = list(dt.columns)
        if not set(present) <= set(meta.schema):
            return None
        if dt.xmin.shape[1] == 1:
            # compact visibility planes can't take per-row writes —
            # expand them ON DEVICE (broadcast, no host transfer)
            # before append tails / MVCC stamp replay land
            S = dt.xmin.shape[0]
            dt.xmin = jnp.broadcast_to(dt.xmin, (S, dt.rmax))
            dt.xmax = jnp.broadcast_to(dt.xmax, (S, dt.rmax))
        # ONE coherent capture per store (ScanView): a concurrent
        # append between the validation below and the tail upload
        # could cross dt.rmax and write past the device buffer, and a
        # commit stamping between the plane read and the sync update
        # would be recorded as synced without having landed on device.
        # The view pins (nrows, planes, mvcc_seq, log) to one moment.
        legacy = self.legacy_fold
        views = self._store_views(stores)
        totals = [v.nrows for v in views]
        seqs = [v.mvcc_seq for v in views]
        structs = [v.structure_version for v in views]
        for sy, st in zip(dt.sync, structs):
            if st != sy["structure"]:
                return None
        for v, sy, nr in zip(views, dt.sync, totals):
            if nr > dt.rmax or nr < sy["nrows"]:
                return None
            for cname in present:
                has_dev = dt.validity[cname] is not None
                if v.has_validity(cname) and not has_dev:
                    return None  # first NULL appeared: mask must materialize
        if any(
            totals[i] > dt.sync[i]["nrows"] for i in range(len(views))
        ):
            # failpoint: device delta-tail upload boundary — an
            # injected error models the refresh dying before any tail
            # lands (dt untouched beyond the pure plane expansion; the
            # next statement retries the same refresh)
            FAULT("fused/delta_tail_upload")
        delta_rows = 0
        tail_delta_rows = 0
        delta_h2d = 0
        replays = 0
        for i, (v, sy) in enumerate(zip(views, dt.sync)):
            old_n, new_n = sy["nrows"], totals[i]
            if new_n > old_n:
                delta_rows += new_n - old_n
                tail_served = v.delta_rows(old_n, new_n)
                tail_delta_rows += tail_served
                stores[i].note_delta_read(tail_served)

                def tset(buf, tail):
                    nonlocal delta_h2d
                    delta_h2d += int(getattr(tail, "nbytes", 0) or 0)
                    if legacy:
                        # historical eager write (whole-plane copy per
                        # call) — the fold-on-read baseline keeps it
                        return buf.at[i, old_n:new_n].set(tail)
                    return _tail_write(buf, i, old_n, tail, dt.rmax)

                for cname in present:
                    tail = np.ascontiguousarray(
                        v.col(cname, old_n, new_n)
                    )
                    dt.columns[cname] = tset(dt.columns[cname], tail)
                    vdev = dt.validity[cname]
                    if vdev is not None:
                        vm = v.validity(cname, old_n, new_n)
                        vt = (
                            np.ones(new_n - old_n, dtype=np.bool_)
                            if vm is None
                            else np.ascontiguousarray(vm)
                        )
                        dt.validity[cname] = tset(vdev, vt)
                    if tail.size and np.issubdtype(tail.dtype, np.integer):
                        tlo, thi = int(tail.min()), int(tail.max())
                        rng = dt.col_range.get(cname)
                        dt.col_range[cname] = (
                            (tlo, thi)
                            if rng is None
                            else (min(rng[0], tlo), max(rng[1], thi))
                        )
                        dt.col_maxabs[cname] = max(
                            dt.col_maxabs[cname] or 0.0,
                            float(max(abs(tlo), abs(thi))),
                        )
                dt.xmin = tset(
                    dt.xmin,
                    np.ascontiguousarray(v.xmin(old_n, new_n)),
                )
                dt.xmax = tset(
                    dt.xmax,
                    np.ascontiguousarray(v.xmax(old_n, new_n)),
                )
                dt.nrows[i] = new_n
            # MVCC stamp replay (idempotent absolute writes, in order)
            # — bounded by the seqs[i] capture: entries stamped after
            # it replay on the NEXT refresh, never silently skip
            if seqs[i] != sy["mvcc_seq"]:
                replays += self._replay_mvcc(
                    dt, i, v, sy, seqs[i], old_n, new_n, legacy
                )
            dt.sync[i] = {
                "nrows": new_n,
                "structure": structs[i],
                "mvcc_seq": seqs[i],
            }
        dt.versions = versions
        self.stats["delta_uploads"] += 1
        self.stats["delta_rows"] += delta_rows
        if tail_delta_rows:
            self.stats["delta_tail_uploads"] += 1
            self.stats["delta_tail_rows"] += tail_delta_rows
        self.stats["h2d_bytes"] += delta_h2d
        self.stats["mvcc_replays"] += replays
        return dt

    def _replay_mvcc(
        self, dt, i, view, sy, seq, old_n, new_n, legacy
    ) -> int:
        """Bring shard ``i``'s device MVCC planes up to ``seq``.
        Returns replay operations performed.

        Non-legacy sizing (ISSUE-15 satellite): entries are position-
        filtered against the freshly-uploaded tail (rows >= old_n
        already carry their current stamps), then coalesced into ONE
        last-write-wins scatter per plane — transfer cost scales with
        ROWS TOUCHED, never with the plane width. A full refresh runs
        only when the log was trimmed past the sync point or the
        touched rows rival the synced prefix itself (at which point
        the contiguous upload is the cheaper device op)."""
        pending = [
            e for e in view.mvcc_log if sy["mvcc_seq"] < e[0] <= seq
        ]
        expect = seq - sy["mvcc_seq"]
        trimmed = len(pending) != expect
        if legacy and (trimmed or len(pending) > 8):
            # the pre-delta-plane heuristic, kept verbatim for the
            # enable_delta_scan=off baseline: whole-plane refresh
            dt.xmin = dt.xmin.at[i, :new_n].set(
                np.ascontiguousarray(view.xmin(0, new_n))
            )
            dt.xmax = dt.xmax.at[i, :new_n].set(
                np.ascontiguousarray(view.xmax(0, new_n))
            )
            return 1
        if legacy:
            n = 0
            for _seq, kind, a, b, ts in pending:
                if kind == "xmin":
                    dt.xmin = dt.xmin.at[i, a:b].set(ts)
                elif kind == "xmax_range":
                    dt.xmax = dt.xmax.at[i, a:b].set(ts)
                elif len(a):
                    dt.xmax = dt.xmax.at[i, a].set(ts)
                n += 1
            return n
        # stamps inside [old_n, new_n) are already device-current (the
        # tail planes above were read at the same view moment as the
        # log), so only positions below old_n need scatters
        synced = old_n
        if trimmed:
            # log trimmed past the sync point: unknown stamps may touch
            # the synced prefix — refresh it; the tail stays as
            # uploaded (an ingest burst longer than the log cap pays
            # O(synced prefix), never O(burst))
            if synced:
                dt.xmin = _tail_write(
                    dt.xmin, i, 0,
                    np.ascontiguousarray(view.xmin(0, synced)),
                    dt.rmax, exact=True,
                )
                dt.xmax = _tail_write(
                    dt.xmax, i, 0,
                    np.ascontiguousarray(view.xmax(0, synced)),
                    dt.rmax, exact=True,
                )
            return 1
        spans = 0
        for _seq, kind, a, b, ts in pending:
            if kind == "xmax" and not isinstance(a, int):
                spans += int((np.asarray(a) < synced).sum())
            else:
                spans += max(0, min(b, synced) - a)
        if spans == 0:
            return 0
        if spans >= max(synced, 1):
            # touched rows rival the synced prefix: one contiguous
            # upload beats an equally-sized scatter
            dt.xmin = _tail_write(
                dt.xmin, i, 0,
                np.ascontiguousarray(view.xmin(0, synced)),
                dt.rmax, exact=True,
            )
            dt.xmax = _tail_write(
                dt.xmax, i, 0,
                np.ascontiguousarray(view.xmax(0, synced)),
                dt.rmax, exact=True,
            )
            return 1
        planes = {"xmin": ([], []), "xmax": ([], [])}
        for _seq, kind, a, b, ts in pending:
            if kind == "xmax" and not isinstance(a, int):
                pos = np.asarray(a, dtype=np.int64)
                pos = pos[pos < synced]
                plane = "xmax"
            else:
                plane = "xmin" if kind == "xmin" else "xmax"
                hi = min(b, synced)
                if hi <= a:
                    continue
                pos = np.arange(a, hi, dtype=np.int64)
            if not len(pos):
                continue
            planes[plane][0].append(pos)
            planes[plane][1].append(
                np.full(len(pos), ts, dtype=np.int64)
            )
        n = 0
        for plane, (poss, valss) in planes.items():
            if not poss:
                continue
            pos = np.concatenate(poss)
            vals = np.concatenate(valss)
            # last-write-wins de-dup: XLA scatter order is undefined
            # for duplicate indices, the log's order is the law
            uniq, first_in_rev = np.unique(
                pos[::-1], return_index=True
            )
            vals = vals[::-1][first_in_rev]
            # bucket-pad the scatter so its XLA program caches across
            # refreshes (varying index counts would recompile per
            # statement); the pad repeats the last (index, value) pair
            # — duplicate indices with EQUAL values are order-immune
            padn = filt_ops.bucket_size(len(uniq))
            if padn != len(uniq):
                uniq = np.concatenate(
                    [uniq, np.full(padn - len(uniq), uniq[-1])]
                )
                vals = np.concatenate(
                    [vals, np.full(padn - len(vals), vals[-1])]
                )
            # donated in-place scatter: O(rows touched), never an
            # O(plane) eager copy — the heart of the satellite fix
            if plane == "xmin":
                dt.xmin = _donated_row_scatter(
                    dt.xmin, jnp.int32(i), jnp.asarray(uniq),
                    jnp.asarray(vals),
                )
            else:
                dt.xmax = _donated_row_scatter(
                    dt.xmax, jnp.int32(i), jnp.asarray(uniq),
                    jnp.asarray(vals),
                )
            n += 1
        return n


def _pad_shards(s: int, d: int) -> int:
    """Shard count padded up to a multiple of the mesh axis size."""
    return ((s + d - 1) // d) * d


# -- donated (in-place) device refresh primitives ---------------------------
# Eager ``.at[].set`` copies the WHOLE [S, rmax] buffer on every call —
# fine for a one-off, ruinous for the per-statement refresh cadence the
# scannable delta plane runs at (a 2k-row tail would pay an O(plane)
# copy per column per statement). Donating the input buffer lets XLA
# alias it in place, so a refresh costs O(rows touched) on EVERY
# backend. Tail lengths and scatter widths are bucket-padded by the
# callers so these compile once per (dtype, width) and then cache.


@partial(jax.jit, donate_argnums=(0,))
def _donated_update_slice(buf, tail2d, row, start):
    return jax.lax.dynamic_update_slice(buf, tail2d, (row, start))


@partial(jax.jit, donate_argnums=(0,))
def _donated_row_scatter(buf, row, idx, vals):
    return buf.at[row, idx].set(vals)


def _tail_write(
    buf, i: int, start: int, tail: np.ndarray, rmax: int,
    exact: bool = False,
):
    """Donated write of ``tail`` into ``buf[i, start:start+len]``,
    bucket-padded into the dead lanes past the live prefix (rows >=
    nrows are masked dead by every consumer, and later tails overwrite
    them) so the compiled update is shape-stable across refreshes.
    ``exact=True`` skips the padding — for writes whose following rows
    are LIVE (the synced-prefix plane refresh) and must not be
    clobbered."""
    span = len(tail)
    L = span if exact else filt_ops.bucket_size(max(span, 1))
    if start + L > rmax:
        L = span  # exact-width fallback at the buffer edge
    if L != span:
        padded = np.empty(L, dtype=tail.dtype)
        padded[:span] = tail
        padded[span:] = tail[-1] if span else 0
        tail = padded
    return _donated_update_slice(
        buf, jnp.asarray(tail)[None, :], jnp.int32(i), jnp.int32(start)
    )


def build_mesh(devices=None) -> Mesh:
    """1-D 'dn' mesh over the given (or default) devices."""
    devices = devices if devices is not None else jax.devices()
    return Mesh(np.asarray(devices), ("dn",))


# ---------------------------------------------------------------------------
# Fragment pattern matching
# ---------------------------------------------------------------------------


@dataclass
class _FusablePartial:
    scan: L.Scan
    steps: list  # Filter/Project chain bottom-up (excluding scan/agg)
    agg: L.Aggregate
    _inlined: Optional[tuple] = None

    def scan_exprs(self) -> tuple:
        """(preds, group keys, aggregate arguments) rewritten over the
        scan schema (the Project steps inlined; None for count(*)'s
        argument): what the column statistics can bound. Both binds of
        a statement (the Pallas certifier, the MXU lane plan) read it,
        so it is made once."""
        if self._inlined is None:
            from opentenbase_tpu.ops import pallas_scan as ps

            chain: list = []
            preds: list = []
            for step in self.steps:
                if isinstance(step, L.Filter):
                    preds.append(ps.inline_projects(step.predicate, chain))
                else:
                    chain.append(tuple(
                        ps.inline_projects(e, chain) for e in step.exprs
                    ))
            self._inlined = (
                preds,
                [ps.inline_projects(g, chain) for g in self.agg.group_exprs],
                [
                    None if a.arg is None
                    else ps.inline_projects(a.arg, chain)
                    for a in self.agg.aggs
                ],
            )
        return self._inlined


class _MxuBind(NamedTuple):
    """One bind's lane plan of the MXU group reduce, as the host sees it."""

    bounds: "agg_ops.MxuBounds"  # part of the program key
    lanes: int  # K of the plan as bound
    lanes_full: int  # K the dtype-wide plan would have had
    narrowed: bool  # a bound dropped at least one lane


def _is_float(ty) -> bool:
    return np.issubdtype(ty.np_dtype, np.floating)


# Resident-cache ceiling for one table's scan columns: beyond this the
# fused path streams fixed-width shard windows instead of caching the
# whole table in HBM (one v5e has 16 GB; leave room for intermediates
# and other tables).
SCAN_HBM_BUDGET = 8_000_000_000


def _match_partial_fragment(root: L.LogicalPlan) -> Optional[_FusablePartial]:
    if not isinstance(root, L.Aggregate):
        return None
    if any(a.distinct for a in root.aggs):
        return None
    steps = []
    node = root.child
    while isinstance(node, (L.Filter, L.Project)):
        steps.append(node)
        node = node.child
    if not isinstance(node, L.Scan):
        return None
    return _FusablePartial(node, list(reversed(steps)), root)


class FusedUnsupported(Exception):
    pass


# ---------------------------------------------------------------------------
# Fused executor
# ---------------------------------------------------------------------------


DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Where jax's persistent compilation cache lives (idempotent). The
    fused join programs take minutes to compile on the chip (PR 21: Q3's
    one DAG program 148 s) — without a disk cache EVERY fresh process
    pays that before its first distributed join answers. With it, a
    second cold process deserializes the executable instead of
    recompiling (13.9 s vs 411 s of compile over the whole smoke).

    ``JAX_COMPILATION_CACHE_DIR`` places the cache from outside: when it
    is set JAX already reads it and no directory is set in code.
    Otherwise the cache sits at ``<checkout>/.jax_cache`` — a FIXED path
    derived from the package location (the path is part of the cache
    key, so a directory that moves never hits). What is worth
    persisting is JAX's own threshold
    (``jax_persistent_cache_min_compile_time_secs``, 1 s): on the chip
    a 2 s floor left the 1-2 s scan programs recompiling in every
    process. Returns the directory in effect."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update(
            "jax_compilation_cache_dir", DEFAULT_COMPILE_CACHE_DIR
        )
    return jax.config.jax_compilation_cache_dir


# Process-lifetime platform-demotion count (the r04/r05 class: a cluster
# configured for TPU silently answering from CPU). Module-level because
# the per-executor counter dies with its executor, and the exporter's
# counter must stay monotone across executor recycles.
PLATFORM_DEMOTIONS_TOTAL = [0]


class FusedExecutor:
    """Compiles eligible partial-agg fragments to one shard_map program."""

    def __init__(self, catalog, node_stores, mesh: Optional[Mesh] = None):
        enable_compile_cache()
        self.catalog = catalog
        self.node_stores = node_stores
        self.mesh = mesh if mesh is not None else build_mesh()
        self.cache = DeviceCache(self.mesh)
        self._programs: dict = {}
        self._dag = None  # lazy DagRunner (executor/fused_dag.py)
        # Pallas programs demoted to the XLA path by a lowering/runtime
        # failure. Loud on purpose (VERDICT r1 §weak-7): a silent
        # demotion would hide a kernel regression behind a
        # slower-but-correct fallback. Exposed via pg_stat_pallas, a
        # warning-level server log record (pg_cluster_logs), and the
        # otb_pallas_demotions_total exporter counter — the r04/r05
        # silent-CPU-run bug class must show on a scrape.
        self.pallas_fallbacks: list[str] = []
        self.pallas_demotions = 0  # monotone counter (exporter)
        # session GUC shadows (the engine writes them under the fused
        # gate, so a program is built under its holder's values): join
        # formulation override + the spill-aware planner's HBM budget
        # (plan/batchplan.py)
        self.join_mode = "auto"
        self.device_memory_limit = 0
        # Unexpected exceptions that demoted a fused/DAG query to the
        # host path (VERDICT r2 §weak-3: the blanket except must not be
        # invisible). Exposed via pg_stat_fused; the monotone counter
        # feeds the exporter (the bounded list clamps at 64).
        self.dag_demotions: list[str] = []
        self.dag_demotion_count = 0
        # statements the device path ANSWERED (single-fragment or DAG).
        # Every way a statement ends up on the host executor instead —
        # an unsupported shape, a group-capacity overflow, a demotion —
        # leaves this flat, so a measurement can tell "rows came back"
        # from "the device produced them" (pg_stat_fused).
        self.fused_statements = 0
        # device-platform watchdog: r04/r05 ran platform=cpu for a
        # TPU-configured cluster and the only warning fired ONCE at
        # executor creation. Every run now stamps the platform it
        # actually executed on; a mismatch with the configured
        # expectation bumps a counter and elogs the FIRST time it
        # happens mid-run, so a lost chip is observable within one
        # statement instead of at bench time. The expectation is the
        # expected_device_platform GUC — explicit, never inferred
        # ('' = no expectation, watchdog off).
        self.expected_platform = ""
        self.last_run_platform: Optional[str] = None
        self.platform_demotions = 0  # monotone counter (exporter)
        self._platform_warned = False
        # zone-map pruning on the DEVICE path (VERDICT r2 missing-5):
        # blocks excluded from the scanned window per fused query
        self.zone_stats = {"pruned_blocks": 0, "total_blocks": 0}
        # launches of the MXU group reduce whose lane plan the column
        # statistics narrowed by at least one lane / by none
        # (pg_stat_fused mxu_plans_bounded / mxu_plans_full)
        self.mxu_plans = {"bounded": 0, "full": 0}
        # joins the radix gate's estimates admitted and the shape rule
        # (fused_dag._lookup_radix: a table only where dimension-sized)
        # sent to sort-merge, counted as each program is traced: once a
        # compiled program, never on a cached re-bind
        # (pg_stat_fused radix_sized_out)
        self.radix_sized_out = 0
        # joins with more than one key pair the DAG runner took, once a
        # traced program that holds one (pg_stat_fused multi_key_joins)
        self.multi_key_joins = 0
        # folds by where their match bit comes from, once a traced
        # program that holds one: riding in a build column the joined
        # row's readers gather anyway, or gathered on its own (a
        # dimension the plan only filters by; fused_dag._lookup_dense;
        # pg_stat_fused fold_bits_carried / fold_bits_own)
        self.fold_bits = {"carried": 0, "own": 0}
        # accepted grouped finals of the DAG by formulation: addressed
        # directly by the packed key, or sorted because the key's range
        # or the aggregates' kinds left no choice (fused_dag._run_final;
        # pg_stat_fused grouped_direct / grouped_sorted)
        self.grouped_direct = 0
        self.grouped_sorted = 0
        # accepted finals of the one-sort grouped top-k (gagg), and the
        # group keys its programs left out of the packed sort key because
        # a kept key determines them (fused_dag._fd_reduce), once a
        # traced program (pg_stat_fused gagg_finals / gagg_keys_dropped)
        self.gagg_finals = 0
        self.gagg_keys_dropped = 0
        self._mxu_binds: dict = {}  # id(plan) -> (plan, stats, _MxuBind)
        # the statement path's one way to call a jitted program
        # (fused.launch span, launch/retry accounting); the DAG runner
        # shares it
        self.launch = Launcher()

    def dag_output(self, dplan, snapshot_ts, dicts_view, subquery_values):
        """Run a whole multi-fragment plan (joins + exchanges + partial
        agg) on the mesh. Returns (final_fragment_index, batch) or None
        when the plan is outside the fused DAG subset."""
        from opentenbase_tpu.executor.fused_dag import DagRunner

        if self._dag is None:
            self._dag = DagRunner(self)
        return self._dag.run(dplan, snapshot_ts, dicts_view, subquery_values)

    def _note_pallas_failure(self, key) -> None:
        import traceback

        from opentenbase_tpu.obs.log import elog

        if str(key) not in self.pallas_fallbacks:
            self.pallas_fallbacks.append(str(key))
        self.pallas_demotions += 1
        _log.warning(
            "pallas kernel demoted to XLA path for %s:\n%s",
            key,
            traceback.format_exc(),
        )
        # the server log an operator actually tails (pg_cluster_logs) —
        # the python logger above is developer-side only
        elog(
            "warning", "device",
            f"pallas kernel demoted to XLA path for {key}",
            demotions=self.pallas_demotions,
        )

    def platform(self) -> str:
        """The mesh's device platform ('tpu'/'cpu'/...) — the exporter
        gauge that makes an r04/r05-style silent CPU run visible on a
        scrape instead of in a bench JSON post-mortem."""
        return str(self.mesh.devices.flat[0].platform)

    def note_run_platform(self) -> str:
        """Watchdog: stamp the platform THIS run actually executed on.
        Called once per successful fused run (DagRunner._run for DAG
        plans, the engine's fused wrapper for single-fragment ones).
        A run on anything but the configured platform bumps the
        demotion counters and elogs a warning the first time — the
        continuous signal the one-shot creation warning never gave."""
        plat = self.platform()
        self.last_run_platform = plat
        expected = self.expected_platform
        if expected and plat != expected:
            self.platform_demotions += 1
            PLATFORM_DEMOTIONS_TOTAL[0] += 1
            if not self._platform_warned:
                self._platform_warned = True
                from opentenbase_tpu.obs.log import elog

                elog(
                    "warning", "device",
                    f"device platform demoted: cluster configured for "
                    f"'{expected}' but this run executed on '{plat}'",
                    demotions=self.platform_demotions,
                )
        return plat

    # -- eligibility -----------------------------------------------------
    def fragment_output(
        self,
        frag: Fragment,
        snapshot_ts: Optional[int],
        dicts_view,
        subquery_values,
        group_cap: int = DEFAULT_GROUP_CAP,
        use_pallas: bool = True,
    ) -> Optional[ColumnBatch]:
        """If the fragment is fusable, compute its gathered output batch
        (what the motion would deliver to the coordinator). Returns None
        when not eligible; raises FusedUnsupported mid-way only for
        overflow (caller falls back)."""
        if frag.motion != "gather":
            return None
        self.launch.begin()
        # hash-slot grouping addresses by hash & (cap-1)
        group_cap = 1 << max(group_cap - 1, 1).bit_length()
        m = _match_partial_fragment(frag.root)
        if m is None:
            return None
        meta = self.catalog.get(m.scan.table)
        if tuple(meta.node_indices) != tuple(frag.nodes):
            return None
        for n in frag.nodes:
            if m.scan.table not in self.node_stores.get(n, {}):
                return None

        # bigger-than-HBM tables STREAM: shard-row windows run through
        # one windowed program sequentially; partial outputs concat and
        # the coordinator merge combines them exactly like any other
        # partials (reference: work_mem batching — nodeHash.c
        # ExecHashIncreaseNumBatches, tuplestore.c spill)
        if self._resident_bytes(meta, m.scan.columns) > SCAN_HBM_BUDGET:
            return self._fragment_chunked(
                m, meta, snapshot_ts, dicts_view, subquery_values,
                group_cap,
            )

        dtab = self.cache.get(
            m.scan.table, meta, self.node_stores, columns=m.scan.columns
        )
        if use_pallas:
            out = self._try_pallas(m, dtab, snapshot_ts)
            if out is not None:
                return out

        # BRIN-style pruning ON DEVICE: the predicate's zone-map envelope
        # becomes a dynamic-slice row window per shard, so the program
        # reads only candidate blocks from HBM instead of the full
        # padded width (reference: src/backend/access/brin/brin.c — the
        # host LocalExecutor got this in r2, the fused path now too)
        zone = self._zone_window(m, meta, dtab)
        return self._run_xla_fragment(
            m, meta, dtab, zone, snapshot_ts, dicts_view,
            subquery_values, group_cap,
        )

    def _run_xla_fragment(
        self, m, meta, dtab, zone, snapshot_ts, dicts_view,
        subquery_values, group_cap,
    ) -> ColumnBatch:
        has_valid = tuple(
            dtab.validity[c] is not None for c in m.scan.columns
        )

        def run_mode(grouping: str, cap: int = group_cap):
            win = zone[1] if zone is not None else None
            with _span(None, "fused.bind", "bind_ms", cat="fused") as bsp:
                # structural key: literals are lifted to params, so
                # queries differing only in constants reuse the
                # compiled program (m.agg IS the fragment root — the
                # match requires it topmost)
                try:
                    skey = plan_skey(m.agg)
                except NotImplementedError:
                    skey = m.agg.key()
                # the MXU group reduce cuts as many limb lanes as the
                # table's CURRENT statistics say the values need: like
                # _try_pallas's sig, the certified widths are part of
                # the key, so data grown past a limb boundary binds a
                # wider program and never reuses a narrower one
                mxu = (
                    self._mxu_bounds(m, dtab, has_valid)
                    if grouping == "hash" else None
                )
                mxu_bounds = None
                if mxu is not None:
                    mxu_bounds = mxu.bounds
                    bsp.set(lanes=mxu.lanes, lanes_full=mxu.lanes_full)
                key = (
                    skey, dtab.rmax, len(dtab.nrows), cap, has_valid,
                    grouping, win, mxu_bounds,
                )
                # the structural key masks literal values; the
                # compile-time param specs BAKE them. Rebuild the
                # (lazily-jitted, cheap) compile output for THIS query
                # and pair the cached executable with the fresh specs —
                # otherwise 'x = 1' silently reuses 'x = 7''s parameter
                fresh = self._compile(
                    m, meta, dtab, cap, has_valid, grouping, win=win,
                    mxu_bounds=mxu_bounds,
                )
                cached = self._programs.get(key)
                bsp.set(cache="miss" if cached is None else "hit")
                if cached is None:
                    self._programs[key] = fresh
                    cached = fresh
                program = cached[0]
                bsp.set(program=program.__name__)
                _prog_unused, param_specs, out_info = fresh
                params = tuple(
                    resolve_param(s, dicts_view, subquery_values)
                    for s in param_specs
                )
                col_args = tuple(dtab.columns[c] for c in m.scan.columns)
                # only pass validity arrays that exist; presence is
                # static in the compiled program (materializing
                # all-ones masks for every all-valid column would
                # stream megabytes per call)
                val_args = tuple(
                    dtab.validity[c]
                    for c in m.scan.columns
                    if dtab.validity[c] is not None
                )

            def args():
                snap = jnp.int64(
                    snapshot_ts if snapshot_ts is not None else 2**61
                )
                nrows_dev = jnp.asarray(dtab.nrows)
                extra = () if zone is None else (jnp.asarray(zone[0]),)
                return (
                    col_args, val_args, dtab.xmin, dtab.xmax, nrows_dev,
                    *extra, snap, params,
                )

            mode = f"{grouping}/{cap}"
            if mxu is not None:
                mode += f"/k{mxu.lanes}"
                self.mxu_plans["bounded" if mxu.narrowed else "full"] += 1
            outs = self.launch(program, args, mode=mode)
            return self._collect(m, outs, out_info, cap, dtab)

        def is_collision(e):
            return "collision" in str(e)

        # capacity ladder: a small slot table first (the one-hot matmul
        # cost scales with cap, and most GROUP BYs have few groups),
        # then the full capacity, then the sort-based device program
        try:
            return run_mode("hash", min(64, group_cap))
        except FusedUnsupported as e:
            if not is_collision(e):
                raise
            self.launch.note_retry("hash collision, bigger cap")
        try:
            return run_mode("hash", group_cap)
        except FusedUnsupported as e:
            if not is_collision(e):
                raise
            self.launch.note_retry("hash collision, sort grouping")
            return run_mode("sort", group_cap)

    def _mxu_bounds(
        self, m: _FusablePartial, dtab: DeviceTable, has_valid
    ) -> Optional["_MxuBind"]:
        """The MXU group reduce's lane plan for this fragment against
        the table's CURRENT statistics, or None where that reduce does
        not run. Asked on every bind; answered from the last answer for
        the same plan object (the plan cache hands it back) while the
        statistics read the same."""
        stats = (
            tuple(dtab.col_maxabs.get(c) for c in m.scan.columns),
            tuple(dtab.col_range.get(c) for c in m.scan.columns),
            has_valid,
        )
        memo = self._mxu_binds.get(id(m.agg))
        if memo is not None and memo[0] is m.agg and memo[1] == stats:
            return memo[2]
        bind = self._mxu_bind(m, *stats)
        if len(self._mxu_binds) >= 256:
            self._mxu_binds.clear()
        # the entry keeps its plan alive, so the id is not reused
        self._mxu_binds[id(m.agg)] = (m.agg, stats, bind)
        return bind

    def _mxu_bind(
        self, m: _FusablePartial, col_bounds, col_ranges, has_valid
    ) -> Optional["_MxuBind"]:
        """What the statistics certify about a grouped fragment's keys
        and sum arguments, quantised to limb counts for
        ``ops/agg.mxu_lane_plan`` — or None where the MXU group reduce
        does not run (ungrouped, min/max, float keys or sums).

        The inputs are the Pallas certifier's: |max| per scan column
        (``col_maxabs``, ``col_range``; kept current by uploads and
        delta tails, over real rows only) through the interval
        arithmetic of ``pallas_scan.bound``. Where that says nothing (a
        division, a CASE, a float, a window table without statistics)
        the value keeps its dtype's width: the same plan, not another
        path."""
        from opentenbase_tpu.ops import pallas_scan as ps
        from opentenbase_tpu.plan import texpr as E

        if not m.agg.group_exprs:
            return None
        specs = []
        for a in m.agg.aggs:
            if a.func not in ("sum", "count"):
                return None
            if a.func == "sum" and _is_float(a.arg.type):
                return None
            specs.append(
                "count_star" if a.arg is None else a.func
            )
        if any(_is_float(g.type) for g in m.agg.group_exprs):
            return None
        _preds, key_exprs, arg_exprs = m.scan_exprs()

        def limbs(e, out_dict=None):
            if isinstance(e, E.Col):
                # a bare column: its physical range, whatever its SQL
                # type (dictionary codes, dates) — unless a text key is
                # re-coded into another dictionary on the way
                src_dict = m.scan.schema[e.index].dict_id
                if e.type.is_text and out_dict != src_dict:
                    return None
                return agg_ops.limbs_for_range(col_ranges[e.index])
            return agg_ops.limbs_for_bound(ps.bound(e, col_bounds))

        def col(e, ty):
            nullable = any(
                isinstance(x, E.Col) and has_valid[x.index]
                for x in E.walk(e)
            )
            return ty.np_dtype, nullable

        key_limbs = tuple(
            limbs(e, oc.dict_id)
            for e, oc in zip(key_exprs, m.agg.schema)
        )
        ids: dict = {}
        arg_ids = tuple(
            None if e is None else ids.setdefault(e, len(ids))
            for e in arg_exprs
        )
        summed = {
            e for e, spec in zip(arg_exprs, specs) if spec == "sum"
        }
        bounds = agg_ops.MxuBounds(
            key_limbs, arg_ids,
            tuple(limbs(e) if e in summed else None for e in ids),
        )
        key_cols = [
            col(e, g.type) for e, g in zip(key_exprs, m.agg.group_exprs)
        ]
        arg_cols = [
            None if e is None else col(e, a.arg.type)
            for e, a in zip(arg_exprs, m.agg.aggs)
        ]
        lanes = len(
            agg_ops.mxu_lane_plan(key_cols, specs, arg_cols, bounds).lanes
        )
        unbounded = agg_ops.MxuBounds(
            (None,) * len(key_limbs), arg_ids, (None,) * len(ids)
        )
        return _MxuBind(
            bounds, lanes,
            agg_ops.mxu_lanes_dtype_wide(key_cols, specs, arg_cols),
            lanes < len(agg_ops.mxu_lane_plan(
                key_cols, specs, arg_cols, unbounded
            ).lanes),
        )

    def _scan_footprint(self, meta, columns) -> tuple[int, int, int, int]:
        """(resident_bytes, row_bytes, S, max_shard_rows) for caching a
        table's scan columns (+16B/row of MVCC timestamps) at padded
        width — the ONE footprint model the chunk trigger and the window
        sizing both use. A table already device-resident with every
        wanted column (e.g. a register_external table: exact row
        capacity, compact [S,1] MVCC planes) reports its ACTUAL bytes —
        the padded-width estimate would overstate it and bounce the
        scan onto the chunked path its stub stores can't serve."""
        row_bytes = 16 + sum(
            np.dtype(meta.schema[c].np_dtype).itemsize + 1
            for c in columns
        )
        mx = 0
        for n in meta.node_indices:
            s = self.node_stores.get(n, {}).get(meta.name)
            if s is not None:
                mx = max(mx, s.nrows)
        S = _pad_shards(len(meta.node_indices), self.mesh.shape["dn"])
        dt = self.cache._tables.get(
            (meta.name, tuple(meta.node_indices))
        )
        if dt is not None and all(c in dt.columns for c in columns):
            actual = sum(
                dt.columns[c].nbytes
                + (
                    dt.validity[c].nbytes
                    if dt.validity.get(c) is not None else 0
                )
                for c in columns
            ) + dt.xmin.nbytes + dt.xmax.nbytes
            return actual, row_bytes, S, mx
        rmax = filt_ops.bucket_size(max(mx, 1))
        return S * rmax * row_bytes, row_bytes, S, mx

    def _resident_bytes(self, meta, columns) -> int:
        return self._scan_footprint(meta, columns)[0]

    def _fragment_chunked(
        self, m, meta, snapshot_ts, dicts_view, subquery_values,
        group_cap,
    ) -> ColumnBatch:
        """Stream a bigger-than-HBM scan: fixed-width shard-row windows
        upload, run the (same, cached) windowed program, and free; the
        concatenated window partials are ordinary partial-agg rows the
        coordinator merge combines. Pallas and zone windows are skipped
        here — the streaming upload dominates and the window program is
        already minimal."""
        from opentenbase_tpu.executor.dist import concat_batches

        _bytes, row_bytes, S, mx = self._scan_footprint(
            meta, m.scan.columns
        )
        budget_rows = max(
            SCAN_HBM_BUDGET // max(S * row_bytes, 1), 4096
        )
        W = filt_ops.bucket_size(budget_rows)
        if W > budget_rows:
            W //= 2  # bucket rounding must not overshoot the budget
        parts: list[ColumnBatch] = []
        start = 0
        nchunks = 0
        while start < mx:
            dtab = self.cache.get_window(
                meta.name, meta, self.node_stores,
                tuple(meta.node_indices), tuple(m.scan.columns),
                start, W,
            )
            parts.append(
                self._run_xla_fragment(
                    m, meta, dtab, None, snapshot_ts, dicts_view,
                    subquery_values, group_cap,
                )
            )
            start += W
            nchunks += 1
        self.cache.stats["chunked_scans"] = (
            self.cache.stats.get("chunked_scans", 0) + 1
        )
        self.cache.stats["scan_chunks"] = (
            self.cache.stats.get("scan_chunks", 0) + nchunks
        )
        if not parts:
            return self._run_xla_fragment(
                m, meta,
                self.cache.get_window(
                    meta.name, meta, self.node_stores,
                    tuple(meta.node_indices), tuple(m.scan.columns),
                    0, 1,
                ),
                None, snapshot_ts, dicts_view, subquery_values,
                group_cap,
            )
        return concat_batches(parts)

    def _zone_window(self, m: "_FusablePartial", meta, dtab):
        """Per-shard contiguous row window covering every zone-map
        candidate block for the fragment's scan predicate. Returns
        (starts [S] int32, W) with W a bucketed static width < rmax, or
        None when pruning wins nothing. Correctness never depends on the
        window — rows inside it still pass through the real predicate;
        rows outside are PROVEN non-matching by the block min/max."""
        if not getattr(meta, "zone_cols", None):
            return None
        if not m.steps or not isinstance(m.steps[0], L.Filter):
            return None
        from opentenbase_tpu.executor.local import _predicate_bounds
        from opentenbase_tpu.ops import filter as filt_ops
        from opentenbase_tpu.storage.table import (
            zone_candidate_blocks,
            zone_usable_bounds,
        )

        bounds = _predicate_bounds(m.steps[0].predicate, m.scan)
        usable = zone_usable_bounds(bounds, meta, m.scan)
        if not usable:
            return None
        starts: list[int] = []
        lens: list[int] = []
        total = pruned = 0
        for node in meta.node_indices:
            store = self.node_stores.get(node, {}).get(m.scan.table)
            if store is None:
                return None
            B = store.ZONE_BLOCK
            nb = -(-store.nrows // B) if store.nrows else 0
            cand = zone_candidate_blocks(store, usable)
            total += nb
            idx = np.nonzero(cand)[0]
            if len(idx) == 0:
                starts.append(0)
                lens.append(0)
                pruned += nb
            else:
                lo_b, hi_b = int(idx[0]), int(idx[-1]) + 1
                starts.append(lo_b * B)
                lens.append(
                    min(hi_b * B, store.nrows) - lo_b * B
                )
                pruned += nb - (hi_b - lo_b)
        W = filt_ops.bucket_size(max(max(lens, default=1), 1))
        if W >= dtab.rmax:
            return None  # window as wide as the scan: no bandwidth win
        self.zone_stats["total_blocks"] += total
        self.zone_stats["pruned_blocks"] += pruned
        S = len(dtab.nrows)
        arr = np.zeros(S, dtype=np.int32)
        arr[: len(starts)] = np.minimum(
            np.asarray(starts, dtype=np.int32),
            max(dtab.rmax - W, 0),  # clamp: slice stays in-bounds and
            # only ever widens the window leftward (extra rows simply
            # fail the predicate)
        )
        return arr, W

    # -- pallas fast path (ops/pallas_scan.py) ---------------------------
    def _try_pallas(
        self, m: _FusablePartial, dtab: DeviceTable, snapshot_ts
    ) -> Optional[ColumnBatch]:
        """Route an eligible filter+SUM/COUNT fragment — ungrouped, or
        grouped by small-domain keys (TPC-H Q1's shape) — through the
        Pallas single-pass kernel. Eligibility is decided by the f32
        certifier against host-side column stats; anything else returns
        None and the XLA-fused program runs instead. Requires one shard
        per mesh device (the standard deployment shape)."""
        from opentenbase_tpu.ops import pallas_scan as ps

        S = len(dtab.nrows)
        if S % self.mesh.shape["dn"] != 0:
            return None
        if any(dtab.validity[c] is not None for c in m.scan.columns):
            return None
        with _span(None, "fused.bind", "bind_ms", cat="fused") as bsp:
            # re-certify against CURRENT column stats on every call:
            # data growth can push values past the f32-exactness bound,
            # and a previously-compiled program must not keep running
            # then. The certification outcome (incl. which products
            # limb-split and the group-key domain) is part of the cache
            # key, so a bound change recompiles or falls back rather
            # than reusing a stale program.
            col_bounds = [dtab.col_maxabs.get(c) for c in m.scan.columns]
            col_ranges = [dtab.col_range.get(c) for c in m.scan.columns]
            try:
                preds, agg_args, group_plan, sig = self._pallas_plan(
                    m, col_bounds, col_ranges
                )
            except ps.PallasUnsupported:
                bsp.set(cache="unsupported")
                return None
            key = ("pallas", m.agg.key(), dtab.rmax, S, sig)
            cached = self._programs.get(key)
            bsp.set(cache="miss" if cached is None else "hit")
            if cached is None:
                try:
                    cached = self._compile_pallas(
                        m, dtab, preds, agg_args, group_plan
                    )
                except ps.PallasUnsupported:
                    cached = False
                self._programs[key] = cached
            if cached is False:
                return None
            program, layout, n_exprs, specs = cached
            bsp.set(program=program.__name__)
            decoders, n_groups = (
                (group_plan[1], group_plan[2]) if group_plan
                else (None, 1)
            )
            cols = tuple(dtab.columns[c] for c in m.scan.columns)

        def args():
            snap = jnp.int64(
                snapshot_ts if snapshot_ts is not None else 2**61
            )
            return cols, dtab.xmin, dtab.xmax, jnp.asarray(dtab.nrows), snap

        try:
            partials = fetch(
                self.launch(program, args, mode=f"pallas/{n_groups}"),
                "result",
            )
        except Exception:
            # pallas lowering/runtime failure: XLA path takes over
            self._programs[key] = False
            self._note_pallas_failure(key)
            self.launch.note_retry("pallas failed, xla program")
            return None
        with _span(None, "fused.collect", "collect_ms", cat="fused") as csp:
            sums, counts = ps.combine_partials(
                partials, layout, n_exprs, n_groups
            )
            if decoders is None:
                out = self._pallas_scalar_batch(
                    m, sums[:, 0], counts[:, 0], specs, S
                )
            else:
                out = self._pallas_grouped_batch(
                    m, sums, counts, specs, decoders, S, n_groups
                )
            csp.set(rows=out.nrows)
        return out

    def _pallas_scalar_batch(self, m, sums, counts, specs, S) -> ColumnBatch:
        # per-shard partial rows, matching the XLA scalar path's output
        # contract (the coordinator's merge aggs combine them)
        cols_out: dict[str, Column] = {}
        e = 0
        for oc, spec in zip(m.agg.schema, specs):
            if spec in ("count_star", "count"):
                d = counts.astype(np.int64)
                v = np.ones(S, dtype=bool)
            else:  # sum
                d = sums[:, e].astype(oc.type.np_dtype)
                v = counts > 0
                e += 1
            cols_out[oc.name] = Column(oc.type, d, v, None)
        return ColumnBatch(cols_out, S)

    def _pallas_grouped_batch(
        self, m, sums, counts, specs, decoders, S, n_groups
    ) -> ColumnBatch:
        """[S, G] grouped partials -> (shard, group) partial rows with
        count > 0, keys decoded from the dense joint index."""
        keep = counts > 0  # [S, G]
        sidx, gidx = np.nonzero(keep)
        nkeys = len(m.agg.group_exprs)
        cols_out: dict[str, Column] = {}
        for i, oc in enumerate(m.agg.schema[:nkeys]):
            _ci, lo, domain, stride = decoders[i]
            vals = (lo + (gidx // stride) % domain).astype(oc.type.np_dtype)
            dic = self.catalog.dictionary(oc.dict_id) if oc.dict_id else None
            cols_out[oc.name] = Column(oc.type, vals, None, dic)
        e = 0
        for oc, spec in zip(m.agg.schema[nkeys:], specs):
            if spec in ("count_star", "count"):
                d = counts[sidx, gidx].astype(np.int64)
            else:  # sum
                d = sums[sidx, gidx, e].astype(oc.type.np_dtype)
                e += 1
            cols_out[oc.name] = Column(oc.type, d, None, None)
        return ColumnBatch(cols_out, len(sidx))

    def _pallas_plan(self, m: _FusablePartial, col_bounds, col_ranges):
        """Inline the Filter/Project chain to scan-schema expressions and
        certify them against current column bounds. Returns
        (preds, agg_args, group_plan, sig) where sig captures every
        certification decision (so the compiled-program cache key
        reflects it) and group_plan is None (ungrouped) or
        (key_exprs, decoders, n_groups).
        Raises PallasUnsupported when outside the certified subset."""
        from opentenbase_tpu.ops import pallas_scan as ps

        preds, key_exprs, arg_exprs = m.scan_exprs()
        for p in preds:
            if not ps.certify_predicate(p, col_bounds):
                raise ps.PallasUnsupported("predicate")
        group_plan = None
        sig_parts: list = []
        if key_exprs:
            _key_fn, decoders, n_groups = ps.plan_group_keys(
                key_exprs, col_ranges
            )
            group_plan = (key_exprs, decoders, n_groups)
            sig_parts.append(("groups", tuple(decoders)))
        agg_args: list = []
        for a, arg in zip(m.agg.aggs, arg_exprs):
            if a.func == "count":
                if arg is not None:
                    # count(expr) == count(*) only when expr can never be
                    # NULL: columns have no validity masks here (gated
                    # above) AND the expression stays in the bounded
                    # arithmetic subset — nullif/division/CASE produce
                    # dynamic NULLs and must keep the XLA path
                    if ps.bound(arg, col_bounds) is None:
                        raise ps.PallasUnsupported("nullable count arg")
                agg_args.append(None)
                sig_parts.append("count")
                continue
            if a.func != "sum":
                raise ps.PallasUnsupported(a.func)
            dec = ps.decompose_value(arg, col_bounds)
            if dec is None:
                raise ps.PallasUnsupported("value bound")
            agg_args.append((arg, dec))
            sig_parts.append(f"sum{len(dec)}")
        return preds, agg_args, group_plan, tuple(sig_parts)

    def _compile_pallas(
        self, m: _FusablePartial, dtab: DeviceTable, preds, agg_args,
        group_plan,
    ):
        from opentenbase_tpu.ops import pallas_scan as ps

        specs: list[str] = []
        layout: list[tuple[int, float]] = []
        val_fns: list = []
        n_exprs = 0
        for entry in agg_args:
            if entry is None:
                specs.append("count_star")
                continue
            _arg, dec = entry
            for fn, scale in dec:
                val_fns.append(fn)
                layout.append((n_exprs, scale))
            specs.append("sum")
            n_exprs += 1
        if preds:
            pred_fns = [ps.compile_f32(p) for p in preds]

            def mask_fn(blk):
                msk = pred_fns[0](blk)
                for f in pred_fns[1:]:
                    msk = msk & f(blk)
                return msk
        else:
            def mask_fn(blk):
                return jnp.ones(blk[0].shape, dtype=jnp.bool_)

        key_fn, n_groups = None, 1
        if group_plan is not None:
            _key_exprs, decoders, n_groups = group_plan
            key_fn = ps.key_fn_from_decoders(decoders)

        # interpret mode means "not a TPU mesh" and nothing else: on a
        # TPU mesh the kernel is compiled by Mosaic or it is an error
        interpret = self.platform() != "tpu"
        n_in = len(m.scan.columns) + 1  # + live-mask column
        run = ps.build_partials(
            n_in, mask_fn, val_fns, interpret=interpret,
            key_fn=key_fn, n_groups=n_groups,
        )
        mesh = self.mesh
        rmax = dtab.rmax

        def program(cols, xmin, xmax, nrows, snap):
            # visibility in XLA (int64 timestamps are not pallas
            # material); the kernel consumes it as an f32 column
            with scope("scan/mvcc"):
                live = (
                    (jnp.arange(rmax)[None, :] < nrows[:, None])
                    & (xmin <= snap)
                    & (snap < xmax)
                ).astype(jnp.float32)

            def block(cols, live):
                # [k, Rmax] per device (k shards per device): flatten
                # the local shards into one row axis — one pallas grid
                # per device, no vmap-of-pallas composition
                with scope("scan/decode"):
                    blk = [
                        c.reshape(-1).astype(jnp.float32) for c in cols
                    ]
                    blk.append(live.reshape(-1))
                return run(blk)[None]

            return shard_map(
                block,
                mesh=mesh,
                in_specs=(tuple(P("dn") for _ in cols), P("dn")),
                out_specs=P("dn"),
                check_vma=False,  # pallas_call carries no vma info
            )(cols, live)

        return (
            named_program(program, "program_scan_pallas"), layout,
            n_exprs, specs,
        )

    # -- compilation -----------------------------------------------------
    def _compile(
        self, m: _FusablePartial, meta, dtab: DeviceTable, group_cap,
        has_valid, grouping: str = "hash", win: Optional[int] = None,
        mxu_bounds=None,
    ):
        comp = ExprCompiler(lift_consts=True)
        scan_dids = [c.dict_id for c in m.scan.schema]

        # compile the filter/project chain
        step_fns = []
        cur_schema = m.scan.schema
        for step in m.steps:
            dids = [c.dict_id for c in cur_schema]
            if isinstance(step, L.Filter):
                step_fns.append(("filter", comp.compile(step.predicate, dids)))
            else:
                want = [c.dict_id for c in step.schema]
                fns = [
                    comp.compile(
                        e, dids, (w or None) if e.type.is_text else None
                    )
                    for e, w in zip(step.exprs, want)
                ]
                step_fns.append(("project", fns))
            cur_schema = step.schema

        dids = [c.dict_id for c in cur_schema]
        gfns = [comp.compile(g, dids) for g in m.agg.group_exprs]
        specs: list[str] = []
        afns: list = []
        for a in m.agg.aggs:
            if a.func == "count" and a.arg is None:
                specs.append("count_star")
                afns.append(None)
            elif a.func in ("sum", "count", "min", "max"):
                if a.func in ("min", "max") and a.arg.type.is_text:
                    # dictionary codes are insertion-ordered, not
                    # collation-ordered: device min/max over codes
                    # would be wrong — the host path ranks first
                    raise FusedUnsupported(f"{a.func} over text")
                specs.append(a.func)
                afns.append(comp.compile(a.arg, dids))
            else:
                raise FusedUnsupported(a.func)
        grouped = bool(m.agg.group_exprs)
        nkeys = len(m.agg.group_exprs)

        rmax0 = dtab.rmax

        def per_device(
            cols, valids, xmin, xmax, nrows, snap, params, starts=None,
        ):
            # one device's k local shards, FLATTENED to a single row
            # axis: [k, Rmax] -> [k*Rmax]. Partial-agg semantics don't
            # care whether partials are per shard or per device — the
            # coordinator merge re-aggregates either way — and a flat
            # pipeline avoids vmap-of-scan/einsum compositions that XLA
            # lowers poorly on TPU. Visibility planes arrive either
            # full [k, Rmax] or compact [k, 1] (uniform per shard) —
            # the 2-D compare broadcasts the compact form for free.
            k = xmin.shape[0]
            rmax = rmax0
            compact = xmin.shape[1] == 1
            if starts is not None:
                # zone-map window: read only the candidate-block slice
                # of each shard from HBM (dynamic start, static width)
                def sl(a2d):
                    return jax.vmap(
                        lambda row, st: jax.lax.dynamic_slice(
                            row, (st,), (win,)
                        )
                    )(a2d, starts)

                with scope("scan/decode"):
                    cols = [sl(c) for c in cols]
                    valids = [sl(v) for v in valids]
                    if not compact:
                        xmin = sl(xmin)
                        xmax = sl(xmax)
                nrows = jnp.clip(
                    nrows - starts.astype(nrows.dtype), 0, win
                )
                rmax = win
            n = k * rmax
            with scope("scan/mvcc"):
                live = (
                    (jnp.arange(rmax)[None, :] < nrows[:, None])
                    & (xmin <= snap) & (snap < xmax)
                ).reshape(n)
            cols = [c.reshape(n) for c in cols]
            valids = [v.reshape(n) for v in valids]
            env = []
            vi = 0
            for ci, d in enumerate(cols):
                if has_valid[ci]:
                    env.append((d, valids[vi]))
                    vi += 1
                else:
                    env.append((d, None))
            mask = live
            for kind, fn in step_fns:
                if kind == "filter":
                    with scope("scan/predicate"):
                        d, v = fn(env, params)
                        keep = d if v is None else (d & v)
                        mask = mask & jnp.broadcast_to(keep, (n,))
                else:
                    with scope("scan/project"):
                        env = [
                            _bcast(f(env, params), n) for f in fn
                        ]
            with scope("agg/args"):
                keys = [_bcast(fn(env, params), n) for fn in gfns]
                vals = [
                    None if fn is None else _bcast(fn(env, params), n)
                    for fn in afns
                ]
            if not grouped:
                with scope("agg/scalar"):
                    outs = agg_ops._scalar_reduce_impl(
                        vals, mask, tuple(specs)
                    )
                return (
                    [],
                    [(jnp.reshape(d, (1,)), jnp.reshape(v, (1,))) for d, v in outs],
                    jnp.ones(1, jnp.bool_),
                    jnp.int32(1),
                    jnp.asarray(False),
                )
            if grouping == "hash":
                # hash-addressed grouping: one linear pass instead of
                # the sort path's O(k) argsorts; collisions (incl. >cap
                # groups) are detected exactly and the caller reruns
                # the sort variant
                if agg_ops.mxu_group_eligible(keys, vals, specs):
                    # scatter-free: one-hot matmuls on the MXU (TPU
                    # scatter/sort are orders of magnitude slower);
                    # its limb and one-hot stages carry their own
                    # scopes (ops/agg.py)
                    with scope("agg/hashslot"):
                        slot, _p64, _vis = agg_ops._hash_slot_ids(
                            keys, mask, group_cap
                        )
                    return agg_ops._mxu_group_reduce_impl(
                        keys, vals, slot, group_cap, tuple(specs),
                        mxu_bounds,
                    )
                with scope("agg/hashslot"):
                    slot, ngroups, collision = agg_ops._hash_slots_impl(
                        keys, mask, group_cap
                    )
                with scope("agg/segreduce"):
                    out_keys, out_vals, gvalid = (
                        agg_ops._group_reduce_impl(
                            keys, vals, jnp.arange(n, dtype=jnp.int32),
                            slot, group_cap, tuple(specs),
                        )
                    )
                return out_keys, out_vals, gvalid, ngroups, collision
            with scope("agg/sortgroup"):
                perm, seg, ngroups = agg_ops._group_ids_impl(keys, mask)
                out_keys, out_vals, gvalid = agg_ops._group_reduce_impl(
                    keys, vals, perm, seg, group_cap, tuple(specs)
                )
            return out_keys, out_vals, gvalid, ngroups, jnp.asarray(False)

        mesh = self.mesh

        # ONE program definition; the zone-window variant simply carries
        # one extra sharded operand (per-shard slice starts)
        def program(cols, valids, xmin, xmax, nrows, *rest):
            if win is not None:
                starts, snap, params = rest
                extra = (starts,)
            else:
                snap, params = rest
                extra = ()

            def block(cols, valids, xmin, xmax, nrows, *xtra):
                # block: [S/D, Rmax] — one flattened pipeline per device
                outs = per_device(
                    list(cols), list(valids), xmin, xmax, nrows,
                    snap, params,
                    starts=xtra[0] if xtra else None,
                )
                return jax.tree.map(lambda x: x[None], outs)

            return shard_map(
                block,
                mesh=mesh,
                in_specs=(
                    tuple(P("dn") for _ in cols),
                    tuple(P("dn") for _ in valids),
                    P("dn"),
                    P("dn"),
                    P("dn"),
                ) + tuple(P("dn") for _ in extra),
                out_specs=P("dn"),
            )(cols, valids, xmin, xmax, nrows, *extra)

        out_info = {
            "grouped": grouped, "nkeys": nkeys, "specs": specs,
            "grouping": grouping,
        }
        return (
            named_program(program, f"program_scan_xla_{grouping}"),
            comp.params, out_info,
        )

    # -- output collection ------------------------------------------------
    def _collect(self, m, outs, out_info, group_cap, dtab) -> ColumnBatch:
        # ONE batched device->host fetch: per-array np.asarray would
        # sync with the device and pay a transfer once per array
        outs = fetch(outs, "result")
        with _span(None, "fused.collect", "collect_ms", cat="fused") as csp:
            out_keys, out_vals, gvalid, ngroups, collision = outs
            grouped = out_info["grouped"]
            if grouped and bool(collision.any()):
                raise FusedUnsupported("group hash collision")
            if grouped and out_info.get("grouping") == "sort" and (
                int(ngroups.max()) >= group_cap
            ):
                # sort mode can exceed the static capacity: the general
                # executor (dynamic group count) recomputes
                raise FusedUnsupported("group capacity overflow")
            # flatten [S, cap] -> rows, keeping only valid groups
            gv = gvalid.reshape(-1)
            agg_plan = m.agg
            cols: dict[str, Column] = {}
            keep = np.nonzero(gv)[0]
            for i, oc in enumerate(agg_plan.schema):
                if i < out_info["nkeys"]:
                    d, v = out_keys[i]
                else:
                    d, v = out_vals[i - out_info["nkeys"]]
                dd = d.reshape(-1)[keep]
                vv = None if v is None else v.reshape(-1)[keep]
                dic = (
                    self.catalog.dictionary(oc.dict_id)
                    if oc.dict_id else None
                )
                ty = oc.type
                if dd.dtype != ty.np_dtype:
                    dd = dd.astype(ty.np_dtype)
                cols[oc.name] = Column(ty, dd, vv, dic)
            csp.set(rows=len(keep))
            return ColumnBatch(cols, len(keep))


def _bcast(kv, n):
    d, v = kv
    if jnp.ndim(d) == 0:
        d = jnp.broadcast_to(d, (n,))
    if v is not None and jnp.ndim(v) == 0:
        v = jnp.broadcast_to(v, (n,))
    return (d, v)
