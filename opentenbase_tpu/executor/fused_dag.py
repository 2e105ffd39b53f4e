"""Fused DAG executor: multi-fragment plans (joins) on the device mesh.

The reference executes a distributed join as plan fragments wired through
the squeue/DataPump socket fabric: producer datanodes hash-route tuples to
consumer fragments (src/backend/pgxc/squeue/squeue.c:403-660), which run
hash joins locally (nodeHash.c / nodeHashjoin.c) and feed two-phase
aggregation upward (createplan.c:1852). This module is the TPU-native
equivalent of that whole pipeline:

- every fragment compiles to one jitted ``shard_map`` program over the
  'dn' mesh axis;
- a ``redistribute`` motion is a bucketed ``jax.lax.all_to_all`` — the
  DataPump exchange as an ICI collective. Each device sorts its routed
  rows once by destination with every column riding the sort, and the
  ``(D, cap)`` slab it sends is D contiguous slices of each sorted
  column: no search, no row gather, no scatter;
- the join is a sort + searchsorted lookup against the (verified-unique)
  build side — the TPU-friendly formulation of a hash join, since sorted
  binary search vectorizes where per-tuple hash probing does not;
- the final fragment's partial aggregation reuses the segment-reduce
  kernels (ops/agg.py) and gathers partial rows to the coordinator, which
  merges them (the ResponseCombiner role, execRemote.c).

Dynamic cardinalities use the two-pass sizing SURVEY.md §7 prescribes:
a cheap counting program fixes each exchange's static bucket capacity
(and the grouped aggregate's group capacity) before the real program
runs. Intermediates stay in HBM between fragments; only tiny count
vectors and the final partial rows cross to the host.

Data-dependent bailouts (duplicate build keys for an inner join) are
exact: the program returns a flag per inner join, and the runner either
flips the build side or gives up so the host path answers instead.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial, wraps
from typing import Callable, Optional

import numpy as np

import opentenbase_tpu.ops  # noqa: F401  (x64)
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from opentenbase_tpu import types as t
from opentenbase_tpu.catalog.locator import route_by_table
from opentenbase_tpu.executor.fused import _pad_shards, fetch, named_program
from opentenbase_tpu.obs import statements as _stmtobs
from opentenbase_tpu.obs.trace import scope, span as _span
from opentenbase_tpu.ops import agg as agg_ops
from opentenbase_tpu.ops import filter as filt_ops
from opentenbase_tpu.ops.expr import ExprCompiler, resolve_param
from opentenbase_tpu.plan import costs
from opentenbase_tpu.plan import logical as L
from opentenbase_tpu.plan import texpr as E
from opentenbase_tpu.plan.distribute import (
    DistributedPlan,
    DistributeError,
    Fragment,
    RemoteSource,
    motion_route,
)
from opentenbase_tpu.plan.costs import estimate_rows
from opentenbase_tpu.plan.skey import plan_skey
from opentenbase_tpu.storage.column import Column
from opentenbase_tpu.storage.table import ColumnBatch
from opentenbase_tpu.utils.hashing import combine_hashes, hash32_jnp

OPTIMISTIC_GROUP_CAP = 1 << 16
# Direct-addressed grouped finals (ops/agg._direct_group_reduce_impl):
# where the packed group key's live range fits this many slots the key
# IS the slot and the reduction is one-hot matmuls; a wider range takes
# the next power of two up to the bound, past it the sort formulation.
DIRECT_START_SLOTS = 1 << 13
DIRECT_MAX_SLOTS = 1 << 16
# A semi join's grouped key set (``semiagg``): its first capacity is
# the planner's estimate of the qualifying keys, at most a dimension's
# size (the radix table's reach, 65,536 padded rows); a set that
# outgrows it retries at the power of two that holds it, remembered per
# plan, data and literals. A gagg over a semi join's rows that ranks by
# keys its packing dropped reads them at the sorted rows' live prefix:
# the key set's capacity times the probe side's rows a key (ANALYZE's
# rows over the key's ndv) times ``RANK_PREFIX_SKEW``, for keys that
# hold more rows than the mean (``DagRunner._rank_prefix_rows``).
SEMIAGG_START_KEYS = 1 << 16
RANK_PREFIX_SKEW = 2

from opentenbase_tpu.ops import join as join_ops
from opentenbase_tpu.plan import batchplan

# Dimension-fold: an inner join whose build side is this small (and at
# most half the probe) is attempted as a dense direct-index lookup — the
# build rows sort once (small) and every probe row gathers its match by
# key arithmetic, replacing the two full-width sorts of the sort-merge
# path. A runtime density flag falls back when the build keys aren't a
# gap-free unique range (the replicated-dim join shippability the
# reference reaches through pgxcship.c:139, done the TPU way).
DIMFOLD_MAX_BUILD = 33_554_432


class _Stage:
    """Consecutive ``otb/`` scopes through one long traced body:
    ``to(stage)`` leaves the current scope and enters the next."""

    def __init__(self):
        self._cm = None

    def to(self, stage: str) -> None:
        self.done()
        self._cm = scope(stage)
        self._cm.__enter__()

    def done(self) -> None:
        cm, self._cm = self._cm, None
        if cm is not None:
            cm.__exit__(None, None, None)


def _staged(fn):
    """``fn(*args, st)`` with a ``_Stage`` it may switch; the last
    scope is left when ``fn`` returns or raises (an open named_scope
    would rename every later trace on the thread)."""

    @wraps(fn)
    def block(*args):
        st = _Stage()
        try:
            return fn(*args, st=st)
        finally:
            st.done()

    return block


def _collecting(fn):
    """A ``_collect*`` method as the ``fused.collect`` span
    (``collect_ms``): the fetched numpy arrays to a ColumnBatch."""

    @wraps(fn)
    def collect(self, *args):
        with _span(None, "fused.collect", "collect_ms", cat="fused") as sp:
            out = fn(self, *args)
            sp.set(rows=out.nrows)
            if self._collect_note:  # a key set's count, fetched with it
                sp.set(**self._collect_note)
                self._collect_note = None
        return out

    return collect


class DagUnsupported(Exception):
    """Plan shape outside the fused DAG subset (silent host fallback)."""


_JOINABLE_KEY_TYPES = (
    t.TypeId.INT4, t.TypeId.INT8, t.TypeId.BOOL,
    t.TypeId.DECIMAL, t.TypeId.DATE, t.TypeId.TIMESTAMP,
)


# ---------------------------------------------------------------------------
# Compile-time plan walking: every expression is compiled BEFORE tracing
# so the ExprCompiler's lifted params are complete when the program runs.
# The result of _build() is a closure evaluated inside the shard_map block:
#   fn(blocks, params, snap) -> (env, mask, n, flags)
# where ``blocks`` are per-leaf array tuples in discovery order.
# ---------------------------------------------------------------------------


def _scan_nodes(meta) -> tuple:
    """Stores a scan reads: every shard for distributed tables, exactly
    ONE replica for replicated ones (reading all would duplicate rows —
    the locator's preferred-replica read, locator.c REPLICATED)."""
    if meta.dist.is_replicated:
        return tuple(meta.node_indices[:1])
    return tuple(meta.node_indices)


def _walk_leaves(node: L.LogicalPlan):
    """Canonical DFS leaf order — the ONE definition both the closure
    builder and the per-run array collection follow."""
    if isinstance(node, (L.Filter, L.Project, L.Aggregate)):
        yield from _walk_leaves(node.child)
    elif isinstance(node, L.Join):
        yield from _walk_leaves(node.left)
        yield from _walk_leaves(node.right)
    elif isinstance(node, (L.Scan, RemoteSource)):
        yield node
    else:
        raise DagUnsupported(type(node).__name__)


# a 64-bit integer column whose resident range lies in [-I32, I32]
# crosses a join as one 32-bit word; -2^31 stays free for a narrowed
# carrier's mark (``_lookup_dense``'s SENT)
I32 = 2**31 - 1
# the key of a dead or NULL row in a sort-merge co-sort of 32-bit keys
# (``_lookup_sortmerge``'s narrow form): a value no real key holds
KEY_DEAD = -(2**31)


def _leaf_arrays(fx, node, exchanged: dict, D: int, fits=None):
    """Device arrays for one leaf — the ONE definition of each leaf's
    block tuple layout. Called fresh every run so cached programs see
    current data: a read-after-write scan picks up an ingest burst as
    a delta-tail refresh (DeviceCache._try_delta serves the appended
    rows straight from pending DeltaBatch segments — no host fold, no
    full re-upload), and the in-program visibility compare below is
    the ONLY filter those fresh rows ever pass through. ``fits``, a
    set: gets the (table, column) of every 64-bit integer column read
    whose range over the arrays handed out fits 32 bits (``I32``), and
    (fragment, i) of a source's columns its producer proved so."""
    if isinstance(node, L.Scan):
        meta = fx.catalog.get(node.table)
        nodes = _scan_nodes(meta)
        for n in nodes:
            if node.table not in fx.node_stores.get(n, {}):
                raise DagUnsupported("missing store")
        dtab = fx.cache.get(
            node.table, meta, fx.node_stores, nodes, columns=node.columns
        )
        if len(dtab.nrows) % D != 0:
            raise DagUnsupported("shards not divisible by mesh")
        if fits is not None:
            for c in node.columns:
                rng = dtab.col_range.get(c)
                if (
                    rng is not None and dtab.columns[c].dtype == jnp.int64
                    and -I32 <= rng[0] and rng[1] <= I32
                ):
                    fits.add((node.table, c))
        valids = tuple(dtab.validity[c] for c in node.columns)
        return (
            tuple(dtab.columns[c] for c in node.columns),
            tuple(v for v in valids if v is not None),
            dtab.xmin, dtab.xmax, jnp.asarray(dtab.nrows),
        )
    ex = exchanged.get(node.fragment)
    if ex is None:
        raise DagUnsupported("remote source order")
    if fits is not None:  # (a key set's, where its program proved it)
        fits.update((node.fragment, i) for i in ex.get("fits", ()))
    return (ex["cols"], ex["valids"], ex["counts"])


def _inline_sources(node, producers: dict):
    """Substitute each RemoteSource with its producer fragment's root
    (recursively: producers may consume earlier fragments). Only valid
    when the motions are identities (1-device mesh)."""
    import dataclasses

    if isinstance(node, RemoteSource):
        return _inline_sources(producers[node.fragment], producers)
    if dataclasses.is_dataclass(node) and not isinstance(node, type):
        changes = {}
        for f in dataclasses.fields(node):
            v = getattr(node, f.name)
            if isinstance(v, (L.LogicalPlan, RemoteSource)):
                nv = _inline_sources(v, producers)
                if nv is not v:
                    changes[f.name] = nv
            elif isinstance(v, tuple) and v and all(
                isinstance(x, L.LogicalPlan) for x in v
            ):
                nv = tuple(_inline_sources(x, producers) for x in v)
                if any(a is not b for a, b in zip(nv, v)):
                    changes[f.name] = nv
        if changes:
            return dataclasses.replace(node, **changes)
    return node


def _pack_group_keys(keys, mask):
    """Pack integer group keys into ONE int64 sort key using runtime
    per-key ranges (data-dependent VALUES, not shapes — no recompile):
    packed = sum((k_i - min_i) * stride_i), NULLs in a dedicated bucket.
    Returns (packed, ok, layout): when the combined range overflows
    int64, ok is False and the caller retries with per-key sorting. Cuts
    the grouped aggregation from one argsort per key part to a single
    argsort. ``layout`` is what ``_unpack_group_keys`` needs to read a
    packed value back: per key ``(min, stride, range, nullable)`` and
    last the product of the ranges (the packed values' span; garbage
    where ok is False)."""
    stride = jnp.int64(1)
    prod = jnp.float64(1.0)
    ok = jnp.asarray(True)
    packed = jnp.zeros(mask.shape[0], dtype=jnp.int64)
    big = jnp.int64(2**62)
    layout: list = []
    for d, v in keys:
        live = mask if v is None else (mask & v)
        d64 = d.astype(jnp.int64)
        mn = jnp.min(jnp.where(live, d64, big))
        mx = jnp.max(jnp.where(live, d64, -big))
        mn = jnp.minimum(mn, mx)  # no live rows: degenerate range 1
        # the range itself can overflow int64 (mx - mn wraps negative):
        # guard in float64 BEFORE using the int64 value
        rngf = (mx.astype(jnp.float64) - mn.astype(jnp.float64)) + 1.0
        ok = ok & (rngf < jnp.float64(2**62))
        rng = jnp.maximum(mx - mn + 1, 1)
        if v is None:
            x = d64 - mn
            r = rng
            rf = rngf
        else:
            x = jnp.where(v, d64 - mn, rng)  # NULL bucket past the range
            r = rng + 1
            rf = rngf + 1.0
        packed = packed + x * stride  # dead rows may wrap: masked anyway
        layout.append((mn, stride, rng, v is not None))
        stride = stride * r
        prod = prod * jnp.maximum(rf, 1.0)
    ok = ok & (prod < jnp.float64(2**62))
    return packed, ok, layout + [stride]


def _packable_keys(agg) -> bool:
    """Every group key an integer by dtype (dictionary-coded text
    included): what ``_pack_group_keys`` packs."""
    return all(
        g.type.id in _JOINABLE_KEY_TYPES or g.type.is_text
        for g in agg.group_exprs
    )


def _direct_grouping(agg) -> bool:
    """Whether a grouped final may address its groups directly: every
    key packable (integer-family or dictionary-coded text) and every
    aggregate a sum / count / count(*) the one-hot matmul sums exactly
    (``ops/agg.direct_group_eligible``). The packed range, which only
    the program sees, decides the rest."""
    if agg is None or not agg.group_exprs or not _packable_keys(agg):
        return False
    return agg_ops.direct_group_eligible(
        [a.func if a.arg is not None else "count_star" for a in agg.aggs],
        [None if a.arg is None else a.arg.type.np_dtype for a in agg.aggs],
    )


def _direct_grouped(keys, vals, mask, gslots: int, specs: tuple, st):
    """The grouped final by direct addressing: the packed key is the
    slot. Inside the capacity every live row's packed value is its own
    group's (injective by ``_pack_group_keys``'s construction: no hash,
    no collision check); past it no row counts and the runner, told the
    span, re-runs wider or sorted. Returns ``_group_reduce_impl``'s
    (out_keys, out_vals, gvalid) at length ``gslots``, the group count
    and the span."""
    st.to("final/grouped/pack")
    packed, pack_ok, layout = _pack_group_keys(keys, mask)
    st.to("final/grouped/slot")
    span = jnp.where(pack_ok, layout[-1], jnp.int64(2**62))
    slot = jnp.where(mask & (span <= gslots), packed, gslots).astype(
        jnp.int32
    )
    st.done()  # (the reduce names its own stages)
    out_vals, gvalid, ngroups = agg_ops._direct_group_reduce_impl(
        vals, slot, gslots, specs
    )
    st.to("final/grouped/recombine")
    out_keys = [
        (d, gvalid if v is None else v & gvalid)
        for d, v in _unpack_group_keys(
            layout, gslots, [d.dtype for d, _v in keys]
        )
    ]
    return out_keys, out_vals, gvalid, ngroups, span


def _unpack_group_keys(layout, cap: int, dtypes):
    """The group keys of the packed values 0..cap-1 (the inverse of
    ``_pack_group_keys`` under its ``layout``, for a span inside
    ``cap``): per key (data, valid), valid None for a key that has no
    NULL bucket. Every stride and range of such a layout is at most
    ``cap``, so the divisions run in int32 (the TPU emulates a 64-bit
    one in thousands of ops); a wider span's are clipped, its keys
    garbage that the caller's span check discards."""
    slots = jnp.arange(cap, dtype=jnp.int32)
    out = []
    for (mn, stride, rng, nullable), dtype in zip(layout[:-1], dtypes):
        stride, rng = (
            jnp.clip(x, 1, cap).astype(jnp.int32) for x in (stride, rng)
        )
        q = (slots // stride) % (rng + 1 if nullable else rng)
        key = mn + q.astype(jnp.int64)
        if nullable:
            valid = q < rng  # the bucket past the range is NULL
            out.append((jnp.where(valid, key, 0).astype(dtype), valid))
        else:
            out.append((key.astype(dtype), None))
    return out


_PACKABLE_SORT_TYPES = (
    t.TypeId.INT4, t.TypeId.INT8, t.TypeId.BOOL,
    t.TypeId.DECIMAL, t.TypeId.DATE, t.TypeId.TIMESTAMP,
)


def _detect_topk(dplan, final):
    """TopK pushdown: when the coordinator plan is
    ``Limit(Sort(Project*...(Aggregate?)(RemoteSource(final))))`` with
    bare-column sort keys, the device can rank and ship only the first
    ``limit+offset`` rows instead of every group — the difference between
    a k-row transfer and a multi-million-row gather (the reference pushes
    LIMIT below the remote subplan the same way,
    src/backend/optimizer/plan/createplan.c make_remotesubplan).

    Returns (k, specs, merged) or None. ``specs`` =
    ((pos, descending, nulls_first), ...) with positions into the final
    fragment's output schema; ``merged`` is True when the coordinator
    re-aggregates (rows are group partials — the caller must prove the
    device groups are complete before ranking them)."""
    node = dplan.root
    if not isinstance(node, L.Limit) or node.limit is None:
        return None
    k = node.limit + (node.offset or 0)
    if k <= 0 or k > 1024:
        return None
    node = node.child
    if not isinstance(node, L.Sort) or not node.keys:
        return None
    positions, descs, nfs = [], [], []
    for sk in node.keys:
        if not isinstance(sk.expr, E.Col):
            return None
        positions.append(sk.expr.index)
        descs.append(sk.descending)
        nfs.append(sk.nulls_first)
    node = node.child
    merged = False
    while True:
        if isinstance(node, L.Project):
            newpos = []
            for p in positions:
                ex = node.exprs[p]
                if not isinstance(ex, E.Col):
                    return None
                newpos.append(ex.index)
            positions = newpos
            node = node.child
        elif isinstance(node, L.Aggregate):
            if merged:
                return None
            merged = True
            nk = len(node.group_exprs)
            newpos = []
            for p in positions:
                if p < nk:
                    ex = node.group_exprs[p]
                    if not isinstance(ex, E.Col):
                        return None
                    newpos.append(ex.index)
                else:
                    a = node.aggs[p - nk]
                    if a.arg is None or not isinstance(a.arg, E.Col):
                        return None
                    if getattr(a, "distinct", False):
                        return None
                    newpos.append(a.arg.index)
            positions = newpos
            node = node.child
        elif isinstance(node, RemoteSource):
            if node.fragment != final.index:
                return None
            break
        else:
            return None
    return k, tuple(zip(positions, descs, nfs)), merged


def _detect_build_group(agg, root, orientation):
    """Group-by over the unique build side of the top join.

    When every GROUP BY expression is a bare column of the top inner
    join's build side (or the probe join key, equal to the build key on
    every matched row) and one of them IS the join key, groups are 1:1
    with real build rows — so the grouped aggregation is a segment
    reduction over the join's build-row index, with NO sort at any width
    (the reference reaches the same shape through nodeAgg's hashed
    grouping over the hashjoin's output; on TPU the scatter-reduce is the
    native form). Returns (capture_id, build_cols) or None; build_cols[i]
    is the build-side column backing group expr i."""
    node = root
    while isinstance(node, L.Filter):
        node = node.child
    if not isinstance(node, L.Join) or node.join_type != "inner":
        return None
    if len(node.left_keys) != 1 or len(node.right_keys) != 1:
        return None
    ji = _count_inner_joins(root) - 1
    build_right = (
        orientation[ji] if ji < len(orientation) else "R"
    ) == "R"
    nl = len(node.left.schema)
    lk, rk = node.left_keys[0], node.right_keys[0]
    if build_right:
        bkey, pkey = rk, lk
        build_lo, build_hi = nl, nl + len(node.right.schema)
        poff = 0
    else:
        bkey, pkey = lk, rk
        build_lo, build_hi = 0, nl
        poff = nl
    if not isinstance(bkey, E.Col):
        return None
    pkey_pos = (poff + pkey.index) if isinstance(pkey, E.Col) else None
    build_cols = []
    has_key = False
    for g in agg.group_exprs:
        if not isinstance(g, E.Col):
            return None
        p = g.index
        if build_lo <= p < build_hi:
            bc = p - build_lo
        elif pkey_pos is not None and p == pkey_pos:
            bc = bkey.index
        else:
            return None
        if bc == bkey.index:
            has_key = True
        build_cols.append(bc)
    if not has_key:
        return None
    return id(node), tuple(build_cols)


def _expr_cols(e, out=None):
    """All child-column positions an expression references."""
    if out is None:
        out = set()
    if isinstance(e, E.Col):
        out.add(e.index)
    for c in e.children():
        _expr_cols(c, out)
    return out


def _agg_reads(agg, keys: bool = True) -> Optional[set]:
    """The positions of its input an aggregate's arguments (and, with
    ``keys``, its group keys) read: what a final program requires of
    the rows under it (``_Builder.build``). None, every one, without an
    aggregate: the rows themselves are the answer."""
    if agg is None:
        return None
    out: set = set()
    for g in agg.group_exprs if keys else ():
        _expr_cols(g, out)
    for a in agg.aggs:
        if a.arg is not None:
            _expr_cols(a.arg, out)
    return out


def _detect_gsort(agg, root, orientation):
    """Eligibility for the co-sort join+group formulation (one
    ``lax.sort`` of concat(build, probe) keys + prefix scans — no
    scatter, no searchsorted; both are serial disasters on TPU while its
    sort streams at memory bandwidth). Requires the gseg shape
    (group-by-unique-build + topk) AND: the aggregate sits directly on
    the join, aggregate args touch only probe columns. Specs may be
    sum/count (cumsum differences) or min/max (one reverse segmented
    scan each lands the run reduction at the build position). A join
    RESIDUAL rides too: its build-side inputs forward-propagate from
    each run's leading build row and failing probe rows drop out of
    every per-run reduction (VERDICT r4 ask #6). Returns a spec dict
    or None."""
    bg = _detect_build_group(agg, root, orientation)
    if bg is None:
        return None
    join = root if isinstance(root, L.Join) else None
    if join is None:
        return None
    ji = _count_inner_joins(root) - 1
    build_right = (
        orientation[ji] if ji < len(orientation) else "R"
    ) == "R"
    nl = len(join.left.schema)
    if build_right:
        plo, phi = 0, nl
    else:
        plo, phi = nl, nl + len(join.right.schema)
    for a in agg.aggs:
        if a.func == "count" and a.arg is None:
            continue
        if a.func not in ("sum", "count", "min", "max"):
            return None
        if a.func in ("min", "max") and a.arg.type.is_text:
            return None  # code order != collation order: host path
        if any(not (plo <= c < phi) for c in _expr_cols(a.arg)):
            return None
    bkey = (join.right_keys if build_right else join.left_keys)[0]
    return {
        "join": join,
        "build_right": build_right,
        "build_cols": bg[1],
        "bkey_col": bkey.index,
        "residual": join.residual,
    }


def _detect_gagg(agg, topk):
    """Eligibility for the sort-based grouped-agg + top-k formulation
    with NO build-side requirement (the ClickBench shape: GROUP BY
    high-cardinality key ORDER BY agg LIMIT k). Groups become runs of a
    single packed-key sort; sums/counts are prefix-sum differences,
    min/max segmented scans; ORDER BY may mix aggregate columns with
    group keys (group-key values decode back out of the monotone
    packing, or ride the sort as operands when packing dropped them);
    only k rows ship."""
    if not agg.group_exprs:
        return None
    for a in agg.aggs:
        if a.func in ("min", "max") and (
            a.arg is not None and a.arg.type.is_text
        ):
            return None  # code order != collation order: host path
        if a.func in ("count", "sum", "min", "max"):
            continue
        return None
    for g in agg.group_exprs:
        if not (
            g.type.id in _JOINABLE_KEY_TYPES or g.type.is_text
        ):
            return None
    return True


def _semiagg_build(join) -> Optional[tuple]:
    """(Aggregate, HAVING predicate or None) where ``join`` is a semi
    join whose build side is a grouped key set: ``Project?(Filter?(
    Aggregate))`` grouped by ONE key of a joinable type, sums, counts,
    minima and maxima its only aggregates, and the join's one key pair
    reading that key (TPC-H Q18's ``o_orderkey in (select l_orderkey
    ... group by l_orderkey having sum(l_quantity) > Q)``). The runner
    answers it by a program of its own (``semiagg``); None for any
    other build side."""
    if (
        join.join_type != "semi" or len(join.right_keys) != 1
        or join.residual is not None
        or not isinstance(join.right_keys[0], E.Col)
    ):
        return None
    node, pos = join.right, join.right_keys[0].index
    if isinstance(node, L.Project):
        ex = node.exprs[pos]
        if not isinstance(ex, E.Col):
            return None
        node, pos = node.child, ex.index
    having = None
    if isinstance(node, L.Filter):
        having, node = node.predicate, node.child
    if (
        not isinstance(node, L.Aggregate) or len(node.group_exprs) != 1
        or pos != 0
        or node.group_exprs[0].type.id not in _JOINABLE_KEY_TYPES
        or node.group_exprs[0].type.is_text
    ):
        return None
    for a in node.aggs:
        if a.distinct or a.func not in ("sum", "count", "min", "max"):
            return None
    return node, having


def _fd_closure(fd: dict, p: int) -> set:
    """Every position that determines ``p`` through ``fd``, directly or
    by way of others."""
    seen: set = set()
    todo = list(fd.get(p, ()))
    while todo:
        q = todo.pop()
        if q != p and q not in seen:
            seen.add(q)
            todo.extend(fd.get(q, ()))
    return seen


def _fd_map(root, orientation):
    """Functional dependencies between output columns, in root.schema
    positions: {determined: {determining, ...}}, each determinant alone
    enough. Every verified-unique inner join makes its build-side
    columns functions of the probe key (the dup/density flags guarantee
    uniqueness at runtime — a program that RETURNS without flags proved
    its FDs) and, the key pair being an equivalence on the rows that
    survive the join, of the build side's own key too: what either key
    determines the other does. A Project closes the dependencies
    transitively before it drops a determinant (TPC-H Q10 groups by
    ``c_custkey`` and six attributes of it over a projection that has
    neither ``o_custkey`` nor ``c_nationkey``). Lets grouped aggregation
    pack a determinant subset of the GROUP BY keys (the reference
    derives the same through unique-index functional dependency,
    check_functional_grouping, src/backend/catalog/pg_constraint.c, and
    remove_useless_groupby_columns, optimizer/plan/planner.c). No proof,
    no entry: a join on several pairs, an outer join, a key that is an
    expression leave their columns undetermined."""
    counter = [0]

    # walk mirrors _Builder.build: recurse BOTH children of every join
    # (semi/anti included — their subtree joins consume indices too),
    # assign this join's index post-order
    def walk(node):
        if isinstance(node, (L.Scan, RemoteSource)):
            return {}
        if isinstance(node, (L.Filter,)):
            return walk(node.child)
        if isinstance(node, L.Project):
            cfd = walk(node.child)
            pos_of = {}
            for o, ex in enumerate(node.exprs):
                if isinstance(ex, E.Col) and ex.index not in pos_of:
                    pos_of[ex.index] = o
            out = {}
            for o, ex in enumerate(node.exprs):
                if not isinstance(ex, E.Col):
                    continue
                by = {
                    pos_of[q] for q in _fd_closure(cfd, ex.index)
                    if q in pos_of
                } - {o}
                if by:
                    out[o] = by
            return out
        if isinstance(node, L.Join):
            if node.join_type in ("semi", "anti"):
                lfd = walk(node.left)
                walk(node.right)  # index alignment only
                return lfd
            lfd = walk(node.left)
            rfd = walk(node.right)
            nl = len(node.left.schema)
            out = {k: set(v) for k, v in lfd.items()}
            out.update({
                k + nl: {q + nl for q in v} for k, v in rfd.items()
            })
            if node.join_type != "inner":
                return out
            ji = counter[0]
            counter[0] += 1
            build_right = (
                orientation[ji] if ji < len(orientation) else "R"
            ) == "R"
            if len(node.left_keys) != 1:
                return out
            pkey, bkey = (
                (node.left_keys[0], node.right_keys[0]) if build_right
                else (node.right_keys[0], node.left_keys[0])
            )
            if not isinstance(pkey, E.Col):
                return out
            pkpos = pkey.index + (0 if build_right else nl)
            lo, hi = (nl, nl + len(node.right.schema)) if build_right \
                else (0, nl)
            by = {pkpos}
            if isinstance(bkey, E.Col):
                # the pair is an equivalence on the joined rows
                bkpos = bkey.index + lo
                by.add(bkpos)
                out.setdefault(pkpos, set()).add(bkpos)
            for p in range(lo, hi):
                out.setdefault(p, set()).update(by - {p})
            return out
        return {}

    return walk(root)


def _chain_leaf(node, folded_ids=None, est=None):
    """Peel a build subtree down to the ONE leaf whose rows it
    preserves: Filters keep rows; an inner join that dimension-FOLDS
    keeps its probe side's rows (folds mask, never drop). Returns
    (leaf, leaf_positions) where leaf_positions are the positions of
    ``node.schema`` backed directly by leaf columns — a fold key must
    be leaf-backed, since folded-in dim columns hold garbage on
    unmatched rows and can't anchor the density domain. None when the
    chain breaks.

    ``folded_ids``: exact set of folded join ids (builder, post-order
    known). ``est``: row-estimate fallback used by the runner's mode
    prediction BEFORE any builder exists — it assumes a small-side
    join will fold, which only risks picking a slower mode, never a
    wrong result."""
    offset = 0
    width = len(node.schema)
    while True:
        if isinstance(node, L.Filter):
            node = node.child
            continue
        if isinstance(node, L.Join) and node.join_type == "inner":
            nl = len(node.left.schema)
            if folded_ids is not None:
                if id(node) not in folded_ids:
                    return None
                build_right = folded_ids[id(node)]
            elif est is not None:
                try:
                    le, re = est(node.left), est(node.right)
                except Exception:
                    return None
                build_right = le > re
                bn_est = min(le, re)
                if not (
                    0 < bn_est <= DIMFOLD_MAX_BUILD
                    and bn_est * 2 <= max(le, re)
                ):
                    return None
            else:
                return None
            if build_right:
                node = node.left
                width = nl
            else:
                node = node.right
                offset += nl
                width = len(node.schema)
            continue
        if isinstance(node, (L.Scan, RemoteSource)):
            return node, range(offset, offset + width)
        return None


def _drive_pair(runner, node: "L.Join", build_right: bool) -> int:
    """Which key pair of an inner join with several drives its lookup:
    the one whose build-side key ANALYZE counted the most distinct
    values for (a key of its table where there is one: ``c_custkey``,
    1.5M values, before ``c_nationkey``, 25) — the likeliest to be
    unique, which every lookup formulation needs of its build side, and
    the only one that can be dense. The first pair without statistics.
    A function of the node, the build side and the catalog alone, so
    the gate, the builder and the hoist agree and a flip of the build
    side chooses again. The other pairs are equalities a match must
    also pass (``_Builder._build_join``)."""
    keys = node.right_keys if build_right else node.left_keys
    if len(keys) < 2 or runner is None:
        return 0
    bnode = node.right if build_right else node.left
    producers = getattr(runner, "_producers", None)
    if producers:  # (a mesh: the statistics are the producers' tables')
        bnode = _inline_sources(bnode, producers)
    ndvs = [
        costs.expr_ndv(k, bnode, runner.fx.catalog) or 0.0 for k in keys
    ]
    return ndvs.index(max(ndvs))


def _all_equal(pairs, mask):
    """``mask`` less the rows on which a key pair differs or holds a
    NULL (``pairs``: [((left data, valid), (right data, valid))] over
    the same rows)."""
    for (ld, lv), (rd, rv) in pairs:
        keep = ld.astype(jnp.int64) == rd.astype(jnp.int64)
        for v in (lv, rv):
            if v is not None:
                keep = keep & v
        mask = mask & keep
    return mask


def _spread_dead_keys(pk, pmask, bk, bmask):
    """Probe keys for a fold whose probe rows come out of an earlier
    join: every row that join left unmatched carries ONE garbage key
    (its ``bidx`` was clipped to build row 0), so six in seven rows of
    a Q5 probe the same fold slot, and which slot is the data's. With
    them the probe's two fused gathers took 2,465 or 3,249 ms by seed,
    the same to the millisecond on a second run of a seed (my chip
    runs, PR 35; 1,229 ms in a join whose probe keys are spread): a Q5
    cost 9.99 or 10.76 s by seed. Dead rows match nothing whatever
    their key, so they probe with keys spread over the build's range
    instead: 2,686 ms on every seed, six seeds within 0.02 %. (Why the
    one-key probe is data-dependent is not known: a gather alone costs
    0.579 s for 67.1M indices whatever their pattern, PERF.md §7.)"""
    pd, pv = pk
    bd, bv = bk
    live = pmask if pv is None else (pmask & pv)
    breal = bmask if bv is None else (bmask & bv)
    base = jnp.min(jnp.where(breal, bd.astype(jnp.int64), jnp.int64(2**62)))
    spread = jnp.arange(pd.shape[0], dtype=jnp.int32) % jnp.int32(
        max(bd.shape[0], 1)
    )
    return (
        jnp.where(live, pd.astype(jnp.int64), base + spread.astype(jnp.int64)),
        pv,
    )


def _key_name(k, i: int) -> str:
    """A join key for a span arg: its column's name, or ``expr<i>``."""
    while isinstance(k, E.CastE):
        k = k.operand
    return k.name if isinstance(k, E.Col) and k.name else f"expr{i}"


def _carrier(benv, cols, nb: int):
    """The build column a fold's match bit rides in, or None: the first
    of ``cols`` (positions of ``benv`` the joined row's readers fetch
    at the probe's width anyway, in schema order) whose physical type
    is a signed 32-bit integer (a dictionary code, an ``int``, a
    ``date``), else the first 64-bit one (a ``bigint``, a fixed-point
    ``decimal``: two compare words). No float and no bool: the mark is
    a value the column is checked not to hold (``_lookup_dense``)."""
    wide = None
    for i in cols:
        d = benv[i][0]
        if d.shape != (nb,):
            continue
        if d.dtype == jnp.int32:
            return i
        if d.dtype == jnp.int64 and wide is None:
            wide = i
    return wide


def _fold_gate(runner, node: "L.Join", ji: int, build_right: bool,
               fold_off, folded_ids=None) -> bool:
    """THE dimension-fold gate — one definition shared by the builder
    (which compiles the fold) and the runner's mode selection (which
    predicts it). Static checks only; density/uniqueness is verified
    at runtime by the fold flag, PER DEVICE, which covers every
    topology where the sort-merge lookup it replaces is correct: the
    fold sees exactly the per-device build rows sort-merge would, an
    empty build shard matches nothing under both, and a sharded
    (non-dense-per-device) build trips the flag once and disables
    itself. Requires a runner (row estimates), a build subtree that
    preserves ONE leaf's rows — Filter chains and already-folded
    child joins both qualify (predicates and join matches peel into
    slot validity) — with the join key backed by that leaf, and a
    build side small in absolute terms AND relative to the probe
    (folding a same-size side would just rename the sort)."""
    if runner is None or ji in fold_off:
        return False
    bnode = node.right if build_right else node.left
    pnode = node.left if build_right else node.right
    chain = _chain_leaf(
        bnode, folded_ids=folded_ids,
        est=runner._est_rows if folded_ids is None else None,
    )
    if chain is None:
        return False
    bkey = (node.right_keys if build_right else node.left_keys)[
        _drive_pair(runner, node, build_right)
    ]
    if not _expr_cols(bkey) <= set(chain[1]):
        return False
    try:
        best = runner._est_rows(bnode)
        pest = runner._est_rows(pnode)
    except Exception:
        return False
    return 0 < best <= DIMFOLD_MAX_BUILD and best * 2 <= pest


def _radix_gate(
    runner, node: "L.Join", ji: int, build_right: bool, radix_off,
    mode: str,
) -> bool:
    """THE radix-hash-join gate, the half of it that reads estimates —
    the builder (which compiles it) and any mode prediction share this
    one definition. The radix table is a candidate where the dense fold
    can't engage (keys unique but not a gap-free range): build side
    estimated small relative to the probe — the planner's cardinality
    estimates, the same signal that seeds build orientation — or the
    ``join_mode`` GUC forcing it. Inner joins only: semi/anti existence
    probes carry no per-join flag slot to report a bucket overflow
    through.

    Admission here is not yet a table: the other half of the rule reads
    the STATIC build width, which only the trace knows, and lives in
    ``_lookup_radix`` — under ``auto`` a table is built only where it is
    dimension-sized (``pallas_join.eligible``); every wider build takes
    sort-merge."""
    if runner is None or ji in radix_off or mode == "sortmerge":
        return False
    bnode = node.right if build_right else node.left
    pnode = node.left if build_right else node.right
    try:
        best = runner._est_rows(bnode)
        pest = runner._est_rows(pnode)
    except Exception:
        return False
    if best <= 0:
        return False
    if mode == "radix":
        return True
    return best * 2 <= pest


def _lookup_radix(pk, pmask, bk, bmask, budget, fallback,
                  pallas_probe: bool = False, note_mode=None,
                  tag: str = "join", forced: bool = False, words=()):
    """Equi-join primitive over the bucket-padded radix hash table
    (ops/join.py): ONE small build-side sort + a log2(bucket)-deep
    bucket search per probe row, instead of sort-merge's full
    (build+probe)-width co-sort. The spill-aware batch planner sizes
    partitions/bucket against ``budget`` at trace time from the STATIC
    shapes.

    THE SHAPE RULE (``auto``, i.e. not ``forced``): a table is built
    only where it is dimension-sized — where
    ``pallas_join.eligible(chunk, P, B)`` holds for the plan of the
    static build width (P <= 4096 and 6 * bucket <= 512: a padded build
    of at most 65,536 rows). Every wider build takes ``fallback`` (the
    sort-merge primitive). Static shapes only: no platform test, no
    GUC. Its source is the chip record (ledger, PR 31,
    ``tpch_sf30_4chip.join`` ``device_ops``): a 2^21-row build probed
    by 2^23 rows through ``probe_radix_first`` cost 3,042 ms a
    statement a chip, seven element-wise gathers ``u32[8388608]`` from
    ``u32[5242881]`` tables at 20 ns an element (362 ns a probe row),
    and its build 512 ms, where the same chips sort at 6-8 ns a row:
    beyond the Pallas kernel's reach the XLA gather probe loses to
    sort-merge by 10x at every shape the estimate gate admits.

    ``forced`` (``join_mode = radix``) keeps what it names at any
    width: a build side whose table would blow the budget splits into
    multi-pass probes (nodeHash.c's nbatch, device-style: probe stays
    resident, one transient table per pass), probed by the XLA probe
    where the kernel is not eligible. Forced or not, when even the
    maximum pass count can't fit, ``fallback`` (O(1) extra HBM) answers
    instead of OOMing the worker.

    ``note_mode(mode, sized_out=False)`` hears what is traced in:
    ``radix`` once a table is, ``pallas`` when the kernel probes it,
    ``merge`` when ``fallback`` answers — ``sized_out`` where the shape
    rule, not the budget, sent it there.

    ``_lookup_sortmerge``'s contract and the build words the kernel
    picked: (matched, bidx, flag, picked); the flag is raised by
    duplicate build keys (in-bucket adjacency or a key matching in two
    passes), or by bucket overflow — the runner then disables the radix
    formulation for this join and the sort-merge retry re-derives the
    exact dup verdict. ``words``: 32-bit build columns the caller
    reads, each (data [nb] 32-bit, valid [nb] bool or None); where the
    Pallas kernel probed every pass, ``picked`` holds each at the
    probe's rows out of its contraction, (int32, valid or None), equal
    to ``take(data, bidx)`` on a matched row. Otherwise (the XLA probe,
    ``fallback``, no words) it is None and the caller gathers."""
    from opentenbase_tpu.ops import pallas_join as pj

    pd, pv = pk
    bd, bv = bk
    nb = bd.shape[0]
    npr = pd.shape[0]
    if nb == 0:  # static: no build rows can ever match
        return (
            jnp.zeros(npr, jnp.bool_),
            jnp.zeros(npr, jnp.int32),
            jnp.asarray(False),
            None,
        )
    plan = batchplan.plan_radix_join(nb, npr, budget)
    chunk = 0 if plan is None else -(-nb // plan.passes)
    sized_out = (
        plan is not None and not forced
        and not pj.eligible(chunk, plan.partitions, plan.bucket)
    )
    if plan is None or sized_out:
        if note_mode is not None:
            note_mode("merge", sized_out=sized_out)
        with scope(f"{tag}/merge"):
            return (*fallback(pk, pmask, bk, bmask, check_dup=True), None)
    if note_mode is not None:
        note_mode("radix")
    breal = bmask if bv is None else (bmask & bv)
    preal = pmask if pv is None else (pmask & pv)
    P, B = plan.partitions, plan.bucket
    matched = jnp.zeros(npr, jnp.bool_)
    bidx = jnp.zeros(npr, jnp.int32)
    flag = jnp.asarray(False)
    picked = None  # the kernel's words, merged over the passes

    for p in range(plan.passes):
        s = p * chunk
        e = min(s + chunk, nb)
        if s >= e:
            break
        with scope(f"{tag}/radix/build"):
            tkeys, tvalid, tbidx, dup, ovf = join_ops.build_radix_table(
                bd[s:e], breal[s:e], P, B
            )
        got = ()
        if pallas_probe and pj.eligible(e - s, P, B):
            # compiled by Mosaic with the rest of the program: a
            # lowering failure fails the program (a counted, logged
            # fused->host demotion, engine._try_fused_inner), never a
            # quiet switch to the XLA probe
            with scope(f"{tag}/pallas/probe"):
                # this pass's words at the table's slots (its width)
                slot = [
                    (jnp.take(d[s:e], tbidx, mode="clip"),
                     None if v is None
                     else jnp.take(v[s:e], tbidx, mode="clip"))
                    for d, v in words
                ]
                m, bi, got = pj.probe_radix_pallas(
                    tkeys, tvalid, tbidx, pd, preal, P, B, words=slot
                )
            if note_mode is not None:
                note_mode("pallas")
        else:
            with scope(f"{tag}/radix/probe"):
                m, bi = join_ops.probe_radix_first(
                    tkeys, tvalid, tbidx, pd, preal, P, B
                )
        # a probe key matching in two passes = build dup across chunks
        flag = flag | dup | ovf | jnp.any(m & matched)
        first = m & ~matched
        bidx = jnp.where(first, bi + jnp.int32(s), bidx)
        if got:
            # (the kernel reads 0 where nothing matched: the first
            # pass's words are the merge of it into nothing)
            picked = got if picked is None else tuple(
                (jnp.where(first, w, aw),
                 None if v is None else jnp.where(first, v, av))
                for (w, v), (aw, av) in zip(got, picked)
            )
        matched = matched | m
    return matched, bidx, flag, picked


def _agg_specs(comp, agg, dids):
    """(specs, afns) for an Aggregate's functions — the ONE compile
    loop shared by every grouped formulation."""
    specs: list[str] = []
    afns: list = []
    for a in agg.aggs:
        if a.func == "count" and a.arg is None:
            specs.append("count_star")
            afns.append(None)
        else:
            if a.func in ("min", "max") and a.arg.type.is_text:
                # dictionary codes are insertion-ordered, not
                # collation-ordered: a device min over codes would be
                # wrong — the host path aggregates over ranks
                raise DagUnsupported(
                    f"{a.func}() over TEXT stays on the host path"
                )
            specs.append(a.func)
            afns.append(comp.compile(a.arg, dids))
    return specs, afns


def _fd_reduce(root, orientation, agg):
    """(kept, dropped) group-expr indices after removing keys
    functionally determined (transitively) by another present key —
    the ONE fixpoint shared by gagg and wgagg (a one-sided change
    would silently group the windowed and in-core paths differently).
    A key goes only while a key that determines it stays: of two that
    determine each other the first is dropped, the second kept."""
    fd = _fd_map(root, orientation)
    nkeys = len(agg.group_exprs)
    colpos = {
        i: g.index
        for i, g in enumerate(agg.group_exprs)
        if isinstance(g, E.Col)
    }
    present = {p: i for i, p in colpos.items()}
    drop: set = set()
    changed = True
    while changed:
        changed = False
        for i, p in colpos.items():
            if i in drop:
                continue
            if any(
                present.get(q, i) != i and present[q] not in drop
                for q in _fd_closure(fd, p)
            ):
                drop.add(i)
                changed = True
    return [i for i in range(nkeys) if i not in drop], sorted(drop)


# what a gagg program checks of its own answer, in the order of the bits
# it returns (``okf``): the packed group key's range fits 62 bits; key
# and integer values fit the 32-bit operands (``narrow``); the sums'
# running prefix is monotone and does not wrap (the cummax run base);
# the ORDER BY keys' packed ranking fits 62 bits
_GAGG_CHECKS = ("packing", "narrowing", "run base", "ranking")
# and where the ORDER BY ranks by dropped keys read at the sorted rows'
# live prefix (``DagRunner._rank_prefix_rows``), a fifth: every live row
# lies in it
_GAGG_PREFIX_CHECKS = _GAGG_CHECKS + ("prefix",)


class _Runs:
    """What ``_gagg_runs`` leaves a final: its three checks so far, the
    sorted operands, the run ends and the aggregates at every position
    (whole at a run's end), the packed key's decoder, and what the
    caller's ``extra`` returned."""

    __slots__ = ("ok", "ok_narrow", "ok_base", "sorted_ops", "salk",
                 "KSENT", "live_end", "out_vals_pos", "decode_key",
                 "extra")


def _gagg_runs(env, mask, n, params, keys, kept, specs, afns,
               narrow: bool, robust: bool, st, stage: str, extra):
    """The core of a one-sort grouping, shared by ``gagg`` (a grouped
    top-k) and ``semiagg`` (a grouped key set): the kept group keys
    packed into one sort key (``i32`` where ``narrow``, dead rows last),
    ONE sort with the aggregate arguments riding, runs as prefix scans
    and the aggregates read at the run ends. ``extra(operands, n, mask,
    narrow_val)`` appends what else must ride the sort (after the
    values, before it sorts) and returns what the caller keeps of it.
    Scopes ``<stage>/{sort,scan}``; the caller opens ``<stage>/pack``.
    """
    # the checks a refused answer may have failed, each its own bit
    # (``_GAGG_CHECKS``): the runner's retry names the ones that went
    # false
    ok = jnp.asarray(True)  # packing
    ok_narrow = jnp.asarray(True)
    ok_base = jnp.asarray(True)

    # pack kept keys, remembering (mn, r, has_null) per key
    # so values decode back out of the sorted key
    stride0 = jnp.int64(1)
    prod0 = jnp.float64(1.0)
    packed = jnp.zeros(n, dtype=jnp.int64)
    decode_info = {}
    big = jnp.int64(2**62)
    for i in kept:
        d, v = keys[i]
        live = mask if v is None else (mask & v)
        d64 = jnp.broadcast_to(d, (n,)).astype(jnp.int64)
        mn = jnp.min(jnp.where(live, d64, big))
        mx = jnp.max(jnp.where(live, d64, -big))
        mn = jnp.minimum(mn, mx)
        rngf = (
            mx.astype(jnp.float64)
            - mn.astype(jnp.float64)
        ) + 1.0
        ok = ok & (rngf < jnp.float64(2**62))
        rng = jnp.maximum(mx - mn + 1, 1)
        if v is None:
            x, r, rf = d64 - mn, rng, rngf
        else:
            x = jnp.where(v, d64 - mn, rng)
            r, rf = rng + 1, rngf + 1.0
        decode_info[i] = (mn, stride0, r, rng)
        packed = packed + x * stride0
        stride0 = stride0 * r
        prod0 = prod0 * jnp.maximum(rf, 1.0)
    ok = ok & (prod0 < jnp.float64(2**62))

    if narrow:
        ok_narrow = prod0 < jnp.float64(2**31 - 1)
        KSENT = jnp.int32(2**31 - 1)
        skeyop = jnp.where(
            mask, packed, jnp.int64(2**31 - 1)
        ).astype(jnp.int32)
    else:
        KSENT = big
        skeyop = jnp.where(mask, packed, big)

    def narrow_val(dv):
        nonlocal ok_narrow
        if narrow and dv.dtype == jnp.int64:
            ok_narrow = ok_narrow & (
                jnp.max(dv) < jnp.int64(2**31 - 1)
            ) & (jnp.min(dv) > jnp.int64(-(2**31 - 1)))
            return dv.astype(jnp.int32)
        return dv

    operands = [skeyop]
    val_pos: list = []
    for spec, fn in zip(specs, afns):
        if fn is None:
            val_pos.append(None)
            continue
        d, v = _bcast(fn(env, params), n)
        if jnp.issubdtype(d.dtype, jnp.integer):
            d = d.astype(jnp.int64)
        elif jnp.issubdtype(d.dtype, jnp.floating):
            d = d.astype(jnp.float64)
        vv = mask if v is None else (mask & v)
        if spec in ("min", "max"):
            # identity padding so dead/NULL rows never win
            # (vvalid masks all-dead runs, so the identity
            # only needs to lose comparisons — it must NOT
            # trip the narrow range check itself)
            if jnp.issubdtype(d.dtype, jnp.floating):
                ident = jnp.asarray(
                    jnp.inf if spec == "min" else -jnp.inf,
                    d.dtype,
                )
            else:
                mag = (2**31 - 2) if narrow else 2**62
                ident = jnp.asarray(
                    mag if spec == "min" else -mag,
                    d.dtype,
                )
            dv = narrow_val(jnp.where(vv, d, ident))
        else:
            dv = jnp.where(vv, d, jnp.zeros((), d.dtype))
            dv = narrow_val(dv)
        operands.append(dv)
        vi = None
        if v is not None or spec in ("min", "max"):
            vi = len(operands)
            operands.append(vv.astype(jnp.int8))
        val_pos.append((len(operands) - (2 if vi else 1), vi))
    kept_extra = extra(operands, n, mask, narrow_val)
    st.to(f"{stage}/sort")
    sorted_ops = jax.lax.sort(
        tuple(operands), num_keys=1, is_stable=False
    )
    st.to(f"{stage}/scan")
    salk = sorted_ops[0]
    boundary = jnp.concatenate([
        jnp.ones(1, jnp.bool_), salk[1:] != salk[:-1]
    ])
    end = jnp.concatenate([
        boundary[1:], jnp.ones(1, jnp.bool_)
    ])
    live_end = end & (salk < KSENT)

    def run_from_start(cs, own):
        # aggregate value at any position = prefix minus the
        # prefix just before the run start (propagated by a
        # cummax — valid because cs is monotone)
        base = jax.lax.cummax(
            jnp.where(
                boundary, cs - own,
                jnp.asarray(-1, dtype=cs.dtype),
            )
        )
        return cs - base

    run_cnt = None

    def get_run_cnt():
        nonlocal run_cnt
        if run_cnt is None:
            lv = (salk < KSENT).astype(jnp.int32)
            run_cnt = run_from_start(jnp.cumsum(lv), lv)
        return run_cnt

    out_vals_pos = []
    for spec, vp in zip(specs, val_pos):
        if spec == "count_star":
            c = get_run_cnt()
            out_vals_pos.append(
                (c.astype(jnp.int64), c > 0)
            )
            continue
        oi, vi = vp
        sval = sorted_ops[oi]
        if vi is not None:
            lv = sorted_ops[vi].astype(jnp.int32)
            vcnt = run_from_start(jnp.cumsum(lv), lv)
            vvalid = vcnt > 0
        else:
            vvalid = live_end
        if spec == "count":
            c = (
                vcnt if vi is not None else get_run_cnt()
            )
            out_vals_pos.append(
                (c.astype(jnp.int64), live_end)
            )
            continue
        if spec in ("min", "max"):
            op = jnp.minimum if spec == "min" else (
                jnp.maximum
            )
            sv = _seg_scan(sval, boundary, op)
            if jnp.issubdtype(sv.dtype, jnp.integer):
                sv = sv.astype(jnp.int64)
            out_vals_pos.append((sv, vvalid))
            continue
        if jnp.issubdtype(sval.dtype, jnp.integer):
            sval = sval.astype(jnp.int64)
        if robust:
            sv = _seg_scan(sval, boundary, jnp.add)
        else:
            # cumsum+cummax base needs non-negative values
            # and a non-wrapping global prefix; the robust
            # retry (segmented add scan) lifts both limits
            ok_base = ok_base & ~(jnp.min(sval) < 0)
            cs = jnp.cumsum(sval)
            if jnp.issubdtype(cs.dtype, jnp.integer):
                ok_base = ok_base & (
                    cs[-1] < jnp.int64(2**62)
                ) & (cs[-1] >= 0)
            sv = run_from_start(cs, sval)
        out_vals_pos.append((sv, vvalid))

    def decode_key(i, src):
        """(value, valid|None) of kept key i from a packed
        key array ``src`` (inverts the monotone packing)."""
        mn, strd, r, rng = decode_info[i]
        x = (src.astype(jnp.int64) // strd) % r
        d = x + mn
        _kd, kv = keys[i]
        if kv is None:
            return d, None
        return jnp.where(x == rng, 0, d), x != rng

    out = _Runs()
    out.ok, out.ok_narrow, out.ok_base = ok, ok_narrow, ok_base
    out.sorted_ops, out.salk, out.KSENT = sorted_ops, salk, KSENT
    out.live_end = live_end
    out.out_vals_pos, out.decode_key = out_vals_pos, decode_key
    out.extra = kept_extra
    return out


def _gagg_carry(keys, carried, need_rid: bool, operands, n, mask,
                narrow_val):
    """What a gagg's sort carries besides the values: the dropped group
    keys an ORDER BY ranks by, and the row id where keys were dropped.
    Returns ({position: (operand, validity operand|None)}, rid's
    operand|None)."""
    carried_pos = {}
    for p in carried:
        d, v = keys[p]
        d64 = jnp.broadcast_to(d, (n,)).astype(jnp.int64)
        dv = narrow_val(jnp.where(mask, d64, 0))
        operands.append(dv)
        ci = len(operands) - 1
        vi = None
        if v is not None:
            operands.append(
                (mask & v).astype(jnp.int8)
            )
            vi = len(operands) - 1
        carried_pos[p] = (ci, vi)
    rid_i = None
    if need_rid:
        rid_i = len(operands)
        operands.append(jnp.arange(n, dtype=jnp.int32))
    return carried_pos, rid_i


def _gagg_late(root, orientation, agg, topk, prefix: int = 0) -> set:
    """The group keys a gagg reads at its output rows alone: those
    ``_fd_reduce`` drops from the packed key, but for the ones an ORDER
    BY key ranks by (they ride the sort), unless ``prefix``: then those
    are read at the sorted rows' live prefix (its first ``prefix``
    rows) and at the output rows. Bare columns all: with them the
    builder defers its joins' gathers (``_Builder.defer``) and the
    final follows each row back through the joins instead."""
    _kept, dropped = _fd_reduce(root, orientation, agg)
    if prefix:
        return set(dropped)
    nkeys = len(agg.group_exprs)
    ranked = {p for p, _d, _nf in topk[1] if p < nkeys}
    return set(dropped) - ranked


def _seg_scan(x, boundary, op, reverse: bool = False):
    """Segmented scan: at every position, ``op`` over the prefix of its
    run (runs delimited by ``boundary``); at run-END positions this is
    the run's full reduction. One associative_scan — the min/max
    counterpart of the cumsum-difference trick (which only works for
    invertible ops).

    ``reverse=True`` scans suffixes instead: ``boundary`` then flags
    run ENDS, and the full-run reduction lands at the run-START
    position — which in the gsort co-sort layout is the build row,
    exactly where per-group outputs live."""

    def comb(a, b):
        af, av = a
        bf, bv = b
        return af | bf, jnp.where(bf, bv, op(av, bv))

    _, out = jax.lax.associative_scan(
        comb, (boundary, x), reverse=reverse
    )
    return out


def _build_side_node(root):
    """The top join node under ``root`` (Filters stripped), or None."""
    node = root
    while isinstance(node, L.Filter):
        node = node.child
    return node if isinstance(node, L.Join) else None


def _top_join(root):
    """The outermost join under ``root``, peeling Filters AND Projects
    (a Project remaps columns but doesn't change which join is
    outermost — used where only the JOIN itself matters: fold-gate
    prediction and build-side hoisting)."""
    node = root
    while isinstance(node, (L.Filter, L.Project)):
        node = node.child
    return node if isinstance(node, L.Join) else None


def _subtree_replicated(node, fx, producer_motions) -> bool:
    """True when every leaf of ``node`` holds ALL its rows on EVERY
    device — the precondition for merging per-device segment partials
    with a psum. Only broadcast-motion RemoteSources qualify: a
    REPLICATED table scanned directly places its one replica store on
    one device of the mesh, so its rows are NOT per-device complete."""
    try:
        leaves = list(_walk_leaves(node))
    except DagUnsupported:
        return False
    for leaf in leaves:
        if isinstance(leaf, L.Scan):
            return False
        if producer_motions.get(leaf.fragment) != "broadcast":
            return False
    return True


def _rank_encode(d64, v, desc, nf, live, bound=2**62):
    """Monotone slot encoding of ONE ORDER BY column over runtime
    min/max ranges: returns (x, r, rf, okbit) where x is the ascending
    slot in [0, r), r its (traced int64) range, rf the float64 range for
    overflow products, okbit false when the value spread itself exceeds
    ``bound``. NULLs land at the PG default end (DESC→first, ASC→last)
    unless nf overrides. Dead rows get bounded garbage — callers mask
    them. The ONE definition shared by every ranking path."""
    big = jnp.int64(2**62)
    nulls_first = desc if nf is None else nf
    lv = live if v is None else (live & v)
    mn = jnp.min(jnp.where(lv, d64, big))
    mx = jnp.max(jnp.where(lv, d64, -big))
    mn = jnp.minimum(mn, mx)  # no live rows: degenerate range 1
    rngf = (mx.astype(jnp.float64) - mn.astype(jnp.float64)) + 1.0
    okbit = rngf < jnp.float64(bound)
    rng = jnp.maximum(mx - mn + 1, 1)
    base = (mx - d64) if desc else (d64 - mn)
    base = jnp.clip(base, 0, rng - 1)
    if v is None:
        return base, rng, rngf, okbit
    if nulls_first:
        x = jnp.where(v, base + 1, 0)
    else:
        x = jnp.where(v, base, rng)
    return x, rng + 1, rngf + 1.0, okbit


def _pack_sort_cols(cols, sspecs, live):
    """Pack ORDER BY key columns into ONE ascending int64 ranking key
    using runtime per-key ranges (data-dependent values, not shapes — no
    recompile), first key most significant. Returns (packed, ok): when
    the combined range overflows int64 ``ok`` is False and the caller
    ships unranked rows instead."""
    stride = jnp.int64(1)
    prod = jnp.float64(1.0)
    ok = jnp.asarray(True)
    n = live.shape[0]
    packed = jnp.zeros(n, dtype=jnp.int64)
    for (d, v), (_pos, desc, nf) in reversed(list(zip(cols, sspecs))):
        x, r, rf, okbit = _rank_encode(
            d.astype(jnp.int64), v, desc, nf, live
        )
        ok = ok & okbit
        packed = packed + x * stride
        stride = stride * r
        prod = prod * jnp.maximum(rf, 1.0)
    ok = ok & (prod < jnp.float64(2**62))
    return packed, ok


def _topk_idx(packed, live, k: int):
    """Indices + validity of the k smallest packed keys among live rows.

    Hierarchical exact selection (k is a LIMIT — tiny): ONE full pass
    computes per-chunk minima, then k iterations touch only the [nc]
    chunk-minima vector and one [cs] chunk — total ~one linear scan,
    versus k full scans for a flat argmin loop or a full O(n log^2 n)
    device sort. Returns (idx [k] int32, valid [k] bool)."""
    big = jnp.int64(2**62)
    key = jnp.where(live, packed, big)
    n = key.shape[0]
    cs = 8192
    nc = max(-(-n // cs), 1)
    pad = nc * cs - n
    kp = jnp.pad(key, (0, pad), constant_values=2**62) if pad else key
    chunks = kp.reshape(nc, cs)
    mins = jnp.min(chunks, axis=1)
    # loop carries derive from ``key`` so their varying-manual-axes match
    # inside shard_map (a plain zeros init is replicated and rejected)
    zero_like = (key[:1] * 0).astype(jnp.int32)  # [1], varying as key
    idx0 = jnp.zeros(k, jnp.int32) + zero_like
    val0 = jnp.zeros(k, jnp.bool_) | (zero_like != 0)
    lane = jnp.arange(cs, dtype=jnp.int32)

    def body(i, st):
        mins, idx, val = st
        c = jnp.argmin(mins).astype(jnp.int32)
        # mask already-taken lanes instead of writing the big chunk
        # array back (an in-loop update would copy it every iteration)
        row = chunks[c]
        taken = (idx // cs == c) & (jnp.arange(k) < i)
        hit = jnp.any(
            taken[:, None] & (lane[None, :] == (idx % cs)[:, None]),
            axis=0,
        )
        row = jnp.where(hit, big, row)
        j = jnp.argmin(row).astype(jnp.int32)
        val = val.at[i].set(row[j] < big)
        mins = mins.at[c].set(
            jnp.min(jnp.where(lane == j, big, row))
        )
        return mins, idx.at[i].set(c * cs + j), val

    _, idx, val = jax.lax.fori_loop(0, k, body, (mins, idx0, val0))
    idx = jnp.minimum(idx, n - 1)  # padding can never win (== big)
    return idx, val


def _rows_a_device(sig: tuple, D: int) -> int:
    """The widest leaf array of a fragment (``_shapes_sig``), a device:
    the padded row count its program works on. A join answers at its
    probe side's rows, so no fragment's root is wider than its widest
    leaf."""
    return max(
        (math.prod(shape) for blk in sig for shape, _dt in blk),
        default=0,
    ) // D


def _dest_counts(mask, dest, D: int):
    """Live rows bound for each of the ``D`` devices, by ``D`` masked
    reductions (a ``segment_sum`` over the rows lowers to a
    scatter-add)."""
    return jnp.stack([
        # otb_lint: ignore[int32-width] -- a count of one device's padded rows, which index as int32 (n < 2^31)
        jnp.sum(mask & (dest == d), dtype=jnp.int32) for d in range(D)
    ])


def _pack_bits(planes: list) -> list:
    """Boolean planes as bits of ``uint32`` words, 32 a word: the
    validity of every nullable column rides a sort or a collective as
    one operand."""
    words = []
    for w in range(0, len(planes), 32):
        word = jnp.zeros(planes[w].shape, dtype=jnp.uint32)
        for j, p in enumerate(planes[w:w + 32]):
            word = word | (p.astype(jnp.uint32) << j)
        words.append(word)
    return words


def _unpack_bits(words: list, count: int) -> list:
    """The ``count`` planes ``_pack_bits`` packed."""
    return [
        ((words[i // 32] >> (i % 32)) & 1).astype(jnp.bool_)
        for i in range(count)
    ]


def _collect_arrays(fx, root, exchanged: dict, D: int, fits=None) -> list:
    return [
        _leaf_arrays(fx, n, exchanged, D, fits) for n in _walk_leaves(root)
    ]


class _Deferred:
    """A build column of a join at the probe's rows, not gathered yet:
    ``benv[ci]`` at ``bidx``. Whoever reads it as an array gathers it at
    the probe's width (``gather``, under the join's own scope); a final
    that wants it at a few output rows alone follows the row through
    the joins instead (``at_rows``) and never pays the width.
    ``narrow``: a 64-bit column that crosses as one 32-bit word
    (``_take_word``), read back as ``int64`` either way; ``note``,
    called at the gather, counts a 64-bit column by its words."""

    __slots__ = ("benv", "ci", "bidx", "mode", "stage", "narrow", "note")

    def __init__(self, benv, ci: int, bidx, mode, stage: str,
                 narrow: bool = False, note=None):
        self.benv, self.ci, self.bidx = benv, ci, bidx
        self.mode, self.stage = mode, stage
        self.narrow, self.note = narrow, note

    def gather(self):
        d, v = self.benv[self.ci]
        if self.note is not None:
            self.note()
            self.note = None  # (one column, counted once a trace)
        with scope(self.stage):
            return (
                _take_word(d, self.bidx, self.mode, self.narrow),
                None if v is None
                else jnp.take(v, self.bidx, axis=0, mode=self.mode),
            )

    def at_rows(self, rows):
        d, v = _col_at_rows(
            self.benv, self.ci, jnp.take(self.bidx, rows, mode="clip")
        )
        return (d.astype(jnp.int64) if self.narrow else d), v


class _LazyEnv(list):
    """A joined row's columns where some are ``_Deferred``: reading
    one by position gathers it once and keeps it, so compiled
    expressions see the (data, validity) pairs they always saw.
    ``raw`` hands the entries on untouched (to the next joined row, to
    a projection of bare columns)."""

    def __getitem__(self, i):
        if isinstance(i, slice):
            return _LazyEnv(list.__getitem__(self, i))
        e = list.__getitem__(self, i)
        if isinstance(e, _Deferred):
            e = e.gather()
            self[i] = e
        return e

    def __iter__(self):
        return (self[i] for i in range(len(self)))

    def raw(self) -> list:
        return [list.__getitem__(self, i) for i in range(len(self))]


def _raw(env) -> list:
    return env.raw() if isinstance(env, _LazyEnv) else list(env)


def _col_at_rows(env, i: int, rows):
    """Column ``i`` of ``env`` at the rows ``rows`` alone: a deferred
    one by its build row's index, through as many joins as deferred
    it."""
    e = (
        list.__getitem__(env, i) if isinstance(env, _LazyEnv) else env[i]
    )
    if isinstance(e, _Deferred):
        return e.at_rows(rows)
    d, v = e
    k = rows.shape[0]
    return (
        jnp.broadcast_to(d, (k,)) if jnp.ndim(d) == 0
        else jnp.take(d, rows, axis=0, mode="clip"),
        None if v is None else (
            jnp.broadcast_to(v, (k,)) if jnp.ndim(v) == 0
            else jnp.take(v, rows, axis=0, mode="clip")
        ),
    )


def _read_first(env, cols) -> None:
    """Gather the deferred columns among ``cols`` now, each under its
    join's scope, before the reader opens a scope of its own."""
    if isinstance(env, _LazyEnv):
        for i in sorted(cols):
            if i < len(env):
                env[i]


@dataclasses.dataclass(frozen=True)
class _JoinInfo:
    """What one compile decided about its inner joins — cached beside
    the program. ``folded``, ``gated`` and ``forced`` are the build's
    inputs to the join lowering (part of the entry's signature: a
    cached executable answers only for the same three); ``radixed`` is
    the builder's own set of joins a radix table was really traced in
    for, filled at the program's first call and read by the flag
    handler after it ran."""

    folded: frozenset
    gated: frozenset  # the radix gate's estimates (or the GUC) admitted
    forced: bool  # join_mode = radix: a table at any width
    radixed: set = dataclasses.field(compare=False)

    def remap(self, fn) -> "_JoinInfo":
        """The same record in another join-index space."""
        return _JoinInfo(
            frozenset(fn(x) for x in self.folded),
            frozenset(fn(x) for x in self.gated),
            self.forced, {fn(x) for x in self.radixed},
        )


class _Builder:
    def __init__(
        self, fx, comp: ExprCompiler, orientation: tuple, root,
        capture_id=None, runner=None, D: int = 1,
        fold_off=frozenset(), window=None, defer: bool = False,
    ):
        self.fx = fx
        self.comp = comp
        # a join's build columns stay ``_Deferred`` until something
        # reads them (``_LazyEnv``): asked for by a final that wants
        # some of them at its few output rows alone (gagg's dropped
        # group keys). Off, every program is traced op for op as ever
        self.defer = defer
        self.orientation = orientation
        self.leaf_index = {
            id(n): i for i, n in enumerate(_walk_leaves(root))
        }
        self.njoin = 0  # inner joins seen (orientation index)
        # dimension-fold state: the runner supplies row estimates and
        # producer motions; ``fold_off`` are join indices whose dense
        # lookup already failed at runtime (fall back to sort-merge);
        # ``folded`` records which joins THIS compile folded so the
        # runner can route their flags to fold-disable instead of
        # orientation flips
        self.runner = runner
        self.D = D
        # (table, column) of the 64-bit integer columns whose resident
        # range fits 32 bits, as the runner read them beside the arrays
        # it keyed this program on: such a column crosses a join as one
        # word where the joined row reads it (``_build_join``)
        self.fits = getattr(runner, "_fits", frozenset())
        # ``fold_off`` arrives either as a plain frozenset (legacy) or
        # as the (fold_off, radix_off) pair the runner's retry loops
        # thread through every compile — joins whose dense fold or
        # radix table failed at runtime fall back to sort-merge
        if (
            isinstance(fold_off, tuple) and len(fold_off) == 2
            and all(isinstance(s, frozenset) for s in fold_off)
        ):
            self.fold_off, self.radix_off = fold_off
        else:
            self.fold_off = frozenset(fold_off)
            self.radix_off = frozenset()
        self.folded: set = set()
        self.folded_ids: dict = {}  # id(join) -> build_right, folded
        # joins of THIS compile the radix gate admitted, and those a
        # table was traced in for (the shape rule of _lookup_radix
        # decides at trace time, so ``radixed`` fills at the program's
        # first call; jinfo() hands it out live)
        self.gated: set = set()
        self.radixed: set = set()
        # the join formulations of THIS compile: 'fold' and a plain
        # 'merge' chosen here; for a join the radix gate admitted,
        # 'radix' (+ 'pallas') or 'merge' noted when the trace knows
        # the build's width. The set rides the jitted program
        # (DagRunner._program), so a run reports the modes of the cache
        # entry that ran
        self.modes: set = set()
        # join<i> -> '<formulations>:<build>x<probe>' (as join_modes
        # spells them, e.g. 'pallas+radix'; static widths a device),
        # written at trace time: the program's fused.bind /
        # fused.launch arg ``joins``
        self.joins: dict = {}
        fx_h = runner.fx if runner is not None else fx
        self.join_mode = str(getattr(fx_h, "join_mode", "auto"))
        self.radix_budget = batchplan.resolve_budget(
            int(getattr(fx_h, "device_memory_limit", 0) or 0),
            batchplan.DEFAULT_EXCHANGE_BUDGET,
        )
        # windowed execution: (leaf id, width) — that scan leaf reads
        # only [wstart, wstart+width) of each shard's rows per run; the
        # runner appends the traced ``wstart`` to the leaf's block tuple
        self.window = window
        # group-by-build-side: the join node whose (bidx, build env) the
        # final program consumes; written at trace time, read right after
        # ev() inside the same trace
        self.capture_id = capture_id
        self.captured = None
        # join primitive: double-sort merge on TPU (searchsorted is a
        # serial binary search there), sorted binary search elsewhere
        self.platform = fx.platform()  # FusedExecutor's one detector
        self.lookup = (
            _lookup_sortmerge if self.platform == "tpu" else _lookup
        )

    def jinfo(self) -> _JoinInfo:
        """This compile's join record — cached beside the program so
        the runner's flag handler knows whether a raised flag means
        fold-disable, radix-disable, or flip. A join the shape rule
        sent to sort-merge is not in ``radixed``: its flag is answered
        like any sort-merge join's (flip), not by a retry that disables
        a table that never was."""
        return _JoinInfo(
            frozenset(self.folded), frozenset(self.gated),
            self.join_mode == "radix" and bool(self.gated), self.radixed,
        )

    def _fold_eligible(self, node: L.Join, ji: int, build_right: bool):
        """Attempt the dense direct-index lookup for this inner join?
        See ``_fold_gate`` — the one shared definition. Children build
        first (post-order), so their fold decisions are exact."""
        return _fold_gate(
            self.runner, node, ji, build_right, self.fold_off,
            folded_ids=self.folded_ids,
        )

    def _repl_scan_leaves(self, node, every: bool = False) -> bool:
        """True when ``node``'s subtree scans a REPLICATED table
        directly (``every``: when it scans nothing else). On a
        multi-device mesh such a scan places the one replica's rows on
        ONE device — fine alone (each row processed once), but a join
        side built from it sees only a fraction of the rows per device.
        The reference never faces this: every datanode holds a full
        copy of a replicated table (pgxc/locator.c
        LOCATOR_TYPE_REPLICATED)."""
        try:
            leaves = list(_walk_leaves(node))
        except DagUnsupported:
            return False
        return (all if every else any)(
            isinstance(lf, L.Scan)
            and self.fx.catalog.get(lf.table).dist.is_replicated
            for lf in leaves
        )

    def _complete_rows(self, ev, D: int) -> Callable:
        """Wrap a side's closure so its rows are per-device COMPLETE:
        all_gather the per-device blocks inside the program — the
        in-program equivalent of the broadcast motion, for replicated
        tables whose single replica store landed on one mesh device."""

        def run(blocks, params, snap):
            env, mask, n, flags = ev(blocks, params, snap)

            def gath(x):
                g = jax.lax.all_gather(x, "dn", axis=0)
                return g.reshape((D * n,) + x.shape[1:])

            env2 = [
                (
                    gath(jnp.broadcast_to(d, (n,) + d.shape[1:])),
                    None if v is None else gath(jnp.broadcast_to(v, (n,))),
                )
                for d, v in env
            ]
            return env2, gath(jnp.broadcast_to(mask, (n,))), D * n, flags

        return run

    # -- leaves -----------------------------------------------------------
    def _leaf_scan(self, node: L.Scan, D: int) -> Callable:
        meta = self.fx.catalog.get(node.table)
        dtab = self.fx.cache.get(
            node.table, meta, self.fx.node_stores, _scan_nodes(meta),
            columns=node.columns,
        )
        has_valid = tuple(
            dtab.validity[c] is not None for c in node.columns
        )
        idx = self.leaf_index[id(node)]
        win = (
            self.window[1]
            if self.window is not None and self.window[0] == id(node)
            else None
        )

        rmax0 = dtab.rmax

        def run(blocks, params, snap):
            # visibility planes are full [k, Rmax] or compact [k, 1]
            # (uniform per shard) — 2-D compares broadcast either form.
            # This vectorized xmin<=snap<xmax compare is the device
            # MVCC filter (tqual.c:2274 analog, SURVEY §7): it covers
            # delta-resident rows too, because the cache keeps the
            # planes append-current via tail uploads + stamp replay —
            # the delta plane needs no separate visibility pass.
            if win is not None:
                cols, valids, xmin, xmax, nrows, wstart = blocks[idx]
                k = xmin.shape[0]
                W = win

                def sl(a2d):
                    return jax.lax.dynamic_slice(
                        a2d,
                        (jnp.asarray(0, wstart.dtype), wstart),
                        (k, W),
                    )

                with scope("scan/decode"):
                    cols = [sl(c) for c in cols]
                    valids = [sl(v) for v in valids]
                    if xmin.shape[1] != 1:
                        xmin, xmax = sl(xmin), sl(xmax)
                n = k * W
                with scope("scan/mvcc"):
                    live = (
                        (wstart + jnp.arange(W)[None, :]
                         < nrows[:, None])
                        & (xmin <= snap) & (snap < xmax)
                    ).reshape(n)
            else:
                cols, valids, xmin, xmax, nrows = blocks[idx]
                k = xmin.shape[0]
                rmax = rmax0
                n = k * rmax
                with scope("scan/mvcc"):
                    live = (
                        (jnp.arange(rmax)[None, :] < nrows[:, None])
                        & (xmin <= snap) & (snap < xmax)
                    ).reshape(n)
            env = []
            vi = 0
            with scope("scan/decode"):
                for ci in range(len(cols)):
                    d = cols[ci].reshape(n)
                    if has_valid[ci]:
                        env.append((d, valids[vi].reshape(n)))
                        vi += 1
                    else:
                        env.append((d, None))
            return env, live, n, []

        return run

    def _leaf_exch(self, node: RemoteSource, exchanged: dict) -> Callable:
        if node.fragment not in exchanged:
            raise DagUnsupported("remote source order")
        idx = self.leaf_index[id(node)]

        def run(blocks, params, snap):
            cols, valids, counts = blocks[idx]
            dsrc, cap = cols[0].shape
            n = dsrc * cap
            with scope("exchange/read"):
                live = (
                    jnp.arange(cap)[None, :] < counts[:, None]
                ).reshape(n)
                env = [
                    (cols[i].reshape(n), valids[i].reshape(n))
                    for i in range(len(cols))
                ]
            return env, live, n, []

        return run

    # -- recursive build ---------------------------------------------------
    def build(
        self, node: L.LogicalPlan, exchanged: dict, D: int,
        required=frozenset(),
    ) -> Callable:
        """``required``: the positions of ``node.schema`` that whatever
        consumes the closure's rows reads (None: every one), walked down
        as ``plan/optimize._prune_node`` walks it. A fold chooses the
        column its match bit rides in among those (``_build_join``); a
        caller that cannot say passes nothing and its folds keep a bit
        of their own, which costs a gather and is never wrong."""
        if isinstance(node, L.Filter):
            child_req = None if required is None else (
                set(required) | _expr_cols(node.predicate)
            )
            child = self.build(node.child, exchanged, D, child_req)
            dids = [c.dict_id for c in node.child.schema]
            pred = self.comp.compile(node.predicate, dids)
            stage = (
                "filter" if _contains_join(node.child) else "scan"
            ) + "/predicate"

            pcols = _expr_cols(node.predicate)

            def run(blocks, params, snap):
                env, mask, n, flags = child(blocks, params, snap)
                _read_first(env, pcols)
                with scope(stage):
                    d, v = pred(env, params)
                    keep = d if v is None else (d & v)
                    mask = mask & jnp.broadcast_to(keep, (n,))
                return env, mask, n, flags

            return run

        if isinstance(node, L.Project):
            child_req: set = set()
            for i, ex in enumerate(node.exprs):
                if required is None or i in required:
                    _expr_cols(ex, child_req)
            child = self.build(node.child, exchanged, D, child_req)
            dids = [c.dict_id for c in node.child.schema]
            fns = [
                self.comp.compile(
                    ex, dids,
                    (oc.dict_id or None) if ex.type.is_text else None,
                )
                for ex, oc in zip(node.exprs, node.schema)
            ]

            stage = (
                "project" if _contains_join(node.child) else "scan"
            ) + "/project"

            # bare columns a deferring build hands on as they are (no
            # dictionary to translate between)
            passed = {
                o: ex.index for o, (ex, oc) in enumerate(
                    zip(node.exprs, node.schema))
                if self.defer and isinstance(ex, E.Col) and (
                    not ex.type.is_text or not oc.dict_id
                    or oc.dict_id == dids[ex.index]
                )
            }
            ecols: set = set()
            for o, ex in enumerate(node.exprs):
                if o not in passed:
                    _expr_cols(ex, ecols)

            def run(blocks, params, snap):
                env, mask, n, flags = child(blocks, params, snap)
                if not passed:
                    with scope(stage):
                        out = [_bcast(fn(env, params), n) for fn in fns]
                    return out, mask, n, flags
                _read_first(env, ecols)
                raw = _raw(env)
                with scope(stage):
                    out = _LazyEnv(
                        raw[passed[o]] if o in passed
                        else _bcast(fn(env, params), n)
                        for o, fn in enumerate(fns)
                    )
                return out, mask, n, flags

            return run

        if isinstance(node, L.Scan):
            return self._leaf_scan(node, D)

        if isinstance(node, RemoteSource):
            return self._leaf_exch(node, exchanged)

        if isinstance(node, L.Join):
            return self._build_join(node, exchanged, D, required)

        if isinstance(node, L.Aggregate):
            # (the runner turns a semi join's one-key key set into a
            # program of its own before anything builds: _semiagg_build)
            raise DagUnsupported(
                "Aggregate under a join: only a semi join's one-key "
                "grouped key set runs on the device, on one device"
            )
        raise DagUnsupported(type(node).__name__)

    def _build_join(
        self, node: L.Join, exchanged: dict, D: int, required=frozenset()
    ) -> Callable:
        if node.join_type not in ("inner", "semi", "anti"):
            raise DagUnsupported(node.join_type)
        npairs = len(node.left_keys)
        if npairs == 0 or npairs != len(node.right_keys):
            raise DagUnsupported(f"{node.join_type} join without keys")
        for k in node.left_keys + node.right_keys:
            if k.type.id not in _JOINABLE_KEY_TYPES:
                raise DagUnsupported(f"join key type {k.type.id}")
        nleft = len(node.left.schema)
        # positions of the joined row read AFTER the lookup: by whatever
        # is above, and by this join's residual
        after = set(
            range(len(node.schema)) if required is None else required
        )
        if node.residual is not None:
            _expr_cols(node.residual, after)
        lreq = {i for i in after if i < nleft}
        rreq = {i - nleft for i in after if i >= nleft}
        for k in node.left_keys:
            _expr_cols(k, lreq)
        for k in node.right_keys:
            _expr_cols(k, rreq)
        left = self.build(node.left, exchanged, D, lreq)
        right = self.build(node.right, exchanged, D, rreq)
        ldids = [c.dict_id for c in node.left.schema]
        rdids = [c.dict_id for c in node.right.schema]
        lkfns = [self.comp.compile(k, ldids) for k in node.left_keys]
        rkfns = [self.comp.compile(k, rdids) for k in node.right_keys]
        resfn = None
        if node.residual is not None:
            jdids = [c.dict_id for c in node.schema]
            resfn = self.comp.compile(node.residual, jdids)
        jt = node.join_type
        build_right = True
        fold = False
        use_radix = False
        bstrip_fn = None
        # a join with several key pairs: ONE pair drives the lookup (the
        # fold's and the radix table's key; the first key of the
        # sort-merge), every other pair is an equality the matched row
        # must also pass — SQL's conjunction, NULL keys matching nothing
        drive = 0
        if jt == "inner":
            ji = self.njoin
            self.njoin += 1
            build_right = (
                self.orientation[ji] if ji < len(self.orientation) else "R"
            ) == "R"
            drive = _drive_pair(self.runner, node, build_right)
            fold = self._fold_eligible(node, ji, build_right)
            if fold:
                self.folded.add(ji)
                self.folded_ids[id(node)] = build_right
                # the chain leaf's closure supplies the density domain
                # (visibility only); the FULL build closure's mask —
                # filters, nested fold matches, everything — becomes
                # slot validity
                bnode = node.right if build_right else node.left
                leaf, _lp = _chain_leaf(
                    bnode, folded_ids=self.folded_ids
                )
                bstrip_fn = self.build(leaf, exchanged, D)
                presorted = isinstance(leaf, RemoteSource) and bool(
                    exchanged.get(leaf.fragment, {}).get("presorted")
                )
            else:
                # mode selection: fold (perfect hash over a dense key
                # range) > radix hash table (build small against the
                # probe by planner estimate AND, under ``auto``,
                # dimension-sized by its static width: at most 65,536
                # padded rows, the Pallas probe's reach) > sort-merge —
                # each failure class degrades one step at runtime via
                # the flag machinery. The width half of the radix rule
                # is _lookup_radix's, at trace time; its source: ledger,
                # PR 31, tpch_sf30_4chip.join device_ops (a 2^21-row
                # build probed by XLA gathers: 3,042 ms of a 5,230 ms
                # statement, 10x what sort-merge costs there)
                use_radix = _radix_gate(
                    self.runner, node, ji, build_right, self.radix_off,
                    self.join_mode,
                )
                if use_radix:
                    self.gated.add(ji)
            if not use_radix:
                self.modes.add("fold" if fold else "merge")
        if self.D > 1:
            # replicated tables scanned INSIDE a multi-device join
            # fragment hold their rows on one device — a build side
            # must be made per-device complete (in-program broadcast),
            # and a one-device probe against a sharded build cannot
            # match at all (host path answers instead)
            motions = (
                getattr(self.runner, "_motions", {})
                if self.runner is not None else {}
            )
            if jt in ("semi", "anti"):
                bnode2, pnode2, b_is_right = node.right, node.left, True
            else:
                bnode2 = node.right if build_right else node.left
                pnode2 = node.left if build_right else node.right
                b_is_right = build_right
            if self._repl_scan_leaves(bnode2):
                if b_is_right:
                    right = self._complete_rows(right, self.D)
                else:
                    left = self._complete_rows(left, self.D)
                if bstrip_fn is not None:
                    bstrip_fn = self._complete_rows(bstrip_fn, self.D)
                b_complete = True
            else:
                b_complete = _subtree_replicated(
                    bnode2, self.fx, motions
                )
            # (a probe subtree that built with a sharded leaf in it has
            # that leaf's rows: its replicated scans were build sides
            # made complete above, or it raised here already)
            if (
                self._repl_scan_leaves(pnode2, every=True)
                and not b_complete
            ):
                raise DagUnsupported(
                    "replicated probe vs sharded build on mesh"
                )
        do_capture = self.capture_id is not None and (
            id(node) == self.capture_id
        )
        builder = self
        lookup = self.lookup
        radix_budget = self.radix_budget
        # the MXU one-hot bucket probe (ops/pallas_join.py) rides only
        # on a TPU mesh; elsewhere interpret mode would measure the
        # emulator (the enable_pallas_scan convention)
        pallas_probe = use_radix and self.platform == "tpu"
        forced = self.join_mode == "radix"
        # scope of this join's lowering: join<i>/<formulation>
        jtag = f"join{ji}" if jt == "inner" else f"join_{jt}"
        # what the trace put in for this join (a radix-gated join's
        # formulation is known only there: _lookup_radix's shape rule)
        traced: set = set() if use_radix else {"fold" if fold else "merge"}

        def note_mode(mode: str, sized_out: bool = False) -> None:
            """The program's join modes (EXPLAIN ANALYZE, pg_stat_fused
            last_join_modes), the flag handler's ``radixed`` and the
            ``radix_sized_out`` count, as the trace decides them."""
            traced.add(mode)
            builder.modes.add(mode)
            if mode == "radix":
                builder.radixed.add(ji)
            if sized_out:
                builder.fx.radix_sized_out += 1

        lkfn, rkfn = lkfns[drive], rkfns[drive]
        others = [i for i in range(npairs) if i != drive]
        # a fold's match bit rides in a build column the joined row's
        # readers fetch anyway, where there is one (``_carrier``): the
        # build side's columns read after the lookup, this join's other
        # key pairs included (Q5's customer join reads ``c_nationkey``
        # only there). The driving key alone is read before it.
        bside = node.right if build_right else node.left
        bschema = bside.schema
        bkeys = node.right_keys if build_right else node.left_keys
        # the build side's positions read after the lookup: by what is
        # above (``bread``), and where the lookup does not see them
        # (fold, radix) this join's other key pairs too (``bchecked``)
        bread: set = set()
        if jt == "inner":
            boff = nleft if build_right else 0
            bread = {
                i - boff for i in after if 0 <= i - boff < len(bschema)
            }
        bchecked = set(bread)
        for i in others:
            _expr_cols(bkeys[i], bchecked)
        # of those the 64-bit integer columns, and the ones that cross
        # as one 32-bit word: bare columns of a table scanned in this
        # fragment whose range fits (``self.fits``). The narrow form is
        # gathered and widened back at once: every expression, residual
        # pair and final reads the declared ``int64``
        w64 = {
            i for i in bchecked
            if bschema[i].type.np_dtype == np.int64
        }
        nar = {i for i in w64 if _origin(bside, i) in self.fits}
        # a sort-merge sorts a one-pair join's key as one 32-bit word
        # where both sides' keys are bare columns whose range fits: a
        # table's scanned in this fragment, or a key set's whose program
        # proved it. A key that arrived through a motion, or an
        # expression, stays wide
        key32 = (
            not others
            and _key_fits(node.left, node.left_keys[0], self.fits)
            and _key_fits(node.right, node.right_keys[0], self.fits)
        )
        merge_keys = self.fx.merge_keys  # (pg_stat_fused, at trace time)
        words: dict = {}  # position -> narrowed?, as the trace reads it
        # ``keys=<pairs>:<the build side's driving key>`` on the join's
        # record, for a join with more than one pair
        keys_arg = ""
        if others:
            keys_arg = f" keys={npairs}:{_key_name(bkeys[drive], drive)}"

        rec: dict = {}

        def note_widths(bn: int, pn: int, bit: str = "") -> None:
            rec.update(widths=f"{bn}x{pn}", bit=bit)
            note_record()

        def note_record() -> None:
            """``join<i>=<formulations>:<build>x<probe>[ words=..][
            key=i32][ bit=..][ narrow=..][ keys=..]``: ``words=``, the
            build columns a Pallas probe returned; ``key=i32``, a
            sort-merge that sorted its key as one word; ``narrow=``
            names the 64-bit build columns that crossed as one word."""
            names = [
                bschema[i].name or f"col{i}"
                for i, one in words.items() if one
            ]
            narrow = f" narrow={'+'.join(names)}" if names else ""
            builder.joins[jtag] = (
                f"{'+'.join(sorted(traced))}:{rec['widths']}"
                f"{rec.get('words', '')}{rec.get('key', '')}{rec['bit']}"
                f"{narrow}{keys_arg}"
            )

        def note_word(i: int) -> None:
            """A 64-bit build column read after the lookup, gathered
            as one word or two (pg_stat_fused join_words_narrowed /
            join_words_wide, at trace time)."""
            one = i in nar
            words[i] = one
            builder.fx.join_words["narrowed" if one else "wide"] += 1
            if "widths" in rec:
                note_record()

        def other_pairs(lenv, ln, renv, rn, params) -> list:
            """[(left key, right key)] of every pair but the driving
            one, each side over its own rows: the further sort keys of
            the sort-merge lookup."""
            return [
                (_bcast(lkfns[i](lenv, params), ln),
                 _bcast(rkfns[i](renv, params), rn))
                for i in others
            ]

        defer = self.defer and jt == "inner"
        rescols = (
            _expr_cols(node.residual) if node.residual is not None
            else set()
        )

        def joined(penv, gathered) -> list:
            """The joined row: left columns, then right."""
            if defer:
                return _LazyEnv(
                    _raw(penv) + gathered if build_right
                    else gathered + _raw(penv)
                )
            return (
                list(penv) + gathered if build_right
                else gathered + list(penv)
            )

        def deferred(benv, bidx, mode, stage: str, reads, but=None):
            """The build side's columns at the probe's rows, each
            gathered when (and if) something reads it."""
            return [
                None if i == but else _Deferred(
                    benv, i, bidx, mode, stage, i in nar & reads,
                    partial(note_word, i) if i in w64 & reads else None,
                )
                for i in range(len(benv))
            ]

        def pairs_equal(env, mask, n, params, formulation: str):
            """The pairs the lookup did not see, checked where the
            residual is: over the joined row the lookup matched."""
            with scope(f"{jtag}/{formulation}/residual"):
                return _all_equal(
                    other_pairs(env[:nleft], n, env[nleft:], n, params),
                    mask,
                )

        def run(blocks, params, snap):
            if fold:
                lenv, lmask, ln, lflags = left(blocks, params, snap)
                renv, rmask, rn, rflags = right(blocks, params, snap)
                flags = lflags + rflags
                if build_right:
                    penv, pmask, pn = lenv, lmask, ln
                    benv, bmask, bn = renv, rmask, rn
                    pk = _bcast(lkfn(penv, params), pn)
                    bk = _bcast(rkfn(benv, params), bn)
                else:
                    penv, pmask, pn = renv, rmask, rn
                    benv, bmask, bn = lenv, lmask, ln
                    pk = _bcast(rkfn(penv, params), pn)
                    bk = _bcast(lkfn(benv, params), bn)
                # density domain: the chain leaf's visibility (XLA CSEs
                # the duplicate leaf read); slot validity: the full
                # build mask (filters + nested fold matches)
                _lenv, bvis, _bvn, _bf = bstrip_fn(blocks, params, snap)
                if others:
                    # (in the branch a join with several pairs alone
                    # takes: every other fold's program keeps its text)
                    with scope(f"{jtag}/fold/spread"):
                        pk = _spread_dead_keys(pk, pmask, bk, bmask)
                # (benv comes back in key order: the slot IS the build
                # row, and gseg's segment id beside its group keys.)
                # The probe costs ``max(W, 1)`` probe-width gathers for
                # W gathered words: with a carrier the match bit is
                # read off the carrier's word (``word``), and the
                # bit's own gather is gone: 648 ms a fold at 67.1M rows,
                # seven of them the costliest ops of a star rotation
                # (ledger, PR 37, ssb_star_sf10_1chip.star breakdown:
                # ``pred[67108864]`` fusions, 1.945 s a window each).
                # Without one (a dimension the plan only filters by) the
                # fold is traced op for op as before.
                if nar:
                    # one 32-bit word a narrowed column from here on,
                    # cast at the build's width: the ordered table, the
                    # mark of a narrowed carrier (the ``int32`` minimum,
                    # which its range leaves free) and the probe's gather
                    with scope(f"{jtag}/fold/order"):
                        benv = [
                            (d.astype(jnp.int32), v) if i in nar else (d, v)
                            for i, (d, v) in enumerate(benv)
                        ]
                ci = _carrier(benv, sorted(bchecked), bn)
                with scope(f"{jtag}/fold"):
                    matched, bidx, dup, benv, word = _lookup_dense(
                        pk, pmask, bk, bvis, bmask, benv,
                        presorted=presorted, carrier=ci,
                    )
                flags = flags + [dup]
                if do_capture:
                    builder.captured = (bidx, _widened(benv, nar), bn)
                bit = "own" if ci is None else (
                    bschema[ci].name or f"col{ci}"
                )
                note_widths(bn, pn, f" bit={bit}")
                builder.fx.fold_bits[  # (at trace time)
                    "own" if ci is None else "carried"
                ] += 1
                with scope(f"{jtag}/fold/gather"):
                    if defer:
                        gathered = deferred(
                            benv, bidx, "clip", f"{jtag}/fold/gather",
                            bchecked, ci,
                        )
                    else:
                        for i in sorted(w64):
                            note_word(i)
                        gathered = _take_rows(
                            [c for i, c in enumerate(benv) if i != ci],
                            bidx, "clip",
                        )
                    if ci is not None:
                        cv = benv[ci][1]
                        carried = (
                            word.astype(jnp.int64) if ci in nar else word,
                            None if cv is None
                            else jnp.take(cv, bidx, axis=0, mode="clip"),
                        )
                        if defer:
                            if ci in w64:
                                note_word(ci)
                            gathered[ci] = carried
                        else:
                            gathered.insert(ci, carried)
                    if nar and not defer:
                        gathered = _widened(gathered, nar - {ci})
                env = joined(penv, gathered)
                mask = pmask & matched
                n = pn
                if others:
                    builder.fx.multi_key_joins += 1  # (at trace time)
                    mask = pairs_equal(env, mask, n, params, "fold")
                _read_first(env, rescols)
                if resfn is not None:
                    d, v = resfn(env, params)
                    keep = d if v is None else (d & v)
                    mask = mask & jnp.broadcast_to(keep, (n,))
                return env, mask, n, flags
            lenv, lmask, ln, lflags = left(blocks, params, snap)
            renv, rmask, rn, rflags = right(blocks, params, snap)
            flags = lflags + rflags
            lk = _bcast(lkfn(lenv, params), ln)
            rk = _bcast(rkfn(renv, params), rn)
            # the sort-merge lookup over every pair: rows meet where all
            # their keys are equal, so a build side that repeats the
            # driving key but not the whole tuple is still a lookup
            xs = ()
            if others:
                builder.fx.multi_key_joins += 1  # (at trace time)
                xs = other_pairs(lenv, ln, renv, rn, params)
                if jt == "inner" and not build_right:
                    xs = [(r, l) for l, r in xs]  # (probe, build) pairs

            def merge(pk, pmask, bk, bmask, check_dup: bool):
                """The sort-merge lookup, its key as one 32-bit word
                where ``key32`` (``key=i32`` on the join's record,
                pg_stat_fused merge_keys_narrowed / merge_keys_wide at
                trace time)."""
                rec["key"] = " key=i32" if key32 else ""
                merge_keys["narrowed" if key32 else "wide"] += 1
                if key32:
                    return lookup(pk, pmask, bk, bmask, check_dup,
                                  narrow=True)
                return lookup(pk, pmask, bk, bmask, check_dup, extra=xs)
            if jt in ("semi", "anti"):
                # existence probe: build-side duplicates are harmless
                with scope(f"{jtag}/merge"):
                    matched, _bidx, _dup = merge(
                        lk, lmask, rk, rmask, check_dup=False
                    )
                note_widths(rn, ln)
                mask = lmask & (matched if jt == "semi" else ~matched)
                env, n = lenv, ln
            else:
                if build_right:
                    pk, pmask, penv, pn = lk, lmask, lenv, ln
                    bk, bmask, benv = rk, rmask, renv
                    bn = rn
                else:
                    pk, pmask, penv, pn = rk, rmask, renv, rn
                    bk, bmask, benv = lk, lmask, lenv
                    bn = ln
                kw: dict = {}  # position -> reads a kernel word back
                picked = None
                if use_radix:
                    # the build columns read after the lookup that are one
                    # 32-bit word ride the Pallas probe's own contraction
                    # (``_as_word``) and are not gathered at the probe's
                    # width; a deferring program reads each where it is
                    # read, as before
                    kwords = []
                    if pallas_probe and not defer:
                        for i in sorted(bchecked):
                            one = _as_word(benv[i], i in nar)
                            if one is not None:
                                kw[i] = one[1]
                                kwords.append((one[0], benv[i][1]))
                    # (a table is keyed on the driving pair alone; the
                    # shape rule's fallback sorts on every pair)
                    matched, bidx, dup, picked = _lookup_radix(
                        pk, pmask, bk, bmask, radix_budget, merge,
                        pallas_probe=pallas_probe,
                        note_mode=note_mode, tag=jtag, forced=forced,
                        words=kwords,
                    )
                    if picked is not None:
                        rec["words"] = " words=" + "+".join(
                            bschema[i].name or f"col{i}" for i in kw
                        )
                        builder.fx.radix_words_in_kernel += len(kw)
                else:
                    with scope(f"{jtag}/merge"):
                        matched, bidx, dup = merge(
                            pk, pmask, bk, bmask, check_dup=True
                        )
                flags = flags + [dup]
                if do_capture:
                    builder.captured = (bidx, benv, bn)
                note_widths(bn, pn)
                jmode = "radix" if "radix" in traced else "merge"
                # (a radix join checks its other pairs on the gathered
                # row; a sort-merge sorted on them)
                reads = bchecked if jmode == "radix" else bread
                if defer:
                    gathered = deferred(
                        benv, bidx, None, f"{jtag}/{jmode}/gather", reads
                    )
                else:
                    for i in sorted(w64 & reads):
                        note_word(i)
                    with scope(f"{jtag}/{jmode}/gather"):
                        gathered = _take_rows(
                            benv, bidx, narrow=nar & reads
                        )
                    # (the gathers of the words the kernel returned are
                    # dead from here: the compiler drops them)
                    for i, (w, v) in zip(kw, picked or ()):
                        gathered[i] = (kw[i](w), v)
                env = joined(penv, gathered)
                mask = pmask & matched
                n = pn
                if others and jmode == "radix":
                    mask = pairs_equal(env, mask, n, params, "radix")
            _read_first(env, rescols)
            if resfn is not None:
                d, v = resfn(env, params)
                keep = d if v is None else (d & v)
                mask = mask & jnp.broadcast_to(keep, (n,))
            return env, mask, n, flags

        return run


# ---------------------------------------------------------------------------
# Runner
# ---------------------------------------------------------------------------


@dataclasses.dataclass(eq=False)
class _Semi:
    """One semi join's grouped key set, as the runner carries it
    through a statement: its source's fragment id, the aggregate and
    HAVING it computes, its plan key, the capacity its program is
    traced for, the probe side's rows a key (0: not known), the
    formulation flags, and — once launched — its program, count and
    checks on the device."""

    frag: int
    agg: object
    having: object
    skey: str
    cap: int
    rows_per_key: float = 0.0
    capkey: tuple = ()
    narrow: bool = True
    robust: bool = False
    prog: object = None
    count: object = None
    checks: object = None


class DagRunner:
    """Compiles and runs an eligible DistributedPlan fragment DAG on the
    mesh of its FusedExecutor. One instance per FusedExecutor (program
    and orientation caches reset together with the device cache)."""

    def __init__(self, fx):
        self.fx = fx  # FusedExecutor: mesh, cache, catalog, node_stores
        self._programs: dict = {}
        self._orientations: dict = {}  # frag skey -> tuple of 'R'/'L'
        self._packing: dict = {}  # skey -> packed grouping viable?
        self._topk_off: dict = {}  # (skey, topk spec) -> ranking overflowed
        self._narrow_off: dict = {}  # skey -> i32 operands overflowed
        self._fold_off: dict = {}  # skey -> {join idx}: dense fold failed
        # skey -> {join idx}: radix table failed at runtime (bucket
        # overflow or duplicate build keys) — sort-merge answers instead
        self._radix_off: dict = {}
        # negative sum values break the cumsum+cummax run-base trick;
        # the robust retry switches those sums to a segmented add scan
        self._robust_on: dict = {}
        # (skey, data versions) -> a gagg's live rows overflowed the
        # prefix its ranking reads dropped keys at: they ride the sort
        self._prefix_off: dict = {}
        # the semi joins' key sets the current final reads (``_Semi``),
        # and what the final's fetch read of them for its collect span
        self._semis: list = []
        self._collect_note = None
        # sizing results remembered per (program, data version): repeat
        # queries on unchanged data skip the count pass / optimistic
        # group-capacity round trip entirely
        self._caps: dict = {}
        # the 64-bit columns of the fragment being bound whose range
        # fits 32 bits (``_leaf_arrays``): part of every program key
        # (``_bind``), read by the builder (``_Builder.fits``)
        self._fits: frozenset = frozenset()
        self.completed = 0  # DAG runs that produced the final batch
        self.last_mode = None  # final-fragment mode of the last run
        self.last_folded = frozenset()  # joins dense-folded in last run
        # join formulations of the programs whose answers the last run
        # ACCEPTED ('fold'/'radix'/'pallas'/'merge'), read from the
        # record each program-cache entry keeps of its own compile —
        # so a cold run, a re-trace and a cached run say the same, and
        # a formulation that was tried and refused shows as a retry,
        # not as a mode. EXPLAIN, pg_stat_fused and the fused.launch
        # span surface them.
        self.last_join_modes: tuple = ()
        self._mode_notes: set = set()
        # the device programs the last run launched, in order
        self.last_programs: tuple = ()
        self._frag = None  # fragment the run is on (span arg)
        # what the motion fragments moved since start-up
        # (pg_stat_fused's exchange_* rows; the per-statement share is
        # the ledger's)
        self.exchange_totals = dict.fromkeys(
            _stmtobs.EXCHANGE_COUNT_FIELDS, 0
        )
        # bounded log of plans that fell back to the host path and why —
        # surfaced through pg_stat_fused so demotion is NEVER silent
        self.unsupported: list = []

    # -- public ----------------------------------------------------------
    def run(
        self, dplan: DistributedPlan, snapshot_ts, dicts_view,
        subquery_values,
    ) -> Optional[tuple[int, ColumnBatch]]:
        """Execute the whole fragment DAG on device. Returns
        (final_fragment_index, gathered_batch) or None if the plan is
        outside the supported subset or bails out on data (duplicate
        join keys both sides)."""
        try:
            return self._run(
                dplan, snapshot_ts, dicts_view, subquery_values
            )
        except DagUnsupported as e:
            self.unsupported.append(str(e) or type(e).__name__)
            del self.unsupported[:-64]
            return None

    def _run(self, dplan, snapshot_ts, dicts_view, subquery_values):
        self.fx.launch.begin()
        self._mode_notes = set()
        self._semis = []
        self._collect_note = None
        frags = dplan.fragments
        if not frags:
            raise DagUnsupported("no fragments")
        final = frags[-1]
        if final.motion != "gather":
            raise DagUnsupported("final motion")
        # Sort/Limit/Distinct wrappers inside the final fragment are
        # pure pushdown optimizations — the coordinator root re-applies
        # each above the gather, so the DAG ships unsorted/uncut rows
        # (merge_keys likewise only order a merge-gather)
        final_root = final.root
        while isinstance(final_root, (L.Sort, L.Limit, L.Distinct)):
            final_root = final_root.child
        probe_root = final_root
        if isinstance(probe_root, L.Project):
            probe_root = probe_root.child
        if len(frags) == 1 and not (
            isinstance(probe_root, L.Aggregate)
            or _contains_join(final_root)
        ):
            # a bare scan chain: the host path answers faster than a
            # device round-trip, and uploading ephemeral tables (system
            # views) would thrash the device cache
            raise DagUnsupported("trivial scan")
        for f in frags[:-1]:
            if f.motion == "broadcast":
                continue
            if f.motion != "redistribute" or not f.hash_positions:
                raise DagUnsupported(f.motion)
        D = self.fx.mesh.shape["dn"]
        snap = jnp.int64(snapshot_ts if snapshot_ts is not None else 2**61)

        versions = self._data_versions(frags)
        # producer roots (orientation seeding) + motions (psum eligibility)
        self._producers = {f.index: f.root for f in frags[:-1]}
        self._motions = {f.index: f.motion for f in frags[:-1]}
        exchanged: dict[int, dict] = {}
        if D == 1 and len(frags) > 1:
            # single-device mesh: every exchange is an identity (all
            # rows already live on the one device), so the whole DAG
            # collapses into ONE program — RemoteSources inline to their
            # producer fragments, eliminating the bucket sorts,
            # inter-fragment buffers, and per-fragment compiles entirely
            final_root = _inline_sources(
                final_root, {f.index: f.root for f in frags[:-1]}
            )
        else:
            for f in frags[:-1]:
                run = (
                    self._run_broadcast
                    if f.motion == "broadcast"
                    else self._run_exchange
                )
                with self._motion_span(f, D) as xsp:
                    exchanged[f.index] = run(
                        f, exchanged, snap, dicts_view, subquery_values,
                        D, versions, xsp,
                    )
                self.exchange_totals["exchange_fragments"] += 1
        self._frag = "final"
        batch = self._run_final(
            final, final_root, exchanged, snap, dicts_view,
            subquery_values, D, versions, dplan,
        )
        if self._semis:
            self._accept(*(sm.prog for sm in self._semis))
            self.fx.semiagg_programs += len(self._semis)
        self.last_join_modes = tuple(sorted(self._mode_notes))
        self.last_programs = tuple(self.fx.launch.programs)
        self.completed += 1
        # device-platform watchdog: every completed DAG run stamps the
        # platform it actually executed on (executor/fused.py) — the
        # r04/r05 silent-CPU class fires a counter + warning here, not
        # at the next bench read
        self.fx.note_run_platform()
        return final.index, batch

    @staticmethod
    def _program(fn, name: str, b=None):
        """Jit ``fn`` as ``program_dag_<name>`` and hang the builder's
        join record on it (the formulations, and each join's with its
        static widths): the record lives and dies with the
        program-cache entry, and is whole once the program was traced,
        i.e. from its first call on."""
        prog = named_program(fn, "program_dag_" + name)
        prog.join_modes = b.modes if b is not None else set()
        prog.joins = b.joins if b is not None else {}
        return prog

    @staticmethod
    def _join_args(prog) -> dict:
        """A program's join record as span args (None where it has no
        join, or was not traced yet). Joins are set apart by ``;``: the
        profiler cuts a TraceMe's arguments at every comma, and a
        statement with several joins kept only its first in a trace."""
        return {
            "join_modes": "+".join(sorted(prog.join_modes)) or None,
            "joins": ";".join(
                f"{k}={v}" for k, v in sorted(prog.joins.items())
            ) or None,
        }

    def _launch(self, prog, arrays, params, snap, **args):
        """Enqueue one DAG program (``fused.launch``): its name, the
        fragment it serves and the join formulations it was compiled
        with ride the span (read after the call: a first call is what
        traces them in)."""
        return self.fx.launch(
            prog, lambda: (tuple(arrays), params, snap),
            late=lambda: self._join_args(prog),
            frag=self._frag, **args,
        )

    def _fetch(self, tree, what: str):
        return fetch(tree, what, frag=self._frag)

    def _accept(self, *progs) -> None:
        """The run keeps these programs' answers: their join
        formulations are the run's."""
        for prog in progs:
            self._mode_notes |= prog.join_modes

    def _retry(self, reason: str) -> None:
        self.fx.launch.note_retry(reason)

    def _data_versions(self, frags) -> tuple:
        """(table, version) for every scanned store — keys the cached
        exchange/group capacities so they refresh when data changes."""
        out = []
        for f in frags:
            root = f.root
            while isinstance(
                root, (L.Sort, L.Limit, L.Distinct, L.Aggregate)
            ):
                root = root.child
            for leaf in _walk_leaves(root):
                if isinstance(leaf, L.Scan):
                    meta = self.fx.catalog.get(leaf.table)
                    for n in _scan_nodes(meta):
                        store = self.fx.node_stores.get(n, {}).get(
                            leaf.table
                        )
                        if store is None:
                            raise DagUnsupported("missing store")
                        out.append((leaf.table, n, store.version))
        return tuple(out)

    # -- shared plumbing ---------------------------------------------------
    def _cached_program(self, key, compile_fn):
        """Program cache with LITERAL-SAFE param binding. Cache keys are
        structural (plan_skey masks constant values so literal changes
        reuse the compiled executable) — but the compile-time
        ExprCompiler BAKES the first query's literal values into its
        param specs. So compile_fn runs on EVERY call (cheap: closure
        building only — jax.jit is lazy, no tracing happens) to bind
        the CURRENT plan's literals, while the jitted program object
        comes from the cache. Without this, 'who = 1' silently reuses
        the program compiled for 'who = 7' WITH 7's parameter."""
        fresh = compile_fn()
        cached = self._programs.get(key)
        if cached is None:
            self._programs[key] = fresh
            return fresh
        np_ = self._NPROGS.get(key[0], 1)
        if self._entry_sig(fresh, np_) != self._entry_sig(cached, np_):
            # compile inputs OUTSIDE the key drifted (e.g. row-estimate
            # fold eligibility flipped as data grew): the cached
            # executable no longer matches the fresh specs — replace
            self._programs[key] = fresh
            return fresh
        # the cached program with the fresh literals; its join record is
        # the cached one too (equal by signature, and the one whose
        # ``radixed`` the cached program's trace filled)
        return tuple(cached[:np_]) + tuple(
            c if isinstance(c, _JoinInfo) else f
            for c, f in zip(cached[np_:], fresh[np_:])
        )

    _NPROGS = {"wgagg": 2}  # cache entries holding >1 jitted program

    @staticmethod
    def _entry_sig(entry, np_):
        """Structure of a cache entry's non-program parts: param-spec
        TYPES (values are the whole point of rebinding), modes, folded
        sets — anything that must agree between the cached executable
        and freshly-bound params."""
        out = []
        for x in entry[np_:]:
            if isinstance(x, ExprCompiler):
                out.append(tuple(type(p).__name__ for p in x.params))
            else:
                out.append(x)
        return tuple(out)

    def _frag_skey(self, frag: Fragment) -> str:
        return _plan_skey_of(frag.root)

    def _shapes_sig(self, arrays) -> tuple:
        return tuple(
            tuple(
                (tuple(a.shape), str(a.dtype))
                for a in jax.tree.leaves(blk)
            )
            for blk in arrays
        )

    def _resolve(self, comp, dicts_view, subquery_values):
        return tuple(
            resolve_param(s, dicts_view, subquery_values)
            for s in comp.params
        )

    def _bind(self, key, compile_fn, dicts_view, subquery_values):
        """``_cached_program`` + ``_resolve`` as the ``fused.bind`` span
        (``bind_ms``): the fresh closure, the program lookup and the
        literals resolved against it. Returns the entry with the
        resolved params appended."""
        # a column joins cross as one word is a compile input: data
        # grown past 32 bits binds the wide program, a range that moves
        # and still fits keeps the one it has
        key = key + (self._fits,)
        with _span(None, "fused.bind", "bind_ms", cat="fused") as bsp:
            # (looked up twice only when a sink will read the verdict)
            hit = bsp.listening and key in self._programs
            entry = self._cached_program(key, compile_fn)
            np_ = self._NPROGS.get(key[0], 1)
            params = self._resolve(
                entry[np_], dicts_view, subquery_values
            )
            if bsp.listening:
                # the join record of a program already traced (a miss
                # learns it at its first launch, whose span carries it)
                bsp.set(**self._join_args(entry[0]))
            bsp.set(
                program=entry[0].__name__,
                cache="hit" if hit else "miss", frag=self._frag,
            )
        return entry + (params,)

    def _est_rows(self, node) -> int:
        """Rough output-width estimate for orientation seeding: the
        largest leaf's live row count under ``node`` (joins/filters keep
        width at most the probe side's)."""
        if isinstance(node, L.Scan):
            meta = self.fx.catalog.get(node.table)
            return sum(
                st.nrows
                for n in _scan_nodes(meta)
                if (st := self.fx.node_stores.get(n, {}).get(node.table))
                is not None
            )
        if isinstance(node, RemoteSource):
            pr = getattr(self, "_producers", {}).get(node.fragment)
            return self._est_rows(pr) if pr is not None else 0
        kids = node.children() if isinstance(node, L.LogicalPlan) else ()
        return max((self._est_rows(c) for c in kids), default=0)

    def _orientation_for(self, skey, root):
        njoins = _count_inner_joins(root)
        o = self._orientations.get(skey, ())
        if len(o) == njoins:
            return o
        # seed build sides from estimated leaf widths: the smaller input
        # is the likelier unique side, and a wrong guess only costs one
        # dup-flag flip (the reference's cost-based join sides,
        # src/backend/optimizer/path/costsize.c final_cost_hashjoin)
        seeded: list = []

        def walk(n):
            if isinstance(n, L.Join):
                walk(n.left)
                walk(n.right)
                if n.join_type == "inner":
                    le, re = self._est_rows(n.left), self._est_rows(n.right)
                    seeded.append("L" if le <= re else "R")
            elif isinstance(n, (L.Filter, L.Project, L.Aggregate)):
                walk(n.child)

        walk(root)
        return tuple(seeded) if len(seeded) == njoins else ("R",) * njoins

    def _cap_store(self, key, value) -> None:
        """Remember a sizing result, bounded: stale (table, version)
        keys from superseded writes would otherwise accumulate for the
        life of the executor."""
        self._caps[key] = value
        while len(self._caps) > 512:
            self._caps.pop(next(iter(self._caps)))

    def _flip(self, orientation, flip_idx):
        if orientation[flip_idx] == "L":
            raise DagUnsupported("duplicate join keys on both sides")
        return tuple(
            "L" if i == flip_idx else o for i, o in enumerate(orientation)
        )

    def _top_join_foldable(self, root, orientation, skey) -> bool:
        """``_fold_gate`` applied to the TOP join — used to choose
        gagg-over-folds instead of the gsort concat-sort before any
        builder exists."""
        join = _top_join(root)
        if join is None or join.join_type != "inner":
            return False
        ji = _count_inner_joins(root) - 1
        build_right = (
            orientation[ji] if ji < len(orientation) else "R"
        ) == "R"
        return _fold_gate(
            self, join, ji, build_right, self._fold_off.get(skey, ())
        )

    def _offs(self, skey) -> tuple:
        """(fold_off, radix_off) frozenset pair for ``skey`` — threaded
        through every compile (the builder unpacks it) and every cache
        key (a disabled formulation must not reuse its old program)."""
        return (
            frozenset(self._fold_off.get(skey, ())),
            frozenset(self._radix_off.get(skey, ())),
        )

    def _on_flag(self, skey, orientation, flip, jinfo):
        """One join raised its data flag. For a folded join the flag
        means 'build keys not a dense unique range' — disable the fold
        for that join (keep the orientation) and let the next
        formulation answer; for a radix join it means 'bucket overflow
        or duplicate build keys' — disable the radix table the same
        way (sort-merge re-derives the exact dup verdict); for a
        sort-merge join it means duplicate build keys — flip the build
        side (raises when both sides were tried)."""
        if flip in jinfo.folded:
            self._retry(f"join{flip} fold density flag: fold off")
            self._fold_off.setdefault(skey, set()).add(flip)
            while len(self._fold_off) > 512:
                self._fold_off.pop(next(iter(self._fold_off)))
            return orientation
        if flip in jinfo.radixed:
            self._retry(f"join{flip} radix overflow or dup: radix off")
            self._radix_off.setdefault(skey, set()).add(flip)
            while len(self._radix_off) > 512:
                self._radix_off.pop(next(iter(self._radix_off)))
            return orientation
        self._retry(f"join{flip} duplicate build keys: flip sides")
        return self._flip(orientation, flip)

    def _check_hbm_budget(
        self, cap: int, schema, D: int, rows: int = 0
    ) -> None:
        """Bail to the host path before an exchange whose buffers would
        exhaust a device's memory (a crashed TPU worker is unrecoverable
        in-process; the host path is merely slower). One device's share
        of the exchange is held against the one-device budget, the
        spill-aware planner's (the device_memory_limit GUC, else the
        constant). ``rows``: the padded rows a device the redistribute's
        bucketing sort carries (a broadcast sorts none)."""
        budget = batchplan.resolve_budget(
            int(getattr(self.fx, "device_memory_limit", 0) or 0),
            batchplan.DEFAULT_EXCHANGE_BUDGET,
        )
        est = batchplan.exchange_bytes(
            cap, batchplan.exchange_row_bytes(schema), D, rows
        )
        if est > budget:
            raise DagUnsupported(
                f"exchange needs ~{est >> 20} MiB a device (> budget "
                f"{budget >> 20} MiB)"
            )

    def _device_route(self, frag, D: int) -> np.ndarray:
        """Mesh device of every bucket of the exchange's route table
        (``route_by_table``): for a redistribute onto a table's
        placement the device that holds the shard ``Locator.route_insert``
        gives the key, else the D devices in turn. A program ARGUMENT: a
        moved shard group changes the table, never the program."""
        if frag.target is None:
            return np.arange(D, dtype=np.int32)
        nodes = _scan_nodes(self.fx.catalog.get(frag.target.table))
        per_dev = _pad_shards(len(nodes), D) // D
        try:
            return motion_route(
                frag, self.fx.catalog,
                {n: i // per_dev for i, n in enumerate(nodes)},
            )
        except DistributeError as e:
            raise DagUnsupported(str(e))

    def _motion_span(self, frag, D: int):
        """The ``fused.exchange`` span of one motion fragment
        (``exchange_ms``: its own time, the binds, launches and waits
        inside it bill their own columns)."""
        self._frag = frag.index
        return _span(
            None, "fused.exchange", "exchange_ms", "exchange_fragments",
            cat="fused", frag=frag.index, motion=frag.motion,
            target=(
                frag.target.label() if frag.target is not None
                else ("hash" if frag.motion == "redistribute" else None)
            ),
            devices=D,
        )

    def _note_motion(self, xsp, moved: int, cap: int, schema, D: int,
                     counted: bool) -> None:
        """What the motion moved between devices, on its span and the
        statement's ledger: rows that left their device, the bucket
        slots shipped for them (``slots / rows`` is the padding waste)
        and those slots' bytes."""
        slots = D * (D - 1) * cap
        nbytes = slots * batchplan.exchange_row_bytes(schema)
        xsp.set(
            rows=moved, cap=cap, slots=slots, bytes=nbytes,
            count_pass="run" if counted else "cached",
        )
        led = _stmtobs.current()
        for field, v in (
            ("exchange_rows", moved), ("exchange_slots", slots),
            ("exchange_bytes", nbytes),
        ):
            self.exchange_totals[field] += v
            if led is not None:
                setattr(led, field, getattr(led, field) + v)

    # -- exchange (redistribute) fragments ---------------------------------
    def _run_exchange(
        self, frag, exchanged, snap, dicts_view, subquery_values, D,
        versions, xsp,
    ) -> dict:
        skey = self._frag_skey(frag)
        orientation = self._orientation_for(skey, frag.root)
        hashpos = tuple(frag.hash_positions)
        for p in hashpos:
            if frag.root.schema[p].type.is_text:
                # text keys are dict codes local to one column; the host
                # path translates — here we simply fall back
                raise DagUnsupported("text redistribution key")

        fits: set = set()
        arrays = _collect_arrays(self.fx, frag.root, exchanged, D, fits)
        self._fits = frozenset(fits)
        sig = self._shapes_sig(arrays)
        route = self._device_route(frag, D)
        while True:
            fo = self._offs(skey)
            # pass 1: per-(src, dest) routed-row counts -> bucket size.
            # Skipped entirely (one round trip saved) when this exact
            # program + literal values + route already sized itself
            # against unchanged data (literals are lifted params, so the
            # skey alone would alias different constants).
            ckey = ("xcnt", skey, orientation, hashpos, D, sig, fo)
            prog, comp, jinfo, params = self._bind(
                ckey,
                lambda: self._compile_count(
                    frag.root, exchanged, orientation, hashpos, D, fo
                ),
                dicts_view, subquery_values,
            )
            capkey = (
                "cap", skey, orientation, hashpos, D, sig, versions, fo,
                _params_sig(params), route.tobytes(),
            )
            sized = self._caps.get(capkey)
            counted = sized is None
            if counted:
                counts, flags = self._fetch(
                    self._launch(prog, arrays, (params, route), snap),
                    "count pass",
                )
                flip = _first_true(flags)
                if flip is not None:
                    orientation = self._on_flag(
                        skey, orientation, flip, jinfo
                    )
                    continue
                counts = np.asarray(counts).reshape(D, D)
                sized = (
                    filt_ops.bucket_size(max(int(counts.max()), 1)),
                    int(counts.sum() - np.trace(counts)),
                )
                self._cap_store(capkey, sized)
            cap, moved = sized
            self._check_hbm_budget(
                cap, frag.root.schema, D, _rows_a_device(sig, D)
            )

            # pass 2: the bucketed all_to_all
            xkey = ("xchg", skey, orientation, hashpos, D, cap, sig, fo)
            prog, comp, jinfo, params = self._bind(
                xkey,
                lambda: self._compile_exchange(
                    frag.root, exchanged, orientation, hashpos, D, cap,
                    fo,
                ),
                dicts_view, subquery_values,
            )
            cols, valids, rcounts, flags = self._launch(
                prog, arrays, (params, route), snap, mode=f"cap/{cap}"
            )
            flip = _first_true(self._fetch(flags, "join flags"))
            if flip is not None:
                orientation = self._on_flag(skey, orientation, flip, jinfo)
                continue
            self._accept(prog)
            self._orientations[skey] = orientation
            self._note_motion(
                xsp, moved, cap, frag.root.schema, D, counted
            )
            return {
                "cols": cols,
                "valids": valids,
                "counts": rcounts,
                "cap": cap,
                "schema": frag.root.schema,
            }

    # -- broadcast fragments -----------------------------------------------
    def _run_broadcast(
        self, frag, exchanged, snap, dicts_view, subquery_values, D,
        versions, xsp,
    ) -> dict:
        """Replicate a (small) fragment's rows to every device: compact
        per source, then all_gather — the broadcast-motion analog of the
        bucketed exchange. Output layout matches _run_exchange so the
        consumer leaf is oblivious."""
        skey = self._frag_skey(frag)
        orientation = self._orientation_for(skey, frag.root)
        fits: set = set()
        arrays = _collect_arrays(self.fx, frag.root, exchanged, D, fits)
        self._fits = frozenset(fits)
        sig = self._shapes_sig(arrays)
        while True:
            fo = self._offs(skey)
            ckey = ("bcnt", skey, orientation, D, sig, fo)
            prog, comp, jinfo, params = self._bind(
                ckey,
                lambda: self._compile_broadcast_count(
                    frag.root, exchanged, orientation, D, fo
                ),
                dicts_view, subquery_values,
            )
            capkey = (
                "bcap", skey, orientation, D, sig, versions, fo,
                _params_sig(params),
            )
            sized = self._caps.get(capkey)
            counted = sized is None
            if counted:
                counts, flags = self._fetch(
                    self._launch(prog, arrays, params, snap),
                    "count pass",
                )
                flip = _first_true(flags)
                if flip is not None:
                    orientation = self._on_flag(
                        skey, orientation, flip, jinfo
                    )
                    continue
                sized = (
                    filt_ops.bucket_size(max(int(counts.max()), 1)),
                    int(counts.sum()) * (D - 1),  # each row to D-1 others
                )
                self._cap_store(capkey, sized)
            cap, moved = sized
            self._check_hbm_budget(cap, frag.root.schema, D)

            bkey = ("bcast", skey, orientation, D, cap, sig, fo)
            prog, comp, jinfo, params = self._bind(
                bkey,
                lambda: self._compile_broadcast(
                    frag.root, exchanged, orientation, D, cap, fo
                ),
                dicts_view, subquery_values,
            )
            cols, valids, rcounts, flags = self._launch(
                prog, arrays, params, snap, mode=f"cap/{cap}"
            )
            flip = _first_true(self._fetch(flags, "join flags"))
            if flip is not None:
                orientation = self._on_flag(skey, orientation, flip, jinfo)
                continue
            self._accept(prog)
            self._orientations[skey] = orientation
            self._note_motion(
                xsp, moved, cap, frag.root.schema, D, counted
            )
            return {
                "cols": cols,
                "valids": valids,
                "counts": rcounts,
                "cap": cap,
                "schema": frag.root.schema,
            }

    def _compile_broadcast_count(
        self, root, exchanged, orientation, D, fo=frozenset()
    ):
        comp = ExprCompiler(lift_consts=True)
        b = _Builder(
            self.fx, comp, orientation, root, runner=self, D=D,
            fold_off=fo,
        )
        ev = b.build(root, exchanged, D)
        mesh = self.fx.mesh
        nflags = _count_inner_joins(root)

        def program(arrays, params, snap):
            def block(blocks):
                _env, mask, _n, flags = ev(blocks, params, snap)
                cnt = jnp.sum(mask, dtype=jnp.int32)
                return cnt.reshape(1), [
                    jnp.reshape(f, (1,)) for f in flags
                ]

            return shard_map(
                block,
                mesh=mesh,
                in_specs=(_specs_like(arrays),),
                out_specs=(P("dn"), [P("dn")] * nflags),
            )(arrays)

        return self._program(program, "count", b), comp, b.jinfo()

    def _compile_broadcast(
        self, root, exchanged, orientation, D, cap, fo=frozenset()
    ):
        comp = ExprCompiler(lift_consts=True)
        b = _Builder(
            self.fx, comp, orientation, root, runner=self, D=D,
            fold_off=fo,
        )
        ev = b.build(root, exchanged, D, None)  # every column is sent
        mesh = self.fx.mesh
        ncols = len(root.schema)
        nflags = _count_inner_joins(root)

        def program(arrays, params, snap):
            def block(blocks):
                env, mask, n, flags = ev(blocks, params, snap)
                order = jnp.argsort(~mask, stable=True)[:cap]
                out_cols = []
                out_valids = []
                for i in range(ncols):
                    d = jnp.broadcast_to(env[i][0], (n,))
                    out_cols.append(jax.lax.all_gather(
                        jnp.take(d, order), "dn", axis=0
                    ))
                    v = (
                        jnp.ones(n, dtype=jnp.bool_)
                        if env[i][1] is None
                        else jnp.broadcast_to(env[i][1], (n,))
                    )
                    out_valids.append(jax.lax.all_gather(
                        jnp.take(v, order), "dn", axis=0
                    ))
                cnt = jnp.minimum(jnp.sum(mask, dtype=jnp.int32), cap)
                rcnt = jax.lax.all_gather(cnt.reshape(1), "dn", axis=0)
                return (
                    out_cols,
                    out_valids,
                    rcnt.reshape(D),
                    [jnp.reshape(f, (1,)) for f in flags],
                )

            return shard_map(
                block,
                mesh=mesh,
                in_specs=(_specs_like(arrays),),
                out_specs=(
                    [P("dn")] * ncols,
                    [P("dn")] * ncols,
                    P("dn"),
                    [P("dn")] * nflags,
                ),
            )(arrays)

        return self._program(program, "broadcast", b), comp, b.jinfo()

    def _routed_eval(self, ev, hashpos):
        """``ev`` plus every row's destination device: the key hash
        through the exchange's route table (``_device_route``), the
        locator's own formula."""
        def run(blocks, params, snap, route):
            env, mask, n, flags = ev(blocks, params, snap)
            hashes = []
            with scope("exchange/route"):
                for p in hashpos:
                    d, v = env[p]
                    h = hash32_jnp(d)
                    if v is not None:
                        # NULL keys route to a deterministic bucket;
                        # the join's matched-logic already excludes
                        # them, and anti-join probes must SURVIVE, so
                        # never drop here
                        h = jnp.where(v, h, jnp.uint32(0))
                    hashes.append(h)
                dest = route_by_table(
                    combine_hashes(hashes, jnp), route, jnp
                )
            return env, mask, n, dest, flags

        return run

    def _compile_count(
        self, root, exchanged, orientation, hashpos, D, fo=frozenset()
    ):
        comp = ExprCompiler(lift_consts=True)
        b = _Builder(
            self.fx, comp, orientation, root, runner=self, D=D,
            fold_off=fo,
        )
        ev = b.build(root, exchanged, D, set(hashpos))  # routed, not sent
        routed = self._routed_eval(ev, hashpos)
        mesh = self.fx.mesh
        nflags = _count_inner_joins(root)

        def program(arrays, params, snap):
            params, route = params

            def block(blocks):
                _env, mask, _n, dest, flags = routed(
                    blocks, params, snap, route
                )
                cnt = _dest_counts(mask, dest, D)
                return cnt[None], [jnp.reshape(f, (1,)) for f in flags]

            return shard_map(
                block,
                mesh=mesh,
                in_specs=(_specs_like(arrays),),
                out_specs=(P("dn"), [P("dn")] * nflags),
            )(arrays)

        return self._program(program, "count", b), comp, b.jinfo()

    def _compile_exchange(
        self, root, exchanged, orientation, hashpos, D, cap,
        fo=frozenset(),
    ):
        comp = ExprCompiler(lift_consts=True)
        b = _Builder(
            self.fx, comp, orientation, root, runner=self, D=D,
            fold_off=fo,
        )
        ev = b.build(root, exchanged, D, None)  # every column is sent
        routed = self._routed_eval(ev, hashpos)
        mesh = self.fx.mesh
        ncols = len(root.schema)
        nflags = _count_inner_joins(root)

        def program(arrays, params, snap):
            params, route = params

            @_staged
            def block(blocks, st):
                env, mask, n, dest, flags = routed(
                    blocks, params, snap, route
                )
                # after ONE stable sort on the destination the rows of
                # bucket d ARE the contiguous run [off[d], off[d] +
                # cnt[d]): the payload rides the sort, every bucket is a
                # slice. No search, no row gather, no scatter (the chip
                # prices those at seconds where a sort streams).
                st.to("exchange/bucket/sort")
                dkey = jnp.where(mask, dest, D).astype(jnp.int32)
                nullable = [
                    i for i in range(ncols) if env[i][1] is not None
                ]
                vwords = _pack_bits(
                    [jnp.broadcast_to(env[i][1], (n,)) for i in nullable]
                )
                sorted_ = jax.lax.sort(
                    [dkey]
                    + [jnp.broadcast_to(env[i][0], (n,))
                       for i in range(ncols)]
                    + vwords,
                    num_keys=1, is_stable=True,
                )
                st.to("exchange/bucket/slab")
                cnt = _dest_counts(mask, dest, D)
                off = jnp.cumsum(cnt) - cnt
                live = jnp.arange(cap)[None, :] < cnt[:, None]

                def slab(col):
                    # cap slots beyond the last row: no start is ever
                    # clamped (a clamped start would shift a bucket)
                    ext = jnp.concatenate(
                        [col, jnp.zeros(cap, dtype=col.dtype)]
                    )
                    rows = jnp.stack([
                        jax.lax.dynamic_slice(ext, (off[d],), (cap,))
                        for d in range(D)
                    ])
                    return jnp.where(live, rows, jnp.zeros_like(rows))

                slabs = [slab(c) for c in sorted_[1:]]
                st.to("exchange/bucket/all_to_all")
                recv = [
                    jax.lax.all_to_all(
                        b, "dn", split_axis=0, concat_axis=0
                    )
                    for b in slabs
                ]
                rcnt = jax.lax.all_to_all(
                    cnt.reshape(D, 1), "dn", split_axis=0, concat_axis=0
                ).reshape(D)
                st.to("exchange/bucket/slab")
                out_cols = recv[:ncols]
                # a validity plane a column keeps the output pytree
                # static: a never-NULL column's is the received slots
                # themselves, a nullable one's its bit of the words
                filled = jnp.arange(cap)[None, :] < rcnt[:, None]
                planes = dict(zip(
                    nullable, _unpack_bits(recv[ncols:], len(nullable))
                ))
                out_valids = [planes.get(i, filled) for i in range(ncols)]
                return (
                    out_cols,
                    out_valids,
                    rcnt,
                    [jnp.reshape(f, (1,)) for f in flags],
                )

            return shard_map(
                block,
                mesh=mesh,
                in_specs=(_specs_like(arrays),),
                out_specs=(
                    [P("dn")] * ncols,
                    [P("dn")] * ncols,
                    P("dn"),
                    [P("dn")] * nflags,
                ),
            )(arrays)

        return self._program(program, "exchange", b), comp, b.jinfo()

    # -- final fragment ----------------------------------------------------
    def _run_final(
        self, frag, final_root, exchanged, snap, dicts_view,
        subquery_values, D, versions, dplan=None,
    ) -> ColumnBatch:
        agg = None
        root = final_root
        # aligned grouped plans (grouping subsumes the shard key) ship a
        # bare-column projection over the aggregate and skip the
        # coordinator merge — absorb it and re-apply at collect time
        out_proj = None
        if (
            isinstance(root, L.Project)
            and isinstance(root.child, L.Aggregate)
            and root.child.group_exprs  # scalar partials need the
            # coordinator merge; shipping D per-device rows un-merged
            # would surface as D result rows
            and all(isinstance(e, E.Col) for e in root.exprs)
            and len({c.name for c in root.schema}) == len(root.schema)
        ):
            out_proj = (
                tuple(e.index for e in root.exprs), root.schema
            )
            root = root.child
        if isinstance(root, L.Aggregate):
            if any(a.distinct for a in root.aggs):
                raise DagUnsupported("distinct agg")
            for a in root.aggs:
                if a.func not in ("sum", "count", "min", "max"):
                    raise DagUnsupported(a.func)
            agg = root
            root = root.child
        # the executed tree (inlined at D==1) keys the program cache —
        # the fragment's own root would alias different producer DAGs
        skey = _plan_skey_of(final_root)
        # a semi join on a grouped key set reads it from a program of
        # its own, launched first (its count is fetched with the answer)
        root, self._semis = self._semiagg_sources(root, D)
        if self._semis:
            exchanged = dict(exchanged)
            self._semiagg_launch(
                self._semis, exchanged, snap, D, versions, dicts_view,
                subquery_values,
            )
        orientation = self._orientation_for(skey, root)
        fits: set = set()
        arrays = _collect_arrays(self.fx, root, exchanged, D, fits)
        self._fits = frozenset(fits)
        sig = self._shapes_sig(arrays)
        # TopK pushdown spec (static per dplan): only rank-and-ship-k when
        # the sort keys are packable integer-family columns.
        # ``complete``: every group lives whole on ONE device (the
        # distributor skipped the coordinator merge-agg), so per-device
        # ranking is exact at any mesh size and devices' rows concatenate.
        tk = _detect_topk(dplan, frag) if dplan is not None else None
        complete = False
        if tk is not None:
            out_frag_schema = (
                out_proj[1] if out_proj is not None
                else (agg.schema if agg is not None else root.schema)
            )
            kk, sspecs, merged = tk
            if any(
                out_frag_schema[p].type.id not in _PACKABLE_SORT_TYPES
                or out_frag_schema[p].type.is_text
                for p, _d, _nf in sspecs
            ):
                tk = None
            elif merged and agg is None:
                tk = None  # coordinator re-agg must mirror a partial agg
            else:
                if not merged and agg is not None:
                    complete = True
                if out_proj is not None and tk is not None:
                    # remap ORDER BY positions through the projection
                    perm = out_proj[0]
                    tk = (
                        kk,
                        tuple(
                            (perm[p], d, nf) for p, d, nf in sspecs
                        ),
                        merged,
                    )
                if self._topk_off.get((skey, tk, versions)):
                    tk = None  # packed ranking overflowed: ship all
        # start from the remembered exact group capacity when this
        # program already ran against unchanged data + literals
        gcapkey = None
        gcap = OPTIMISTIC_GROUP_CAP
        # packed single-sort grouping until its range overflows — the
        # outcome is remembered per plan so repeat queries never re-run
        # a doomed packed program
        packing = self._packing.get(skey, True)
        n_dup = _count_inner_joins(root)
        # direct-addressed grouping wherever the rule allows it, at the
        # capacity remembered with ``gcap``; 0: the sort formulation
        eligible = _direct_grouping(agg)
        gslots = DIRECT_START_SLOTS if eligible else 0

        while True:
            # per-orientation mode selection: gseg (segment-reduce over
            # the unique build side, groups complete per device or made
            # complete by psum) > grouped+topk (single device: groups
            # trivially complete) > plain grouped/rows/scalar
            bg = None
            gs = None
            ga = None
            psum = False
            use_topk = tk is not None
            if use_topk and agg is not None and (D == 1 or complete):
                # co-sort formulation: needs whole groups per device —
                # a 1-device mesh, or a plan whose grouping subsumes the
                # sharding (per-device runs aren't group-aligned across
                # devices, so partials can't psum). When the top join
                # dimension-folds, gagg over the folded tree beats the
                # gsort concat-sort (probe-width sort vs probe+build,
                # and the folded build costs one small sort + gathers)
                ga_ok = _detect_gagg(agg, tk)
                if ga_ok and self._top_join_foldable(
                    root, orientation, skey
                ):
                    ga = ga_ok
                else:
                    gs = _detect_gsort(agg, root, orientation)
                    if gs is None:
                        ga = ga_ok
            if ga is not None and D == 1 and not self._semis:
                # bigger-than-HBM probe: stream the dominant scan leaf
                # through the same program in windows (device-resident
                # partials, one merge, one fetch; a key set's count is
                # read with the in-core final's answer alone)
                wplan = self._wgagg_leaf(root, agg, tk)
                if wplan is not None:
                    return self._run_wgagg(
                        wplan, agg, root, exchanged, tk, D, skey,
                        orientation, sig, versions, snap, dicts_view,
                        subquery_values, out_proj,
                    )
            if use_topk and agg is not None and gs is None and ga is None:
                bg = _detect_build_group(agg, root, orientation)
                if bg is not None and D > 1 and not complete:
                    join = _build_side_node(root)
                    ji = _count_inner_joins(root) - 1
                    bright = (
                        orientation[ji]
                        if ji < len(orientation)
                        else "R"
                    ) == "R"
                    bside = join.right if bright else join.left
                    if _subtree_replicated(
                        bside, self.fx, getattr(self, "_motions", {})
                    ):
                        psum = True
                    else:
                        bg = None
                if bg is None and D > 1 and not complete:
                    use_topk = False  # partial groups: must ship all
            narrow = (
                gs is not None or ga is not None
            ) and not self._narrow_off.get(skey)
            robust = bool(self._robust_on.get(skey))
            # where key sets bound the rows, a gagg that ranks by keys
            # its packing dropped reads them at the live prefix
            prefix = (
                self._rank_prefix_rows()
                if ga is not None
                and not self._prefix_off.get((skey, versions))
                else 0
            )
            fo = self._offs(skey)
            # (gseg, gsort and gagg are other functions; a final whose
            # packing overflowed sorts key by key)
            direct = gslots if packing and gs is None and ga is None and (
                bg is None or not use_topk
            ) else 0
            fkey = (
                "final", skey, orientation, None if direct else gcap, D,
                sig, packing,
                tk if use_topk else None, bg is not None, psum,
                gs is not None, ga is not None, narrow, fo, robust, direct,
                prefix,
            )
            def compile_final():
                if gs is not None:
                    comp = ExprCompiler(lift_consts=True)
                    b = _Builder(
                        self.fx, comp, orientation, root, runner=self,
                        D=D, fold_off=fo,
                    )
                    return self._compile_gsort(
                        b, comp, agg, gs, root, exchanged, tk, D,
                        _count_inner_joins(root), narrow=narrow,
                    ) + (b.jinfo(),)
                if ga is not None:
                    comp = ExprCompiler(lift_consts=True)
                    b = _Builder(
                        self.fx, comp, orientation, root, runner=self,
                        D=D, fold_off=fo,
                        defer=bool(
                            _gagg_late(root, orientation, agg, tk, prefix)
                        ),
                    )
                    ev = b.build(root, exchanged, D, _agg_reads(agg))
                    return self._compile_gagg(
                        b, ev, comp, agg, root, tk, D,
                        _count_inner_joins(root), narrow=narrow,
                        robust=robust, prefix=prefix,
                    ) + (b.jinfo(),)
                return self._compile_final(
                    frag, agg, root, exchanged, orientation, gcap, D,
                    packing,
                    topk=tk if use_topk else None, bg=bg, psum=psum,
                    fo=fo, gslots=direct,
                )

            prog, comp, mode, jinfo, params = self._bind(
                fkey, compile_final, dicts_view, subquery_values
            )
            if gcapkey is None:
                gcapkey = (
                    "gcap", skey, orientation, D, sig, versions,
                    _params_sig(params),
                )
                gcap_known = self._caps.get(gcapkey)
                gslots_known = self._caps.get(("gslots",) + gcapkey[1:])
                rebind = False
                if direct and gslots_known not in (None, gslots):
                    gslots = gslots_known  # the slots that held, or 0
                    rebind = True
                if gcap_known is not None and gcap_known != gcap:
                    gcap = gcap_known
                    rebind = rebind or not direct
                if rebind:
                    continue  # recompile/lookup at the exact capacity
            gargs = {}
            if mode in ("grouped", "grouped_topk"):
                # the formulation the program holds, the capacity it was
                # compiled for, and its keys
                ntext = sum(g.type.is_text for g in agg.group_exprs)
                gargs = {
                    "grouping": f"direct/{direct}" if direct else "sort",
                    "groups": direct or gcap,
                    "group_keys": f"{len(agg.group_exprs)} ({ntext} text)",
                }
            elif mode == "gagg":
                # what the packed sort key kept of the group keys, and
                # whether key and values rode the sort as 32-bit words
                gargs = dict(prog.gagg)
            launched = self._launch(
                prog, arrays, params, snap, mode=mode, **gargs
            )
            if not self._semis:
                outs = self._fetch(launched, "result")
            else:
                outs, verdicts = self._fetch(
                    (launched,
                     [(sm.count, sm.checks) for sm in self._semis]),
                    "result",
                )
                if self._semiagg_refused(self._semis, verdicts):
                    # an answer that read a refused key set: again,
                    # from the key sets on
                    self._semiagg_launch(
                        self._semis, exchanged, snap, D, versions,
                        dicts_view, subquery_values,
                    )
                    fits = set()
                    arrays = _collect_arrays(
                        self.fx, root, exchanged, D, fits
                    )
                    self._fits = frozenset(fits)
                    sig = self._shapes_sig(arrays)
                    gcapkey = None
                    continue
                self._collect_note = {"semiagg_keys": "+".join(
                    str(int(np.asarray(c).max())) for c, _k in verdicts
                )}
            self.last_mode = mode
            self.last_folded = jinfo.folded
            okf = None
            ngroups = None
            if direct:
                *outs, span = outs
            if mode in ("gseg", "gsort", "gagg"):
                out_keys, out_vals, gvalid, okf, flags = outs
            elif mode == "grouped_topk":
                out_keys, out_vals, gvalid, ngroups, okf, flags = outs
            elif mode == "grouped":
                out_keys, out_vals, gvalid, ngroups, flags = outs
            elif mode == "scalar":
                out_vals, flags = outs
            elif mode == "rows_topk":
                cols, valids, live, okf, flags = outs
            else:
                cols, valids, cnt, nrows_full, flags = outs
            flip = _first_true(flags)
            if flip is not None:
                if flip >= n_dup:
                    # the packed-key range overflowed int64: retry with
                    # per-key sorting (correctness never depended on it)
                    self._retry("packed group key overflow: packing off")
                    packing = False
                    self._packing[skey] = False
                    continue
                orientation = self._on_flag(skey, orientation, flip, jinfo)
                gcapkey = None  # keyed per orientation
                continue
            if direct and (span := int(np.asarray(span).max())) > direct:
                # a device's packed keys span more slots than the
                # program has (no row was counted): the power of two
                # that holds them, or past the bound the sort
                gslots = (
                    filt_ops.bucket_size(span)
                    if span <= DIRECT_MAX_SLOTS else 0
                )
                self._retry(
                    f"packed group keys span {span} > {direct} slots: "
                    + (f"direct/{gslots}" if gslots else "sort")
                )
                continue
            if okf is not None and not bool(np.asarray(okf).all()):
                # a gagg says which of its checks went false (on any
                # device); the ladder below is what it was, its reasons
                # name the cause
                why = ""
                failed: list = []
                if mode == "gagg":
                    checks = prog.gagg_checks
                    bad = ~np.asarray(okf).reshape(-1, len(checks))
                    failed = [
                        c for c, f in zip(checks, bad.any(axis=0)) if f
                    ]
                    why = " [" + ", ".join(failed) + " went false]"
                if "prefix" in failed:
                    # more live rows than the ranking's prefix holds:
                    # the dropped keys it ranks by ride the sort again
                    self._retry(
                        f"live rows past the ranking prefix{why}: "
                        "prefix off"
                    )
                    self._prefix_off[(skey, versions)] = True
                    while len(self._prefix_off) > 512:
                        self._prefix_off.pop(next(iter(self._prefix_off)))
                    continue
                if mode in ("gsort", "gagg") and narrow:
                    # i32 operand range overflowed: retry the wide
                    # program before giving up on ranking entirely
                    self._retry(
                        f"i32 operands overflowed{why}: narrow off"
                    )
                    self._narrow_off[skey] = True
                    while len(self._narrow_off) > 512:
                        self._narrow_off.pop(
                            next(iter(self._narrow_off))
                        )
                    continue
                if mode == "gagg" and not robust:
                    # negative sum values (or a wrapping global prefix)
                    # broke the cumsum run base: retry with segmented
                    # add scans before giving up on ranking
                    self._retry(
                        f"cumsum run base broke{why}: robust on"
                    )
                    self._robust_on[skey] = True
                    while len(self._robust_on) > 512:
                        self._robust_on.pop(
                            next(iter(self._robust_on))
                        )
                    continue
                # ranking-key range overflowed int64 (data-dependent, so
                # keyed by data version): remember and ship unranked
                # (correct, just a bigger transfer)
                self._retry(f"ranking key overflow{why}: topk off")
                self._topk_off[(skey, tk, versions)] = True
                while len(self._topk_off) > 512:
                    self._topk_off.pop(next(iter(self._topk_off)))
                tk = None
                continue
            # (the capacity checks below may still re-run the program
            # at a bigger size: the same formulations, so accepting its
            # join modes here is already what ran)
            self._accept(prog)
            if mode in ("gseg", "gsort", "gagg"):
                if mode == "gagg":
                    self.fx.gagg_finals += 1
                self._orientations[skey] = orientation
                if not complete:
                    # psum/D==1: every device holds the SAME complete
                    # top-k rows — collect device 0 only (collecting all
                    # would make the coordinator merge double-count)
                    out_keys = jax.tree.map(lambda x: x[:1], out_keys)
                    out_vals = jax.tree.map(lambda x: x[:1], out_vals)
                    gvalid = gvalid[:1]
                return self._apply_proj(
                    self._collect_grouped(agg, out_keys, out_vals, gvalid),
                    agg, out_proj,
                )
            if mode in ("grouped", "grouped_topk"):
                actual = int(np.asarray(ngroups).max())
                if not direct and actual >= gcap:
                    self._retry(f"group capacity {gcap} < {actual + 1}")
                    gcap = filt_ops.bucket_size(actual + 1)
                    continue
                if direct:
                    self.fx.grouped_direct += 1
                else:
                    self.fx.grouped_sorted += 1
                    self._cap_store(gcapkey, gcap)
                if eligible and (direct or not gslots):
                    # (what the span decided, not what another mode or
                    # an overflowed packing kept from being asked)
                    self._cap_store(("gslots",) + gcapkey[1:], direct)
                self._orientations[skey] = orientation
                if mode == "grouped_topk" and not complete:
                    out_keys = jax.tree.map(lambda x: x[:1], out_keys)
                    out_vals = jax.tree.map(lambda x: x[:1], out_vals)
                    gvalid = gvalid[:1]
                return self._apply_proj(
                    self._collect_grouped(agg, out_keys, out_vals, gvalid),
                    agg, out_proj,
                )
            if mode == "rows_topk":
                self._orientations[skey] = orientation
                return self._collect_rows_live(
                    root.schema, cols, valids, live
                )
            if mode == "rows":
                actual = int(np.asarray(nrows_full).max())
                if actual > gcap:  # a device overflowed the row capacity
                    self._retry(f"row capacity {gcap} < {actual}")
                    gcap = filt_ops.bucket_size(actual)
                    continue
                self._cap_store(gcapkey, gcap)
                self._orientations[skey] = orientation
                return self._collect_rows(root.schema, cols, valids, cnt)
            self._orientations[skey] = orientation
            return self._apply_proj(
                self._collect_scalar(agg, out_vals), agg, out_proj
            )

    def _compile_gseg(
        self, b, ev, comp, agg, root, topk, psum: bool, D, nflags
    ):
        """Grouped aggregation as a segment reduction over the top join's
        build-row index + device top-k: groups are 1:1 with real build
        rows (unique-key verified), so NO sort at any width, and only the
        LIMIT rows ever leave the device. With a replicated build side
        and sharded probe (D>1), per-device partials merge with psum/
        pmin/pmax before ranking — every device then holds the complete
        answer and the collector reads device 0."""
        dids = [c.dict_id for c in root.schema]
        specs: list[str] = []
        afns: list = []
        for a in agg.aggs:
            if a.func == "count" and a.arg is None:
                specs.append("count_star")
                afns.append(None)
            else:
                specs.append(a.func)
                afns.append(comp.compile(a.arg, dids))
        specs_t = tuple(specs)
        bgc = _detect_build_group(agg, root, b.orientation)
        assert bgc is not None
        build_cols = bgc[1]
        k, sspecs, _merged = topk
        nkeys = len(agg.group_exprs)
        naggs = len(agg.aggs)
        mesh = self.fx.mesh

        def program(arrays, params, snap):
            @_staged
            def block(blocks, st):
                env, mask, n, flags = ev(blocks, params, snap)
                st.to("final/gseg/segreduce")
                flags = [jnp.reshape(f, (1,)) for f in flags]
                bidx, benv, bn = b.captured
                seg = jnp.where(
                    mask, bidx.astype(jnp.int32), jnp.int32(bn)
                )
                nseg = bn + 1
                vals = [
                    None if fn is None else _bcast(fn(env, params), n)
                    for fn in afns
                ]
                rows = jax.ops.segment_sum(
                    mask.astype(jnp.int64), seg, num_segments=nseg
                )[:bn]
                if psum:
                    rows = jax.lax.psum(rows, "dn")
                out_vals = []
                for spec, val in zip(specs_t, vals):
                    if spec == "count_star":
                        out_vals.append((rows, rows > 0))
                        continue
                    data, valid = val
                    vvalid = mask if valid is None else (mask & valid)
                    if spec == "count":
                        c = jax.ops.segment_sum(
                            vvalid.astype(jnp.int64), seg,
                            num_segments=nseg,
                        )[:bn]
                        if psum:
                            c = jax.lax.psum(c, "dn")
                        out_vals.append((c, rows > 0))
                        continue
                    cv = jax.ops.segment_sum(
                        vvalid.astype(jnp.int32), seg, num_segments=nseg
                    )[:bn]
                    if psum:
                        cv = jax.lax.psum(cv, "dn")
                    if spec == "sum":
                        if jnp.issubdtype(data.dtype, jnp.integer):
                            data = data.astype(jnp.int64)
                        zero = jnp.zeros((), dtype=data.dtype)
                        s = jax.ops.segment_sum(
                            jnp.where(vvalid, data, zero), seg,
                            num_segments=nseg,
                        )[:bn]
                        if psum:
                            s = jax.lax.psum(s, "dn")
                        out_vals.append((s, cv > 0))
                        continue
                    # min / max
                    if jnp.issubdtype(data.dtype, jnp.floating):
                        sent = jnp.inf if spec == "min" else -jnp.inf
                    elif data.dtype == jnp.bool_:
                        data = data.astype(jnp.int32)
                        sent = 2 if spec == "min" else -1
                    elif jnp.dtype(data.dtype).itemsize < 8:
                        info = jnp.iinfo(data.dtype)
                        sent = info.max if spec == "min" else info.min
                    else:
                        sent = (
                            np.int64(2**62) if spec == "min"
                            else np.int64(-(2**62))
                        )
                    d = jnp.where(
                        vvalid, data, jnp.asarray(sent, dtype=data.dtype)
                    )
                    red = (
                        jax.ops.segment_min if spec == "min"
                        else jax.ops.segment_max
                    )
                    m = red(d, seg, num_segments=nseg)[:bn]
                    if psum:
                        m = (
                            jax.lax.pmin(m, "dn") if spec == "min"
                            else jax.lax.pmax(m, "dn")
                        )
                    out_vals.append((m, cv > 0))
                gvalid = rows > 0
                out_keys = []
                for ci in build_cols:
                    d, v = benv[ci]
                    d = jnp.broadcast_to(d, (bn,))
                    v = (
                        jnp.ones(bn, jnp.bool_)
                        if v is None
                        else jnp.broadcast_to(v, (bn,))
                    )
                    out_keys.append((d, v))
                sortcols = [
                    out_keys[p] if p < nkeys else out_vals[p - nkeys]
                    for p, _d, _nf in sspecs
                ]
                packed, ok = _pack_sort_cols(sortcols, sspecs, gvalid)
                st.to("final/gseg/topk")
                idx, sel = _topk_idx(packed, gvalid, k)

                def take(pair):
                    d, v = pair
                    return (jnp.take(d, idx), jnp.take(v, idx))

                out_keys = [take(p) for p in out_keys]
                out_vals = [take(p) for p in out_vals]
                return (
                    jax.tree.map(lambda x: x[None], out_keys),
                    jax.tree.map(lambda x: x[None], out_vals),
                    sel[None],
                    jnp.reshape(ok, (1,)),
                    flags,
                )

            return shard_map(
                block,
                mesh=mesh,
                in_specs=(_specs_like(arrays),),
                out_specs=(
                    [(P("dn"), P("dn"))] * nkeys,
                    [(P("dn"), P("dn"))] * naggs,
                    P("dn"),
                    P("dn"),
                    [P("dn")] * nflags,
                ),
            )(arrays)

        return self._program(program, "gseg", b), comp, "gseg"

    def _compile_gagg(
        self, b, ev, comp, agg, root, topk, D, nflags,
        narrow: bool = False, robust: bool = False, prefix: int = 0,
    ):
        """Grouped aggregation + top-k as ONE sort + prefix scans, no
        join required (reference shape: nodeAgg.c hashed grouping +
        LIMIT pushdown). Rows co-sort by the runtime-packed group key;
        groups are runs; sums/counts are prefix differences against a
        cummax-propagated run base, min/max one segmented scan each;
        ranking happens at run-END positions where every aggregate is
        final. High-cardinality GROUP BY never touches a scatter or a
        multi-pass argsort, and only LIMIT rows leave the device.

        Sort-width minimization (the sort IS the cost on a TPU):
        - group keys functionally determined by another grouped key
          (through verified-unique joins, ``_fd_map``) stay OUT of the
          packed key and are recovered per output row: by the row id
          that rides the sort, and where the builder deferred its
          joins' gathers (``_gagg_late``) through the joins' own row
          indices, so a dropped key is never gathered at the probe's
          width only to be read at LIMIT rows (TPC-H Q10: six
          attributes of ``c_custkey``, seven 32-bit words, 0.58 s each
          at 67.1M rows);
        - the packed key and integer value operands narrow to i32 when
          runtime ranges fit (flag -> wide retry, like gsort);
        - when nothing was FD-dropped the row-id operand is dropped
          too: the monotone packing is INVERTIBLE, so output key
          values decode straight out of the sorted key — ClickBench's
          count(*) shape sorts ONE i32 operand and nothing else;
        - ``prefix``: ORDER BY keys that were FD-dropped do not ride the
          sort either. Dead rows sort last, so every live group's run
          ends within the first ``prefix`` sorted rows where that many
          hold every live row (a fifth check bit): the ranking runs
          there, reading those keys by the row id at that prefix's
          width (TPC-H Q18: ``o_totalprice``, ``o_orderdate``
          behind ``o_orderkey``, some 700 live rows of 67.1M)."""
        dids = [c.dict_id for c in root.schema]
        gfns = [comp.compile(g, dids) for g in agg.group_exprs]
        specs, afns = _agg_specs(comp, agg, dids)
        k, sspecs, _merged = topk
        nkeys = len(agg.group_exprs)
        naggs = len(agg.aggs)
        mesh = self.fx.mesh

        # FD-reduce the packed key set: keys determined (transitively)
        # by another present key don't need to sort — grouping by a
        # determinant subset yields identical runs
        kept, dropped = _fd_reduce(root, b.orientation, agg)
        drop = set(dropped)
        need_rid = bool(drop)
        # ORDER BY group keys that were FD-dropped must ride the sort
        # as carried operands (their values aren't in the packed key)
        ranked_drop = sorted({
            p for p, _d, _nf in sspecs if p < nkeys and p in drop
        })
        prefix = prefix if ranked_drop else 0
        carried = [] if prefix else ranked_drop
        # dropped keys read at the output rows alone (bare columns, or
        # ``_fd_reduce`` had kept them), and what every other reader
        # needs of the joined row at full width
        late = (drop - set(carried)) if b.defer else set()
        wide_cols: set = set()
        for i, gx in enumerate(agg.group_exprs):
            if i not in late:
                _expr_cols(gx, wide_cols)
        for ag in agg.aggs:
            if ag.arg is not None:
                _expr_cols(ag.arg, wide_cols)
        ntext = sum(gx.type.is_text for gx in agg.group_exprs)
        # the launch's record: what the packing kept, whether key and
        # values ride the sort as 32-bit words, the rows that leave
        record = {
            "grouping": f"gagg/{len(kept)}of{nkeys}",
            "group_keys": f"{nkeys} ({ntext} text)",
            "narrow": bool(narrow),
            "rows_out": int(k),
        }
        if ranked_drop:
            # how the ORDER BY keys the packing dropped reach the
            # ranking: riding the sort, or read at the live prefix
            record["ranks"] = "+".join(
                f"{agg.group_exprs[p].name or p}:"
                f"{'prefix' if prefix else 'carried'}"
                for p in ranked_drop
            )
            if prefix:
                record["rank_rows"] = prefix

        def program(arrays, params, snap):
            @_staged
            def block(blocks, st):
                env, mask, n, flags = ev(blocks, params, snap)
                self.fx.gagg_keys_dropped += len(drop)  # (at trace time)
                _read_first(env, wide_cols)
                st.to("final/gagg/pack")
                flags = [jnp.reshape(f, (1,)) for f in flags]
                keys = [
                    None if i in late else _bcast(fn(env, params), n)
                    for i, fn in enumerate(gfns)
                ]
                ck = _gagg_runs(
                    env, mask, n, params, keys, kept, specs, afns,
                    narrow, robust, st, "final/gagg", partial(
                        _gagg_carry, keys, carried, need_rid,
                    ),
                )
                ok, ok_narrow, ok_base = ck.ok, ck.ok_narrow, ck.ok_base
                ok_rank = jnp.asarray(True)
                sorted_ops, salk = ck.sorted_ops, ck.salk
                live_end, decode_key = ck.live_end, ck.decode_key
                out_vals_pos = ck.out_vals_pos
                carried_pos, rid_i = ck.extra
                st.to("final/gagg/topk")
                live_r, salk_r, vals_r = live_end, salk, out_vals_pos
                if prefix:
                    # dead rows sort last: where the first ``c`` sorted
                    # rows hold every live one, every live run ends
                    # there, and the ranking reads the dropped keys it
                    # ranks by at those rows alone, by the row id
                    c = min(prefix, n)
                    ok_prefix = (
                        salk[c] >= ck.KSENT if c < n
                        else jnp.asarray(True)
                    )
                    rid_r = sorted_ops[rid_i][:c]
                    live_r, salk_r = live_end[:c], salk[:c]
                    vals_r = [(d[:c], v[:c]) for d, v in out_vals_pos]
                stride = jnp.int64(1)
                prod = jnp.float64(1.0)
                packed_rank = jnp.zeros(live_r.shape[0], dtype=jnp.int64)
                for p, desc, nf in reversed(sspecs):
                    if p >= nkeys:
                        d64, v = vals_r[p - nkeys]
                        d64 = d64.astype(jnp.int64)
                    elif p in drop and prefix:
                        d64, v = _col_at_rows(
                            env, agg.group_exprs[p].index, rid_r
                        )
                        d64 = d64.astype(jnp.int64)
                    elif p in drop:
                        ci, vi = carried_pos[p]
                        d64 = sorted_ops[ci].astype(jnp.int64)
                        v = (
                            None if vi is None
                            else sorted_ops[vi] > 0
                        )
                    else:
                        d64, v = decode_key(p, salk_r)
                    x, r, rf, okbit = _rank_encode(
                        d64, v, desc, nf, live_r
                    )
                    packed_rank = packed_rank + x * stride
                    stride = stride * r
                    prod = prod * jnp.maximum(rf, 1.0)
                    ok_rank = ok_rank & okbit
                ok_rank = ok_rank & (prod < jnp.float64(2**62))

                idx, sel = _topk_idx(packed_rank, live_r, k)
                row_k = (
                    None if rid_i is None
                    else jnp.take(sorted_ops[rid_i], idx)
                )
                salk_k = jnp.take(salk, idx)
                out_vals = [
                    (jnp.take(dd, idx), jnp.take(vv, idx))
                    for dd, vv in out_vals_pos
                ]
                if drop:
                    # a dropped key's value at the row that stands for
                    # its group: a late one through the joins' own row
                    # indices, LIMIT rows wide
                    st.to("final/gagg/recover")
                out_keys = []
                for i, kv in enumerate(keys):
                    if i in late:
                        dk, vk = _col_at_rows(
                            env, agg.group_exprs[i].index, row_k
                        )
                    elif i in drop:
                        dk, vk = _col_at_rows([kv], 0, row_k)
                    else:
                        dk, vk = decode_key(i, salk_k)
                        dk = dk.astype(jnp.asarray(kv[0]).dtype)
                    if vk is None:
                        vk = jnp.ones(k, jnp.bool_)
                    out_keys.append((dk, vk))
                return (
                    jax.tree.map(lambda x: x[None], out_keys),
                    jax.tree.map(lambda x: x[None], out_vals),
                    sel[None],
                    jnp.stack(
                        [ok, ok_narrow, ok_base, ok_rank]
                        + ([ok_prefix] if prefix else [])
                    )[None],
                    flags,
                )

            return shard_map(
                block,
                mesh=mesh,
                in_specs=(_specs_like(arrays),),
                out_specs=(
                    [(P("dn"), P("dn"))] * nkeys,
                    [(P("dn"), P("dn"))] * naggs,
                    P("dn"),
                    P("dn"),
                    [P("dn")] * nflags,
                ),
            )(arrays)

        prog = self._program(program, "gagg", b)
        prog.gagg = record
        prog.gagg_checks = _GAGG_PREFIX_CHECKS if prefix else _GAGG_CHECKS
        return prog, comp, "gagg"

    # -- windowed grouped aggregation (bigger-than-HBM probes) -----------
    def _wgagg_leaf(self, root, agg, tk):
        """(leaf, window_plan) when the final gagg program's sort
        operands would exceed the window budget: the dominant Scan leaf
        streams in shard-row windows. None when it all fits."""
        budget = batchplan.resolve_budget(
            int(getattr(self.fx, "device_memory_limit", 0) or 0),
            batchplan.DEFAULT_WINDOW_BUDGET,
        )
        leaves = [
            lf for lf in _walk_leaves(root) if isinstance(lf, L.Scan)
        ]
        if not leaves:
            return None
        big = max(leaves, key=lambda lf: self._est_rows(lf))
        rows = self._est_rows(big)
        # sort-operand footprint per probe row: key + per-agg value and
        # validity + carried keys + rid, roughly tripled for the sorted
        # copies and prefix scans
        per_row = 8 + len(agg.aggs) * 9 + 8 + 4
        if rows * per_row * 3 <= budget:
            return None
        meta = self.fx.catalog.get(big.table)
        nodes = _scan_nodes(meta)
        stores = [
            self.fx.node_stores[n][big.table] for n in nodes
        ]
        # the cache's ACTUAL padded capacity (external registrations
        # are exact-sized, not bucket-padded)
        dtab = self.fx.cache.get(
            big.table, meta, self.fx.node_stores, nodes,
            columns=big.columns,
        )
        rmax = dtab.rmax
        k = len(stores)
        # power-of-two window width dividing the power-of-two rmax, so
        # dynamic_slice never clamps into the previous window
        width = batchplan.probe_window_width(
            rmax, per_row * 3, k, budget
        )
        if width >= rmax:
            return None
        return big, width, rmax

    def _run_wgagg(
        self, wplan, agg, root, exchanged, tk, D, skey, orientation,
        sig, versions, snap, dicts_view, subquery_values, out_proj,
    ):
        """Windowed gagg: the dominant scan leaf streams in shard-row
        windows through the SAME folded/filtered tree; each window
        emits its compacted per-group partials (device-resident — no
        fetch), and one merge program re-groups the partials, ranks,
        and ships only the LIMIT rows. Build sides stay resident, so
        the reference's multi-batch hash join
        (nodeHash.c ExecHashIncreaseNumBatches) becomes: same program,
        sliding window, one concat+sort of partials at the end."""
        leaf, width, rmax = wplan
        nwin = rmax // width
        k, sspecs, _merged = tk
        cap = max(width // 4, 4096)
        wcapkey = ("wcap", skey, orientation, D, sig, versions)
        cap = self._caps.get(wcapkey, cap)
        h = None
        h_key = None
        while True:
            fo = self._offs(skey)
            robust = bool(self._robust_on.get(skey))
            root_c, exch_c = root, exchanged
            ori_c, fo_c = orientation, fo
            gmap = None
            if h_key != (orientation, fo):
                # prep survives cap/robust retries; only orientation or
                # fold-off changes invalidate the hoisted build
                h = self._maybe_hoist(
                    root, agg, orientation, skey, exchanged, D, snap,
                    dicts_view, subquery_values, leaf, sig, versions,
                )
                h_key = (orientation, fo)
            if h == "retry":
                h_key = None
                continue
            if h is not None:
                root_c, exch_c, gmap = h
                nj2 = _count_inner_joins(root_c)
                ori_c = tuple(
                    orientation[gmap(i)]
                    if gmap(i) < len(orientation) else "R"
                    for i in range(nj2 - 1)
                ) + ("R",)  # prepped source always sits on the right
                fo_c = tuple(
                    frozenset(
                        i for i in range(nj2) if gmap(i) in s
                    )
                    for s in fo
                )
            ckey = (
                "wgagg", skey, orientation, D, sig, fo, cap, width,
                robust, h is not None,
            )
            wprog, mprog, comp, jinfo, params = self._bind(
                ckey,
                lambda rc=root_c, ec=exch_c, oc=ori_c, fc=fo_c, rb=robust:
                self._compile_wgagg(
                    agg, rc, ec, tk, D, oc, fc, leaf, width, cap,
                    robust=rb,
                ),
                dicts_view, subquery_values,
            )
            arrays = _collect_arrays(self.fx, root_c, exch_c, D)
            lidx = self.leaf_index_of(root_c, leaf)
            wouts = []
            for w in range(nwin):
                arr_w = list(arrays)
                arr_w[lidx] = tuple(arr_w[lidx]) + (
                    jnp.int32(w * width),
                )
                # device handles only — nothing fetches until merge
                wouts.append(self._launch(
                    wprog, arr_w, params, snap, mode=f"window/{w}"
                ))
            outs = self._fetch(
                self._launch(mprog, wouts, params, snap, mode="merge"),
                "result",
            )
            (out_keys, out_vals, gvalid, novf, okf, flags) = outs
            gjinfo = jinfo if gmap is None else jinfo.remap(gmap)
            self.last_mode = "wgagg"
            self.last_folded = gjinfo.folded
            flip = _first_true(flags)
            if flip is not None:
                orientation = self._on_flag(
                    skey, orientation,
                    flip if gmap is None else gmap(flip),
                    gjinfo,
                )
                continue
            if bool(np.asarray(novf).any()):
                self._retry(f"window partials exceed cap {cap}")
                cap *= 2  # a window had more groups than the compact cap
                if cap > width:
                    raise DagUnsupported("wgagg partials exceed window")
                self._cap_store(wcapkey, cap)
                continue
            if not bool(np.asarray(okf).all()):
                if not robust:
                    self._retry("cumsum run base broke: robust on")
                    self._robust_on[skey] = True
                    continue
                self._topk_off[(skey, tk, versions)] = True
                raise DagUnsupported("wgagg ranking overflow")
            self._accept(wprog, mprog)
            self._orientations[skey] = orientation
            out_keys = jax.tree.map(lambda x: x[:1], out_keys)
            out_vals = jax.tree.map(lambda x: x[:1], out_vals)
            gvalid = gvalid[:1]
            return self._apply_proj(
                self._collect_grouped(agg, out_keys, out_vals, gvalid),
                agg, out_proj,
            )

    def leaf_index_of(self, root, leaf) -> int:
        for i, lf in enumerate(_walk_leaves(root)):
            if lf is leaf:
                return i
        raise DagUnsupported("window leaf not found")

    # -- fold-prep hoisting (window-invariant build sides) ---------------
    PREP_FRAG = -7
    SEMI_FRAG = -100  # a key set's source: -100, -101, ...

    def _semiagg_sources(self, root, D: int):
        """Every semi join under ``root`` whose build side is a grouped
        key set (``_semiagg_build``) reads it as a source of its own
        (``RemoteSource`` -100, -101, ...: one key column, the join's
        key pair on it), which ``_semiagg_launch`` fills. Returns the
        rewritten tree and one ``_Semi`` a key set."""
        found: list = []

        def walk(node):
            if isinstance(node, L.Join):
                if _semiagg_build(node) is not None:
                    found.append(node)
                    walk(node.left)
                    return
                walk(node.left)
                walk(node.right)
            elif isinstance(node, (L.Filter, L.Project, L.Aggregate)):
                walk(node.child)

        walk(root)
        if not found:
            return root, []
        if D > 1:
            raise DagUnsupported(
                "semi join on a grouped key set: one device only"
            )
        semis = []
        for i, join in enumerate(found):
            agg, having = _semiagg_build(join)
            rk = join.right_keys[0]
            frag = self.SEMI_FRAG - i
            rs = RemoteSource(
                fragment=frag, schema=(join.right.schema[rk.index],)
            )
            root = _replace_node(root, join, dataclasses.replace(
                join, right=rs, right_keys=(E.Col(0, rk.type, rk.name),),
            ))
            cat = self.fx.catalog
            est = int(estimate_rows(join.right, cat))
            ndv = costs.expr_ndv(join.left_keys[0], join.left, cat)
            semis.append(_Semi(
                frag=frag, agg=agg, having=having,
                skey=_plan_skey_of(join.right),
                cap=min(filt_ops.bucket_size(est), SEMIAGG_START_KEYS),
                rows_per_key=(
                    estimate_rows(join.left, cat) / ndv if ndv else 0.0
                ),
            ))
        return root, semis

    def _rank_prefix_rows(self) -> int:
        """How many sorted rows a gagg over the key sets' rows ranks
        at, 0 for none: every key set bounds the rows that meet it by
        its capacity times the probe's rows a key (``RANK_PREFIX_SKEW``
        times the mean, for the keys that hold more). A join above the
        semi join can multiply them; the final's fifth check catches
        that and the runner retries with the keys riding the sort."""
        if not self._semis or not all(
            sm.rows_per_key for sm in self._semis
        ):
            return 0
        return min(
            filt_ops.bucket_size(
                sm.cap * math.ceil(RANK_PREFIX_SKEW * sm.rows_per_key)
            )
            for sm in self._semis
        )

    def _semiagg_launch(self, semis, exchanged, snap, D, versions,
                        dicts_view, subquery_values) -> None:
        """Launch each key set's ``program_dag_semiagg`` (no host sync:
        its count and checks are fetched with the final's answer) and
        put its keys where its source reads them."""
        frag0 = self._frag
        self._frag = "semiagg"
        try:
            for sm in semis:
                sub = sm.agg.child
                fits: set = set()
                arrays = _collect_arrays(self.fx, sub, exchanged, D, fits)
                self._fits = frozenset(fits)
                sig = self._shapes_sig(arrays)
                sm.narrow = not self._narrow_off.get(("semiagg", sm.skey))
                sm.robust = bool(self._robust_on.get(("semiagg", sm.skey)))
                capkey = None
                while True:
                    cap = sm.cap
                    prog, comp, params = self._bind(
                        ("semiagg", sm.skey, D, sig, cap, sm.narrow,
                         sm.robust),
                        lambda: self._compile_semiagg(
                            sm, exchanged, D, cap
                        ),
                        dicts_view, subquery_values,
                    )
                    if capkey is None:
                        capkey = ("semicap", sm.skey, D, sig, versions,
                                  _params_sig(params))
                        known = self._caps.get(capkey)
                        if known is not None and known != cap:
                            sm.cap = known
                            continue
                    break
                sm.capkey, sm.prog = capkey, prog
                keys, valid, count, checks = self._launch(
                    prog, arrays, params, snap,
                    semiagg=f"{_rows_a_device(sig, D)}:{cap}",
                )
                sm.count, sm.checks = count, checks
                exchanged[sm.frag] = {
                    "cols": (keys,), "valids": (valid,), "counts": count,
                    "cap": cap,
                    # its live keys are values of a bare column whose
                    # range fits (a dead slot is not live): the final's
                    # semi join may sort them as one word (``fits`` of
                    # the final's key then holds the source's column)
                    "fits": (0,) if _key_fits(
                        sub, sm.agg.group_exprs[0], fits
                    ) else (),
                }
        finally:
            self._frag = frag0

    def _semiagg_refused(self, semis, verdicts) -> bool:
        """Read each key set's count and checks (fetched with the
        final's answer): a refused one is retried with what failed
        changed — its capacity, its 32-bit operands, its run base —
        and the final's answer that read it is thrown away."""
        refused = False
        for sm, (count, checks) in zip(semis, verdicts):
            bad = ~np.asarray(checks).reshape(-1, 3).all(axis=0)
            count = int(np.asarray(count).max())
            if bad[1]:
                self._retry("semiagg i32 operands overflowed: narrow off")
                self._narrow_off[("semiagg", sm.skey)] = True
            elif bad[2]:
                self._retry("semiagg cumsum run base broke: robust on")
                self._robust_on[("semiagg", sm.skey)] = True
            elif bad[0]:
                raise DagUnsupported("semiagg group key past 62 bits")
            elif count > sm.cap:
                self._retry("semiagg keys over cap")
                self.fx.semiagg_overflows += 1
                sm.cap = filt_ops.bucket_size(count)
                self._cap_store(sm.capkey, sm.cap)
            else:
                continue
            refused = True
        return refused

    def _compile_semiagg(self, sm, exchanged, D, cap: int):
        """A semi join's grouped key set as ONE program: the group key
        packed (``i32`` where its range fits), ONE sort with the
        aggregate arguments riding, the runs' aggregates at their ends
        (``_gagg_runs``, gagg's own core), HAVING there in the engine's
        exact fixed point, and the qualifying keys compacted into
        ``cap`` slots by a binary search of their running count (no
        scatter). Returns (keys [1, cap], live [1, cap], count [1],
        checks [1, 3]: packing, narrowing, run base); a count past
        ``cap`` is the runner's to retry. Scopes ``semiagg/{pack,sort,
        scan,having,compact}``."""
        agg, sub = sm.agg, sm.agg.child
        if _count_inner_joins(sub):
            raise DagUnsupported("semiagg over a join")
        comp = ExprCompiler(lift_consts=True)
        b = _Builder(self.fx, comp, (), sub, runner=self, D=D)
        ev = b.build(sub, exchanged, D, _agg_reads(agg))
        dids = [c.dict_id for c in sub.schema]
        kfn = comp.compile(agg.group_exprs[0], dids)
        specs, afns = _agg_specs(comp, agg, dids)
        hfn = None
        if sm.having is not None:
            hfn = comp.compile(
                sm.having, [c.dict_id for c in agg.schema]
            )
        kdt = agg.schema[0].type.np_dtype
        narrow, robust = sm.narrow, sm.robust
        mesh = self.fx.mesh

        def program(arrays, params, snap):
            @_staged
            def block(blocks, st):
                env, mask, n, _flags = ev(blocks, params, snap)
                st.to("semiagg/pack")
                d, v = _bcast(kfn(env, params), n)
                if v is not None:
                    # a NULL key's group matches no probe key
                    mask = mask & v
                ck = _gagg_runs(
                    env, mask, n, params, [(d, None)], [0], specs, afns,
                    narrow, robust, st, "semiagg", lambda *_a: None,
                )
                st.to("semiagg/having")
                keep = ck.live_end
                if hfn is not None:
                    kd, _kv = ck.decode_key(0, ck.salk)
                    hd, hv = hfn([(kd, None)] + ck.out_vals_pos, params)
                    hk = hd if hv is None else (hd & hv)
                    keep = keep & jnp.broadcast_to(hk, (n,))
                st.to("semiagg/compact")
                # otb_lint: ignore[int32-width] -- a count of one device's padded rows, which index as int32 (n < 2^31)
                run = jnp.cumsum(keep.astype(jnp.int32))
                count = run[-1]
                at = jnp.searchsorted(
                    run, jnp.arange(1, cap + 1, dtype=jnp.int32),
                    side="left", method="scan",
                )
                keys, _kv = ck.decode_key(
                    0, jnp.take(ck.salk, at, mode="clip")
                )
                live = jnp.arange(cap, dtype=jnp.int32) < count
                return (
                    jnp.where(live, keys, 0).astype(kdt)[None],
                    live[None],
                    count.reshape(1),
                    jnp.stack([ck.ok, ck.ok_narrow, ck.ok_base])[None],
                )

            return shard_map(
                block,
                mesh=mesh,
                in_specs=(_specs_like(arrays),),
                out_specs=(P("dn"), P("dn"), P("dn"), P("dn")),
            )(arrays)

        return self._program(program, "semiagg", b), comp
    HOIST_MIN_ROWS = 4_000_000

    def _maybe_hoist(
        self, root, agg, orientation, skey, exchanged, D, snap,
        dicts_view, subquery_values, wleaf, sig, versions,
    ):
        """When the top join's build side is window-invariant and big,
        evaluate + key-sort it ONCE in a prep program and rewrite the
        tree so every window consumes it as a presorted RemoteSource
        behind a match-validity Filter — otherwise each window would
        re-sort the whole build (the multi-batch hash join keeps its
        hash table across batches for the same reason, nodeHash.c).
        Returns (root2, exchanged2, ori_map) or None; ``ori_map``
        translates the rewritten tree's join indices back to the
        original orientation/fold-off index space."""
        top = _top_join(root)
        if top is None or top.join_type != "inner":
            return None
        gji = _count_inner_joins(root) - 1
        build_right = (
            orientation[gji] if gji < len(orientation) else "R"
        ) == "R"
        if build_right:
            bnode, pnode = top.right, top.left
        else:
            if top.residual is not None:
                return None  # residual positions would need remapping
            bnode, pnode = top.left, top.right
        if any(lf is wleaf for lf in _walk_leaves(bnode)):
            return None  # windowed leaf on the build side: not invariant
        if not any(lf is wleaf for lf in _walk_leaves(pnode)):
            return None
        if self._est_rows(bnode) < self.HOIST_MIN_ROWS:
            return None  # per-window sort of a small build is cheap
        if not self._top_join_foldable(root, orientation, skey):
            return None
        p = _count_inner_joins(pnode)
        b = _count_inner_joins(bnode)
        # post-order numbering: the FIRST-BUILT child's joins come
        # first — build joins occupy [p, p+b) when the build side is
        # the right child, [0, b) when it is the left
        boff = p if build_right else 0
        poff = 0 if build_right else b
        ori_local = tuple(orientation[boff:boff + b])
        fo_local = tuple(
            frozenset(
                x - boff for x in s if boff <= x < boff + b
            )
            for s in self._offs(skey)
        )
        bkey = (top.right_keys if build_right else top.left_keys)[
            _drive_pair(self, top, build_right)
        ]
        pkey = (
            "prep", skey, tuple(orientation), D, fo_local, sig,
            versions,
        )
        prog, comp, jinfo_local, params = self._bind(
            pkey,
            lambda: self._compile_fold_prep(
                bnode, exchanged, ori_local, fo_local, D, bkey
            ),
            dicts_view, subquery_values,
        )
        arrays = _collect_arrays(self.fx, bnode, exchanged, D)
        cols, valids, counts, flags = self._launch(
            prog, arrays, params, snap
        )
        # tiny; build data stays on device
        flip = _first_true(self._fetch(flags, "join flags"))
        if flip is not None:
            # map the prep-local join index back to the global space
            self._on_flag(
                skey, orientation, flip + boff,
                jinfo_local.remap(lambda x: x + boff),
            )
            return "retry"
        self._accept(prog)
        schema2 = tuple(bnode.schema) + (
            L.OutCol("__match_ok", t.BOOL),
        )
        rs = RemoteSource(fragment=self.PREP_FRAG, schema=schema2)
        filt = L.Filter(
            child=rs,
            predicate=E.Col(len(bnode.schema), t.BOOL, "__match_ok"),
            schema=schema2,
        )
        import dataclasses

        if build_right:
            top2 = dataclasses.replace(top, right=filt)
            repl = top2
        else:
            # swap sides so the prepped source (with its trailing
            # __match_ok column) sits on the RIGHT — appending there
            # shifts no downstream positions — and restore the
            # original column order with a Project above
            nr0 = len(top.right.schema)
            swapped = dataclasses.replace(
                top, left=top.right, right=filt,
                left_keys=top.right_keys, right_keys=top.left_keys,
                schema=tuple(top.right.schema) + schema2,
            )
            proj_exprs = tuple(
                E.Col(nr0 + i, c.type, c.name)
                for i, c in enumerate(top.left.schema)
            ) + tuple(
                E.Col(i, c.type, c.name)
                for i, c in enumerate(top.right.schema)
            )
            repl = L.Project(
                child=swapped, exprs=proj_exprs, schema=top.schema
            )
        root2 = _replace_node(root, top, repl)
        exchanged2 = dict(exchanged)
        exchanged2[self.PREP_FRAG] = {
            "cols": cols,
            "valids": valids,
            "counts": counts,
            "cap": cols[0].shape[-1],
            "schema": schema2,
            "presorted": True,
        }
        self._producers = dict(getattr(self, "_producers", {}))
        self._producers[self.PREP_FRAG] = bnode

        def ori_map(local_idx: int) -> int:
            # rewritten tree: probe joins occupy local [0, p) (the
            # prepped source replaced the build subtree and always
            # sits right), the top join is local p -> global p + b
            return poff + local_idx if local_idx < p else p + b

        return root2, exchanged2, ori_map

    def _compile_fold_prep(
        self, bnode, exchanged, ori_local, fo_local, D, bkey
    ):
        """ONE evaluation + key-sort of a build subtree: rows sorted by
        the join key over the density domain (chain-leaf visibility),
        every schema column + validity riding the sort, the full build
        mask appended as a __match_ok column. Output is exchange-layout
        so the window programs read it like any motioned fragment."""
        comp = ExprCompiler(lift_consts=True)
        b = _Builder(
            self.fx, comp, ori_local, bnode, runner=self, D=D,
            fold_off=fo_local,
        )
        ev = b.build(bnode, exchanged, D, None)  # every column rides
        chain = _chain_leaf(bnode, folded_ids=b.folded_ids)
        if chain is None:
            # a nested build join was runtime-disabled (fold_off):
            # the spine no longer folds — loud fallback, host answers
            raise DagUnsupported("prep build side is not a fold chain")
        leaf = chain[0]
        bstrip = b.build(leaf, exchanged, D)
        dids = [c.dict_id for c in bnode.schema]
        bkfn = comp.compile(bkey, dids)
        ncols = len(bnode.schema)
        nflags = _count_inner_joins(bnode)
        mesh = self.fx.mesh
        BIG = jnp.int64(2**62)

        def program(arrays, params, snap):
            def block(blocks):
                env, mask, n, flags = ev(blocks, params, snap)
                _e2, vis, _n2, _f2 = bstrip(blocks, params, snap)
                kd, kv = _bcast(bkfn(env, params), n)
                kreal = vis if kv is None else (vis & kv)
                key = jnp.where(kreal, kd.astype(jnp.int64), BIG)
                ops = [key]
                for i in range(ncols):
                    d, v = env[i]
                    ops.append(jnp.broadcast_to(d, (n,)))
                    ops.append(
                        jnp.ones(n, jnp.bool_) if v is None
                        else jnp.broadcast_to(v, (n,))
                    )
                ops.append(mask)
                sops = jax.lax.sort(
                    tuple(ops), num_keys=1, is_stable=False
                )
                cnt = jnp.sum(kreal, dtype=jnp.int32)
                out_cols = [sops[1 + 2 * i][None] for i in range(ncols)]
                out_cols.append(sops[-1][None])  # __match_ok data
                out_valids = [
                    sops[2 + 2 * i][None] for i in range(ncols)
                ]
                out_valids.append(jnp.ones((1, n), jnp.bool_))
                return (
                    out_cols,
                    out_valids,
                    cnt.reshape(1),
                    [jnp.reshape(f, (1,)) for f in flags],
                )

            return shard_map(
                block,
                mesh=mesh,
                in_specs=(_specs_like(arrays),),
                out_specs=(
                    [P("dn")] * (ncols + 1),
                    [P("dn")] * (ncols + 1),
                    P("dn"),
                    [P("dn")] * nflags,
                ),
            )(arrays)

        return self._program(program, "fold_prep", b), comp, b.jinfo()

    def _compile_wgagg(
        self, agg, root, exchanged, topk, D, orientation, fo, leaf,
        width, cap, robust: bool = False,
    ):
        """Compile the (window, merge) program pair. Restriction: after
        FD-reduction exactly ONE bare integer group key remains — its
        RAW value is the sort key in both programs, so per-window sorts
        stay comparable without a global range pass."""
        comp = ExprCompiler(lift_consts=True)
        b = _Builder(
            self.fx, comp, orientation, root, runner=self, D=D,
            fold_off=fo, window=(id(leaf), width),
        )
        ev = b.build(root, exchanged, D, _agg_reads(agg))
        dids = [c.dict_id for c in root.schema]
        gfns = [comp.compile(g, dids) for g in agg.group_exprs]
        specs, afns = _agg_specs(comp, agg, dids)
        k, sspecs, _merged = topk
        nkeys = len(agg.group_exprs)
        naggs = len(agg.aggs)
        mesh = self.fx.mesh
        nflags = _count_inner_joins(root)

        kept, dropped = _fd_reduce(root, orientation, agg)
        if len(kept) != 1 or not isinstance(
            agg.group_exprs[kept[0]], E.Col
        ):
            raise DagUnsupported("wgagg needs one bare group key")
        kidx = kept[0]
        if agg.group_exprs[kidx].type.is_text:
            raise DagUnsupported("wgagg text group key")
        NULLS = jnp.int64(2**62 - 1)
        DEADS = jnp.int64(2**62)
        # merge semantics per partial: sum/count partials re-SUM,
        # min/min, max/max (the reference's two-phase split,
        # src/backend/optimizer/plan/createplan.c:1852)
        merge_op = [
            "sum" if s in ("sum", "count", "count_star") else s
            for s in specs
        ]

        def window_program(arrays, params, snap):
            @_staged
            def block(blocks, st):
                env, mask, n, flags = ev(blocks, params, snap)
                st.to("final/wgagg/pack")
                flags = [jnp.reshape(f, (1,)) for f in flags]
                ok = jnp.asarray(True)
                kd, kv = _bcast(gfns[kidx](env, params), n)
                k64 = kd.astype(jnp.int64)
                # raw keys must stay strictly below the NULL/dead
                # sentinels (the packed gagg path rebases instead; keys
                # this extreme flag out and demote)
                live_k = mask if kv is None else (mask & kv)
                ok = ok & jnp.all(
                    jnp.where(live_k, k64 < NULLS, True)
                ) & jnp.all(
                    jnp.where(live_k, k64 > -DEADS, True)
                )
                if kv is not None:
                    k64 = jnp.where(kv, k64, NULLS)
                keyop = jnp.where(mask, k64, DEADS)
                operands = [keyop]
                val_pos: list = []
                for spec, fn in zip(specs, afns):
                    if fn is None:
                        val_pos.append(None)
                        continue
                    d, v = _bcast(fn(env, params), n)
                    if jnp.issubdtype(d.dtype, jnp.integer):
                        d = d.astype(jnp.int64)
                    elif jnp.issubdtype(d.dtype, jnp.floating):
                        d = d.astype(jnp.float64)
                    vv = mask if v is None else (mask & v)
                    if spec in ("min", "max"):
                        if jnp.issubdtype(d.dtype, jnp.floating):
                            ident = jnp.asarray(
                                jnp.inf if spec == "min" else -jnp.inf,
                                d.dtype,
                            )
                        else:
                            ident = jnp.asarray(
                                2**62 if spec == "min" else -(2**62),
                                d.dtype,
                            )
                        dv = jnp.where(vv, d, ident)
                    else:
                        dv = jnp.where(vv, d, jnp.zeros((), d.dtype))
                    operands.append(dv)
                    vi = len(operands)
                    operands.append(vv.astype(jnp.int8))
                    val_pos.append((vi - 1, vi))
                carried_pos = []
                for p in dropped:
                    d, v = _bcast(gfns[p](env, params), n)
                    operands.append(
                        jnp.where(mask, d.astype(jnp.int64), 0)
                    )
                    ci = len(operands) - 1
                    vi = None
                    if v is not None:
                        operands.append((mask & v).astype(jnp.int8))
                        vi = len(operands) - 1
                    carried_pos.append((ci, vi))
                st.to("final/wgagg/sort")
                sorted_ops = jax.lax.sort(
                    tuple(operands), num_keys=1, is_stable=False
                )
                salk = sorted_ops[0]
                boundary = jnp.concatenate([
                    jnp.ones(1, jnp.bool_), salk[1:] != salk[:-1]
                ])
                end = jnp.concatenate([
                    boundary[1:], jnp.ones(1, jnp.bool_)
                ])
                live_end = end & (salk < DEADS)

                def run_from_start(cs, own):
                    base = jax.lax.cummax(
                        jnp.where(
                            boundary, cs - own,
                            jnp.asarray(-1, dtype=cs.dtype),
                        )
                    )
                    return cs - base

                run_cnt = None

                def get_run_cnt():
                    nonlocal run_cnt
                    if run_cnt is None:
                        lv = (salk < DEADS).astype(jnp.int32)
                        run_cnt = run_from_start(jnp.cumsum(lv), lv)
                    return run_cnt

                pvals = []  # per agg: (partial value, partial valid)
                for spec, vp in zip(specs, val_pos):
                    if spec == "count_star":
                        c = get_run_cnt()
                        pvals.append((c.astype(jnp.int64), c > 0))
                        continue
                    oi, vi = vp
                    sval = sorted_ops[oi]
                    lv = sorted_ops[vi].astype(jnp.int32)
                    vcnt = run_from_start(jnp.cumsum(lv), lv)
                    vvalid = vcnt > 0
                    if spec == "count":
                        pvals.append(
                            (vcnt.astype(jnp.int64), live_end)
                        )
                        continue
                    if spec in ("min", "max"):
                        op = jnp.minimum if spec == "min" else (
                            jnp.maximum
                        )
                        sv = _seg_scan(sval, boundary, op)
                        if jnp.issubdtype(sv.dtype, jnp.integer):
                            sv = sv.astype(jnp.int64)
                        pvals.append((sv, vvalid))
                        continue
                    if jnp.issubdtype(sval.dtype, jnp.integer):
                        sval = sval.astype(jnp.int64)
                    if robust:
                        sv = _seg_scan(sval, boundary, jnp.add)
                    else:
                        ok = ok & ~(jnp.min(sval) < 0)
                        cs = jnp.cumsum(sval)
                        if jnp.issubdtype(cs.dtype, jnp.integer):
                            ok = ok & (
                                cs[-1] < jnp.int64(2**62)
                            ) & (cs[-1] >= 0)
                        sv = run_from_start(cs, sval)
                    pvals.append((sv, vvalid))

                nend = jnp.sum(live_end, dtype=jnp.int32)
                novf = nend > cap
                order = jnp.argsort(~live_end)[:cap]

                def pick(x):
                    return jnp.take(x, order)

                out = [pick(salk)]
                for dd, vv in pvals:
                    out.append(pick(dd))
                    out.append(pick(vv))
                for ci, vi in carried_pos:
                    out.append(pick(sorted_ops[ci]))
                    out.append(
                        pick(
                            sorted_ops[vi] > 0 if vi is not None
                            else jnp.ones_like(salk, jnp.bool_)
                        )
                    )
                out.append(pick(live_end))
                return (
                    [o[None] for o in out],
                    jnp.reshape(novf, (1,)),
                    jnp.reshape(ok, (1,)),
                    flags,
                )

            return shard_map(
                block,
                mesh=mesh,
                in_specs=(_specs_like(arrays),),
                out_specs=(
                    [P("dn")] * (1 + 2 * naggs + 2 * len(dropped) + 1),
                    P("dn"),
                    P("dn"),
                    [P("dn")] * nflags,
                ),
            )(arrays)

        nwcols = 1 + 2 * naggs + 2 * len(dropped) + 1

        def merge_program(wouts, params, snap):
            @_staged
            def block(*wcols_flat, st):
                # wcols_flat per window: nwcols columns + novf + ok
                # + flags
                per = nwcols + 2 + nflags
                wins = [
                    wcols_flat[i * per:(i + 1) * per]
                    for i in range(len(wouts))
                ]
                cols = [
                    jnp.concatenate([w[i].reshape(-1) for w in wins])
                    for i in range(nwcols)
                ]
                novf = jnp.any(
                    jnp.stack([w[nwcols].any() for w in wins])
                )
                wok = jnp.all(
                    jnp.stack([w[nwcols + 1].all() for w in wins])
                )
                flags = [
                    jnp.reshape(
                        jnp.any(jnp.stack([
                            w[nwcols + 2 + f].any() for w in wins
                        ])),
                        (1,),
                    )
                    for f in range(nflags)
                ]
                live_in = cols[-1]
                key_in = jnp.where(
                    live_in, cols[0], DEADS
                )
                operands = [key_in] + list(cols[1:-1])
                st.to("final/wgagg/mergesort")
                sorted_ops = jax.lax.sort(
                    tuple(operands), num_keys=1, is_stable=False
                )
                salk = sorted_ops[0]
                m = salk.shape[0]
                boundary = jnp.concatenate([
                    jnp.ones(1, jnp.bool_), salk[1:] != salk[:-1]
                ])
                end = jnp.concatenate([
                    boundary[1:], jnp.ones(1, jnp.bool_)
                ])
                live_end = end & (salk < DEADS)
                ok = wok

                def run_from_start(cs, own):
                    base = jax.lax.cummax(
                        jnp.where(
                            boundary, cs - own,
                            jnp.asarray(-1, dtype=cs.dtype),
                        )
                    )
                    return cs - base

                out_vals_pos = []
                for ai, mop in enumerate(merge_op):
                    sval = sorted_ops[1 + 2 * ai]
                    svld = sorted_ops[2 + 2 * ai]
                    lv = svld.astype(jnp.int32)
                    vcnt = run_from_start(jnp.cumsum(lv), lv)
                    vvalid = vcnt > 0
                    if mop in ("min", "max"):
                        if jnp.issubdtype(sval.dtype, jnp.floating):
                            ident = jnp.asarray(
                                jnp.inf if mop == "min" else -jnp.inf,
                                sval.dtype,
                            )
                        else:
                            ident = jnp.asarray(
                                2**62 if mop == "min" else -(2**62),
                                sval.dtype,
                            )
                        sv = jnp.where(lv > 0, sval, ident)
                        op = jnp.minimum if mop == "min" else (
                            jnp.maximum
                        )
                        out_vals_pos.append(
                            (_seg_scan(sv, boundary, op), vvalid)
                        )
                        continue
                    sv = jnp.where(lv > 0, sval, jnp.zeros(
                        (), sval.dtype
                    ))
                    if jnp.issubdtype(sv.dtype, jnp.integer):
                        sv = sv.astype(jnp.int64)
                    if robust:
                        out_vals_pos.append(
                            (_seg_scan(sv, boundary, jnp.add), vvalid)
                        )
                        continue
                    ok = ok & ~(jnp.min(sv) < 0)
                    cs = jnp.cumsum(sv)
                    if jnp.issubdtype(cs.dtype, jnp.integer):
                        ok = ok & (cs[-1] < jnp.int64(2**62)) & (
                            cs[-1] >= 0
                        )
                    out_vals_pos.append(
                        (run_from_start(cs, sv), vvalid)
                    )

                coff = 1 + 2 * naggs
                stride = jnp.int64(1)
                prod = jnp.float64(1.0)
                packed_rank = jnp.zeros(m, dtype=jnp.int64)
                for p, desc, nf in reversed(sspecs):
                    if p >= nkeys:
                        d64, v = out_vals_pos[p - nkeys]
                        d64 = d64.astype(jnp.int64)
                    elif p == kidx:
                        d64 = salk
                        v = salk != NULLS
                    else:
                        di = dropped.index(p)
                        d64 = sorted_ops[coff + 2 * di]
                        v = sorted_ops[coff + 2 * di + 1]
                    x, r, rf, okbit = _rank_encode(
                        d64, v, desc, nf, live_end
                    )
                    packed_rank = packed_rank + x * stride
                    stride = stride * r
                    prod = prod * jnp.maximum(rf, 1.0)
                    ok = ok & okbit
                ok = ok & (prod < jnp.float64(2**62))

                st.to("final/wgagg/topk")
                idx, sel = _topk_idx(packed_rank, live_end, k)
                salk_k = jnp.take(salk, idx)
                out_keys = []
                for i in range(nkeys):
                    if i == kidx:
                        out_keys.append(
                            (salk_k, salk_k != NULLS)
                        )
                    else:
                        di = dropped.index(i)
                        out_keys.append((
                            jnp.take(
                                sorted_ops[coff + 2 * di], idx
                            ),
                            jnp.take(
                                sorted_ops[coff + 2 * di + 1], idx
                            ).astype(jnp.bool_),
                        ))
                out_vals = [
                    (jnp.take(dd, idx), jnp.take(vv, idx))
                    for dd, vv in out_vals_pos
                ]
                return (
                    jax.tree.map(lambda x: x[None], out_keys),
                    jax.tree.map(lambda x: x[None], out_vals),
                    sel[None],
                    jnp.reshape(novf, (1,)),
                    jnp.reshape(ok, (1,)),
                    flags,
                )

            flat = []
            for wo in wouts:
                cols_w, novf_w, ok_w, flags_w = wo
                flat.extend(cols_w)
                flat.append(novf_w)
                flat.append(ok_w)
                flat.extend(flags_w)
            in_specs = tuple([P("dn")] * len(flat))
            return shard_map(
                block,
                mesh=mesh,
                in_specs=in_specs,
                out_specs=(
                    [(P("dn"), P("dn"))] * nkeys,
                    [(P("dn"), P("dn"))] * naggs,
                    P("dn"),
                    P("dn"),
                    P("dn"),
                    [P("dn")] * nflags,
                ),
            )(*flat)

        return (
            self._program(window_program, "wgagg_window", b),
            self._program(merge_program, "wgagg_merge"),
            comp,
            b.jinfo(),
        )

    def _compile_gsort(
        self, b, comp, agg, gs, root, exchanged, topk, D, nflags,
        narrow: bool = False,
    ):
        """Co-sort join + grouped aggregation + top-k in ONE program.

        The TPU-native replacement for hash join + hash aggregate when
        grouping by the unique build key (reference shape:
        nodeHashjoin.c + nodeAgg.c): concatenate [build keys, probe
        keys], lax.sort with (key, is_probe) so each run starts with its
        build row, then every per-group quantity falls out of prefix
        scans — run sums via cumsum differences, run totals propagated
        BACK to the build position via a reverse cummin over run-end
        prefix values (valid because the shifted cumsum is monotone).
        No scatter (8.9s/60M on v5e), no searchsorted (29.5s/60M), no
        gather at width; the sort (~0.6s/76M) and a few linear scans
        are the whole cost. Ranking happens at build positions where
        build-side ORDER BY columns are LOCAL; only LIMIT rows leave."""
        join = gs["join"]
        build_right = gs["build_right"]
        build_cols = gs["build_cols"]
        bkey_col = gs["bkey_col"]
        residual = gs.get("residual")
        # (what the co-sort reads of either side is not walked down: a
        # fold under it keeps a match bit of its own)
        left_fn = b.build(join.left, exchanged, D)
        right_fn = b.build(join.right, exchanged, D)
        # the top join is this program's co-sort: a sort-merge
        b.modes.add("merge")
        jtag = f"join{b.njoin}/merge"  # children numbered first
        ldids = [c.dict_id for c in join.left.schema]
        rdids = [c.dict_id for c in join.right.schema]
        lkfn = comp.compile(join.left_keys[0], ldids)
        rkfn = comp.compile(join.right_keys[0], rdids)
        jdids = [c.dict_id for c in join.schema]
        resfn = (
            comp.compile(residual, jdids)
            if residual is not None else None
        )
        res_cols = (
            sorted(_expr_cols(residual))
            if residual is not None else []
        )
        specs: list[str] = []
        afns: list = []
        for a in agg.aggs:
            if a.func == "count" and a.arg is None:
                specs.append("count_star")
                afns.append(None)
            else:
                specs.append(a.func)
                afns.append(comp.compile(a.arg, jdids))
        k, sspecs, _merged = topk
        nkeys = len(agg.group_exprs)
        naggs = len(agg.aggs)
        nl = len(join.left.schema)
        nr = len(join.right.schema)
        # build-side ORDER BY columns (slots computed at the build side
        # pre-sort and carried as payload — local at build positions)
        bslot_cols = sorted({
            build_cols[p]
            for p, _d, _nf in sspecs
            if p < nkeys and build_cols[p] != bkey_col
        })
        mesh = self.fx.mesh

        def program(arrays, params, snap):
            @_staged
            def block(blocks, st):
                lenv, lmask, ln, lflags = left_fn(blocks, params, snap)
                renv, rmask, rn, rflags = right_fn(blocks, params, snap)
                flags = lflags + rflags
                st.to(jtag + "/build")
                lk = _bcast(lkfn(lenv, params), ln)
                rk = _bcast(rkfn(renv, params), rn)
                if build_right:
                    bk, benv, bmask, bn = rk, renv, rmask, rn
                    pk, penv, pmask, pn = lk, lenv, lmask, ln
                    poff, boff = 0, nl
                else:
                    bk, benv, bmask, bn = lk, lenv, lmask, ln
                    pk, penv, pmask, pn = rk, renv, rmask, rn
                    poff, boff = nl, 0
                bkd, bkv = bk
                pkd, pkv = pk
                breal = bmask if bkv is None else (bmask & bkv)
                preal = pmask if pkv is None else (pmask & pkv)
                BIGK = jnp.int64(2**62)
                # ONE sort key: key*2 + is_probe — build rows lead their
                # runs; dead rows ride in the BIGK run at the end
                ok = jnp.asarray(True)
                allk = jnp.concatenate([
                    jnp.where(breal, bkd.astype(jnp.int64) * 2, BIGK),
                    jnp.where(preal, pkd.astype(jnp.int64) * 2 + 1, BIGK),
                ])
                kmax = jnp.maximum(
                    jnp.max(jnp.where(breal, bkd.astype(jnp.int64), 0)),
                    jnp.max(jnp.where(preal, pkd.astype(jnp.int64), 0)),
                )
                kmin = jnp.minimum(
                    jnp.min(jnp.where(breal, bkd.astype(jnp.int64), 0)),
                    jnp.min(jnp.where(preal, pkd.astype(jnp.int64), 0)),
                )
                ok = ok & (kmax < jnp.int64(2**61)) & (
                    kmin > jnp.int64(-(2**61))
                )
                if narrow:
                    # i32 sort operands when the data fits (a v5e sorts
                    # i32 ~40% faster): runtime range flags fall back to
                    # the wide program on overflow
                    ok = ok & (kmax < jnp.int64(2**29)) & (
                        kmin > jnp.int64(-(2**29))
                    )
                    # dead-row sentinel for the narrow key
                    allk = jnp.where(
                        allk >= BIGK, jnp.int64(2**31 - 1), allk
                    ).astype(jnp.int32)
                # probe-side agg inputs (build positions ride as zeros)
                env_full: list = [
                    (jnp.zeros((), jnp.int32), None)
                ] * (nl + nr)
                for i in range(len(penv)):
                    env_full[poff + i] = penv[i]
                operands = [allk]
                val_pos: list = []  # per agg: (operand idx, vcnt idx|None)
                sents: list = []  # per agg: min/max sentinel or None
                pz = jnp.zeros(bn, jnp.int64)
                for spec, fn in zip(specs, afns):
                    if fn is None:
                        val_pos.append(None)
                        sents.append(None)
                        continue
                    d, v = _bcast(fn(env_full, params), pn)
                    if jnp.issubdtype(d.dtype, jnp.integer):
                        d = d.astype(jnp.int64)
                    elif jnp.issubdtype(d.dtype, jnp.floating):
                        d = d.astype(jnp.float64)
                    vv = preal if v is None else (preal & v)
                    dv = jnp.where(vv, d, jnp.zeros((), d.dtype))
                    if narrow and dv.dtype == jnp.int64:
                        # two-sided bound, NOT abs(): abs(INT64_MIN)
                        # wraps negative and would slip through
                        ok = ok & (
                            jnp.max(dv) < jnp.int64(2**31 - 1)
                        ) & (jnp.min(dv) > jnp.int64(-(2**31 - 1)))
                        dv = dv.astype(jnp.int32)
                    if spec in ("min", "max"):
                        # dead/NULL rows AND build positions carry the
                        # op identity so the reverse segmented scan
                        # reduces over live probe rows only (the
                        # narrow-bound guard above keeps live values
                        # strictly inside the sentinel)
                        if jnp.issubdtype(dv.dtype, jnp.floating):
                            sent = jnp.inf if spec == "min" else -jnp.inf
                        elif dv.dtype == jnp.int32:
                            info = jnp.iinfo(jnp.int32)
                            sent = (
                                info.max if spec == "min" else info.min
                            )
                        else:
                            sent = (
                                np.int64(2**62) if spec == "min"
                                else np.int64(-(2**62))
                            )
                        sentv = jnp.asarray(sent, dtype=dv.dtype)
                        dv = jnp.where(vv, dv, sentv)
                        bfill = jnp.full(bn, sentv, dtype=dv.dtype)
                        sents.append(sentv)
                    else:
                        bfill = pz.astype(dv.dtype)
                        sents.append(None)
                    operands.append(jnp.concatenate([bfill, dv]))
                    vi = None
                    if v is not None:
                        vi = len(operands)
                        operands.append(jnp.concatenate([
                            jnp.zeros(bn, jnp.int8),
                            vv.astype(jnp.int8),
                        ]))
                    val_pos.append((len(operands) - (2 if vi else 1), vi))
                # residual inputs ride the sort: probe-side columns are
                # local at probe positions; build-side columns sit at
                # each run's LEADING build row and forward-propagate
                # after the sort (the ON-clause evaluation of
                # nodeHashjoin.c's joinqual, co-sort style)
                res_pos: dict = {}  # col -> (op idx, valid idx, is_build)
                if resfn is not None:
                    pspan = range(poff, poff + len(penv))
                    for c in res_cols:
                        if c in pspan:
                            d, v = penv[c - poff]
                            d = jnp.broadcast_to(d, (pn,))
                            dv = jnp.concatenate([
                                jnp.zeros(bn, d.dtype), d
                            ])
                            v8 = (
                                None if v is None else jnp.concatenate([
                                    jnp.zeros(bn, jnp.int8),
                                    jnp.broadcast_to(
                                        v, (pn,)
                                    ).astype(jnp.int8),
                                ])
                            )
                        else:
                            d, v = benv[c - boff]
                            d = jnp.broadcast_to(d, (bn,))
                            dv = jnp.concatenate([
                                d, jnp.zeros(pn, d.dtype)
                            ])
                            v8 = (
                                None if v is None else jnp.concatenate([
                                    jnp.broadcast_to(
                                        v, (bn,)
                                    ).astype(jnp.int8),
                                    jnp.zeros(pn, jnp.int8),
                                ])
                            )
                        oi = len(operands)
                        operands.append(dv)
                        vi = None
                        if v8 is not None:
                            vi = len(operands)
                            operands.append(v8)
                        res_pos[c] = (oi, vi, c not in pspan)
                # build ORDER BY slots: direction+NULL encoded at the
                # build side (ranges over real build rows — a superset of
                # matched groups, still order-preserving). All slots pack
                # with the build row index into ONE i64 payload operand.
                slot_rng: dict = {}
                slot_stride: dict = {}
                sb_acc = jnp.zeros(bn, jnp.int64)
                sb_stride = jnp.int64(1)
                sb_prod = jnp.float64(1.0)
                for bc in bslot_cols:
                    sp = next(
                        s for s in sspecs
                        if s[0] < nkeys and build_cols[s[0]] == bc
                    )
                    _p, desc, nf = sp
                    d, v = benv[bc]
                    d64 = jnp.broadcast_to(d, (bn,)).astype(jnp.int64)
                    vb = (
                        None if v is None
                        else jnp.broadcast_to(v, (bn,))
                    )
                    slot, r, rf, okbit = _rank_encode(
                        d64, vb, desc, nf, breal, bound=2**61
                    )
                    ok = ok & okbit
                    slot_rng[bc] = r
                    slot_stride[bc] = sb_stride
                    sb_acc = sb_acc + slot * sb_stride
                    sb_stride = sb_stride * r
                    sb_prod = sb_prod * jnp.maximum(rf, 1.0)
                ok = ok & (
                    sb_prod * jnp.float64(max(bn, 1))
                    < jnp.float64(2**62)
                )
                sb_i = len(operands)
                operands.append(jnp.concatenate([
                    sb_acc * bn + jnp.arange(bn, dtype=jnp.int64),
                    jnp.zeros(pn, jnp.int64),
                ]))

                st.to(jtag + "/sort")
                sorted_ops = jax.lax.sort(
                    tuple(operands), num_keys=1, is_stable=False
                )
                st.to(jtag + "/prefix")
                salk = sorted_ops[0]
                # dead-row sentinel matches the key dtype (narrow keys
                # compare in i32 — an i64 BIGK would never exclude them)
                KSENT = (
                    jnp.int32(2**31 - 1) if narrow else BIGK
                )
                skey = jnp.right_shift(salk, 1)  # run key (floor: neg ok)
                M = bn + pn
                boundary = jnp.concatenate([
                    jnp.ones(1, jnp.bool_), skey[1:] != skey[:-1]
                ])
                isb = (
                    (jnp.bitwise_and(salk, 1) == 0) & (salk < KSENT)
                )
                isp = (
                    (jnp.bitwise_and(salk, 1) == 1) & (salk < KSENT)
                )
                # duplicate real build keys: adjacent build rows in one
                # run (build sorts first) — exact, same contract as
                # _lookup's dup flag
                dupf = jnp.any(isb[1:] & isb[:-1] & ~boundary[1:])
                flags = flags + [dupf]
                end = jnp.concatenate([
                    boundary[1:], jnp.ones(1, jnp.bool_)
                ])
                BIG32 = jnp.int32(2**31 - 1)
                # residual evaluation at SORTED positions: build-side
                # inputs forward-propagate from each run's leading
                # build row (keep-first segmented scan); rows failing
                # the residual drop out of every reduction below
                resid_ok = None
                if resfn is not None:
                    env_res: list = [
                        (jnp.zeros((), jnp.int32), None)
                    ] * (nl + nr)
                    for c, (oi, vi, is_bld) in res_pos.items():
                        rd = sorted_ops[oi]
                        rv = None if vi is None else sorted_ops[vi]
                        if is_bld:
                            keep_first = lambda a, _b: a  # noqa: E731
                            rd = _seg_scan(rd, boundary, keep_first)
                            if rv is not None:
                                rv = _seg_scan(
                                    rv, boundary, keep_first
                                )
                        env_res[c] = (
                            rd, None if rv is None else rv > 0
                        )
                    okd, okv = resfn(env_res, params)
                    okd = jnp.broadcast_to(okd, (bn + pn,))
                    resid_ok = (
                        okd if okv is None
                        else okd & jnp.broadcast_to(okv, (bn + pn,))
                    )
                isp_ok = isp if resid_ok is None else (isp & resid_ok)

                def run_total(cs):
                    # cs must be monotone; value at BUILD position =
                    # run-end prefix minus own prefix (build row is the
                    # run's first element and contributes nothing).
                    # Probe rows in build-less runs never surface (their
                    # run has no live build position), so no
                    # matched-mask is needed anywhere.
                    big = jnp.asarray(
                        jnp.inf if jnp.issubdtype(cs.dtype, jnp.floating)
                        else (
                            BIG32 if cs.dtype == jnp.int32
                            else jnp.int64(2**62)
                        ),
                        dtype=cs.dtype,
                    )
                    at_end = jnp.where(end, cs, big)
                    return jax.lax.cummin(at_end, reverse=True) - cs

                run_cnt = None  # computed only when a COUNT needs it

                def get_run_cnt():
                    nonlocal run_cnt
                    if run_cnt is None:
                        run_cnt = run_total(
                            jnp.cumsum(isp_ok.astype(jnp.int32))
                        )
                    return run_cnt

                # group existence: without a residual it is free (the
                # run's leading build row is not also its end); with
                # one, a group lives iff any probe row PASSED
                has_probe = (
                    ~end if resid_ok is None else (get_run_cnt() > 0)
                )

                out_vals_pos = []  # per agg: (value array, valid array)
                for spec, vp, sentv in zip(specs, val_pos, sents):
                    if spec == "count_star":
                        out_vals_pos.append(
                            (get_run_cnt().astype(jnp.int64), has_probe)
                        )
                        continue
                    oi, vi = vp
                    sval = sorted_ops[oi]
                    if resid_ok is not None:
                        # failing probe rows leave every reduction:
                        # identity for sums, sentinel for min/max
                        fail = isp & ~resid_ok
                        sval = jnp.where(
                            fail,
                            sentv if sentv is not None
                            else jnp.zeros((), sval.dtype),
                            sval,
                        )
                    if vi is not None:
                        vlive = isp_ok & (sorted_ops[vi] > 0)
                        vcnt = run_total(
                            jnp.cumsum(vlive.astype(jnp.int32))
                        )
                        vvalid = vcnt > 0
                    else:
                        vlive = isp_ok
                        vcnt = None
                        vvalid = has_probe

                    if spec == "count":
                        c = (
                            vcnt if vcnt is not None else get_run_cnt()
                        )
                        out_vals_pos.append(
                            (c.astype(jnp.int64), has_probe)
                        )
                        continue
                    if spec in ("min", "max"):
                        # one reverse segmented scan: the full-run
                        # reduction lands at the run-START position —
                        # the build row, where every other per-group
                        # output already lives (sentinel-filled dead
                        # rows are the op identity)
                        opf = (
                            jnp.minimum if spec == "min"
                            else jnp.maximum
                        )
                        m = _seg_scan(sval, end, opf, reverse=True)
                        out_vals_pos.append((m, vvalid))
                        continue
                    # sum: the reverse-cummin propagation needs a
                    # monotone prefix sum. Fast path assumes values are
                    # non-negative (true for every TPC-H measure); a
                    # runtime flag falls back to the full-width ship.
                    # (the operand was zeroed pre-sort wherever the row
                    # is dead or the arg is NULL, so no re-mask here)
                    ok = ok & ~(jnp.min(sval) < 0)
                    if jnp.issubdtype(sval.dtype, jnp.integer):
                        # widen: narrow i32 operands still sum in i64
                        cs = jnp.cumsum(sval, dtype=jnp.int64)
                        # the GLOBAL prefix sum can wrap int64 even when
                        # every per-group sum is small — guard the last
                        # (= max, values are non-negative) prefix value
                        ok = ok & (cs[-1] < jnp.int64(2**62)) & (
                            cs[-1] >= 0
                        )
                    else:
                        cs = jnp.cumsum(sval)
                    s2 = run_total(cs)
                    out_vals_pos.append((s2, vvalid))

                live = isb & has_probe
                ssb = sorted_ops[sb_i]
                sslots = ssb // jnp.int64(max(bn, 1))
                # rank at build positions: build ORDER BY slots are
                # LOCAL, run-level values just computed
                stride = jnp.int64(1)
                prod = jnp.float64(1.0)
                packed = jnp.zeros(M, dtype=jnp.int64)
                for p, desc, nf in reversed(sspecs):
                    if p < nkeys and build_cols[p] == bkey_col:
                        d64 = skey
                        v = None
                    elif p < nkeys:
                        bc = build_cols[p]
                        sl = (sslots // slot_stride[bc]) % slot_rng[bc]
                        packed = packed + sl * stride
                        stride = stride * slot_rng[bc]
                        prod = prod * jnp.maximum(
                            slot_rng[bc].astype(jnp.float64), 1.0
                        )
                        continue
                    else:
                        d64, v = out_vals_pos[p - nkeys]
                        d64 = d64.astype(jnp.int64)
                    x, r, rf, okbit = _rank_encode(
                        d64, v, desc, nf, live
                    )
                    packed = packed + x * stride
                    stride = stride * r
                    prod = prod * jnp.maximum(rf, 1.0)
                    ok = ok & okbit
                ok = ok & (prod < jnp.float64(2**62))

                st.to("final/gsort/topk")
                idx, sel = _topk_idx(packed, live, k)
                st.to("final/gsort/gather")
                brow_k = (
                    jnp.take(ssb, idx) % jnp.int64(max(bn, 1))
                ).astype(jnp.int32)
                out_keys = []
                for gi in range(nkeys):
                    bc = build_cols[gi]
                    if bc == bkey_col:
                        out_keys.append((
                            jnp.take(skey, idx),
                            jnp.ones(k, jnp.bool_) & sel,
                        ))
                    else:
                        d, v = benv[bc]
                        dk = jnp.take(
                            jnp.broadcast_to(d, (bn,)), brow_k
                        )
                        vk = (
                            jnp.ones(k, jnp.bool_)
                            if v is None
                            else jnp.take(
                                jnp.broadcast_to(v, (bn,)), brow_k
                            )
                        )
                        out_keys.append((dk, vk))
                out_vals = [
                    (jnp.take(dd, idx), jnp.take(vv, idx))
                    for dd, vv in out_vals_pos
                ]
                return (
                    jax.tree.map(lambda x: x[None], out_keys),
                    jax.tree.map(lambda x: x[None], out_vals),
                    sel[None],
                    jnp.reshape(ok, (1,)),
                    [jnp.reshape(f, (1,)) for f in flags],
                )

            return shard_map(
                block,
                mesh=mesh,
                in_specs=(_specs_like(arrays),),
                out_specs=(
                    [(P("dn"), P("dn"))] * nkeys,
                    [(P("dn"), P("dn"))] * naggs,
                    P("dn"),
                    P("dn"),
                    [P("dn")] * nflags,
                ),
            )(arrays)

        return self._program(program, "gsort", b), comp, "gsort"

    def _compile_final(
        self, frag, agg, root, exchanged, orientation, gcap, D,
        packing: bool = True, topk=None, bg=None, psum: bool = False,
        fo=frozenset(), gslots: int = 0,
    ):
        """``gslots`` > 0 (``_run_final`` asks only where
        ``_direct_grouping`` allows): the grouped final addresses
        ``gslots`` slots by the packed key instead of sorting."""
        comp = ExprCompiler(lift_consts=True)
        b = _Builder(
            self.fx, comp, orientation, root,
            capture_id=bg[0] if bg is not None else None,
            runner=self, D=D, fold_off=fo,
        )
        gseg = agg is not None and bg is not None and topk is not None
        # (gseg's group keys are the captured build side's columns, at
        # the build's width: only its aggregates read the joined rows)
        ev = b.build(root, exchanged, D, _agg_reads(agg, keys=not gseg))
        mesh = self.fx.mesh
        nflags = _count_inner_joins(root)

        if gseg:
            return self._compile_gseg(
                b, ev, comp, agg, root, topk, psum, D, nflags
            ) + (b.jinfo(),)

        if agg is not None:
            dids = [c.dict_id for c in root.schema]
            gfns = [comp.compile(g, dids) for g in agg.group_exprs]
            specs: list[str] = []
            afns: list = []
            for a in agg.aggs:
                if a.func == "count" and a.arg is None:
                    specs.append("count_star")
                    afns.append(None)
                else:
                    if a.func in ("min", "max") and (
                        a.arg.type.is_text
                    ):
                        raise DagUnsupported(
                            f"{a.func}() over TEXT stays on the "
                            "host path (code order != collation)"
                        )
                    specs.append(a.func)
                    afns.append(comp.compile(a.arg, dids))
            grouped = bool(agg.group_exprs)
            mode = "grouped" if grouped else "scalar"
            if grouped and topk is not None:
                mode = "grouped_topk"  # single device: groups complete
            nkeys = len(agg.group_exprs)
            naggs = len(agg.aggs)
            # packed single-sort grouping applies to all-integer keys
            # (dtype is static); a runtime range-overflow flag retries
            # with per-key sorting
            use_packed = packing and grouped and _packable_keys(agg)

            def program(arrays, params, snap):
                @_staged
                def block(blocks, st):
                    env, mask, n, flags = ev(blocks, params, snap)
                    # (the scalar final groups nothing: one stage of its
                    # own name; scope names re-key no plain XLA program)
                    st.to(
                        "final/grouped/keys" if grouped
                        else "final/scalar/reduce"
                    )
                    flags = [jnp.reshape(f, (1,)) for f in flags]
                    keys = [_bcast(fn(env, params), n) for fn in gfns]
                    vals = [
                        None if fn is None else _bcast(fn(env, params), n)
                        for fn in afns
                    ]
                    if not grouped:
                        outs = agg_ops._scalar_reduce_impl(
                            vals, mask, tuple(specs)
                        )
                        return [
                            (jnp.reshape(d, (1,)), jnp.reshape(v, (1,)))
                            for d, v in outs
                        ], flags
                    if gslots:
                        out_keys, out_vals, gvalid, ngroups, span = (
                            _direct_grouped(
                                keys, vals, mask, gslots, tuple(specs), st
                            )
                        )
                    else:
                        if use_packed:
                            st.to("final/grouped/pack")
                            packed, pack_ok, _layout = _pack_group_keys(
                                keys, mask
                            )
                            st.to("final/grouped/sort")
                            perm, seg, ngroups = agg_ops._group_ids_impl(
                                [(packed, None)], mask
                            )
                            flags = flags + [jnp.reshape(~pack_ok, (1,))]
                        else:
                            st.to("final/grouped/sort")
                            perm, seg, ngroups = agg_ops._group_ids_impl(
                                keys, mask
                            )
                        st.to("final/grouped/segreduce")
                        out_keys, out_vals, gvalid = (
                            agg_ops._group_reduce_impl(
                                keys, vals, perm, seg, gcap, tuple(specs)
                            )
                        )
                    # (the direct final's last output is its packed
                    # span, one a device, for the runner's capacity)
                    tail = (span.reshape(1),) if gslots else ()
                    if topk is not None:
                        kk, sspecs, _m = topk
                        sortcols = [
                            out_keys[p] if p < nkeys else out_vals[p - nkeys]
                            for p, _d, _nf in sspecs
                        ]
                        packed, ok = _pack_sort_cols(
                            sortcols, sspecs, gvalid
                        )
                        st.to("final/grouped/topk")
                        idx, sel = _topk_idx(packed, gvalid, kk)

                        def take(pair):
                            d, v = pair
                            return (jnp.take(d, idx), jnp.take(v, idx))

                        out_keys = [take(p) for p in out_keys]
                        out_vals = [take(p) for p in out_vals]
                        return (
                            jax.tree.map(lambda x: x[None], out_keys),
                            jax.tree.map(lambda x: x[None], out_vals),
                            sel[None],
                            ngroups.reshape(1),
                            jnp.reshape(ok, (1,)),
                            flags,
                        ) + tail
                    return (
                        jax.tree.map(lambda x: x[None], out_keys),
                        jax.tree.map(lambda x: x[None], out_vals),
                        gvalid[None],
                        ngroups.reshape(1),
                        flags,
                    ) + tail

                # the sort formulation's last flag is its packing's
                npack = 1 if use_packed and not gslots else 0
                tail_specs = (P("dn"),) if gslots else ()
                if grouped and topk is not None:
                    out_specs = (
                        [(P("dn"), P("dn"))] * nkeys,
                        [(P("dn"), P("dn"))] * naggs,
                        P("dn"),
                        P("dn"),
                        P("dn"),
                        [P("dn")] * (nflags + npack),
                    ) + tail_specs
                elif grouped:
                    out_specs = (
                        [(P("dn"), P("dn"))] * nkeys,
                        [(P("dn"), P("dn"))] * naggs,
                        P("dn"),
                        P("dn"),
                        [P("dn")] * (nflags + npack),
                    ) + tail_specs
                else:
                    out_specs = (
                        [(P("dn"), P("dn"))] * naggs,
                        [P("dn")] * nflags,
                    )
                return shard_map(
                    block,
                    mesh=mesh,
                    in_specs=(_specs_like(arrays),),
                    out_specs=out_specs,
                )(arrays)

            return self._program(program, mode, b), comp, mode, b.jinfo()

        # no aggregate: compact surviving rows on DEVICE to a static
        # per-device capacity before shipping — never transfer the padded
        # scan width to the host (the capacity comes from a counting
        # pass, like the exchange buckets)
        ncols = len(root.schema)
        if topk is not None:
            # ORDER BY ... LIMIT k over plain rows: rank on device and
            # ship k rows per device — rows are independent, so the
            # global top-k is always inside the union of per-device
            # top-k's, at any D
            kk, sspecs, _m = topk

            def program(arrays, params, snap):
                @_staged
                def block(blocks, st):
                    env, mask, n, flags = ev(blocks, params, snap)
                    st.to("final/rows/pack")
                    cols = []
                    valids = []
                    for i in range(ncols):
                        d = jnp.broadcast_to(env[i][0], (n,))
                        v = (
                            jnp.ones(n, jnp.bool_)
                            if env[i][1] is None
                            else jnp.broadcast_to(env[i][1], (n,))
                        )
                        cols.append(d)
                        valids.append(v)
                    sortcols = [
                        (cols[p], valids[p]) for p, _d, _nf in sspecs
                    ]
                    packed, ok = _pack_sort_cols(sortcols, sspecs, mask)
                    st.to("final/rows/topk")
                    idx, sel = _topk_idx(packed, mask, kk)
                    return (
                        [jnp.take(d, idx)[None] for d in cols],
                        [jnp.take(v, idx)[None] for v in valids],
                        sel[None],
                        jnp.reshape(ok, (1,)),
                        [jnp.reshape(f, (1,)) for f in flags],
                    )

                return shard_map(
                    block,
                    mesh=mesh,
                    in_specs=(_specs_like(arrays),),
                    out_specs=(
                        [P("dn")] * ncols,
                        [P("dn")] * ncols,
                        P("dn"),
                        P("dn"),
                        [P("dn")] * nflags,
                    ),
                )(arrays)

            return (
                self._program(program, "rows_topk", b), comp, "rows_topk",
                b.jinfo(),
            )

        rowcap = gcap  # reused capacity slot for rows mode

        def program(arrays, params, snap):
            @_staged
            def block(blocks, st):
                env, mask, n, flags = ev(blocks, params, snap)
                st.to("final/rows/compact")
                order = jnp.argsort(~mask, stable=True)[:rowcap]
                cnt = jnp.minimum(
                    jnp.sum(mask, dtype=jnp.int32), rowcap
                )
                cols = []
                valids = []
                for i in range(ncols):
                    d = jnp.broadcast_to(env[i][0], (n,))
                    cols.append(jnp.take(d, order)[None])
                    v = (
                        jnp.ones(n, jnp.bool_)
                        if env[i][1] is None
                        else jnp.broadcast_to(env[i][1], (n,))
                    )
                    valids.append(jnp.take(v, order)[None])
                nrows_full = jnp.sum(mask, dtype=jnp.int64)
                return (
                    cols, valids, cnt.reshape(1),
                    nrows_full.reshape(1),
                    [jnp.reshape(f, (1,)) for f in flags],
                )

            return shard_map(
                block,
                mesh=mesh,
                in_specs=(_specs_like(arrays),),
                out_specs=(
                    [P("dn")] * ncols,
                    [P("dn")] * ncols,
                    P("dn"),
                    P("dn"),
                    [P("dn")] * nflags,
                ),
            )(arrays)

        return self._program(program, "rows", b), comp, "rows", b.jinfo()

    # -- output collection -------------------------------------------------
    def _apply_proj(self, batch, agg, out_proj):
        """Re-apply an absorbed bare-column projection: reorder/rename
        the aggregate-schema batch to the fragment's shipped schema."""
        if out_proj is None:
            return batch
        perm, schema = out_proj
        src = list(batch.columns.values())
        cols = {
            oc.name: src[perm[i]] for i, oc in enumerate(schema)
        }
        return ColumnBatch(cols, batch.nrows)

    def _dic(self, oc):
        return self.fx.catalog.dictionary(oc.dict_id) if oc.dict_id else None

    @_collecting
    def _collect_grouped(self, agg, out_keys, out_vals, gvalid):
        gv = np.asarray(gvalid).reshape(-1)
        keep = np.nonzero(gv)[0]
        nkeys = len(agg.group_exprs)
        cols: dict[str, Column] = {}
        for i, oc in enumerate(agg.schema):
            if i < nkeys:
                d, v = out_keys[i]
            else:
                d, v = out_vals[i - nkeys]
            dd = np.asarray(d).reshape(-1)[keep]
            vv = None if v is None else np.asarray(v).reshape(-1)[keep]
            if dd.dtype != oc.type.np_dtype:
                dd = dd.astype(oc.type.np_dtype)
            cols[oc.name] = Column(oc.type, dd, vv, self._dic(oc))
        return ColumnBatch(cols, len(keep))

    @_collecting
    def _collect_scalar(self, agg, out_vals):
        cols: dict[str, Column] = {}
        n = 0
        for oc, (d, v) in zip(agg.schema, out_vals):
            dd = np.asarray(d).reshape(-1)
            vv = np.asarray(v).reshape(-1)
            if dd.dtype != oc.type.np_dtype:
                dd = dd.astype(oc.type.np_dtype)
            cols[oc.name] = Column(oc.type, dd, vv, None)
            n = len(dd)
        return ColumnBatch(cols, n)

    @_collecting
    def _collect_rows_live(self, schema, cols, valids, live):
        """Device top-k rows: [D, k] planes with a per-lane live mask
        (union of per-device top-k's; the coordinator re-sorts/limits)."""
        lv = np.asarray(live).reshape(-1)
        keep = np.nonzero(lv)[0]
        out: dict[str, Column] = {}
        for i, oc in enumerate(schema):
            d = np.asarray(cols[i]).reshape(-1)[keep]
            v = np.asarray(valids[i]).reshape(-1)[keep]
            if d.dtype != oc.type.np_dtype:
                d = d.astype(oc.type.np_dtype)
            out[oc.name] = Column(oc.type, d, v, self._dic(oc))
        return ColumnBatch(out, len(keep))

    @_collecting
    def _collect_rows(self, schema, cols, valids, cnt):
        """Device-compacted rows: per device, the first cnt[d] lanes of
        each [D, cap] column are live."""
        cnt = np.asarray(cnt).reshape(-1)
        cap = np.asarray(cols[0]).shape[-1] if len(cols) else 0
        keep = np.concatenate([
            np.arange(d * cap, d * cap + c) for d, c in enumerate(cnt)
        ]) if len(cnt) else np.empty(0, np.int64)
        out: dict[str, Column] = {}
        for i, oc in enumerate(schema):
            d = np.asarray(cols[i]).reshape(-1)[keep]
            v = np.asarray(valids[i]).reshape(-1)[keep]
            if d.dtype != oc.type.np_dtype:
                d = d.astype(oc.type.np_dtype)
            out[oc.name] = Column(oc.type, d, v, self._dic(oc))
        return ColumnBatch(out, len(keep))


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def _specs_like(arrays):
    # scalars (e.g. the wgagg window start) replicate; arrays shard
    return jax.tree.map(
        lambda a: P() if jnp.ndim(a) == 0 else P("dn"), tuple(arrays)
    )


def _bcast(kv, n):
    d, v = kv
    if jnp.ndim(d) == 0:
        d = jnp.broadcast_to(d, (n,))
    if v is not None and jnp.ndim(v) == 0:
        v = jnp.broadcast_to(v, (n,))
    return (d, v)


def _replace_node(root, old, new):
    """Rebuild ``root`` with the subtree ``old`` (by identity) replaced
    by ``new``. Dataclass-generic, mirrors _inline_sources."""
    import dataclasses

    if root is old:
        return new
    if dataclasses.is_dataclass(root) and not isinstance(root, type):
        changes = {}
        for f in dataclasses.fields(root):
            v = getattr(root, f.name)
            if isinstance(v, (L.LogicalPlan, RemoteSource)):
                nv = _replace_node(v, old, new)
                if nv is not v:
                    changes[f.name] = nv
        if changes:
            return dataclasses.replace(root, **changes)
    return root


def _contains_join(plan) -> bool:
    stack = [plan]
    while stack:
        node = stack.pop()
        if isinstance(node, L.Join):
            return True
        if isinstance(node, (L.Filter, L.Project, L.Aggregate)):
            stack.append(node.child)
    return False


def _count_inner_joins(plan) -> int:
    n = 0
    stack = [plan]
    while stack:
        node = stack.pop()
        if isinstance(node, L.Join):
            if node.join_type == "inner":
                n += 1
            stack.extend([node.left, node.right])
        elif isinstance(node, (L.Filter, L.Project)):
            stack.append(node.child)
        elif isinstance(node, L.Aggregate):
            stack.append(node.child)
    return n


def _plan_skey_of(plan) -> str:
    """Structural cache key: literals lifted to params where supported."""
    try:
        return plan_skey(plan)
    except NotImplementedError:
        return plan.key()


def _params_sig(params) -> tuple:
    """Hashable digest of resolved literal params — cached data-dependent
    capacities must not alias across different literal values."""
    out = []
    for p in params:
        a = np.asarray(p)
        out.append((a.shape, str(a.dtype), hash(a.tobytes())))
    return tuple(out)


def _first_true(flags) -> Optional[int]:
    """Index of the first raised flag. Each flag gathers per-shard as a
    [D] vector — ANY shard's duplicate detection must count."""
    for i, f in enumerate(flags):
        if bool(np.asarray(f).reshape(-1).any()):
            return i
    return None


def _take_rows(env, idx, mode=None, narrow=frozenset()):
    """Every (data, validity) column of ``env`` at the rows ``idx``
    (``mode``: ``jnp.take``'s for an index out of range; ``narrow``:
    the positions of 64-bit columns that cross as one word)."""
    return [
        (_take_word(d, idx, mode, i in narrow),
         None if v is None else jnp.take(v, idx, axis=0, mode=mode))
        for i, (d, v) in enumerate(env)
    ]


def _take_word(d, idx, mode, narrow: bool):
    """``d`` at the rows ``idx``. ``narrow``: a 64-bit integer column
    whose every value fits 32 bits (``_origin``, ``I32``), gathered as
    one ``int32`` word and widened right after, so no reader sees the
    narrow form. The TPU moves an ``int64`` as two 32-bit halves, a
    probe-width gather each: 575.6 ms apiece at 67.1M rows (ledger,
    PR 39, ``tpch_q10_sf10_1chip.q10``: ``c_custkey``'s two halves
    are its two costliest ops)."""
    if not narrow:
        return jnp.take(d, idx, axis=0, mode=mode)
    return jnp.take(
        d.astype(jnp.int32), idx, axis=0, mode=mode
    ).astype(jnp.int64)


def _as_word(col, narrow: bool):
    """A build column's data as one 32-bit word the Pallas probe can
    return (``pallas_join.pack_words``), and the function that reads
    the word back as the column's dtype; None where it is no column of
    rows or takes two words (a 64-bit column that is not ``narrow``,
    ``_take_word``'s rule)."""
    d, v = col
    if jnp.ndim(d) != 1 or (v is not None and jnp.ndim(v) != 1):
        return None
    dt = np.dtype(d.dtype)
    if dt == np.int32:
        return d, lambda w: w
    if dt == np.int64 and narrow:
        return d.astype(jnp.int32), lambda w: w.astype(jnp.int64)
    if dt.itemsize == 4:
        return (jax.lax.bitcast_convert_type(d, jnp.int32),
                lambda w: jax.lax.bitcast_convert_type(w, dt))
    if dt.itemsize < 4 and dt.kind in "biu":
        return d.astype(jnp.int32), lambda w: w.astype(dt)
    return None


def _widened(env, narrow) -> list:
    """``env`` with its narrowed positions read back as ``int64``."""
    return [
        (d.astype(jnp.int64), v) if i in narrow else (d, v)
        for i, (d, v) in enumerate(env)
    ]


def _origin(node, i: int):
    """(table, column) that position ``i`` of ``node``'s rows is a bare
    copy of, through filters, projections and joins; (fragment, i) for
    a source's column (``RemoteSource``: a motion's, whose range nothing
    proves, or a key set's, which ``_semiagg_launch`` may); None for an
    expression."""
    while True:
        if isinstance(node, L.Scan):
            return node.table, node.columns[i]
        if isinstance(node, RemoteSource):
            return node.fragment, i
        if isinstance(node, L.Filter):
            node = node.child
        elif isinstance(node, L.Project):
            ex = node.exprs[i]
            if not isinstance(ex, E.Col):
                return None
            node, i = node.child, ex.index
        elif isinstance(node, L.Join):
            nl = len(node.left.schema)
            if node.join_type in ("semi", "anti") or i < nl:
                node = node.left
            else:
                node, i = node.right, i - nl
        else:
            return None


def _key_fits(node, key, fits) -> bool:
    """A join key that is a bare column (``_origin``) of a range proven
    to fit ``[-I32, I32]`` (``fits``: the runner's, keyed with the
    program)."""
    return isinstance(key, E.Col) and _origin(node, key.index) in fits


def _lookup_dense(pk, pmask, bk, bvis, bfull, benv=(), presorted=False,
                  carrier=None):
    """Equi-join primitive for a small dense-keyed build side.

    Sort the build rows by key (cheap — the build side is small by the
    fold gate), then verify the VISIBLE keys form a gap-free unique
    range [base, base+cnt): sorted position i must hold key base+i.
    When they do, the build side IN KEY ORDER is a perfect-hash table
    and every probe row finds its build row with pure arithmetic:
    slot = key - base. The build columns ``benv`` ([(data, validity)])
    are put in that order ONCE, at the build's width (scope ``order``;
    a ``presorted`` build, a fold-prep program's, is in it already).
    Reaching them through the sort permutation (``take(sidx, slot)``)
    was a second probe-width gather a fold: 578 of 1,230 ms at 67.1M
    rows (ledger, PR 35, star cell).

    The density domain is ``bvis`` (storage visibility only); query
    predicates arrive separately as ``bfull`` and act as SLOT validity
    — a filtered dimension keeps its dense key range, its filtered-out
    rows just match nothing (otherwise any selective dim filter would
    punch gaps and defeat the fold). Duplicates and gaps both break
    the position identity, so the single ``notdense`` flag subsumes
    the dup check.

    Where the match bit comes from. ``carrier`` None: ``bfull`` is
    ordered with the columns and gathered by the slot, a probe-width
    gather of its own: 648 ms at 67.1M rows, 9.66 ns an element, the
    seven costliest device ops of a star rotation (ledger, PR 37,
    ``ssb_star_sf10_1chip.star`` ``breakdown``: seven
    ``pred[67108864]`` fusions of 1.945 s a window each). ``carrier``
    = i: ``benv[i]`` is a signed-integer column the join gathers by the
    slot anyway, and the bit rides in it: a build row that fails
    ``bfull`` is marked with ``SENT``, the dtype's minimum, at the
    build's width and BEFORE the ``order`` gather (what the probe reads
    stays the single output of a gather fusion, which the compiler
    places in memory space S(1): PR 36), ``bfull`` itself is no longer
    ordered, and the probe reads the match off the gathered word: ``W``
    gathers at either width, not ``1 + W``. A NULL's data word is
    don't-care and is zeroed so that it cannot read as ``SENT``; its
    validity plane rides as before. A passing row that HOLDS ``SENT``
    would read as unmatched: it raises the flag, and the runner answers
    as for a build that is not dense (fold off for that join, the next
    formulation is exact).

    Returns (matched [np] bool, slot [np] int32: the build row's index
    INTO the returned columns, flag 0-d bool, ``benv`` in key order
    (the carrier's data marked), the carrier's data at every probe
    row's slot [np] or None)."""
    pd, pv = pk
    bd, bv = bk
    nb = bd.shape[0]
    npr = pd.shape[0]
    if nb == 0:  # static: no build rows can ever match
        return (
            jnp.zeros(npr, jnp.bool_),
            jnp.zeros(npr, jnp.int32),
            jnp.asarray(False),
            benv,
            None if carrier is None
            else jnp.zeros(npr, benv[carrier][0].dtype),
        )
    breal = bvis if bv is None else (bvis & bv)
    preal = pmask if pv is None else (pmask & pv)
    BIG = jnp.int64(2**62)
    bkey = jnp.where(breal, bd.astype(jnp.int64), BIG)
    clash = None
    if carrier is not None:
        cd, cv = benv[carrier]
        SENT = jnp.asarray(jnp.iinfo(cd.dtype).min, cd.dtype)
        with jax.named_scope("mark"):
            if cv is not None:
                cd = jnp.where(cv, cd, jnp.zeros((), cd.dtype))
            clash = jnp.any(bfull & (cd == SENT))
            benv = list(benv)
            benv[carrier] = (jnp.where(bfull, cd, SENT), cv)
    if presorted:
        # a fold-prep program already key-sorted these rows; the
        # position-identity check below still fully verifies the claim
        # (an out-of-place or dead row breaks sk[i] == base + i)
        sk = bkey
    else:
        with jax.named_scope("build"):
            sk, sidx = jax.lax.sort(
                (bkey, jnp.arange(nb, dtype=jnp.int32)), num_keys=1,
                is_stable=False,
            )
        # (``mode="clip"``, here and wherever the slot gathers: every
        # index is in range, and ``jnp.take``'s fill for one that is
        # not is a select over each result, which XLA fuses across
        # sibling gathers; a table out of such a fusion stays outside
        # memory space S(1) and the probe's gather from it costs 14-22
        # ns an element for 8.6: a Q5 9.0 s for 6.5, my chip runs, PR 36)
        with jax.named_scope("order"):
            if carrier is None:
                bfull = jnp.take(bfull, sidx, mode="clip")
            benv = _take_rows(benv, sidx, "clip")
    cnt = jnp.sum(breal, dtype=jnp.int32)
    iota = jnp.arange(nb, dtype=jnp.int64)
    base = sk[0]
    dense = jnp.all(
        jnp.where(iota < cnt, sk == base + iota, True)
    )
    with jax.named_scope("probe"):
        slot = pd.astype(jnp.int64) - base
        inr = (slot >= 0) & (slot < cnt.astype(jnp.int64))
        sloti = jnp.clip(slot, 0, max(nb - 1, 0)).astype(jnp.int32)
        if carrier is None:
            word = None
            matched = inr & preal & jnp.take(bfull, sloti, mode="clip")
        else:
            word = jnp.take(benv[carrier][0], sloti, mode="clip")
            matched = inr & preal & (word != SENT)
    flag = ~dense if clash is None else (~dense | clash)
    return matched, sloti, flag, benv, word


def _lookup_sortmerge(pk, pmask, bk, bmask, check_dup: bool, extra=(),
                      narrow: bool = False):
    """Equi-join primitive by double sort — the TPU formulation.

    ``searchsorted`` (a vectorized binary search) costs ~30s per 60M
    probes on a v5e (24 serial gather rounds); XLA's TPU sort streams at
    near memory bandwidth. So: co-sort [build keys*2, probe keys*2+1]
    (build rows lead their equal-key runs), mark probe rows whose run
    holds a real build row, then a second sort by original probe
    position restores row order. Same contract as ``_lookup``:
    (matched [np] bool, bidx [np] int, dup 0-d bool).

    No gather: XLA's TPU gather costs 7.25 ns an element whatever the
    order of its indices (my chip run, PR 32: 8.4M elements from tables
    of 2^16..2^23.3 rows; 13-20 ns from 2^26), so fetching each slot's
    build row by position, ``take(sokey, pbpos)``, was 91 of this
    function's 174 ms at 2^21 x 2^23 rows. The one ``cummax`` that finds
    the last real build row carries that row's run (high word) and its
    original position (low word) instead.

    ``extra``: the further key pairs of a join with several, as
    [((probe data, valid), (build data, valid))]: each is one more sort
    key after the first, so a run is a run of rows equal on EVERY pair,
    a NULL in any key keeps its row out, and ``dup`` means two real
    build rows equal on the whole tuple (a build side that repeats its
    first key alone is still looked up exactly).

    ``narrow`` (one pair; the caller's proof that every real row's key
    on both sides lies in ``[-I32, I32]``): the key is sorted as ONE
    32-bit word and the side byte goes. A dead or NULL row takes
    ``KEY_DEAD`` (-2^31, which no real key holds), and real build rows
    lead their run because their positions (``okey``) come first. The
    TPU moves an ``int64`` as two 32-bit halves, so the wide co-sort
    carries four operands: 509.7 ms at 83.9M rows, where the restore's
    two 32-bit operands take 254.7 (the ledger's ``breakdown`` of the
    Q5, Q10 and Q18 cells). ``prefix``, ``restore`` and what is
    returned are the wide form's."""
    pd, pv = pk
    bd, bv = bk
    nb = bd.shape[0]
    npr = pd.shape[0]
    breal = bmask if bv is None else (bmask & bv)
    preal = pmask if pv is None else (pmask & pv)
    for (_xpd, xpv), (_xbd, xbv) in extra:
        if xbv is not None:
            breal = breal & xbv
        if xpv is not None:
            preal = preal & xpv
    narrow = narrow and not extra
    if narrow:
        key = jnp.concatenate([
            jnp.where(breal, bd.astype(jnp.int32), KEY_DEAD),
            jnp.where(preal, pd.astype(jnp.int32), KEY_DEAD),
        ])
    else:
        # two sort keys — the raw key keeps its FULL int64 range (no *2
        # encode), the side byte orders real-build < real-probe < dead
        # within each key run
        key = jnp.concatenate([
            bd.astype(jnp.int64), pd.astype(jnp.int64)
        ])
        side = jnp.concatenate([
            jnp.where(breal, jnp.int8(0), jnp.int8(2)),
            jnp.where(preal, jnp.int8(1), jnp.int8(2)),
        ])
    okey = jnp.concatenate([
        jnp.arange(nb, dtype=jnp.int32),
        # probe original positions offset past nb so the restore sort
        # can address both sides with one operand
        jnp.arange(nb, nb + npr, dtype=jnp.int32),
    ])
    xkeys = tuple(
        jnp.concatenate([xbd.astype(jnp.int64), xpd.astype(jnp.int64)])
        for (xpd, _xpv), (xbd, _xbv) in extra
    )
    with jax.named_scope("sort"):
        if narrow:
            skey, sokey = jax.lax.sort(
                (key, okey), num_keys=2, is_stable=False
            )
            sxkeys = ()
        else:
            skey, *sxkeys, sside, sokey = jax.lax.sort(
                (key,) + xkeys + (side, okey), num_keys=2 + len(xkeys),
                is_stable=False,
            )
    M = nb + npr
    first = jnp.ones(1, jnp.bool_)
    differs = skey[1:] != skey[:-1]
    for sx in sxkeys:
        differs = differs | (sx[1:] != sx[:-1])
    boundary = jnp.concatenate([first, differs])
    if narrow:
        live = skey != KEY_DEAD
        isb = (sokey < nb) & live
        isp = (sokey >= nb) & live
    else:
        isb = sside == 0
    if check_dup and M > 1:
        dup = jnp.any(isb[1:] & isb[:-1] & ~boundary[1:])
    else:
        dup = jnp.asarray(False)
    with jax.named_scope("prefix"):
        runid = jnp.cumsum(boundary.astype(jnp.int32))
        # runs are numbered upward, so the running max over the real
        # build rows' (run << 32 | original position) is the LAST such
        # row at or before each slot; -1 until the first
        last = jax.lax.cummax(jnp.where(
            isb,
            (runid.astype(jnp.int64) << 32) | sokey.astype(jnp.int64),
            jnp.int64(-1),
        ))
        matched_s = ((last >> 32).astype(jnp.int32) == runid) & (
            isp if narrow else sside == 1
        )
        # the restore sort carries one operand: the build row's
        # position, or -1 for 'no match'
        bidx_s = jnp.where(matched_s, last.astype(jnp.int32), jnp.int32(-1))
    # restore probe-row order: probe original positions are unique keys;
    # dead probe rows restore too (they must land back in place)
    rkey = jnp.where(sokey >= nb, sokey - nb, jnp.int32(2**31 - 1))
    with jax.named_scope("restore"):
        _rk, b_p = jax.lax.sort(
            (rkey, bidx_s), num_keys=1, is_stable=False
        )
    matched = (b_p[:npr] >= 0) & pmask
    bidx = jnp.clip(b_p[:npr], 0, max(nb - 1, 0))
    return matched, bidx, dup


def _lookup(pk, pmask, bk, bmask, check_dup: bool, extra=(),
            narrow: bool = False):
    """Sorted-lookup equi-join primitive. Probe keys pk=(data, valid)
    [np] against build keys bk [nb]; returns (matched [np] bool,
    bidx [np] int, dup 0-d bool).

    A join with several key pairs (``extra``) takes the double sort on
    every backend: a binary search has one key. ``narrow`` is the
    double sort's (a co-sort of 32-bit keys, ``_lookup_sortmerge``); the
    binary search reads its keys as ``int64`` either way.

    Dead/NULL build rows participate in the sort but are flagged
    not-real; the composite stable sort (reals first within equal keys)
    guarantees ``searchsorted(..., 'left')`` lands on a real row whenever
    one exists, so no sentinel values are needed and no collision can
    produce a false or missed match. ``dup`` is exact: adjacent equal
    keys where both rows are real."""
    if extra:
        return _lookup_sortmerge(pk, pmask, bk, bmask, check_dup, extra)
    pd, pv = pk
    bd, bv = bk
    nb = bd.shape[0]
    breal = bmask if bv is None else (bmask & bv)
    bkey = bd.astype(jnp.int64)
    order = jnp.argsort(~breal, stable=True)  # reals first
    order = jnp.take(order, jnp.argsort(
        jnp.take(bkey, order), stable=True
    ))
    bs = jnp.take(bkey, order)
    sreal = jnp.take(breal, order)
    if check_dup and nb > 1:
        dup = jnp.any((bs[1:] == bs[:-1]) & sreal[1:] & sreal[:-1])
    else:
        dup = jnp.asarray(False)
    pkey = pd.astype(jnp.int64)
    pos = jnp.searchsorted(bs, pkey, side="left")
    posc = jnp.clip(pos, 0, nb - 1)
    matched = (jnp.take(bs, posc) == pkey) & jnp.take(sreal, posc)
    if pv is not None:
        matched = matched & pv
    matched = matched & pmask
    bidx = jnp.take(order, posc)
    return matched, bidx, dup
