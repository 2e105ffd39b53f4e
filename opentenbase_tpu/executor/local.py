"""Per-datanode plan evaluation: the DN executor.

The reference DN runs the Volcano interpreter over heap tuples
(src/backend/executor/execMain.c, execProcnode.c). Here a "datanode" is a
LocalExecutor bound to one shard of every table: plans evaluate bottom-up
over whole padded columns on device, with a boolean visibility mask in
place of tuple-at-a-time qual checks. Operators that need dense input
(sort gathers, join encodes) consume the mask via the kernels in ops/.

Batches are static-shape: every intermediate is padded to a power-of-two
bucket so XLA compilations are reused across runs (the plan-cache analog
of src/backend/utils/cache/plancache.c is the jit cache keyed on shapes).

MVCC: scans receive a snapshot timestamp and start from the vectorized
visibility predicate xmin_ts <= snap < xmax_ts — the device-side analog of
HeapTupleSatisfiesMVCC (src/backend/utils/time/tqual.c:2274).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import numpy as np

import opentenbase_tpu.ops  # noqa: F401  (enables x64)
import jax.numpy as jnp

from opentenbase_tpu import types as t
from opentenbase_tpu.catalog.catalog import Catalog
from opentenbase_tpu.ops import agg as agg_ops
from opentenbase_tpu.ops import filter as filt_ops
from opentenbase_tpu.ops import join as join_ops
from opentenbase_tpu.ops import sort as sort_ops
from opentenbase_tpu.ops.expr import (
    LITERAL_DICT,
    DictTranslateParam,
    ExprCompiler,
    resolve_param,
)
from opentenbase_tpu.plan import logical as L
from opentenbase_tpu.plan import texpr as E
from opentenbase_tpu.storage.column import Column, Dictionary
from opentenbase_tpu.storage.table import INF_TS, ColumnBatch, ShardStore


@dataclass
class DevBatch:
    """A device-resident batch: padded columns + visibility mask."""

    schema: tuple[L.OutCol, ...]
    cols: list  # list[(data, valid_or_None)]
    mask: Optional[object]  # bool array or None (= all live)
    n: int  # padded row count (static)

    def live_count(self) -> int:
        if self.mask is None:
            return self.n
        return int(filt_ops.mask_count(self.mask))


class ExecError(RuntimeError):
    pass


class LocalExecutor:
    """Executes logical plans against one shard of every table."""

    def __init__(
        self,
        catalog: Catalog,
        stores: dict[str, ShardStore],
        snapshot_ts: Optional[int] = None,
        remote_inputs: Optional[dict[int, ColumnBatch]] = None,
        subquery_values: Optional[list] = None,
        own_writes: Optional[dict] = None,
        instrument: bool = False,
        cancel_check=None,
        fold_on_read: bool = False,
    ):
        self.catalog = catalog
        self.stores = stores
        self.snapshot_ts = snapshot_ts
        # fragment index -> motioned input batch (distributed execution;
        # the squeue consumer side of the reference)
        self.remote_inputs = remote_inputs or {}
        if subquery_values is not None:
            self._subquery_values = subquery_values
        # table -> (ins_ranges, del_idx): the executing transaction's own
        # uncommitted writes, made visible/invisible on top of the snapshot
        # (the reference's "xmin is my own xid" branch of
        # HeapTupleSatisfiesMVCC, tqual.c)
        self.own_writes = own_writes or {}
        # within-fragment parallel worker: restrict the (single) base
        # scan to this physical row block — the parallel seq scan
        # chunking of execParallel.c:565 (each worker scans a disjoint
        # block; a Gather-analog merge combines partials)
        self.scan_block: Optional[tuple[int, int]] = None
        # per-operator instrumentation (EXPLAIN ANALYZE, the
        # InstrStartNode/InstrStopNode pair of instrument.c): pre-order
        # records {depth, op, detail, ms, rows, batch_rows} filled by
        # eval(); None = off, the untraced hot path
        self.op_records: Optional[list[dict]] = [] if instrument else None
        self._op_depth = 0
        # DN-side cancel (dn/server.py cancel_fragment): a callable that
        # raises when the coordinator abandoned this fragment, polled at
        # every operator boundary. None (the overwhelmingly common case)
        # costs one attribute test per operator.
        self._cancel_check = cancel_check
        # enable_delta_scan = off (the escape hatch): scans fold pending
        # deltas before reading, restoring the pre-delta-plane read
        # path on the same binary
        self._fold_on_read = fold_on_read
        # delta-resident rows the last _eval_scan served (EXPLAIN
        # ANALYZE evidence that the scan read the delta plane directly)
        self.last_scan_delta_rows = 0

    # -- dictionary access ----------------------------------------------
    def _dict(self, dict_id: str) -> Dictionary:
        return self.catalog.dictionary(dict_id)

    def _dicts_view(self):
        class _View:
            def __init__(v, ex):
                v.ex = ex

            def __getitem__(v, key):
                return v.ex._dict(key)

        return _View(self)

    # -- expression binding ---------------------------------------------
    def _bind(self, exprs, schema, subquery_values=None, want_dids=None):
        comp = ExprCompiler()
        dids = [c.dict_id for c in schema]
        fns = []
        for i, e in enumerate(exprs):
            want = None
            if want_dids is not None and e.type.is_text:
                want = want_dids[i] or LITERAL_DICT
            fns.append(comp.compile(e, dids, want))
        params = tuple(
            resolve_param(s, self._dicts_view(), subquery_values)
            for s in comp.params
        )
        return fns, params

    # -- statement entry -------------------------------------------------
    def execute(self, splan: L.StatementPlan) -> ColumnBatch:
        self._subquery_values = self._run_subplans(splan.subplans)
        batch = self.eval(splan.root)
        return self.to_host(batch)

    def _run_subplans(self, subplans):
        vals = []
        for sp in subplans:
            b = self.to_host(self.eval(sp))
            if b.nrows > 1:
                raise ExecError("more than one row returned by a subquery used as an expression")
            col0 = next(iter(b.columns.values())) if b.columns else None
            if b.nrows == 0 or col0 is None:
                vals.append((None, sp.schema[0].type))
            else:
                v = col0.data[0] if col0.valid_mask[0] else None
                vals.append((v, sp.schema[0].type))
        return vals

    # -- host materialization --------------------------------------------
    def to_host(self, b: DevBatch) -> ColumnBatch:
        if b.mask is None:
            keep = np.ones(b.n, dtype=np.bool_)
        else:
            keep = np.asarray(b.mask)
        cols: dict[str, Column] = {}
        used: dict[str, int] = {}
        for oc, (data, valid) in zip(b.schema, b.cols):
            name = oc.name
            if name in cols:
                used[name] = used.get(name, 0) + 1
                name = f"{name}_{used[oc.name]}"
            d = np.asarray(data)[keep]
            v = None if valid is None else np.asarray(valid)[keep]
            ty = oc.type
            if ty.id == t.TypeId.FLOAT8 and d.dtype != np.float64:
                d = d.astype(np.float64)
            if oc.dict_id:
                dic = self._dict(oc.dict_id)
            elif ty.id == t.TypeId.TEXT:
                dic = self.catalog.literals
            else:
                dic = None
            cols[name] = Column(ty, d.astype(ty.np_dtype), v, dic)
        n = int(keep.sum())
        return ColumnBatch(cols, n)

    def run_plan(self, root: L.LogicalPlan) -> ColumnBatch:
        """Evaluate one plan tree (no subplan handling) to a host batch."""
        return self.to_host(self.eval(root))

    # -- plan dispatch ----------------------------------------------------
    def eval(self, plan: L.LogicalPlan) -> DevBatch:
        if self._cancel_check is not None:
            # coordinator-abandoned fragment: stop at the next operator
            # boundary instead of running the plan to completion
            self._cancel_check()
        m = getattr(self, f"_eval_{type(plan).__name__.lower()}", None)
        if m is None:
            raise ExecError(f"no executor for {type(plan).__name__}")
        recs = self.op_records
        if recs is None:
            return m(plan)
        # instrumented (EXPLAIN ANALYZE) path: record pre-order so the
        # list reads as the plan tree; times are INCLUSIVE of children
        # (instrument.c's actual-total convention). live_count() is a
        # device reduce — a cost only ANALYZE pays.
        import time as _time

        rec = {
            "depth": self._op_depth,
            "op": type(plan).__name__,
            "detail": _op_detail(plan),
        }
        recs.append(rec)
        self._op_depth += 1
        t0 = _time.perf_counter()
        try:
            out = m(plan)
        finally:
            self._op_depth -= 1
        rec["ms"] = (_time.perf_counter() - t0) * 1000.0
        rec["rows"] = int(out.live_count())
        rec["batch_rows"] = int(out.n)
        if rec["op"] == "Join":
            # which formulation answered (radix hash vs encode+sort) —
            # a mode-selection regression must show in EXPLAIN ANALYZE,
            # not only in a bench post-mortem
            jm = getattr(self, "last_join_mode", None)
            if jm:
                rec["detail"] = f"{rec.get('detail') or ''} ({jm})".strip()
        elif rec["op"] == "Scan" and self.last_scan_delta_rows:
            # how much of the scan answered from the delta plane
            # without a fold — the read-after-write evidence
            # tests/test_delta_scan.py asserts on
            rec["detail"] = (
                f"{rec.get('detail') or ''} (delta-resident: "
                f"{self.last_scan_delta_rows} rows)"
            ).strip()
        return out

    def _eval_remotesource(self, plan) -> DevBatch:
        batch = self.remote_inputs.get(plan.fragment)
        if batch is None:
            raise ExecError(f"no input for fragment {plan.fragment}")
        return self._batch_to_dev(batch, plan.schema)

    def _batch_to_dev(self, batch: ColumnBatch, schema) -> DevBatch:
        nrows = batch.nrows
        padded = filt_ops.bucket_size(max(nrows, 1))
        cols = []
        for col in batch.columns.values():
            d = _pad_to(np.asarray(col.data), padded)
            v = (
                None
                if col.validity is None
                else _pad_to(col.validity, padded, fill=False)
            )
            cols.append((jnp.asarray(d), None if v is None else jnp.asarray(v)))
        live = np.zeros(padded, dtype=np.bool_)
        live[:nrows] = True
        return DevBatch(tuple(schema), cols, jnp.asarray(live), padded)

    # -- leaves -----------------------------------------------------------
    def _eval_scan(self, plan: L.Scan, row_idx=None) -> DevBatch:
        """``row_idx``: optional physical row subset (zone-map pruning).
        Callers passing it must have ruled out own-write overlays, whose
        references are positional over the full store."""
        store = self._foreign_store(plan.table)
        if store is None:
            store = self.stores.get(plan.table)
        if store is None:
            raise ExecError(f"no shard for table {plan.table} on this node")
        # ONE coherent capture (scan_view): a concurrent append
        # advances store.nrows AFTER the new rows are fully written, so
        # the captured view is a consistent fully-written prefix across
        # every column AND the MVCC planes. The view assembles base +
        # pending delta segments straight into the padded batch — the
        # same one copy the batch build always paid, with NO fold:
        # reads never mutate storage (the scannable delta plane).
        view = store.scan_view(fold=self._fold_on_read)
        n0 = view.nrows
        blk = self.scan_block
        if blk is not None:
            assert row_idx is None and not self.own_writes
            s0, e0 = max(0, blk[0]), min(blk[1], n0)
            e0 = max(e0, s0)
        else:
            s0, e0 = 0, n0
        nrows = (e0 - s0) if row_idx is None else len(row_idx)
        padded = filt_ops.bucket_size(max(nrows, 1))

        cols = []
        for name, oc in zip(plan.columns, plan.schema):
            if row_idx is None:
                d = view.col(name, s0, e0, pad=padded)
                v = view.validity(name, s0, e0, pad=padded)
            else:
                # zone-pruned subset: positional gathers, O(rows
                # taken) — never materialize the whole column while a
                # burst is delta-resident
                d = _pad_to(view.col_at(name, row_idx), padded)
                vm = view.validity_at(name, row_idx)
                v = (
                    None if vm is None
                    else _pad_to(vm, padded, fill=False)
                )
            cols.append(
                (jnp.asarray(d), None if v is None else jnp.asarray(v))
            )
        live = np.zeros(padded, dtype=np.bool_)
        live[:nrows] = True
        if self.snapshot_ts is not None:
            snap = np.int64(self.snapshot_ts)
            if row_idx is None:
                xm, xx = view.xmin(s0, e0), view.xmax(s0, e0)
            else:
                xm = view.xmin_at(row_idx)
                xx = view.xmax_at(row_idx)
            live[:nrows] &= (xm <= snap) & (snap < xx)
        self.last_scan_delta_rows = (
            view.delta_rows(s0, e0) if row_idx is None
            else int((np.asarray(row_idx) >= view.base_rows).sum())
        )
        # fold-avoided evidence covers the rows THIS scan served — a
        # block worker its block, a pruned scan its subset
        store.note_delta_read(self.last_scan_delta_rows)
        own = self.own_writes.get(plan.table)
        if own is not None:
            assert row_idx is None, "own-writes are positional"
            ins_ranges, del_idx = own
            for s, e in ins_ranges:
                live[s:min(e, nrows)] = True
            if len(del_idx):
                live[np.asarray(del_idx)] = False
        mask = jnp.asarray(live)
        return DevBatch(plan.schema, cols, mask, padded)

    def _eval_valuesscan(self, plan: L.ValuesScan) -> DevBatch:
        nrows = len(plan.rows)
        padded = filt_ops.bucket_size(max(nrows, 1))
        ncols = len(plan.schema)
        cols = []
        for ci in range(ncols):
            oc = plan.schema[ci]
            data = np.zeros(padded, dtype=oc.type.np_dtype)
            valid = np.zeros(padded, dtype=np.bool_)
            for ri, row in enumerate(plan.rows):
                e = row[ci]
                if isinstance(e, E.SubqueryParam):
                    # an InitPlan's scalar result may sit in a VALUES
                    # row: resolve it like any subquery parameter
                    v, _ty = self._subq()[e.index]
                    if v is None:
                        continue
                elif not isinstance(e, E.Const):
                    # VALUES exprs are closed (the analyzer binds them
                    # in an empty scope, so no Col refs): evaluate
                    # through the ordinary compiler over a no-column
                    # row, landing text straight in the target dict
                    want = [oc.dict_id] if oc.type.is_text else None
                    fns, params = self._bind(
                        [e], (), self._subq(), want_dids=want
                    )
                    dv, vv = fns[0]([], params)
                    if vv is not None and not bool(
                        np.asarray(vv).reshape(-1)[0]
                    ):
                        continue
                    data[ri] = np.asarray(dv).reshape(-1)[0]
                    valid[ri] = True
                    continue
                elif e.value is None:
                    continue
                else:
                    v = e.value
                if oc.type.is_text:
                    d = self._dict(oc.dict_id or LITERAL_DICT)
                    v = d.encode_one(str(v))
                data[ri] = v
                valid[ri] = True
            all_valid = bool(valid[:nrows].all()) and nrows > 0
            cols.append(
                (jnp.asarray(data), None if all_valid else jnp.asarray(valid))
            )
        live = np.zeros(padded, dtype=np.bool_)
        live[:nrows] = True
        return DevBatch(plan.schema, cols, jnp.asarray(live), padded)

    # -- filter / project --------------------------------------------------
    def _eval_filter(self, plan: L.Filter) -> DevBatch:
        child = None
        if isinstance(plan.child, L.Scan):
            child = self._eval_scan_pruned(plan.child, plan.predicate)
        if child is None:
            child = self.eval(plan.child)
        fns, params = self._bind(
            [plan.predicate], plan.child.schema, self._subq()
        )
        d, v = fns[0](child.cols, params)
        keep = d if v is None else (d & v)
        keep = jnp.broadcast_to(keep, (child.n,))
        mask = keep if child.mask is None else (child.mask & keep)
        return DevBatch(plan.schema, child.cols, mask, child.n)

    def _foreign_store(self, table: str):
        """Foreign tables materialize at scan time (fdw.py)."""
        try:
            meta = self.catalog.get(table)
        except Exception:
            return None
        if getattr(meta, "foreign", None) is None:
            return None
        from opentenbase_tpu.fdw import foreign_store

        return foreign_store(meta)

    # -- zone-map block pruning (BRIN-style, CREATE INDEX builds maps) --
    def _eval_scan_pruned(
        self, plan: L.Scan, pred
    ) -> Optional[DevBatch]:
        """Scan only the blocks whose zone-map [min, max] intersects the
        predicate's per-column bounds. Returns None when pruning does
        not apply (no indexed columns bound, no blocks skipped, pending
        own-writes with positional references)."""
        store = self.stores.get(plan.table)
        if store is None or store.nrows == 0:
            return None
        if self.scan_block is not None:
            return None  # block workers scan plain contiguous ranges
        if plan.table in self.own_writes:
            return None  # ins_ranges/del_idx are positional
        try:
            meta = self.catalog.get(plan.table)
        except Exception:
            return None
        if not meta.zone_cols:
            return None
        from opentenbase_tpu.storage.table import (
            zone_candidate_blocks,
            zone_usable_bounds,
        )

        bounds = _predicate_bounds(pred, plan)
        usable = zone_usable_bounds(bounds, meta, plan)
        if not usable:
            return None
        b = store.ZONE_BLOCK
        nblocks = -(-store.nrows // b)
        sel = zone_candidate_blocks(store, usable)
        self.zone_total_blocks = getattr(self, "zone_total_blocks", 0) + nblocks
        nsel = int(sel.sum())
        if nsel == nblocks:
            return None  # nothing pruned: the plain scan path is simpler
        self.zone_pruned_blocks = (
            getattr(self, "zone_pruned_blocks", 0) + (nblocks - nsel)
        )
        starts = np.nonzero(sel)[0] * b
        idx = np.concatenate([
            np.arange(s, min(s + b, store.nrows)) for s in starts
        ]) if nsel else np.empty(0, dtype=np.int64)
        return self._eval_scan(plan, row_idx=idx)

    def _eval_project(self, plan: L.Project) -> DevBatch:
        child = self.eval(plan.child)
        fns, params = self._bind(
            plan.exprs,
            plan.child.schema,
            self._subq(),
            want_dids=[c.dict_id for c in plan.schema],
        )
        cols = []
        for fn in fns:
            d, v = fn(child.cols, params)
            d = jnp.broadcast_to(d, (child.n,) + jnp.shape(d)[1:]) if jnp.ndim(d) == 0 else d
            if v is not None and jnp.ndim(v) == 0:
                v = jnp.broadcast_to(v, (child.n,))
            cols.append((d, v))
        return DevBatch(plan.schema, cols, child.mask, child.n)

    def _subq(self):
        return getattr(self, "_subquery_values", None)

    # -- aggregate ---------------------------------------------------------
    def _eval_aggregate(self, plan: L.Aggregate) -> DevBatch:
        child = self.eval(plan.child)
        gfns, gparams = self._bind(
            plan.group_exprs, plan.child.schema, self._subq()
        )
        keys = [fn(child.cols, gparams) for fn in gfns]
        keys = [self._broadcast(kv, child.n) for kv in keys]

        specs, vals = self._agg_inputs(plan.aggs, child)

        if not plan.group_exprs:
            distinct = [a for a in plan.aggs if a.distinct]
            if distinct:
                return self._eval_distinct_agg(plan, child, keys, specs, vals)
            mask = (
                child.mask
                if child.mask is not None
                else jnp.ones(child.n, jnp.bool_)
            )
            outs = agg_ops.scalar_reduce(vals, mask, tuple(specs))
            cols = self._finalize_aggs(plan.aggs, specs, outs, scalar=True)
            return DevBatch(plan.schema, _as_rows(cols), None, 1)

        if any(a.distinct for a in plan.aggs):
            return self._eval_distinct_agg(plan, child, keys, specs, vals)

        perm, seg, ngroups = agg_ops.group_ids(keys, child.mask)
        ng = max(int(ngroups), 1)
        cap = filt_ops.bucket_size(ng)
        out_keys, out_vals, gvalid = agg_ops.group_reduce(
            keys, vals, perm, seg, cap, tuple(specs)
        )
        agg_cols = self._finalize_aggs(plan.aggs, specs, out_vals, scalar=False)
        cols = list(out_keys) + agg_cols
        return DevBatch(plan.schema, cols, gvalid, cap)

    def _broadcast(self, kv, n):
        d, v = kv
        if jnp.ndim(d) == 0:
            d = jnp.broadcast_to(d, (n,))
        if v is not None and jnp.ndim(v) == 0:
            v = jnp.broadcast_to(v, (n,))
        return (d, v)

    def _agg_inputs(self, aggs, child: DevBatch):
        """Lower AggCalls to kernel specs + input value columns. avg(x)
        becomes sum+count (merged in _finalize_aggs) — the same transition
        split the reference's 2-phase aggregation uses. min/max over
        TEXT aggregate over dictionary RANKS (codes are insertion-
        ordered, not collation-ordered — the same mapping ORDER BY
        uses) and _finalize_aggs maps the winning rank back to a code."""
        specs: list[str] = []
        vals: list = []
        self._agg_rank_inv: list = []  # per-spec rank->code map or None
        afns = []
        comp = ExprCompiler()
        dids = [c.dict_id for c in child.schema]
        for a in aggs:
            afns.append(
                comp.compile(a.arg, dids) if a.arg is not None else None
            )
        params = tuple(
            resolve_param(s, self._dicts_view(), self._subq())
            for s in comp.params
        )
        for a, fn in zip(aggs, afns):
            if a.func == "count" and a.arg is None:
                specs.append("count_star")
                vals.append(None)
                self._agg_rank_inv.append(None)
                continue
            d, v = fn(child.cols, params)
            d, v = self._broadcast((d, v), child.n)
            if a.func == "avg":
                specs.append("sum")
                vals.append((d, v))
                specs.append("count")
                vals.append((d, v))
                self._agg_rank_inv.extend([None, None])
            elif a.func in ("sum", "count", "min", "max"):
                inv = None
                if a.func in ("min", "max") and a.arg.type.is_text:
                    did = _texpr_did(a.arg, child.schema) or LITERAL_DICT
                    ranks, inv = self._dict_ranks(did, with_order=True)
                    d = ranks[jnp.clip(d, 0, ranks.shape[0] - 1)]
                specs.append(a.func)
                vals.append((d, v))
                self._agg_rank_inv.append(inv)
            else:
                raise ExecError(f"aggregate {a.func} not supported")
        return specs, vals

    def _finalize_aggs(self, aggs, specs, outs, scalar: bool):
        """Map kernel outputs back to one column per AggCall (avg = sum/count)."""
        cols = []
        i = 0
        for a in aggs:
            if a.func == "avg":
                s_d, s_v = outs[i]
                c_d, _ = outs[i + 1]
                i += 2
                denom = jnp.maximum(c_d, 1)
                arg_t = a.arg.type
                if arg_t.id == t.TypeId.DECIMAL:
                    num = s_d / arg_t.decimal_factor
                else:
                    num = s_d
                d = num / denom
                v = s_v if s_v is not None else None
                cols.append((d, v))
            else:
                d, v = outs[i]
                inv = getattr(self, "_agg_rank_inv", None)
                if inv is not None and inv[i] is not None:
                    # min/max over TEXT reduced in rank space: map the
                    # winning rank back to its dictionary code
                    d = inv[i][jnp.clip(d, 0, inv[i].shape[0] - 1)]
                i += 1
                if a.func == "sum" and a.type.id == t.TypeId.INT8:
                    d = d.astype(jnp.int64)
                cols.append((d, v))
        return cols

    def _eval_distinct_agg(self, plan, child, keys, specs, vals):
        """DISTINCT aggregates via two-level grouping: first dedup on
        (group keys, arg), then aggregate the deduped level. Mixing
        DISTINCT and plain aggs over different args is not yet supported."""
        dargs = {a.arg.key() for a in plan.aggs if a.distinct}
        if len(dargs) > 1:
            raise ExecError("multiple DISTINCT aggregate arguments")
        plain = [a for a in plan.aggs if not a.distinct and a.func != "count"]
        if plain and {a.arg.key() for a in plain if a.arg} - dargs:
            raise ExecError("mix of DISTINCT and non-DISTINCT aggregates")
        # level 1: dedup (keys + arg)
        arg_val = None
        for s, vv in zip(specs, vals):
            if vv is not None:
                arg_val = vv
                break
        lvl1_keys = keys + [arg_val]
        perm, seg, ngroups = agg_ops.group_ids(lvl1_keys, child.mask)
        cap1 = filt_ops.bucket_size(max(int(ngroups), 1))
        out_keys, out_vals, gvalid = agg_ops.group_reduce(
            lvl1_keys, [arg_val], perm, seg, cap1, ("any",)
        )
        ded_keys = out_keys[:-1]
        ded_arg = out_vals[0]
        # level 2: aggregate over deduped rows
        specs2 = []
        vals2 = []
        for a in plan.aggs:
            if a.func == "count" and a.arg is None:
                specs2.append("count_star")
                vals2.append(None)
            else:
                specs2.append(a.func if a.func != "avg" else "sum")
                vals2.append(ded_arg)
                if a.func == "avg":
                    specs2.append("count")
                    vals2.append(ded_arg)
        if not plan.group_exprs:
            gv = gvalid if gvalid is not None else jnp.ones(cap1, jnp.bool_)
            outs = agg_ops.scalar_reduce(vals2, gv, tuple(specs2))
            cols = self._finalize_aggs(plan.aggs, specs2, outs, scalar=True)
            return DevBatch(plan.schema, _as_rows(cols), None, 1)
        perm2, seg2, ng2 = agg_ops.group_ids(ded_keys, gvalid)
        cap2 = filt_ops.bucket_size(max(int(ng2), 1))
        out_keys2, out_vals2, gvalid2 = agg_ops.group_reduce(
            ded_keys, vals2, perm2, seg2, cap2, tuple(specs2)
        )
        agg_cols = self._finalize_aggs(plan.aggs, specs2, out_vals2, scalar=False)
        cols = list(out_keys2) + agg_cols
        return DevBatch(plan.schema, cols, gvalid2, cap2)

    # -- distinct ----------------------------------------------------------
    def _eval_distinct(self, plan: L.Distinct) -> DevBatch:
        child = self.eval(plan.child)
        keys = [self._broadcast(c, child.n) for c in child.cols]
        perm, seg, ngroups = agg_ops.group_ids(keys, child.mask)
        cap = filt_ops.bucket_size(max(int(ngroups), 1))
        out_keys, _, gvalid = agg_ops.group_reduce(
            keys, [], perm, seg, cap, ()
        )
        return DevBatch(plan.schema, list(out_keys), gvalid, cap)

    # -- sort / limit ------------------------------------------------------
    def _sort_key_arrays(self, plan_keys, schema, cols, n):
        fns, params = self._bind(
            [k.expr for k in plan_keys], schema, self._subq()
        )
        keys = []
        for k, fn in zip(plan_keys, fns):
            d, v = self._broadcast(fn(cols, params), n)
            if k.expr.type.is_text:
                did = _texpr_did(k.expr, schema)
                if did is None:
                    raise ExecError("ORDER BY on TEXT without dictionary")
                ranks = self._dict_ranks(did)
                d = ranks[jnp.clip(d, 0, ranks.shape[0] - 1)]
            keys.append((d, v, k.descending, k.nulls_first))
        return keys

    def _dict_ranks(self, dict_id: str, with_order: bool = False):
        """code->collation-rank map (padded); with_order also returns
        the INVERSE (rank->code) from the same single argsort —
        callers needing both must not sort the dictionary twice."""
        dic = self._dict(dict_id)
        vals = dic.values
        order = np.argsort(np.asarray(vals, dtype=object)).astype(
            np.int32
        )
        ranks = np.empty(max(len(vals), 1), dtype=np.int32)
        ranks[order if len(vals) else slice(0, 0)] = np.arange(
            len(vals), dtype=np.int32
        )
        padded = filt_ops.bucket_size(max(len(vals), 1))
        out = np.zeros(padded, dtype=np.int32)
        out[: len(vals)] = ranks[: len(vals)]
        if not with_order:
            return jnp.asarray(out)
        inv = np.zeros(padded, dtype=np.int32)
        inv[: len(order)] = order
        return jnp.asarray(out), jnp.asarray(inv)

    def _eval_sort(self, plan: L.Sort) -> DevBatch:
        child = self.eval(plan.child)
        keys = self._sort_key_arrays(
            plan.keys, plan.child.schema, child.cols, child.n
        )
        perm = sort_ops.order_indices(keys, child.mask)
        cols = filt_ops.gather_cols(
            child.cols, perm, jnp.ones(child.n, jnp.bool_)
        )
        cols = [
            (d, None if v is None else v)
            for (d, v) in cols
        ]
        mask = (
            None
            if child.mask is None
            else jnp.take(child.mask, perm, axis=0)
        )
        return DevBatch(plan.schema, cols, mask, child.n)

    def _eval_window(self, plan: L.Window) -> DevBatch:
        """nodeWindowAgg: host-vectorized (numpy lexsort + segmented
        scans) over the padded batch — window shapes are inherently
        data-dependent, so this stays on the coordinator/DN host; results
        are written back in the original row order."""
        child = self.eval(plan.child)
        n = child.n
        mask = (
            np.ones(n, dtype=bool)
            if child.mask is None
            else np.asarray(child.mask)
        )
        live = np.nonzero(mask)[0]
        host_cols = [
            (np.asarray(d), None if v is None else np.asarray(v))
            for d, v in child.cols
        ]
        out_cols = list(child.cols)
        for spec in plan.specs:
            data, valid = self._window_one(
                spec, host_cols, live, n, plan.child.schema
            )
            out_cols.append((jnp.asarray(data), jnp.asarray(valid)))
        return DevBatch(plan.schema, out_cols, child.mask, n)

    def _window_key(self, col: int, schema, host_cols, rows):
        """(comparable values, isnull) for a key column over ``rows`` —
        TEXT keys compare by sorted-dictionary rank, exactly as
        _sort_key_arrays does for ORDER BY."""
        d, v = host_cols[col]
        vals = d[rows]
        isnull = (
            np.zeros(len(rows), dtype=bool) if v is None else ~v[rows]
        )
        oc = schema[col]
        if oc.type.is_text and oc.dict_id is not None:
            ranks = np.asarray(self._dict_ranks(oc.dict_id))
            vals = ranks[np.clip(vals, 0, len(ranks) - 1)]
        return vals, isnull

    def _window_one(self, spec: L.WinSpec, host_cols, live, n, schema):
        """Compute one window column over the live rows."""
        m = len(live)
        oty = spec.out.type
        out = np.zeros(n, dtype=oty.np_dtype)
        outv = np.zeros(n, dtype=bool)
        if m == 0:
            return out, outv
        # sort live rows by (partition, order keys); numpy lexsort is
        # stable, takes keys least-significant first, and NULL keys sort
        # via an explicit flag (PG: NULLS LAST asc / FIRST desc), never by
        # their padded storage value
        lex: list[np.ndarray] = []
        for col, desc in reversed(spec.order):
            k, isnull = self._window_key(col, schema, host_cols, live)
            if desc:
                k = -k.astype(np.int64) if k.dtype.kind in "iu" else -k.astype(np.float64)
                flag = ~isnull  # NULLS FIRST
            else:
                flag = isnull  # NULLS LAST
            lex.append(k)
            lex.append(flag)
        for col in reversed(spec.partition):
            k, isnull = self._window_key(col, schema, host_cols, live)
            lex.append(k)
            lex.append(isnull)
        perm = np.lexsort(lex) if lex else np.arange(m)
        srows = live[perm]

        def boundary(cols_idx, base):
            nb = base.copy()
            nb[0] = True
            for c in cols_idx:
                k, isnull = self._window_key(c, schema, host_cols, srows)
                nb[1:] |= (k[1:] != k[:-1]) & ~(isnull[1:] & isnull[:-1])
                nb[1:] |= isnull[1:] != isnull[:-1]
            return nb

        newpart = boundary(spec.partition, np.zeros(m, dtype=bool))
        part_id = np.cumsum(newpart) - 1
        part_start = np.maximum.accumulate(
            np.where(newpart, np.arange(m), 0)
        )
        pos = np.arange(m) - part_start  # 0-based position in partition

        # peer groups: same partition AND same order-key values
        newpeer = (
            boundary([c for c, _d in spec.order], newpart)
            if spec.order
            else newpart.copy()
        )

        kind = spec.kind
        if kind == "row_number":
            vals = pos + 1
            valid = np.ones(m, dtype=bool)
        elif kind in ("rank", "dense_rank"):
            if kind == "rank":
                vals = self._rank_from(newpeer, pos)
            else:
                # dense_rank: count of peer-group heads so far in partition
                cums = np.cumsum(newpeer.astype(np.int64))
                base = np.where(newpart, cums - 1, 0)
                vals = cums - np.maximum.accumulate(base)
            valid = np.ones(m, dtype=bool)
        elif kind in ("lag", "lead"):
            off = spec.offset if kind == "lag" else -spec.offset
            src_idx = np.arange(m) - off
            ok_range = (src_idx >= 0) & (src_idx < m)
            src_clip = np.clip(src_idx, 0, m - 1)
            same_part = ok_range & (
                part_id[src_clip] == part_id
            )
            ad, av = host_cols[spec.arg]
            vals = np.where(same_part, ad[srows][src_clip], 0)
            srcv = (
                np.ones(m, dtype=bool) if av is None else av[srows][src_clip]
            )
            valid = same_part & srcv
        else:  # count / sum / avg / min / max
            postmap = None
            if spec.arg is not None:
                ad, av = host_cols[spec.arg]
                a = ad[srows]
                avm = np.ones(m, dtype=bool) if av is None else av[srows]
                aty = schema[spec.arg]
                if aty.type.is_text and aty.dict_id is not None:
                    # min/max over text: compare by rank, map the winning
                    # rank back to its code afterwards
                    ranks = np.asarray(self._dict_ranks(aty.dict_id))
                    nvals = len(self._dict(aty.dict_id).values)
                    inv = np.zeros(max(len(ranks), 1), dtype=np.int64)
                    inv[ranks[:nvals]] = np.arange(nvals)
                    a = ranks[np.clip(a, 0, len(ranks) - 1)]
                    postmap = lambda r: inv[  # noqa: E731
                        np.clip(r.astype(np.int64), 0, len(inv) - 1)
                    ]
                scale = (
                    aty.type.decimal_factor
                    if aty.type.id == t.TypeId.DECIMAL
                    else 1
                )
            else:
                a = np.ones(m, dtype=np.int64)
                avm = np.ones(m, dtype=bool)
                scale = 1
            if spec.frame is not None:
                vals, valid = self._window_agg_framed(
                    kind, a, avm, newpart, spec.frame
                )
            else:
                vals, valid = self._window_agg(
                    kind, a, avm, newpart, newpeer, bool(spec.order)
                )
            if kind == "avg" and scale != 1:
                vals = vals / scale  # unscale DECIMAL averages (agg parity)
            if postmap is not None:
                vals = postmap(vals)
        out[srows] = vals.astype(oty.np_dtype, copy=False)
        outv[srows] = valid
        return out, outv

    @staticmethod
    def _rank_from(newpeer, pos):
        """rank(): 1 + partition-relative position of each row's
        peer-group head (ties share the head's position; every partition
        head is a peer head, so partitions reset naturally)."""
        m = len(pos)
        have = np.where(newpeer, np.arange(m), -1)
        ff = np.maximum.accumulate(have)  # index of the current peer head
        return pos[ff] + 1

    @staticmethod
    def _window_agg_framed(kind, a, avm, newpart, frame):
        """ROWS-frame aggregation (nodeWindowAgg's row-mode frames):
        per-row window [i+start, i+end] clamped to the partition.
        sums/counts are prefix differences; min/max answer range
        queries from an O(m log m) sparse table — both fully
        vectorized."""
        m = len(a)
        s_off, e_off = frame
        idx = np.arange(m)
        part_id = np.cumsum(newpart) - 1
        starts_idx = np.nonzero(newpart)[0]
        ps = starts_idx[part_id]
        ends_idx = np.append(starts_idx[1:], m) - 1
        pe = ends_idx[part_id]
        lo = ps if s_off is None else np.maximum(idx + s_off, ps)
        hi = pe if e_off is None else np.minimum(idx + e_off, pe)
        nonempty = lo <= hi
        lo = np.clip(lo, 0, m - 1)
        hi = np.clip(hi, 0, m - 1)
        af = a.astype(np.float64)
        contrib = np.where(avm, af, 0.0)
        ccnt = np.concatenate(
            [[0], np.cumsum(avm.astype(np.int64))]
        )
        cnt = np.where(nonempty, ccnt[hi + 1] - ccnt[lo], 0)
        if kind == "count":
            return cnt, np.ones(m, dtype=bool)
        if kind in ("sum", "avg"):
            cs = np.concatenate([[0.0], np.cumsum(contrib)])
            s = np.where(nonempty, cs[hi + 1] - cs[lo], 0.0)
            if kind == "sum":
                return s, cnt > 0
            return s / np.maximum(cnt, 1), cnt > 0
        # min / max: sparse table over sentinel-filled values
        big = np.float64(np.inf if kind == "min" else -np.inf)
        red = np.minimum if kind == "min" else np.maximum
        level0 = np.where(avm, af, big)
        tables = [level0]
        span = 1
        while span * 2 <= m:
            prev = tables[-1]
            nxt = prev.copy()
            nxt[: m - span] = red(prev[: m - span], prev[span:])
            tables.append(nxt)
            span *= 2
        length = hi - lo + 1
        k = np.floor(
            np.log2(np.maximum(length, 1))
        ).astype(np.int64)
        pow2 = 1 << k
        t_idx = np.clip(k, 0, len(tables) - 1)
        stacked = np.stack(tables)
        left = stacked[t_idx, lo]
        right = stacked[t_idx, np.maximum(hi - pow2 + 1, 0)]
        vals = red(left, right)
        valid = nonempty & (cnt > 0)
        vals = np.where(valid, vals, 0.0)
        return vals, valid

    @staticmethod
    def _window_agg(kind, a, avm, newpart, newpeer, running: bool):
        m = len(a)
        part_id = np.cumsum(newpart) - 1
        nparts = int(part_id[-1]) + 1
        af = a.astype(np.float64)
        contrib = np.where(avm, af, 0.0)
        cnt_contrib = avm.astype(np.int64)
        if not running:
            # whole-partition value broadcast to every member
            sums = np.bincount(part_id, weights=contrib, minlength=nparts)
            cnts = np.bincount(part_id, weights=cnt_contrib, minlength=nparts)
            if kind == "count":
                return cnts[part_id], np.ones(m, dtype=bool)
            if kind == "sum":
                return sums[part_id], cnts[part_id] > 0
            if kind == "avg":
                safe = np.maximum(cnts, 1)
                return sums[part_id] / safe[part_id], cnts[part_id] > 0
            # min / max via reduceat over partition starts
            starts = np.nonzero(newpart)[0]
            big = np.float64(np.inf if kind == "min" else -np.inf)
            masked = np.where(avm, af, big)
            red = (
                np.minimum.reduceat(masked, starts)
                if kind == "min"
                else np.maximum.reduceat(masked, starts)
            )
            return red[part_id], cnts[part_id] > 0
        # running (cumulative, peers share values): global cumsum minus
        # the value just before each partition head — the head INDEX is
        # forward-filled (monotonic), never the head value, so negative
        # partial sums stay exact
        csum = np.cumsum(contrib)
        ccnt = np.cumsum(cnt_contrib)
        head_idx = np.maximum.accumulate(np.where(newpart, np.arange(m), 0))
        base_sum = csum[head_idx] - contrib[head_idx]
        base_cnt = ccnt[head_idx] - cnt_contrib[head_idx]
        run_sum = csum - base_sum
        run_cnt = ccnt - base_cnt
        if kind in ("min", "max"):
            big = np.float64(np.inf if kind == "min" else -np.inf)
            masked = np.where(avm, af, big)
            acc = (
                np.minimum.accumulate
                if kind == "min"
                else np.maximum.accumulate
            )
            # segmented accumulate: reset at partition heads by replacing
            # the head with +-inf baseline then re-accumulating per block
            starts = np.nonzero(newpart)[0]
            run_mm = masked.copy()
            for s, e in zip(starts, list(starts[1:]) + [m]):
                run_mm[s:e] = acc(masked[s:e])
            run_val = run_mm
        # peers share the frame end: take the value at each peer group's
        # last row
        grp = np.cumsum(newpeer) - 1
        last_of_group = np.zeros(grp[-1] + 1, dtype=np.int64)
        last_of_group[grp] = np.arange(m)  # later rows overwrite
        take = last_of_group[grp]
        if kind == "count":
            return run_cnt[take], np.ones(m, dtype=bool)
        if kind == "sum":
            return run_sum[take], run_cnt[take] > 0
        if kind == "avg":
            safe = np.maximum(run_cnt[take], 1)
            return run_sum[take] / safe, run_cnt[take] > 0
        return run_val[take], run_cnt[take] > 0

    def _eval_limit(self, plan: L.Limit) -> DevBatch:
        child = self.eval(plan.child)
        mask = (
            child.mask
            if child.mask is not None
            else jnp.ones(child.n, jnp.bool_)
        )
        # int64 running rank: an int32 cumsum wraps past 2^31 live rows
        # (the emit_pairs overflow class, PR 6)
        rank = jnp.cumsum(mask.astype(jnp.int64))  # 1-based among live rows
        keep = mask & (rank > plan.offset)
        if plan.limit is not None:
            keep = keep & (rank <= plan.offset + plan.limit)
        return DevBatch(plan.schema, child.cols, keep, child.n)

    # -- join --------------------------------------------------------------
    def _eval_join(self, plan: L.Join) -> DevBatch:
        left = self.eval(plan.left)
        right = self.eval(plan.right)
        jt = plan.join_type

        if jt == "right":
            # plan flipped: build on left of the flip
            return self._join_impl(plan, right, left, "left", flipped=True)
        return self._join_impl(plan, left, right, jt, flipped=False)

    def _join_impl(self, plan, probe, build, jt, flipped):
        lk = plan.right_keys if flipped else plan.left_keys
        rk = plan.left_keys if flipped else plan.right_keys
        pf, pp = self._bind(
            lk, plan.right.schema if flipped else plan.left.schema, self._subq()
        )
        bf, bp = self._bind(
            rk, plan.left.schema if flipped else plan.right.schema, self._subq()
        )
        probe_keys = [
            self._broadcast(fn(probe.cols, pp), probe.n) for fn in pf
        ]
        build_keys = [
            self._broadcast(fn(build.cols, bp), build.n) for fn in bf
        ]
        # TEXT keys: dictionary codes only compare within one dictionary.
        # Translate the probe side's codes into the build side's dictionary
        # (inserting unseen values) so equality on codes is equality on
        # strings — the cross-table alignment the reference never needs
        # because it ships raw datums (squeue.c).
        pschema = plan.right.schema if flipped else plan.left.schema
        bschema = plan.left.schema if flipped else plan.right.schema
        for i, (lk_e, rk_e) in enumerate(zip(lk, rk)):
            if not lk_e.type.is_text:
                continue
            pdid = _texpr_did(lk_e, pschema) or LITERAL_DICT
            bdid = _texpr_did(rk_e, bschema) or LITERAL_DICT
            if pdid == bdid:
                continue
            d, v = probe_keys[i]
            probe_keys[i] = (self._translate_codes(d, pdid, bdid), v)
        probe_keys, build_keys = _align_key_dtypes(probe_keys, build_keys)

        # single integer-family key: the bucket-padded radix hash table
        # skips the joint encode sort AND the probe-width searchsorted
        # (ops/join.py radix path; FULL joins also need the reverse
        # counts, so they keep the encode ids)
        build_ids = probe_ids = None
        radix = None if jt == "full" else self._radix_counts(
            probe_keys, build_keys, probe, build
        )
        if radix is not None:
            build_order, lo, counts, total = radix
            self.last_join_mode = "radix"
        else:
            build_ids, probe_ids = join_ops.encode_keys(
                build_keys, probe_keys, build.mask, probe.mask
            )
            build_order, lo, counts, total = join_ops.match_counts(
                build_ids, probe_ids
            )
            self.last_join_mode = "merge"

        if jt in ("semi", "anti"):
            has = counts > 0
            keep = has if jt == "semi" else ~has
            if probe.mask is not None:
                keep = keep & probe.mask
            schema = plan.schema
            return DevBatch(schema, probe.cols, keep, probe.n)

        outer = jt in ("left", "full")
        tot = int(total)
        if outer:
            # every zero-count probe lane emits one null-extended row on
            # device (invisible ones are masked after the gather), so size
            # for exactly that
            tot = tot + int(jnp.sum(counts == 0))
        out_size = filt_ops.bucket_size(max(tot, 1))
        probe_idx, build_idx, matched, valid = join_ops.emit_pairs(
            build_order, lo, counts, out_size, outer
        )
        # Padding lanes of emit_pairs count unmatched probe rows once for
        # outer joins; for inner joins valid already excludes them.
        if probe.mask is not None:
            valid = valid & jnp.take(probe.mask, probe_idx, axis=0)

        pcols = filt_ops.gather_cols(
            probe.cols, probe_idx, jnp.ones(out_size, jnp.bool_)
        )
        bvalid = matched
        bcols = []
        for data, v in build.cols:
            d = jnp.take(data, build_idx, axis=0)
            vv = bvalid if v is None else (jnp.take(v, build_idx, axis=0) & bvalid)
            bcols.append((d, vv))

        if flipped:
            cols = bcols + pcols  # original left = build side
        else:
            cols = pcols + bcols
        out = DevBatch(plan.schema, cols, valid, out_size)

        if plan.residual is not None:
            fns, params = self._bind(
                [plan.residual], plan.schema, self._subq()
            )
            d, v = fns[0](out.cols, params)
            keep = d if v is None else (d & v)
            if jt in ("left", "full"):
                # residual only filters matched rows; unmatched stay
                keep = keep | ~matched
            out = DevBatch(
                plan.schema, out.cols, out.mask & keep, out.n
            )

        if jt == "full":
            # the probe side's unmatched rows are already null-extended
            # (outer=True above); append the unmatched BUILD rows with
            # a null-extended probe side — the full-join second half
            # (nodeHashjoin.c's HJ_FILL_INNER pass over unmatched
            # build-bucket tuples)
            _bo2, _lo2, counts_b, _t2 = join_ops.match_counts(
                probe_ids, build_ids
            )
            un_b = counts_b == 0
            if build.mask is not None:
                un_b = un_b & build.mask
            seg_p = [
                (
                    jnp.zeros((build.n,), data.dtype),
                    jnp.zeros(build.n, jnp.bool_),
                )
                for data, _v in probe.cols
            ]
            seg_b = [
                (
                    data,
                    jnp.ones(build.n, jnp.bool_) if v is None else v,
                )
                for data, v in build.cols
            ]
            seg_cols = (
                seg_b + seg_p if flipped else seg_p + seg_b
            )
            new_n = filt_ops.bucket_size(out.n + build.n)

            def cat(a, n_a, b, n_b):
                return _pad_dev(
                    jnp.concatenate([a[:n_a], b[:n_b]]), new_n
                )

            cols2 = []
            for (da, va), (db, vb) in zip(out.cols, seg_cols):
                d2 = cat(da, out.n, db, build.n)
                if va is None and vb is None:
                    v2 = None
                else:
                    v2 = cat(
                        jnp.ones(out.n, jnp.bool_) if va is None
                        else va,
                        out.n,
                        jnp.ones(build.n, jnp.bool_) if vb is None
                        else vb,
                        build.n,
                    )
                cols2.append((d2, v2))
            m2 = cat(
                jnp.ones(out.n, jnp.bool_) if out.mask is None
                else out.mask,
                out.n,
                un_b,
                build.n,
            )
            out = DevBatch(plan.schema, cols2, m2, new_n)
        return out

    def _radix_counts(self, probe_keys, build_keys, probe, build):
        """match_counts-contract tuple (build_order, lo, counts, total)
        through the bucket-padded radix table, or None when the shape
        stays on the encode+sort path: multi-key and float keys need the
        joint encoding; a bucket-overflowed table (skewed hash) retries
        once at 4x the quantum, then falls back rather than probing a
        table that dropped rows."""
        from opentenbase_tpu.ops.join import JOIN_MODE
        from opentenbase_tpu.plan import batchplan

        if JOIN_MODE() == "sortmerge" or len(build_keys) != 1:
            return None
        bd, bv = build_keys[0]
        pd, pv = probe_keys[0]
        if jnp.issubdtype(bd.dtype, jnp.floating) or jnp.issubdtype(
            pd.dtype, jnp.floating
        ):
            return None
        plan = batchplan.plan_radix_join(
            build.n, probe.n, batchplan.DEFAULT_EXCHANGE_BUDGET
        )
        if plan is None or plan.passes != 1:
            return None

        def real(mask, v, n):
            if mask is None and v is None:
                return jnp.ones(n, jnp.bool_)
            if mask is None:
                return v
            return mask if v is None else (mask & v)

        breal = real(build.mask, bv, build.n)
        preal = real(probe.mask, pv, probe.n)
        bucket = plan.bucket
        for _ in range(2):
            bo, lo, cnt, tot, ovf = join_ops.radix_match_counts(
                bd, breal, pd, preal, plan.partitions, bucket
            )
            if not bool(ovf):
                return bo, lo, cnt, tot
            bucket *= 4
        return None

    # -- union -------------------------------------------------------------
    def _translate_codes(self, d, src_did: str, dst_did: str):
        """Map TEXT codes from one dictionary into another on device."""
        tbl = resolve_param(
            DictTranslateParam(src_did, dst_did), self._dicts_view()
        )
        return tbl[jnp.clip(d, 0, tbl.shape[0] - 1)]

    def _eval_union(self, plan: L.Union) -> DevBatch:
        parts = [self.eval(c) for c in plan.inputs]
        total = sum(p.n for p in parts)
        padded = filt_ops.bucket_size(max(total, 1))
        ncols = len(plan.schema)
        cols = []
        for ci in range(ncols):
            datas = []
            valids = []
            any_valid = any(p.cols[ci][1] is not None for p in parts)
            out_did = (
                (plan.schema[ci].dict_id or LITERAL_DICT)
                if plan.schema[ci].type.is_text
                else None
            )
            for pi, p in enumerate(parts):
                d, v = p.cols[ci]
                if out_did is not None:
                    src_did = (
                        plan.inputs[pi].schema[ci].dict_id or LITERAL_DICT
                    )
                    if src_did != out_did:
                        # branches carry codes of different dictionaries;
                        # align them or the decode step reads garbage
                        d = self._translate_codes(d, src_did, out_did)
                datas.append(d)
                if any_valid:
                    valids.append(
                        jnp.ones(p.n, jnp.bool_) if v is None else v
                    )
            d = jnp.concatenate(datas)
            d = _pad_dev(d, padded)
            v = None
            if any_valid:
                v = _pad_dev(jnp.concatenate(valids), padded, fill=False)
            cols.append((d, v))
        masks = []
        for p in parts:
            masks.append(
                jnp.ones(p.n, jnp.bool_) if p.mask is None else p.mask
            )
        mask = _pad_dev(jnp.concatenate(masks), padded, fill=False)
        return DevBatch(plan.schema, cols, mask, padded)

    # -- DML helper --------------------------------------------------------
    def predicate_rows(self, table: str, predicate: Optional[E.TExpr]) -> np.ndarray:
        """Row indices in this node's shard store matching the predicate
        under the current snapshot (UPDATE/DELETE target selection).

        Row location is a WRITE-path cost (every TPC-B-style UPDATE pays
        it), and the device round trip — upload all columns, run the
        compiled predicate, download a mask — is pure overhead for the
        point/range predicates DML overwhelmingly uses. Simple
        predicates over non-text columns therefore evaluate HOST-side
        in numpy (``_np_pred_eval``); anything it can't prove identical
        (text, CASE, subqueries, decimals) takes the device path, which
        alone defines the semantics."""
        store = self.stores.get(table)
        if (
            store is not None
            and self._foreign_store(table) is None
            and self.scan_block is None
        ):
            # non-folding capture: UPDATE/DELETE target selection
            # addresses delta rows by the same global positions the
            # stamp paths use, so DML on fresh rows never forces a
            # fold. Evaluation runs PER SEGMENT — the base portion on
            # zero-copy views, the delta tail on its (small) assembled
            # slices — so a point UPDATE during an ingest burst never
            # pays a whole-column materialization.
            view = store.scan_view(fold=self._fold_on_read)
            store.note_delta_read(view.delta_rows())  # whole-table read
            n0 = view.nrows
            cols = list(self.catalog.get(table).schema)
            b = min(view.base_rows, n0)
            keep_live = np.empty(n0, dtype=np.bool_)
            ok = True
            for seg in ((0, b), (b, n0)):
                s0, e0 = seg
                if s0 >= e0:
                    continue
                res = (
                    (np.ones(e0 - s0, np.bool_), None)
                    if predicate is None
                    else _np_pred_eval(predicate, view, cols, s0, e0)
                )
                if res is None:
                    ok = False  # device path defines the semantics
                    break
                d, v = res
                keep = d if v is None else (d & v)
                keep = np.broadcast_to(keep, (e0 - s0,)).copy()
                if self.snapshot_ts is not None:
                    snap = np.int64(self.snapshot_ts)
                    keep &= (view.xmin(s0, e0) <= snap) & (
                        snap < view.xmax(s0, e0)
                    )
                keep_live[s0:e0] = keep
            if ok:
                own = self.own_writes.get(table)
                if own is not None:
                    ins_ranges, del_idx = own
                    if self.snapshot_ts is not None:
                        # own writes override visibility only; the
                        # predicate verdict must still hold, so re-AND
                        # the overlay with the predicate mask
                        for s, e in ins_ranges:
                            e = min(e, n0)
                            res = (
                                (np.ones(e - s, np.bool_), None)
                                if predicate is None
                                else _np_pred_eval(
                                    predicate, view, cols, s, e
                                )
                            )
                            d, v = res
                            kp = d if v is None else (d & v)
                            keep_live[s:e] = np.broadcast_to(
                                kp, (e - s,)
                            )
                    if len(del_idx):
                        keep_live[np.asarray(del_idx)] = False
                return np.nonzero(keep_live)[0]
        meta = self.catalog.get(table)
        schema = tuple(
            L.OutCol(
                name,
                ty,
                f"{table}.{name}" if ty.id == t.TypeId.TEXT else None,
            )
            for name, ty in meta.schema.items()
        )
        scan = L.Scan(table, tuple(meta.schema.keys()), schema)
        batch = self._eval_scan(scan)
        store = self.stores[table]
        if predicate is not None:
            fns, params = self._bind([predicate], schema, self._subq())
            d, v = fns[0](batch.cols, params)
            keep = d if v is None else (d & v)
            mask = batch.mask & keep
        else:
            mask = batch.mask
        m = np.asarray(mask)[: store.nrows]
        return np.nonzero(m)[0]


_NP_CMP = {
    "=": np.equal, "<>": np.not_equal, "<": np.less,
    "<=": np.less_equal, ">": np.greater, ">=": np.greater_equal,
}


def _np_and_valid(lv, rv):
    if lv is None:
        return rv
    if rv is None:
        return lv
    return lv & rv


def _np_pred_eval(e, view, cols, s, n):
    """(data, validity) for a SIMPLE predicate over rows [s, n) of a
    store's :class:`~opentenbase_tpu.storage.table.ScanView` in numpy,
    or None when the expression needs the compiled device path (see
    ``np_expr_eval``). Range-based so callers evaluate per SEGMENT:
    the base portion reads zero-copy views, the delta tail its small
    assembled slices — DML row location stays fold-free AND
    allocation-light while a burst is delta-resident."""
    def getcol(idx):
        if idx >= len(cols):
            return None
        name = cols[idx]
        if name not in view.schema:
            return None
        return (view.col(name, s, n), view.validity(name, s, n))

    return np_expr_eval(e, getcol)


def np_expr_eval(e, getcol):
    """(data, validity) for a SIMPLE expression evaluated in numpy, or
    None when it needs the compiled device path. ``getcol(index)``
    resolves a column reference to (data, validity) host arrays (or
    None = unsupported column). Supported: Col/Const of non-text
    non-decimal types, comparisons, and/or (three-valued NULL semantics
    mirroring ops/expr.py run_and/run_or exactly), + - * arithmetic,
    unary -/not. Everything else — text (dictionary codes), CASE,
    casts, IN lists, subqueries, decimal scaling, / and % (div-by-zero
    semantics) — returns None; the ExprCompiler alone defines those.
    Shared by DML row location (predicate_rows) and UPDATE SET
    evaluation (engine._apply_assignments) — the write path's two
    per-statement expression costs."""
    from opentenbase_tpu.ops.expr import _np_cast_const

    if isinstance(e, E.Col):
        if e.type.is_text or e.type.id == t.TypeId.DECIMAL:
            return None
        return getcol(e.index)
    if isinstance(e, E.Const):
        if e.type.is_text or e.type.id == t.TypeId.DECIMAL:
            return None
        if e.value is None:
            return (
                np.zeros((), dtype=e.type.np_dtype),
                np.zeros((), dtype=np.bool_),
            )
        try:
            return (_np_cast_const(e.value, e.type), None)
        except (TypeError, ValueError, OverflowError):
            return None
    if isinstance(e, E.UnaryE):
        r = np_expr_eval(e.operand, getcol)
        if r is None:
            return None
        d, v = r
        if e.op == "-":
            return (-d, v)
        if e.op == "not":
            return (~d, v)
        return None
    if isinstance(e, E.BinE):
        if e.left.type.is_text or e.right.type.is_text:
            return None
        if (
            e.type.id == t.TypeId.DECIMAL
            or e.left.type.id == t.TypeId.DECIMAL
            or e.right.type.id == t.TypeId.DECIMAL
        ):
            return None
        lr = np_expr_eval(e.left, getcol)
        rr = np_expr_eval(e.right, getcol)
        if lr is None or rr is None:
            return None
        ld, lv = lr
        rd, rv = rr
        if e.op == "and":
            if lv is None and rv is None:
                return (ld & rd, None)
            lF = (ld == False) if lv is None else (lv & ~ld)  # noqa: E712
            rF = (rd == False) if rv is None else (rv & ~rd)  # noqa: E712
            valid = _np_and_valid(lv, rv)
            defl = lF | rF
            valid = defl if valid is None else (valid | defl)
            return (np.where(defl, False, ld & rd), valid)
        if e.op == "or":
            if lv is None and rv is None:
                return (ld | rd, None)
            lT = ld if lv is None else (lv & ld)
            rT = rd if rv is None else (rv & rd)
            valid = _np_and_valid(lv, rv)
            deft = lT | rT
            valid = deft if valid is None else (valid | deft)
            return (np.where(deft, True, ld | rd), valid)
        if e.op in _NP_CMP:
            return (_NP_CMP[e.op](ld, rd), _np_and_valid(lv, rv))
        if e.op == "+":
            return (ld + rd, _np_and_valid(lv, rv))
        if e.op == "-":
            return (ld - rd, _np_and_valid(lv, rv))
        if e.op == "*":
            return (ld * rd, _np_and_valid(lv, rv))
        return None  # / and % have div-by-zero semantics: device path
    return None


def _op_detail(plan) -> Optional[str]:
    """Short per-node annotation for the EXPLAIN ANALYZE tree."""
    table = getattr(plan, "table", None)
    if isinstance(table, str):
        return table
    frag = getattr(plan, "fragment", None)
    if frag is not None and type(plan).__name__ == "RemoteSource":
        return f"fragment {frag}"
    jt = getattr(plan, "join_type", None)
    if jt is not None:
        return str(jt)
    return None


def _align_key_dtypes(probe_keys, build_keys):
    """Promote paired join-key columns to a common dtype so joint encoding
    compares equal values equal (int4 key vs int8 key, float4 vs float8)."""
    pk, bk = [], []
    for (pd, pv), (bd, bv) in zip(probe_keys, build_keys):
        if pd.dtype != bd.dtype:
            target = jnp.promote_types(pd.dtype, bd.dtype)
            pd = pd.astype(target)
            bd = bd.astype(target)
        pk.append((pd, pv))
        bk.append((bd, bv))
    return pk, bk


def _as_rows(cols):
    """Reshape 0-d scalar-agg outputs to 1-row columns."""
    out = []
    for d, v in cols:
        d = jnp.reshape(d, (1,))
        if v is not None:
            v = jnp.reshape(v, (1,))
        out.append((d, v))
    return out


def _texpr_did(e: E.TExpr, schema) -> Optional[str]:
    if isinstance(e, E.Col):
        return schema[e.index].dict_id
    if isinstance(e, E.CastE):
        return _texpr_did(e.operand, schema)
    if e.type.is_text:
        # computed text (upper(col), col || 'x') canonicalizes into
        # the literal pool (ops/expr.py: dst = want or LITERAL_DICT)
        return LITERAL_DICT
    return None


def _pad_to(arr: np.ndarray, n: int, fill=0) -> np.ndarray:
    if len(arr) == n:
        return np.ascontiguousarray(arr)
    out = np.full(n, fill, dtype=arr.dtype)
    out[: len(arr)] = arr
    return out


def _pad_dev(arr, n: int, fill=0):
    cur = arr.shape[0]
    if cur == n:
        return arr
    pad = jnp.full((n - cur,), fill, dtype=arr.dtype)
    return jnp.concatenate([arr, pad])


def _predicate_bounds(pred, scan: L.Scan) -> dict:
    """Per-column [lo, hi] bounds (either side None = unbounded) implied
    by a predicate's top-level conjuncts, in PHYSICAL column units
    (scaled decimals / epoch days — the analyzer lowers literals to
    physical form). Only bare Col-vs-Const comparisons and IN lists
    contribute; anything else is ignored (conservative)."""
    out: dict = {}

    def narrow(ci: int, lo, hi):
        name = scan.columns[ci]
        cur = out.get(name, (None, None))
        nlo = cur[0] if lo is None else (
            lo if cur[0] is None else max(cur[0], lo)
        )
        nhi = cur[1] if hi is None else (
            hi if cur[1] is None else min(cur[1], hi)
        )
        out[name] = (nlo, nhi)

    _FLIP = {"<": ">", "<=": ">=", ">": "<", ">=": "<="}
    for c in E.conjuncts(pred):
        if isinstance(c, E.BinE) and c.op in ("=", "<", "<=", ">", ">="):
            op = c.op
            col, k = c.left, c.right
            if isinstance(col, E.Const) and isinstance(k, E.Col):
                col, k = k, col
                op = _FLIP.get(op, op)
            if not (isinstance(col, E.Col) and isinstance(k, E.Const)):
                continue
            if k.value is None or isinstance(k.value, (str, bytes)):
                continue
            try:
                v = int(k.value)
            except (TypeError, ValueError):
                continue
            if op == "=":
                narrow(col.index, v, v)
            elif op == "<":
                narrow(col.index, None, v - 1)
            elif op == "<=":
                narrow(col.index, None, v)
            elif op == ">":
                narrow(col.index, v + 1, None)
            elif op == ">=":
                narrow(col.index, v, None)
        elif isinstance(c, E.InListE) and not c.negated:
            if not isinstance(c.operand, E.Col):
                continue
            vals = []
            for item in c.items:
                if not isinstance(item, E.Const) or item.value is None:
                    vals = []
                    break
                if isinstance(item.value, (str, bytes)):
                    vals = []
                    break
                try:
                    vals.append(int(item.value))
                except (TypeError, ValueError):
                    vals = []
                    break
            if vals:
                narrow(c.operand.index, min(vals), max(vals))
    return out


# ---------------------------------------------------------------------------
# Within-fragment parallelism (execParallel.c:565 / nodeGather.c:134):
# split a fragment's base scan across K host threads over contiguous row
# blocks, run the SAME partial-aggregate plan per block, and merge the
# block partials with the 2-phase merge functions — the parallel seq
# scan + Gather shape, columnar style. numpy/XLA release the GIL during
# kernel execution, so host threads give real scan parallelism.
# ---------------------------------------------------------------------------

_BLOCK_MERGE_FUNC = {"sum": "sum", "count": "sum", "min": "min", "max": "max"}


def _parallel_min_rows() -> int:
    """Read per call (not at import) so DN processes and tests can
    lower it through the environment."""
    import os

    return int(os.environ.get("OTB_DN_PARALLEL_MIN_ROWS", 100_000))


def _parallel_shape(plan):
    """(aggregate, scan) when the fragment is a mergeable partial
    aggregate over a Filter/Project chain to ONE base scan — the shape
    block workers can split; None otherwise."""
    from opentenbase_tpu.plan import logical as L

    if not isinstance(plan, L.Aggregate):
        return None
    for a in plan.aggs:
        if a.distinct or a.func not in _BLOCK_MERGE_FUNC:
            return None
    node = plan.child
    while isinstance(node, (L.Filter, L.Project)):
        node = node.child
    if not isinstance(node, L.Scan):
        return None
    return plan, node


def run_fragment_parallel(
    catalog, stores, snapshot_ts, plan, remote_inputs,
    subquery_values, nworkers: int, cancel_check=None,
    fold_on_read: bool = False,
):
    """Run ``plan`` split across ``nworkers`` scan-block threads, or
    return None when the shape/size doesn't qualify (caller falls back
    to the single-threaded path)."""
    import threading

    from opentenbase_tpu.plan import logical as L
    from opentenbase_tpu.plan import texpr as E
    from opentenbase_tpu.plan.distribute import RemoteSource

    shape = _parallel_shape(plan)
    if shape is None or nworkers <= 1:
        return None
    agg, scan = shape
    store = stores.get(scan.table)
    min_rows = _parallel_min_rows()
    if store is None or store.nrows < min_rows:
        return None
    # block workers scan plain contiguous ranges; when zone-map pruning
    # would apply (indexed columns bound by the predicate) the serial
    # path's block skipping usually beats brute-force parallel scanning
    # — leave those to the pruned path
    node = agg.child
    pred = None
    while isinstance(node, (L.Filter, L.Project)):
        if isinstance(node, L.Filter) and isinstance(
            node.child, L.Scan
        ):
            pred = node.predicate
        node = node.child
    if pred is not None:
        try:
            meta = catalog.get(scan.table)
            if meta.zone_cols:
                from opentenbase_tpu.storage.table import (
                    zone_usable_bounds,
                )

                if zone_usable_bounds(
                    _predicate_bounds(pred, scan), meta, scan
                ):
                    return None
        except Exception:
            pass
    n0 = store.nrows  # ONE capture: blocks cover a consistent prefix
    k = min(nworkers, max(n0 // max(min_rows // 2, 1), 1))
    if k <= 1:
        return None
    bounds = [
        (n0 * i // k, n0 * (i + 1) // k) for i in range(k)
    ]
    parts: list = [None] * k
    errors: list = []

    def worker(i):
        try:
            # cancel_check rides into every block worker so an
            # abandoned parallel fragment (dn/server cancel_fragment)
            # stops at its next operator boundary like the serial path
            # — these are the largest fragments, the likeliest to be
            # cut at a statement deadline
            ex = LocalExecutor(
                catalog, stores, snapshot_ts,
                remote_inputs=remote_inputs,
                subquery_values=subquery_values,
                cancel_check=cancel_check,
                fold_on_read=fold_on_read,
            )
            ex.scan_block = bounds[i]
            parts[i] = ex.run_plan(plan)
        except Exception as e:
            errors.append(e)

    threads = [
        threading.Thread(target=worker, args=(i,)) for i in range(k)
    ]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    if errors:
        raise errors[0]
    from opentenbase_tpu.executor.dist import concat_batches

    merged_in = concat_batches(parts)
    ngroups = len(agg.group_exprs)
    merge_groups = tuple(
        E.Col(i, agg.schema[i].type) for i in range(ngroups)
    )
    merge_aggs = tuple(
        E.AggCall(
            _BLOCK_MERGE_FUNC[a.func],
            E.Col(ngroups + i, agg.schema[ngroups + i].type),
            False,
            agg.schema[ngroups + i].type,
        )
        for i, a in enumerate(agg.aggs)
    )
    src = RemoteSource(fragment=-1, schema=tuple(agg.schema))
    merge_plan = L.Aggregate(
        src, merge_groups, merge_aggs, tuple(agg.schema)
    )
    ex = LocalExecutor(
        catalog, {}, None, remote_inputs={-1: merged_in},
        subquery_values=subquery_values,
    )
    return ex.run_plan(merge_plan)
