#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system starts on the chip.

One process (it owns the chip) drives the normal statement path once:

    client (net.client.connect_tcp) -> wire -> coordinator Session ->
    planner/caches -> FusedExecutor / DagRunner device programs ->
    coordinator merge -> rows

against a durable ``Cluster`` + ``ClusterServer`` built exactly as
``cli/otb_server.py`` builds them, at TPC-H SF10 scale (60M-row
``lineitem``, 15M ``orders``, 1.5M ``customer``), and checks every
answer against a plain numpy computation over the same seeded arrays.
After EVERY statement it reads ``pg_stat_fused``, ``pg_stat_pallas`` and
``pg_cluster_health`` over the wire and fails on the first sign that the
host executor, an XLA fallback or another platform produced the rows.

    python chip_smoke.py                      # needs a TPU; exits 2 without
    python chip_smoke.py --dry-run-cpu --rows 200000   # sandbox debugging

The last line of stdout is ``{"ok": true, "device": {...}}``; the line
before it is the full record (also written to ``chiprun_out/``). The
figures in it inform the next PR; they are not a baseline.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import threading
import time
import traceback
from decimal import Decimal

import numpy as np

Q6 = (
    "select sum(l_extendedprice * l_discount) from lineitem "
    "where l_shipdate >= date '1994-01-01' and l_shipdate < date '1995-01-01' "
    "and l_discount between 0.05 and 0.07 and l_quantity < 24"
)
Q1 = (
    "select l_returnflag, l_linestatus, sum(l_quantity), "
    "sum(l_extendedprice), sum(l_extendedprice * l_discount), "
    "count(*) from lineitem where l_shipdate <= date '1998-09-02' "
    "group by l_returnflag, l_linestatus "
    "order by l_returnflag, l_linestatus"
)
# c_mktsegment is generated as an int code; 0 plays 'BUILDING'
Q3 = (
    "select l_orderkey, sum(l_extendedprice * (1 - l_discount)), "
    "o_orderdate, o_shippriority "
    "from customer, orders, lineitem "
    "where c_mktsegment = 0 and c_custkey = o_custkey "
    "and l_orderkey = o_orderkey and o_orderdate < date '1995-03-15' "
    "and l_shipdate > date '1995-03-15' "
    "group by l_orderkey, o_orderdate, o_shippriority "
    "order by 2 desc, o_orderdate limit 10"
)
# high-cardinality GROUP BY + top-k (n/4 groups): the gagg path
Q_TOPK = (
    "select l_orderkey, count(*) from lineitem group by l_orderkey "
    "order by 2 desc limit 10"
)
# a join whose build side is small with unique but NOT gap-free keys, so
# the dense dimension-fold trips its density flag and the join falls to
# the formulation join_mode names: radix (ops/pallas_join.py probes on a
# TPU mesh) or sort-merge
Q_DIM = (
    "select w_tag, count(*), sum(o_custkey) from smoke_w, orders "
    "where w_key = o_orderkey group by w_tag order by w_tag"
)
Q_W_AGG = (
    "select count(*), sum(w_val), sum(w_key), min(w_key), max(w_key) "
    "from smoke_w"
)
Q_W_GROUP = (
    "select w_tag, count(*), sum(w_val) from smoke_w "
    "group by w_tag order by w_tag"
)

D_1994, D_1995, D_19950315, D_19980902 = 8766, 9131, 9204, 10471
WRITE_ROWS_PER_STMT = 2000


class SmokeFailure(Exception):
    """A phase failed; the smoke exits non-zero."""


def log(msg: str) -> None:
    print(f"[chip_smoke +{time.monotonic() - T0:6.1f}s] {msg}",
          file=sys.stderr, flush=True)


T0 = time.monotonic()


# ---------------------------------------------------------------------------
# data (regenerated here from --seed)
# ---------------------------------------------------------------------------


def make_lineitem(n: int, seed: int) -> dict:
    rng = np.random.default_rng(seed)
    n_orders = max(n // 4, 1)
    return {
        "l_orderkey": rng.integers(1, n_orders + 1, n).astype(np.int64),
        "l_quantity": (rng.uniform(1, 51, n) * 100).astype(np.int64),
        "l_extendedprice": rng.uniform(900, 105000, n).astype(np.int64),
        "l_discount": rng.integers(0, 11, n).astype(np.int64),
        "l_shipdate": (8036 + rng.integers(0, 2556, n)).astype(np.int32),
        "l_returnflag": rng.integers(0, 3, n).astype(np.int32),
        "l_linestatus": rng.integers(0, 2, n).astype(np.int32),
    }


def make_dims(n: int, seed: int):
    rng = np.random.default_rng(seed)
    n_orders = max(n // 4, 1)
    n_cust = max(n // 40, 1)
    orders = {
        "o_orderkey": np.arange(1, n_orders + 1, dtype=np.int64),
        "o_custkey": rng.integers(1, n_cust + 1, n_orders).astype(np.int64),
        "o_orderdate": (8036 + rng.integers(0, 2405, n_orders)).astype(
            np.int32
        ),
        "o_shippriority": rng.integers(0, 3, n_orders).astype(np.int32),
    }
    customer = {
        "c_custkey": np.arange(1, n_cust + 1, dtype=np.int64),
        "c_mktsegment": rng.integers(0, 5, n_cust).astype(np.int32),
    }
    return orders, customer


def nbytes(arrays: dict) -> int:
    return int(sum(a.nbytes for a in arrays.values()))


# ---------------------------------------------------------------------------
# the plain reference: numpy over the same arrays, nothing of the engine
# ---------------------------------------------------------------------------


def dec(physical: int, scale: int) -> Decimal:
    return Decimal(int(physical)).scaleb(-scale)


def ref_q6(li) -> Decimal:
    keep = (
        (li["l_shipdate"] >= D_1994) & (li["l_shipdate"] < D_1995)
        & (li["l_discount"] >= 5) & (li["l_discount"] <= 7)
        & (li["l_quantity"] < 2400)
    )
    return dec(
        np.sum(li["l_extendedprice"][keep] * li["l_discount"][keep]), 4
    )


def ref_q1(li) -> list:
    keep = li["l_shipdate"] <= D_19980902
    key = (li["l_returnflag"] * 2 + li["l_linestatus"])[keep]
    qty, price, disc = (
        li[c][keep] for c in ("l_quantity", "l_extendedprice", "l_discount")
    )
    out = []
    for k in range(6):
        m = key == k
        if not m.any():
            continue
        out.append((
            k // 2, k % 2, dec(qty[m].sum(), 2), dec(price[m].sum(), 2),
            dec((price[m] * disc[m]).sum(), 4), int(m.sum()),
        ))
    return out


def ref_q3(li, orders, customer):
    """Per-order revenue (physical, scale 4) of the qualifying rows."""
    nc, no = len(customer["c_custkey"]), len(orders["o_orderkey"])
    building = np.zeros(nc + 1, dtype=bool)
    building[customer["c_custkey"][customer["c_mktsegment"] == 0]] = True
    okeep = (orders["o_orderdate"] < D_19950315) & building[
        orders["o_custkey"]
    ]
    okmask = np.zeros(no + 1, dtype=bool)
    okmask[orders["o_orderkey"][okeep]] = True
    lk = li["l_orderkey"]
    keep = (li["l_shipdate"] > D_19950315) & okmask[lk]
    return _revenue_by_key(li, keep, no)


def _revenue_by_key(li, keep, nkeys: int):
    """(rev int64 [nkeys+1], hit bool [nkeys+1]) — exact integer sums of
    price * (1 - discount) at scale 4 per l_orderkey over ``keep``."""
    lk = li["l_orderkey"][keep]
    w = li["l_extendedprice"][keep] * (100 - li["l_discount"][keep])
    # exact in int64: accumulate hi/lo halves through float64 bincounts
    # (each partial stays far below 2^53)
    hi, lo = w >> 20, w & ((1 << 20) - 1)
    rev = (
        np.bincount(lk, weights=hi, minlength=nkeys + 1).astype(np.int64)
        << 20
    ) + np.bincount(lk, weights=lo, minlength=nkeys + 1).astype(np.int64)
    hit = np.bincount(lk, minlength=nkeys + 1) > 0
    return rev, hit


def check_topk_revenue(rows, rev, hit, extra_cols, what: str) -> None:
    """Tie-proof top-10 check: the returned revenues are the reference's
    ten largest in order, and every returned key carries its own
    reference revenue and attributes."""
    live = np.nonzero(hit)[0]
    want = np.sort(rev[live])[::-1][:10]
    if len(rows) != min(10, len(live)):
        raise SmokeFailure(f"{what}: {len(rows)} rows, want {len(want)}")
    seen = set()
    for i, row in enumerate(rows):
        k = int(row[0])
        got = Decimal(str(row[1]))
        if k in seen or not hit[k]:
            raise SmokeFailure(f"{what}: bad key {k} in row {i}")
        seen.add(k)
        if got != dec(want[i], 4) or got != dec(rev[k], 4):
            raise SmokeFailure(
                f"{what}: row {i} key {k} revenue {got}, reference "
                f"rank {dec(want[i], 4)} / key {dec(rev[k], 4)}"
            )
        for col, arr in extra_cols:
            if _norm(row[col]) != _norm(arr(k)):
                raise SmokeFailure(
                    f"{what}: row {i} key {k} col {col}: {row[col]!r} "
                    f"!= {arr(k)!r}"
                )


def _norm(v):
    """Dates travel as ISO strings or day numbers depending on the
    column path; compare on day numbers."""
    if isinstance(v, str) and len(v) == 10 and v[4] == "-":
        return int(
            (np.datetime64(v, "D") - np.datetime64("1970-01-01", "D"))
            .astype(int)
        )
    return int(v)


# ---------------------------------------------------------------------------
# the smoke
# ---------------------------------------------------------------------------


class Smoke:
    def __init__(self, args, jax, devices):
        self.args = args
        self.jax = jax
        self.devices = devices
        self.platform = devices[0].platform
        self.record: dict = {}
        self.statements: list = []
        self.client = None
        self.cluster = None
        self.server = None
        self.data_dir = None
        self.cache_events = {"hits": 0, "misses": 0}
        self.fused_seen = 0
        self.dag_completed = 0
        self.last_views: dict = {}  # views as of the last statement

    # -- wire ------------------------------------------------------------
    def sql(self, text: str):
        return self.client.execute(text)

    def views(self) -> dict:
        fused: dict = {}
        for ev, detail in self.sql(
            "select event, detail from pg_stat_fused"
        ).rows:
            fused.setdefault(ev, []).append(detail)
        pallas = [
            tuple(r) for r in self.sql(
                "select program, state from pg_stat_pallas"
            ).rows
        ]
        health = self.sql(
            "select node_name, role, device_platform from pg_cluster_health"
        ).rows
        cache = {
            k: int(v) for k, v in self.sql(
                "select stat, value from pg_stat_device_cache"
            ).rows
        }
        # a bare scan, summed here: an aggregate over a view would be
        # a plan the DAG runner declines with a reason of its own
        compile_ms = sum(
            float(r[0]) for r in self.sql(
                "select compile_ms from pg_stat_statements"
            ).rows
        )
        return {
            "fused": fused, "pallas": pallas, "health": health,
            "cache": cache, "compile_ms": compile_ms,
        }

    def check_views(self, what: str, fused_expected: bool) -> dict:
        """Step 5: after EVERY statement. A statement that answered from
        the host executor is a failed smoke, whatever it returned."""
        v = self.views()
        f = v["fused"]

        def one(name, default=None):
            return f.get(name, [default])[-1]

        if f.get("demoted"):
            raise SmokeFailure(
                f"{what}: fused->host demotion(s): {f['demoted']}"
            )
        if int(one("platform_demotions", "0")) != 0:
            raise SmokeFailure(
                f"{what}: platform_demotions = {one('platform_demotions')}"
            )
        bad = [r for r in v["pallas"] if r[1] != "compiled"]
        if bad:
            raise SmokeFailure(f"{what}: pallas fallback(s): {bad}")
        # view reads themselves are bare scans the DAG runner declines
        # by design ("trivial scan"); any other reason is a smoke query
        # that fell out of the fused subset
        odd = [u for u in f.get("unsupported", []) if u != "trivial scan"]
        if odd:
            raise SmokeFailure(f"{what}: unsupported on device: {odd}")
        n = int(one("fused_statements", "0"))
        if fused_expected:
            if n != self.fused_seen + 1:
                raise SmokeFailure(
                    f"{what}: the device path did not answer "
                    f"(fused_statements {self.fused_seen} -> {n})"
                )
            if one("last_run_platform") != self.platform:
                raise SmokeFailure(
                    f"{what}: last_run_platform = "
                    f"{one('last_run_platform')!r}, want {self.platform!r}"
                )
        elif n != self.fused_seen:
            raise SmokeFailure(
                f"{what}: fused_statements moved {self.fused_seen} -> {n} "
                "on a statement that has no device path"
            )
        self.fused_seen = n
        self.dag_completed = int(one("completed", "0"))
        self.last_views = v
        cn = [h for h in v["health"] if h[1].startswith("coordinator")]
        if not cn or cn[0][2] != self.platform:
            raise SmokeFailure(
                f"{what}: pg_cluster_health device_platform = "
                f"{cn[0][2] if cn else None!r}, want {self.platform!r}"
            )
        return v

    def statement(self, text: str, what: str, fused: bool = False):
        t0 = time.perf_counter()
        res = self.sql(text)
        dt = time.perf_counter() - t0
        v = self.check_views(what, fused)
        return res, dt, v

    def query(self, name: str, text: str, check, warm: bool = True) -> dict:
        """One smoke query: cold run, optional warm run, both checked
        against the reference and against the views."""
        entry: dict = {"name": name}
        dag0 = self.dag_completed
        compile0 = self.last_views.get("compile_ms", 0.0)
        res, cold_s, v = self.statement(text, f"{name} (cold)", fused=True)
        check(res.rows)
        f = v["fused"]
        entry["cold_s"] = round(cold_s, 3)
        # pg_stat_statements' compile_ms, this statement's share
        entry["compile_ms"] = v["compile_ms"] - compile0
        entry["rows"] = len(res.rows)
        if warm:
            compile0 = v["compile_ms"]
            res, warm_s, v = self.statement(
                text, f"{name} (warm)", fused=True
            )
            check(res.rows)
            f = v["fused"]
            entry["warm_ms"] = round(warm_s * 1000.0, 3)
            entry["warm_compile_ms"] = v["compile_ms"] - compile0
        entry["correct"] = True
        # which device route answered: the DAG runner (its completion
        # count moved; mode/joins/fragments are this statement's) or
        # the single-fragment scan->agg program
        entry["path"] = "dag" if self.dag_completed > dag0 else "fragment"
        dag = entry["path"] == "dag"
        entry["mode"] = f.get("last_mode", [None])[-1] if dag else None
        # the join formulations and the device programs of the run
        # that answered (cold and cached runs report the same)
        entry["join_modes"] = (
            f.get("last_join_modes", [None])[-1] if dag else None
        )
        entry["programs"] = (
            f.get("last_programs", [""])[-1].split(",") if dag else []
        )
        entry["pallas_programs"] = len(v["pallas"])
        self.statements.append(entry)
        log(f"{name}: cold {entry['cold_s']}s compile "
            f"{entry['compile_ms']:.0f}ms warm {entry.get('warm_ms')}ms "
            f"path={entry['path']} mode={entry['mode']} "
            f"joins={entry['join_modes']}")
        return entry

    # -- phases ----------------------------------------------------------
    def build(self) -> None:
        """The deployment, as cli/otb_server.py builds it: durable
        Cluster (WAL + checkpoints) behind a ClusterServer."""
        from opentenbase_tpu.engine import Cluster
        from opentenbase_tpu.net.client import connect_tcp
        from opentenbase_tpu.net.server import ClusterServer

        self.data_dir = tempfile.mkdtemp(prefix="otb_chip_smoke_")
        self.cluster = Cluster(
            self.args.datanodes, 256, os.path.join(self.data_dir, "cn"),
            gts_backend="python",
        )
        self.server = ClusterServer(self.cluster, "127.0.0.1", 0).start()
        self.client = connect_tcp(
            self.server.host, self.server.port, timeout=900.0
        )
        log(f"coordinator up on {self.server.host}:{self.server.port}, "
            f"{self.args.datanodes} datanodes, data_dir {self.data_dir}")

    def bulk_append(self, table: str, arrays: dict) -> None:
        """Pre-sharded append straight into the shard stores (COPY
        FROM is a row-at-a-time CSV loop that would take tens of
        minutes at this size)."""
        from opentenbase_tpu.storage.column import Column
        from opentenbase_tpu.storage.table import ColumnBatch

        c = self.cluster
        meta = c.catalog.get(table)
        n = len(next(iter(arrays.values())))
        nn = len(meta.node_indices)
        commit_ts = c.gts.get_gts()
        for i, node in enumerate(meta.node_indices):
            sl = slice(i * n // nn, (i + 1) * n // nn)
            cols = {
                name: Column(meta.schema[name], arrays[name][sl])
                for name in meta.schema
            }
            c.stores[node][table].append_batch(
                ColumnBatch(cols, sl.stop - sl.start), commit_ts
            )

    def load(self, li, orders, customer) -> None:
        t0 = time.perf_counter()
        self.sql(
            "create table lineitem (l_orderkey bigint, l_quantity "
            "numeric(10,2), l_extendedprice numeric(12,2), l_discount "
            "numeric(4,2), l_shipdate date, l_returnflag int, "
            "l_linestatus int) distribute by roundrobin"
        )
        self.sql(
            "create table orders (o_orderkey bigint, o_custkey bigint, "
            "o_orderdate date, o_shippriority int) distribute by roundrobin"
        )
        self.sql(
            "create table customer (c_custkey bigint, c_mktsegment int) "
            "distribute by roundrobin"
        )
        self.bulk_append("lineitem", li)
        self.bulk_append("orders", orders)
        t_bulk = time.perf_counter() - t0
        # customer takes the real load path: a server-side file read by
        # a COPY statement sent over the wire
        t0 = time.perf_counter()
        path = os.path.join(self.data_dir, "customer.csv")
        np.savetxt(
            path,
            np.stack(
                [customer["c_custkey"], customer["c_mktsegment"]], axis=1
            ),
            fmt="%d", delimiter=",",
        )
        res = self.sql(f"copy customer from '{path}' csv")
        t_copy = time.perf_counter() - t0
        if res.rowcount != len(customer["c_custkey"]):
            raise SmokeFailure(
                f"COPY loaded {res.rowcount} of "
                f"{len(customer['c_custkey'])} customer rows"
            )
        t0 = time.perf_counter()
        self.sql("analyze")  # stats feed join order + motion costing
        t_analyze = time.perf_counter() - t0
        self.record["load"] = {
            "load_path": {
                "lineitem": "bulk_append (shard stores)",
                "orders": "bulk_append (shard stores)",
                "customer": "COPY FROM over the wire (server-side file)",
            },
            "rows": {
                "lineitem": len(li["l_orderkey"]),
                "orders": len(orders["o_orderkey"]),
                "customer": len(customer["c_custkey"]),
            },
            "bytes": {
                "lineitem": nbytes(li), "orders": nbytes(orders),
                "customer": nbytes(customer),
            },
            "bulk_append_s": round(t_bulk, 3),
            "copy_s": round(t_copy, 3),
            "analyze_s": round(t_analyze, 3),
        }
        log(f"loaded: bulk {t_bulk:.1f}s copy {t_copy:.1f}s "
            f"analyze {t_analyze:.1f}s")

    def reads(self, li, orders, customer) -> None:
        want6 = ref_q6(li)

        def check6(rows):
            got = Decimal(str(rows[0][0]))
            if len(rows) != 1 or got != want6:
                raise SmokeFailure(f"Q6: got {rows}, reference {want6}")

        want1 = ref_q1(li)

        def check1(rows):
            got = [
                (int(r[0]), int(r[1]), Decimal(str(r[2])),
                 Decimal(str(r[3])), Decimal(str(r[4])), int(r[5]))
                for r in rows
            ]
            if got != want1:
                raise SmokeFailure(f"Q1: got {got}, reference {want1}")

        rev3, hit3 = ref_q3(li, orders, customer)
        odate, oprio = orders["o_orderdate"], orders["o_shippriority"]

        def check3(rows):
            check_topk_revenue(
                rows, rev3, hit3,
                [(2, lambda k: odate[k - 1]), (3, lambda k: oprio[k - 1])],
                "Q3",
            )

        counts = np.bincount(li["l_orderkey"])
        top_counts = np.sort(counts)[::-1][:10]

        def check_topk(rows):
            keys = [int(r[0]) for r in rows]
            if (
                len(set(keys)) != 10
                or [int(r[1]) for r in rows] != top_counts.tolist()
                or any(counts[k] != int(r[1]) for k, r in zip(keys, rows))
            ):
                raise SmokeFailure(
                    f"top-k: got {rows}, reference counts "
                    f"{top_counts.tolist()}"
                )

        self.statement(
            f"set expected_device_platform = {self.platform}",
            "set expected_device_platform",
        )
        limits = [b for b in self.record["bytes_limit"] if b]
        if len(self.devices) > 1 and limits:
            # PR 21's four-chip run needed this to pass the exchange
            # budget: the check held every device's buffers together
            # (Q3's 3.84 GiB) against the one-device 4e9 bytes. Since
            # PR 29 it judges one device's share (0.96 GiB here) and
            # passes without it; the setting stays until a four-chip
            # run of this smoke shows the Q3 legs on the device with
            # the default budgets (the GUC also sizes the radix tables
            # and the probe windows, so their join modes may move).
            limit = min(limits) // 2
            self.statement(
                f"set device_memory_limit = {limit}", "device_memory_limit"
            )
            self.record["settings"] = {"device_memory_limit": limit}
        # Q6: the Pallas single-pass kernel, then the XLA-fused program
        self.statement("set enable_pallas_scan = on", "set pallas on")
        before = self.query("q6_pallas", Q6, check6)["pallas_programs"]
        if before < 1:
            raise SmokeFailure("Q6 pallas=on compiled no Pallas program")
        self.statement("set enable_pallas_scan = off", "set pallas off")
        self.query("q6_xla", Q6, check6)
        # Q1: the grouped Pallas kernel
        self.statement("set enable_pallas_scan = on", "set pallas on")
        if self.query("q1_pallas", Q1, check1)["pallas_programs"] <= before:
            raise SmokeFailure("Q1 compiled no grouped Pallas program")
        self.statement("reset enable_pallas_scan", "reset pallas")
        self.query("q3", Q3, check3)
        self.sample_device_memory()
        for mode in ("sortmerge", "radix"):
            self.statement(f"set join_mode = {mode}", f"join_mode {mode}")
            self.query(f"q3_{mode}", Q3, check3)
        self.statement("set join_mode = auto", "join_mode auto")
        self.query("group_topk", Q_TOPK, check_topk)

    def sample_device_memory(self) -> None:
        """bytes_in_use per device once Q6/Q1/Q3 have made their columns
        resident (uploads are lazy: first touch). On a multi-device mesh
        resident bytes must be balanced — _pad_shards would let 2 shards
        on 4 devices pass with half the mesh empty."""
        stats = [d.memory_stats() or {} for d in self.devices]
        used = [int(s.get("bytes_in_use", 0)) for s in stats]
        self.record["device_bytes_in_use"] = used
        self.record["device_peak_bytes_in_use"] = [
            int(s.get("peak_bytes_in_use", 0)) for s in stats
        ]
        if len(used) > 1 and self.platform == "tpu":
            if min(used) <= 0 or max(used) > 2 * min(used):
                raise SmokeFailure(
                    f"resident bytes unbalanced across devices: {used}"
                )
        if self.platform == "tpu":
            # the device tables must be real: not a few MB
            floor = 64 << 20 if self.args.rows >= 10_000_000 else 0
            if min(used) < floor:
                raise SmokeFailure(
                    f"device tables too small to mean anything: {used}"
                )

    def writes(self, orders) -> None:
        """The write leg: durable commits at the default
        synchronous_commit, then reads that must see exactly those
        writes THROUGH THE DEVICE PATH (delta-tail upload, no rebuild)."""
        model: dict = {}  # w_key -> [w_tag, w_val]

        def insert(lo: int, hi: int) -> float:
            vals = []
            for i in range(lo, hi):
                model[7 * i + 3] = [i % 3, i]
                vals.append(f"({7 * i + 3},{i % 3},{i})")
            res, dt, _ = self.statement(
                "insert into smoke_w values " + ",".join(vals),
                f"insert [{lo},{hi})",
            )
            if res.rowcount != hi - lo:
                raise SmokeFailure(f"insert rowcount {res.rowcount}")
            return dt

        def check_agg(rows):
            keys = list(model)
            want = (
                len(keys), sum(v[1] for v in model.values()), sum(keys),
                min(keys), max(keys),
            )
            if tuple(int(x) for x in rows[0]) != want:
                raise SmokeFailure(f"smoke_w agg: {rows[0]} != {want}")

        def check_group(rows):
            want = []
            for tag in (0, 1, 2):
                vs = [v[1] for v in model.values() if v[0] == tag]
                if vs:
                    want.append((tag, len(vs), sum(vs)))
            if [tuple(int(x) for x in r) for r in rows] != want:
                raise SmokeFailure(f"smoke_w groups: {rows} != {want}")

        w: dict = {}
        _, w["create_table_s"], _ = self.statement(
            "create table smoke_w (w_key bigint, w_tag int, w_val bigint) "
            "distribute by shard(w_key)", "create smoke_w",
        )
        n = WRITE_ROWS_PER_STMT
        w["insert_s"] = [insert(i * n, (i + 1) * n) for i in range(5)]
        # first read: full upload of smoke_w into the device cache
        e0 = self.query("write_read_initial", Q_W_AGG, check_agg, warm=False)
        v0 = self.last_views
        # the burst stays inside the padded row capacity (5n rows were
        # uploaded; +n fits the power-of-two bucket on 2 and on 4 shards)
        w["insert_s"].append(insert(5 * n, 6 * n))
        res, w["update_s"], _ = self.statement(
            "update smoke_w set w_val = w_val + 1000000 where w_val < 500",
            "update",
        )
        for v in model.values():
            if v[1] < 500:
                v[1] += 1000000
        if res.rowcount != 500:
            raise SmokeFailure(f"update rowcount {res.rowcount}")
        lo, hi = 6 * n - 500, 6 * n
        res, w["delete_s"], _ = self.statement(
            f"delete from smoke_w where w_val >= {lo} and w_val < {hi}",
            "delete",
        )
        for k in [k for k, v in model.items() if lo <= v[1] < hi]:
            del model[k]
        if res.rowcount != 500:
            raise SmokeFailure(f"delete rowcount {res.rowcount}")
        e1 = self.query("write_read_back", Q_W_AGG, check_agg)
        v1 = self.last_views
        self.query("write_read_groups", Q_W_GROUP, check_group)
        tails0 = int(v0["fused"].get("delta_tail_uploads", ["0"])[-1])
        tails1 = int(v1["fused"].get("delta_tail_uploads", ["0"])[-1])
        full0 = v0["cache"].get("full_uploads", 0)
        full1 = v1["cache"].get("full_uploads", 0)
        if tails1 <= tails0 or full1 != full0:
            raise SmokeFailure(
                "read-after-write did not ride the delta plane: "
                f"delta_tail_uploads {tails0}->{tails1}, "
                f"full_uploads {full0}->{full1}"
            )
        w["delta_tail_uploads"] = [tails0, tails1]
        w["full_uploads"] = [full0, full1]
        w["read_initial_s"] = e0["cold_s"]
        w["read_back_s"] = e1["cold_s"]
        w["synchronous_commit"] = self.sql("show synchronous_commit").rows[0][0]
        self.record["write_leg"] = w
        log(f"write leg ok: {w}")

        # the small-build join, under both formulations
        keys = np.fromiter(model, dtype=np.int64)
        tags = np.fromiter((v[0] for v in model.values()), dtype=np.int64)
        no = len(orders["o_orderkey"])
        inb = keys <= no
        tag_of = np.full(no + 1, -1, dtype=np.int64)
        tag_of[keys[inb]] = tags[inb]
        ot = tag_of[orders["o_orderkey"]]
        want_dim = [
            (tag, int((ot == tag).sum()),
             int(orders["o_custkey"][ot == tag].sum()))
            for tag in (0, 1, 2) if (ot == tag).any()
        ]

        def check_dim(rows):
            got = [tuple(int(x) for x in r) for r in rows]
            if got != want_dim:
                raise SmokeFailure(f"dim join: {got} != {want_dim}")

        for mode in ("radix", "sortmerge"):
            self.statement(f"set join_mode = {mode}", f"join_mode {mode}")
            e = self.query(f"dim_join_{mode}", Q_DIM, check_dim)
            want = "radix" if mode == "radix" else "merge"
            if want not in (e["join_modes"] or ""):
                raise SmokeFailure(
                    f"join_mode={mode}: ran as {e['join_modes']!r}"
                )
            if mode == "radix" and self.platform == "tpu" and (
                "pallas" not in e["join_modes"]
            ):
                raise SmokeFailure(
                    "radix join on a TPU mesh did not go through the "
                    f"Pallas probe (ops/pallas_join.py): {e['join_modes']!r}"
                )
        self.statement("set join_mode = auto", "join_mode auto")

    def mesh_checks(self) -> None:
        """Four chips: the mesh spans them all, and Q3's exchange
        fragments ran on it."""
        n = len(self.devices)
        fx = self.cluster._fused
        self.record["mesh_devices"] = int(fx.mesh.devices.size)
        if fx.mesh.devices.size != n:
            raise SmokeFailure(
                f"mesh has {fx.mesh.devices.size} devices, JAX has {n}"
            )
        if n > 1:
            q3 = next(s for s in self.statements if s["name"] == "q3")
            exch = [
                p for p in q3["programs"]
                if p in ("program_dag_exchange", "program_dag_broadcast")
            ]
            if not exch:
                raise SmokeFailure(
                    f"Q3 ran no exchange fragment on the {n}-device mesh: "
                    f"{q3['programs']}"
                )

    def run(self) -> None:
        a = self.args
        jax = self.jax
        import jax.monitoring as monitoring

        def on_event(event, **_kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_events["hits"] += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.cache_events["misses"] += 1

        monitoring.register_event_listener(on_event)
        t0 = time.perf_counter()
        li = make_lineitem(a.rows, a.seed)
        orders, customer = make_dims(a.rows, a.seed + 1)
        log(f"data generated in {time.perf_counter() - t0:.1f}s")
        self.build()
        from opentenbase_tpu.executor.fused import enable_compile_cache

        cache_dir = enable_compile_cache()
        entries0 = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
        self.load(li, orders, customer)
        self.reads(li, orders, customer)
        self.writes(orders)
        self.mesh_checks()
        v = self.last_views
        entries1 = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
        self.record["compile_cache"] = {
            "dir": cache_dir,
            "from_env": bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
            "entries_before": entries0, "entries_after": entries1,
            "hits": self.cache_events["hits"],
            "misses": self.cache_events["misses"],
            "hit": self.cache_events["hits"] > 0,
            "min_compile_time_secs": float(
                jax.config.jax_persistent_cache_min_compile_time_secs
            ),
        }
        self.record["compile_ms_total"] = round(sum(
            s["compile_ms"] + s.get("warm_compile_ms", 0.0)
            for s in self.statements
        ), 1)
        self.record["counters"] = {
            "fused_statements": self.fused_seen,
            "completed": int(v["fused"].get("completed", ["0"])[-1]),
            "platform_demotions": int(
                v["fused"].get("platform_demotions", ["0"])[-1]
            ),
            "fused_demotions": len(v["fused"].get("demoted", [])),
            "pallas_fallbacks": sum(
                1 for _prog, state in v["pallas"] if state != "compiled"
            ),
            "pallas_programs": [list(r) for r in v["pallas"]],
            "device_cache": v["cache"],
        }
        self.record["statements"] = self.statements

    def close(self) -> None:
        for step in (
            lambda: self.client and self.client.close(),
            lambda: self.server and self.server.stop(),
            lambda: self.cluster and self.cluster.close(),
            lambda: self.data_dir and shutil.rmtree(
                self.data_dir, ignore_errors=True
            ),
        ):
            try:
                step()
            except Exception:  # teardown must reach every step
                traceback.print_exc()


def device_header(jax, devices) -> dict:
    import importlib.metadata as md

    import jaxlib

    def ver(pkg):
        try:
            return md.version(pkg)
        except md.PackageNotFoundError:
            return None

    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "libtpu": ver("libtpu"),
        "bytes_limit": [
            (d.memory_stats() or {}).get("bytes_limit") for d in devices
        ],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=60_000_000,
                    help="lineitem rows (orders n/4, customer n/40)")
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument("--datanodes", type=int, default=0,
                    help="default: 2 on one chip, one per chip on four")
    ap.add_argument("--dry-run-cpu", action="store_true",
                    help="debug the command on the CPU backend at a tiny "
                         "--rows (Pallas in interpret mode); proves nothing "
                         "about the chip")
    ap.add_argument("--time-limit", type=float, default=1150.0,
                    help="fail (exit 3) rather than outlive this many s")
    args = ap.parse_args(argv)

    # Step 1, before anything else: the device, or no run at all.
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if args.dry_run_cpu:
        if platform != "cpu":
            print("--dry-run-cpu needs JAX_PLATFORMS=cpu; found "
                  f"{platform!r}", file=sys.stderr)
            return 2
    elif platform != "tpu":
        print("chip_smoke.py needs a TPU: jax.devices()[0].platform is "
              f"{platform!r} (use --dry-run-cpu --rows 200000 to debug "
              "the command without one)", file=sys.stderr)
        return 2
    try:
        import opentenbase_tpu.ops  # noqa: F401  (x64 + host placement)
    except ImportError:
        print("chip_smoke.py must run from the root of a checkout of the "
              "repository (opentenbase_tpu/ not importable)",
              file=sys.stderr)
        return 2
    header = device_header(jax, devices)
    if args.dry_run_cpu:
        header["dry_run"] = True
    log(f"device: {json.dumps(header)}")
    if not args.datanodes:
        args.datanodes = max(2, len(devices))

    smoke = Smoke(args, jax, devices)
    smoke.record = {
        **header, "seed": args.seed, "datanodes": args.datanodes,
        "reduced": [],
    }
    if args.rows != 60_000_000:
        smoke.record["reduced"].append(
            f"lineitem rows {args.rows} (default 60000000)"
        )
    failure: list = []

    def work():
        try:
            smoke.run()
        except BaseException:  # reported by the main thread
            failure.append(traceback.format_exc())

    th = threading.Thread(target=work, name="chip-smoke", daemon=True)
    th.start()
    th.join(args.time_limit)
    if th.is_alive():
        print(f"chip_smoke.py exceeded --time-limit {args.time_limit}s",
              file=sys.stderr, flush=True)
        os._exit(3)  # a wedged device call cannot be unwound
    smoke.close()
    smoke.record["total_s"] = round(time.monotonic() - T0, 1)
    if failure:
        print(failure[0], file=sys.stderr, flush=True)
        print("chip_smoke.py FAILED", file=sys.stderr, flush=True)
        return 1
    smoke.record["ok"] = True
    line = json.dumps(smoke.record)
    out_dir = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "chiprun_out"
    )
    os.makedirs(out_dir, exist_ok=True)
    tag = f"{platform}{len(devices)}"
    with open(os.path.join(out_dir, f"chip_smoke_{tag}.jsonl"), "a") as f:
        f.write(line + "\n")
    print(line, flush=True)
    final = {
        "ok": True,
        "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        },
    }
    if args.dry_run_cpu:
        final["dry_run"] = True
    print(json.dumps(final), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
