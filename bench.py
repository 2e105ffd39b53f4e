"""Benchmark: TPC-H Q6 rows/sec through the coordinator, TPU vs CPU.

The north-star metric from BASELINE.md: end-to-end rows/sec for the
lineitem filter+aggregate (Q6) executed through the SQL front end and the
fused TPU fragment executor, compared against a vectorized numpy CPU
baseline doing the identical computation (the stand-in for the reference's
single-node C executor — generous to the baseline, since PG's
tuple-at-a-time interpreter is far slower than numpy).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

Environment knobs:
  BENCH_ROWS   total lineitem rows (default 60_000_000 ≈ SF10)
  BENCH_DN     datanode count      (default 2)

Runs on a TPU only: without one it exits non-zero before loading data
(chip_smoke.py is the quick proof that the system starts on the chip).
The last chip record (BENCH_r03) showed a ~110ms fixed per-query cost,
so throughput scales with data volume — SF10 is where the fused TPU
path's advantage is visible end-to-end.
"""

from __future__ import annotations

import json
import os
import random
import subprocess
import sys
import threading
import time

import numpy as np

# Engine knobs for the large legs, set BEFORE the package imports bake
# module constants: the SF100 leg's orders build side (151M rows) must
# pass the dimension-fold gate, and its 4 resident i32 columns (9.7GB)
# must stay on the non-chunked scan path.
os.environ.setdefault("OTB_DIMFOLD_MAX", "260000000")
os.environ.setdefault("OTB_SCAN_HBM_BUDGET", "11000000000")

# Watchdog: if anything (device init, a compile) wedges, a daemon timer
# prints an error record and force-exits non-zero.
BENCH_TIMEOUT = int(os.environ.get("BENCH_TIMEOUT", 3300))


def _watchdog():
    time.sleep(BENCH_TIMEOUT)
    print(
        json.dumps(
            {
                "metric": "tpch_q6_rows_per_sec",
                "value": 0,
                "unit": "rows/s",
                "vs_baseline": 0.0,
                "error": f"bench timed out after {BENCH_TIMEOUT}s",
            }
        ),
        flush=True,
    )
    os._exit(3)


threading.Thread(target=_watchdog, daemon=True).start()

from opentenbase_tpu import types as t  # noqa: E402
from opentenbase_tpu.engine import Cluster  # noqa: E402
from opentenbase_tpu.storage.column import Column  # noqa: E402
from opentenbase_tpu.storage.table import ColumnBatch  # noqa: E402

ROWS = int(os.environ.get("BENCH_ROWS", 60_000_000))
NUM_DN = int(os.environ.get("BENCH_DN", 2))

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


def make_lineitem(n: int, seed: int = 42):
    rng = np.random.default_rng(seed)
    n_orders = max(n // 4, 1)
    return {
        "l_orderkey": rng.integers(1, n_orders + 1, n).astype(np.int64),
        "l_quantity": (rng.uniform(1, 51, n) * 100).astype(np.int64),
        "l_extendedprice": (rng.uniform(900, 105000, n)).astype(np.int64),
        "l_discount": rng.integers(0, 11, n).astype(np.int64),
        "l_shipdate": (8036 + rng.integers(0, 2556, n)).astype(np.int32),
        # TPC-H flag distribution: A/R for returns, N otherwise; status
        # derived from shipdate — 4 populated (flag, status) groups
        "l_returnflag": rng.integers(0, 3, n).astype(np.int32),
        "l_linestatus": rng.integers(0, 2, n).astype(np.int32),
    }


def make_q3_dims(n: int, seed: int = 43):
    """orders (n/4 rows) + customer (n/40 rows) scaled off lineitem size,
    mirroring TPC-H row ratios; segment 0 plays BUILDING (1 of 5)."""
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


def _bulk_append(cluster, table: str, arrays) -> None:
    """Pre-sharded append straight into the stores (the COPY fast path
    without CSV in the middle). Replicated tables receive the FULL row
    set on every replica."""
    meta = cluster.catalog.get(table)
    n = len(next(iter(arrays.values())))
    nn = len(meta.node_indices)
    commit_ts = cluster.gts.get_gts()
    for i, node in enumerate(meta.node_indices):
        sl = (
            slice(0, n) if meta.dist.is_replicated
            else slice(i * n // nn, (i + 1) * n // nn)
        )
        cols = {
            name: Column(meta.schema[name], arrays[name][sl])
            for name in meta.schema
        }
        batch = ColumnBatch(cols, sl.stop - sl.start)
        cluster.stores[node][table].append_batch(batch, commit_ts)


def load_cluster(arrays, orders=None, customer=None) -> Cluster:
    cluster = Cluster(num_datanodes=NUM_DN, shard_groups=256)
    s = cluster.session()
    s.execute(
        "create table lineitem (l_orderkey bigint, l_quantity numeric(10,2), "
        "l_extendedprice numeric(12,2), l_discount numeric(4,2), "
        "l_shipdate date, l_returnflag int, l_linestatus int) "
        "distribute by roundrobin"
    )
    _bulk_append(cluster, "lineitem", arrays)
    if orders is not None:
        s.execute(
            "create table orders (o_orderkey bigint, o_custkey bigint, "
            "o_orderdate date, o_shippriority int) distribute by roundrobin"
        )
        _bulk_append(cluster, "orders", orders)
    if customer is not None:
        s.execute(
            "create table customer (c_custkey bigint, c_mktsegment int) "
            "distribute by roundrobin"
        )
        _bulk_append(cluster, "customer", customer)
    return cluster


def cpu_baseline(arrays, repeats: int = 2):
    qty, price, disc, ship = (
        arrays["l_quantity"],
        arrays["l_extendedprice"],
        arrays["l_discount"],
        arrays["l_shipdate"],
    )
    best = float("inf")
    result = None
    for _ in range(repeats):
        t0 = time.perf_counter()
        keep = (
            (ship >= 8766)
            & (ship < 9131)
            & (disc >= 5)
            & (disc <= 7)
            & (qty < 2400)
        )
        revenue = np.sum(np.where(keep, price * disc, 0))
        best = min(best, time.perf_counter() - t0)
        result = revenue
    return result / 10**4, best


def cpu_baseline_q1(arrays, repeats: int = 3):
    """Vectorized numpy Q1: masked per-group sums via bincount over the
    joint (returnflag, linestatus) key — the same generous stand-in for
    the reference's single-node executor as the Q6 baseline."""
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        keep = arrays["l_shipdate"] <= 10471
        key = (
            arrays["l_returnflag"] * 2 + arrays["l_linestatus"]
        )[keep]
        np.bincount(key, weights=arrays["l_quantity"][keep])
        np.bincount(key, weights=arrays["l_extendedprice"][keep])
        np.bincount(
            key,
            weights=(
                arrays["l_extendedprice"][keep]
                * arrays["l_discount"][keep]
            ),
        )
        np.bincount(key)
        best = min(best, time.perf_counter() - t0)
    return best


def cpu_baseline_q3(arrays, orders, customer, repeats: int = 2):
    """Vectorized numpy Q3: array-indexed joins (generous to the CPU —
    dense integer keys make the 'hash join' a direct index) + bincount
    group-by + top-10 partition."""
    no = len(orders["o_orderkey"])
    nc = len(customer["c_custkey"])
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        building = np.zeros(nc + 1, dtype=bool)
        building[customer["c_custkey"][customer["c_mktsegment"] == 0]] = True
        okeep = (orders["o_orderdate"] < 9204) & building[orders["o_custkey"]]
        okmask = np.zeros(no + 1, dtype=bool)
        okmask[orders["o_orderkey"][okeep]] = True
        lk = arrays["l_orderkey"]
        keep = (arrays["l_shipdate"] > 9204) & okmask[lk]
        rev = np.bincount(
            lk[keep],
            weights=arrays["l_extendedprice"][keep]
            * (10000 - arrays["l_discount"][keep] * 100),
            minlength=no + 1,
        )
        top = np.argpartition(rev, -10)[-10:]
        _ = top[np.argsort(-rev[top])]
        best = min(best, time.perf_counter() - t0)
    return best


def _measure(s, cpu_result, repeats: int = 3) -> float:
    """Best wall-clock for Q6 through the coordinator (warm)."""
    warm = s.query(Q6)[0][0]
    assert warm is not None
    best = float("inf")
    got = None
    for _ in range(repeats):
        t0 = time.perf_counter()
        got = s.query(Q6)[0][0]
        best = min(best, time.perf_counter() - t0)
    assert abs(got - cpu_result) < 1e-6 * max(1.0, abs(cpu_result)), (
        got,
        cpu_result,
    )
    return best


def _phase(msg: str, t0: float) -> None:
    print(f"[bench +{time.monotonic() - t0:.0f}s] {msg}",
          file=sys.stderr, flush=True)


def _fault_off_probe(calls: int = 200_000) -> dict:
    """Measure the disarmed-failpoint cost (fault/): every FAULT site
    the scan/agg legs crossed was a single empty-dict lookup. Returns
    {armed: 0, ns_per_site: <measured>} for the BENCH record — the
    evidence that injection-off overhead is within noise."""
    from opentenbase_tpu import fault

    assert not fault.armed(), "bench must run with no faults armed"
    f = fault.FAULT
    t0 = time.perf_counter()
    for _ in range(calls):
        f("bench/probe")
    t1 = time.perf_counter()
    return {
        "armed": 0,
        "ns_per_site": round((t1 - t0) / calls * 1e9, 1),
    }


def _phase_breakdown(cluster) -> dict:
    """Where the measured queries spent their time (obs/): the fused
    executor's cumulative compile/device/host split plus host-path
    motion ms — so future rounds can attribute perf wins and losses
    instead of reporting only end-to-end ratios. Disable with
    BENCH_PHASES=0."""
    out = {}
    metrics = getattr(cluster, "metrics", None)
    if metrics is not None:
        for name in ("compile", "device", "host", "motion", "execute",
                     "plan"):
            h = metrics.histograms.get(f"phase.{name}")
            if h is not None and h.count:
                out[f"{name}_ms"] = round(h.total, 3)
    return out


def _require_tpu() -> str:
    """The one platform check: a bench without a chip is a failure, not
    a smaller bench."""
    import jax

    plat = jax.devices()[0].platform
    if plat != "tpu":
        print(
            f"bench.py needs a TPU: jax.devices()[0].platform is "
            f"{plat!r}", file=sys.stderr,
        )
        sys.exit(2)
    return plat


def main():
    t_start = time.monotonic()
    platform = _require_tpu()
    arrays = make_lineitem(ROWS)
    orders, customer = make_q3_dims(ROWS)
    _phase("data generated", t_start)
    cpu_result, cpu_time = cpu_baseline(arrays)
    _phase("cpu baseline done", t_start)

    cluster = load_cluster(arrays, orders, customer)
    s = cluster.session()
    s.execute("analyze")  # stats feed join order + motion costing
    _phase("cluster loaded", t_start)

    # XLA-fused path
    s.execute("set enable_pallas_scan = off")
    xla_best = _measure(s, cpu_result)
    _phase("q6 xla measured", t_start)
    # pallas single-pass kernel (ops/pallas_scan.py)
    try:
        s.execute("set enable_pallas_scan = on")
        cluster._fused = None
        pallas_best = _measure(s, cpu_result)
    except Exception:
        pallas_best = None

    best = min(x for x in (xla_best, pallas_best) if x is not None)
    rows_per_sec = ROWS / best
    cpu_rows_per_sec = ROWS / cpu_time
    record = {
        "metric": "tpch_q6_rows_per_sec",
        "value": round(rows_per_sec),
        "unit": "rows/s",
        "vs_baseline": round(rows_per_sec / cpu_rows_per_sec, 3),
        "platform": platform,
        "rows": ROWS,
        "xla_rows_per_sec": round(ROWS / xla_best),
    }
    if pallas_best is not None:
        record["pallas_rows_per_sec"] = round(ROWS / pallas_best)
    if os.environ.get("BENCH_PHASES", "1") == "1":
        try:
            record["phase_breakdown"] = _phase_breakdown(cluster)
        except Exception:
            pass  # attribution is optional; never sink the headline
    try:
        # fault-injection-off overhead (fault/): the scan/agg legs above
        # ran with every FAULT site disarmed; record the measured ns per
        # site visit so the "within noise" claim is a number. A single
        # empty-dict lookup costs tens of ns — against multi-ms legs the
        # per-query overhead (a handful of site visits) is sub-ppm.
        record["fault_injection"] = _fault_off_probe()
    except Exception:
        pass

    # Emit the headline IMMEDIATELY — before any optional leg can wedge.
    # Extra legs re-print an enriched superset record afterwards; a driver
    # reading either the first or the last JSON line gets value > 0.
    _phase("q6 measured", t_start)
    print(json.dumps(record), flush=True)

    # Dispatch-amortized kernel roof (VERDICT r2 §weak-6): the per-query
    # wall time sat near a ~110ms fixed floor in BENCH_r03, so also
    # time a jitted 16-iteration on-device loop over the SAME resident
    # columns and report effective HBM GB/s next to rows/s.
    try:
        import jax as _j
        import jax.numpy as _jnp

        fx = cluster.fused_executor()
        meta = cluster.catalog.get("lineitem")
        cols = ["l_quantity", "l_extendedprice", "l_discount",
                "l_shipdate"]
        dtab = fx.cache.get(
            "lineitem", meta, cluster.stores,
            tuple(meta.node_indices), columns=cols,
        )
        qty, price, disc, ship = (dtab.columns[c] for c in cols)
        iters = 16

        @_j.jit
        def loop(qty, price, disc, ship):
            def body(i, acc):
                # the i-dependent bound stops XLA hoisting the whole
                # body out of the loop as loop-invariant
                keep = (
                    (ship >= 8766 + i) & (ship < 9131)
                    & (disc >= 5) & (disc <= 7) & (qty < 2400)
                )
                rev = _jnp.sum(_jnp.where(keep, price * disc, 0))
                return acc + rev

            return _j.lax.fori_loop(0, iters, body, _jnp.int64(0))

        got = int(_j.device_get(loop(qty, price, disc, ship)))  # warm
        assert got != 0
        t0 = time.perf_counter()
        int(_j.device_get(loop(qty, price, disc, ship)))
        amort = (time.perf_counter() - t0) / iters
        touched = ROWS * (8 + 8 + 8 + 4)
        record["q6_amortized_rows_per_sec"] = round(ROWS / amort)
        record["q6_effective_gbps"] = round(touched / amort / 1e9, 1)
        _phase("q6 amortized measured", t_start)
        print(json.dumps(record), flush=True)
    except Exception as e:
        _phase(f"q6 amortized failed: {e!r:.120}", t_start)

    # Q1: the grouped-aggregation path; headline stays Q6 for cross-round
    # comparability. The headline is already out, so a watchdog cut here
    # loses nothing.
    try:
        q1_warm = s.query(Q1)  # compile
        assert len(q1_warm) >= 1
        _phase("q1 compiled", t_start)
        q1_best = float("inf")
        for _ in range(2):
            t0 = time.perf_counter()
            s.query(Q1)
            q1_best = min(q1_best, time.perf_counter() - t0)
        q1_cpu = cpu_baseline_q1(arrays)
        record["q1_rows_per_sec"] = round(ROWS / q1_best)
        record["q1_platform"] = _leg_platform()
        record["q1_vs_baseline"] = round(
            (ROWS / q1_best) / (ROWS / q1_cpu), 3
        )
        _phase("q1 measured", t_start)
        print(json.dumps(record), flush=True)
    except Exception as e:  # Q1 must never break the headline
        _phase(f"q1 failed: {e!r:.200}", t_start)

    # Q3: the distributed-join path (BASELINE config 3) at FULL size —
    # the round-3 co-sort engine (executor/fused_dag.py gsort mode:
    # one lax.sort + prefix scans + device top-k, no scatter, no
    # searchsorted) runs 60M rows in-HBM with no row cap.
    try:
        record["q3_rows"] = ROWS
        q3_c0 = _dag_completed(cluster)
        q3_warm = s.query(Q3)  # compile
        assert len(q3_warm) >= 1
        _phase("q3 compiled", t_start)
        q3_best = float("inf")
        for _ in range(2):
            t0 = time.perf_counter()
            s.query(Q3)
            q3_best = min(q3_best, time.perf_counter() - t0)
        q3_cpu = cpu_baseline_q3(arrays, orders, customer)
        record["q3_rows_per_sec"] = round(ROWS / q3_best)
        record["q3_vs_baseline"] = round(
            (ROWS / q3_best) / (ROWS / q3_cpu), 3
        )
        record["q3_mode"], record["q3_join_modes"] = _q3_modes(
            cluster, q3_c0
        )
        record["q3_platform"] = _leg_platform()
        _phase("q3 measured", t_start)
        print(json.dumps(record), flush=True)
    except Exception as e:  # Q3 must never break the headline
        _phase(f"q3 failed: {e!r:.200}", t_start)

    # dnproc leg FIRST among the optional legs (VERDICT r4 weak-2: it
    # needs no TPU — pure process-fabric evidence must not sit behind
    # the 100M-row device legs).
    try:
        if os.environ.get("BENCH_DN_PROCS", "1") == "1":
            dnproc_leg(record, t_start)
    except Exception as e:
        _phase(f"dnproc leg failed: {e!r:.200}", t_start)

    # matview serving leg (matview/): the hot-aggregate path — the same
    # GROUP BY answered from a continuously-maintained materialized
    # view (planner rewrite) vs recomputed on the fly. No TPU needed.
    try:
        if os.environ.get("BENCH_MATVIEW", "1") == "1":
            matview_leg(record, t_start)
    except Exception as e:
        _phase(f"matview leg failed: {e!r:.200}", t_start)

    # serving-plane leg (serving/ + net/concentrator.py): 10k+ pgwire
    # clients multiplexed over a bounded backend pool with the plan and
    # result caches on, vs the uncached/unconcentrated baseline on the
    # same hot queries. No TPU needed.
    try:
        if os.environ.get("BENCH_SERVING", "1") == "1":
            serving_leg(record, t_start)
    except Exception as e:
        _phase(f"serving leg failed: {e!r:.200}", t_start)

    # write-path leg (ROADMAP item 4): TPC-B-style mixed tps, the
    # prepared-insert burst, and bulk multi-row ingest — each against
    # the seed configuration on the same binary. No TPU needed.
    try:
        if os.environ.get("BENCH_WRITE", "1") == "1":
            write_leg(record, t_start)
    except Exception as e:
        _phase(f"write leg failed: {e!r:.200}", t_start)

    # HTAP read-after-write leg (ISSUE-15): interleaved ingest + point
    # updates + top-k scans, scannable delta plane vs the fold-on-read
    # baseline on the same binary. Runs the fused path (device cache
    # delta tails) but needs no real TPU.
    try:
        if os.environ.get("BENCH_HTAP", "1") == "1":
            htap_leg(record, t_start)
    except Exception as e:
        _phase(f"htap leg failed: {e!r:.200}", t_start)

    # ClickBench-like (BASELINE config 5): high-cardinality GROUP BY +
    # TopK over a single wide table — the fused gagg path (one packed-key
    # sort + prefix scans + device top-k). SSB-like star join (config 4)
    # follows on the same cluster. Both at half scale to fit the bench
    # wall-clock; row counts are recorded so ratios stay honest.
    try:
        # ClickBench's spec'd config is hits_100m (BASELINE.md config 5)
        # and SSB is SF100-class: the extra legs default to 100M rows
        # with int32 columns — honest scale amortizes the fixed
        # per-query cost, and the CPU baseline's bincount goes
        # DRAM-bound at the real 1:5 user:hits cardinality while the
        # device sort degrades only as n log n.
        ex_rows = int(os.environ.get(
            "BENCH_EX_ROWS",
            # real runs scale to the spec'd 100M; smoke-test configs
            # (tiny BENCH_ROWS) stay small
            100_000_000 if ROWS >= 8_000_000 else ROWS,
        ))
        # free the TPC-H residency (HBM via the device cache, host RAM
        # via the stores) before loading the second dataset
        cluster._fused = None
        cluster.stores.clear()
        del arrays, orders, customer
        rng = np.random.default_rng(7)
        n_users = max(ex_rows // 5, 1)  # hits_100m: 17.6M/100M uniques
        hits = {
            "userid": rng.integers(0, n_users, ex_rows).astype(np.int32),
            "duration": rng.integers(0, 10_000, ex_rows).astype(np.int32),
        }
        n_dates, n_parts = 2556, 200_000
        lineorder = {
            "lo_orderdate": rng.integers(0, n_dates, ex_rows).astype(
                np.int32
            ),
            "lo_partkey": rng.integers(0, n_parts, ex_rows).astype(
                np.int32
            ),
            "lo_revenue": rng.integers(100, 10_000, ex_rows).astype(
                np.int32
            ),
        }
        date_dim = {
            "d_datekey": np.arange(n_dates, dtype=np.int32),
            "d_year": (1992 + np.arange(n_dates) // 365).astype(np.int32),
        }
        part = {
            "p_partkey": np.arange(n_parts, dtype=np.int32),
            "p_category": rng.integers(0, 25, n_parts).astype(np.int32),
            "p_brand": rng.integers(0, 1000, n_parts).astype(np.int32),
        }
        cluster2 = Cluster(num_datanodes=NUM_DN, shard_groups=256)
        s3 = cluster2.session()
        s3.execute(
            "create table hits (userid int, duration int) "
            "distribute by roundrobin"
        )
        _bulk_append(cluster2, "hits", hits)
        s3.execute(
            "create table lineorder (lo_orderdate int, lo_partkey "
            "int, lo_revenue int) distribute by roundrobin"
        )
        _bulk_append(cluster2, "lineorder", lineorder)
        s3.execute(
            "create table date_dim (d_datekey int, d_year int) "
            "distribute by replication"
        )
        _bulk_append(cluster2, "date_dim", date_dim)
        s3.execute(
            "create table part (p_partkey int, p_category int, "
            "p_brand int) distribute by replication"
        )
        _bulk_append(cluster2, "part", part)
        s3.execute("analyze")
        _phase("extra datasets loaded", t_start)

        Q_CB = (
            "select userid, count(*) from hits group by userid "
            "order by 2 desc limit 10"
        )
        cb_c0 = _dag_completed(cluster2)
        s3.query(Q_CB)  # compile
        _phase("clickbench compiled", t_start)
        cb_best = float("inf")
        for _ in range(2):
            t0 = time.perf_counter()
            s3.query(Q_CB)
            cb_best = min(cb_best, time.perf_counter() - t0)
        t0 = time.perf_counter()
        cnt = np.bincount(hits["userid"], minlength=n_users)
        top = np.argpartition(cnt, -10)[-10:]
        _ = top[np.argsort(-cnt[top])]
        cb_cpu = time.perf_counter() - t0
        record["clickbench_rows"] = ex_rows
        record["clickbench_platform"] = _leg_platform()
        record["clickbench_rows_per_sec"] = round(ex_rows / cb_best)
        record["clickbench_vs_baseline"] = round(cb_cpu / cb_best, 3)
        record["clickbench_mode"], _jm = _q3_modes(cluster2, cb_c0)
        _phase("clickbench measured", t_start)
        print(json.dumps(record), flush=True)

        Q_SSB = (
            "select d_year, p_brand, sum(lo_revenue) "
            "from lineorder, date_dim, part "
            "where lo_orderdate = d_datekey and lo_partkey = p_partkey "
            "and p_category = 1 group by d_year, p_brand "
            "order by 3 desc limit 10"
        )
        ssb_c0 = _dag_completed(cluster2)
        s3.query(Q_SSB)  # compile
        _phase("ssb compiled", t_start)
        ssb_best = float("inf")
        for _ in range(2):
            t0 = time.perf_counter()
            s3.query(Q_SSB)
            ssb_best = min(ssb_best, time.perf_counter() - t0)
        t0 = time.perf_counter()
        keep = part["p_category"][lineorder["lo_partkey"]] == 1
        year = date_dim["d_year"][lineorder["lo_orderdate"]][keep]
        brand = part["p_brand"][lineorder["lo_partkey"]][keep]
        key = (year - 1992) * 1000 + brand
        rev = np.bincount(
            key, weights=lineorder["lo_revenue"][keep],
            minlength=8 * 1000,
        )
        top = np.argpartition(rev, -10)[-10:]
        _ = top[np.argsort(-rev[top])]
        ssb_cpu = time.perf_counter() - t0
        record["ssb_rows"] = ex_rows
        record["ssb_platform"] = _leg_platform()
        record["ssb_rows_per_sec"] = round(ex_rows / ssb_best)
        record["ssb_vs_baseline"] = round(ssb_cpu / ssb_best, 3)
        record["ssb_mode"], record["ssb_join_modes"] = _q3_modes(
            cluster2, ssb_c0
        )
        fx2 = cluster2.fused_executor()
        if fx2 is not None and fx2._dag is not None:
            record["ssb_folds"] = len(fx2._dag.last_folded)
        _phase("ssb measured", t_start)
        print(json.dumps(record), flush=True)
    except Exception as e:  # extra legs must never break the record
        _phase(f"extra legs failed: {e!r:.200}", t_start)

    try:
        if os.environ.get("BENCH_SF100", "1") == "1":
            # free the extra-leg residency first
            try:
                cluster2._fused = None
                cluster2.stores.clear()
                del hits, lineorder, date_dim, part
            except Exception:
                pass
            sf100_legs(record, t_start)
    except Exception as e:
        _phase(f"sf100 legs failed: {e!r:.200}", t_start)
    return record


def _dag_completed(cluster) -> int:
    fx = getattr(cluster, "_fused", None)
    dag = getattr(fx, "_dag", None) if fx is not None else None
    return dag.completed if dag is not None else 0


def _q3_modes(cluster, before: int) -> tuple:
    """(final mode, join formulations) of the leg's fused runs —
    'host'/'' when the leg never completed on the device DAG (compared
    against the pre-leg completion count, so a stale mode from an
    EARLIER leg can't masquerade as this one's), so EVERY record says
    which formulation actually answered."""
    fx = getattr(cluster, "_fused", None)
    dag = getattr(fx, "_dag", None) if fx is not None else None
    if dag is None or dag.completed <= before or dag.last_mode is None:
        return "host", ""
    return str(dag.last_mode), ",".join(dag.last_join_modes)


def matview_leg(record, t_start) -> None:
    """Matview serving: a hot aggregate query answered by the planner
    rewrite from a fresh incrementally-maintained matview vs computed
    on the fly from the fact table, plus the incremental refresh cost
    after a 1% DML batch. Runs on its own small durable cluster (WAL
    is the delta stream) so the headline clusters stay untouched."""
    import tempfile

    from opentenbase_tpu.engine import Cluster
    from opentenbase_tpu.storage.table import ColumnBatch

    n = int(os.environ.get("BENCH_MATVIEW_ROWS", min(ROWS, 2_000_000)))
    rng = np.random.default_rng(11)
    data = {
        "k": np.arange(n, dtype=np.int64),
        "g": rng.integers(0, 1000, n).astype(np.int64),
        "v": rng.integers(0, 10_000, n).astype(np.int64),
    }
    d = tempfile.mkdtemp(prefix="otb_bench_mv_")
    c = Cluster(num_datanodes=NUM_DN, shard_groups=64, data_dir=d)
    s = c.session()
    s.execute(
        "create table mvfact (k bigint, g bigint, v bigint) "
        "distribute by shard(k)"
    )
    _bulk_append(c, "mvfact", data)
    q = (
        "select g, count(*) as cnt, sum(v) as rev, avg(v) as av "
        "from mvfact group by g"
    )
    t0 = time.perf_counter()
    s.execute(f"create materialized view mvagg as {q}")
    build_s = time.perf_counter() - t0
    # on-the-fly: rewrite off, best of 3
    s.execute("set enable_matview_rewrite = off")
    fly = min(
        _timed(lambda: s.query(q)) for _ in range(3)
    )
    # served: rewrite on, best of 3
    s.execute("set enable_matview_rewrite = on")
    served = min(
        _timed(lambda: s.query(q)) for _ in range(3)
    )
    # 1% randomized DML through the TRANSACTIONAL path (the WAL 'G'
    # frames are the delta stream incremental maintenance consumes —
    # _bulk_append's store fast path would be invisible to it), then
    # the incremental refresh folds it in
    batch = max(n // 100, 1)
    upd = {
        "k": np.arange(n, n + batch, dtype=np.int64),
        "g": rng.integers(0, 1000, batch).astype(np.int64),
        "v": rng.integers(0, 10_000, batch).astype(np.int64),
    }
    meta = c.catalog.get("mvfact")
    dml = ColumnBatch(
        {
            name: Column(meta.schema[name], upd[name])
            for name in meta.schema
        },
        batch,
    )
    txn, _ = s._begin_implicit()
    s._route_and_append(meta, dml, txn)
    s._commit_txn(txn)
    refresh_s = _timed(
        lambda: s.execute("refresh materialized view mvagg")
    )
    mode = s.query(
        "select last_mode from pg_stat_matview "
        "where matviewname = 'mvagg'"
    )[0][0]
    record["matview_rows"] = n
    record["matview_build_s"] = round(build_s, 4)
    record["matview_onthefly_s"] = round(fly, 4)
    record["matview_serving_s"] = round(served, 4)
    record["matview_speedup"] = round(fly / max(served, 1e-9), 1)
    record["matview_refresh_s"] = round(refresh_s, 4)
    record["matview_refresh_mode"] = mode
    c.close()
    _phase(
        f"matview leg: serve {served*1e3:.1f}ms vs fly "
        f"{fly*1e3:.1f}ms ({mode} refresh {refresh_s*1e3:.1f}ms)",
        t_start,
    )
    print(json.dumps(record), flush=True)


def _timed(fn) -> float:
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


# Client half of the serving leg, run in its OWN process: 10k client
# sockets plus 10k server-side sockets would blow one process's file-
# descriptor budget, and a separate GIL makes the closed-loop drivers
# honest competition rather than the server's own threads.
_SERVING_DRIVER = r"""
import json, resource, socket, struct, sys, threading, time

host, port = sys.argv[1], int(sys.argv[2])
want, duration = int(sys.argv[3]), float(sys.argv[4])
queries = json.loads(sys.argv[5])

soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
try:
    resource.setrlimit(resource.RLIMIT_NOFILE, (hard, hard))
    soft = hard
except (ValueError, OSError):
    pass
n = min(want, max(soft - 500, 64))

class Cli:
    def __init__(self):
        self.sock = socket.create_connection((host, port), timeout=60)
        body = struct.pack("!I", 196608) + b"user\0bench\0\0"
        self.sock.sendall(struct.pack("!I", len(body) + 4) + body)
        self.drain()
    def rd(self, k):
        buf = b""
        while len(buf) < k:
            c = self.sock.recv(k - len(buf))
            if not c:
                raise ConnectionError("eof")
            buf += c
        return buf
    def drain(self):
        err = None
        while True:
            tag = self.rd(1)
            (ln,) = struct.unpack("!I", self.rd(4))
            body = self.rd(ln - 4)
            if tag == b"E":
                err = body
            if tag == b"Z":
                if err:
                    raise RuntimeError(err.decode(errors="replace"))
                return
    def q(self, sql):
        b = sql.encode() + b"\0"
        self.sock.sendall(b"Q" + struct.pack("!I", len(b) + 4) + b)
        self.drain()

t0 = time.time()
mu = threading.Lock()
clients = []
def connect(k):
    mine = [Cli() for _ in range(k)]
    with mu:
        clients.extend(mine)
errs = []
ths = [threading.Thread(target=connect, args=(n // 4 + (i < n % 4),))
       for i in range(4)]
for t in ths: t.start()
for t in ths: t.join()
connect_s = time.time() - t0
clients[0].q(queries[0])  # end-to-end warmth probe

lat = []
done = time.time() + duration
def drive(shard):
    mine = []
    i = 0
    while time.time() < done:
        cli = shard[i % len(shard)]
        q = queries[i % len(queries)]
        t1 = time.perf_counter()
        try:
            cli.q(q)
        except Exception as e:
            errs.append(repr(e))
            return
        mine.append(time.perf_counter() - t1)
        i += 1
    with mu:
        lat.extend(mine)

NDRV = 8
shards = [clients[i::NDRV] for i in range(NDRV)]
t0 = time.perf_counter()
ths = [threading.Thread(target=drive, args=(sh,)) for sh in shards if sh]
for t in ths: t.start()
for t in ths: t.join()
wall = time.perf_counter() - t0
lat.sort()
out = {
    "connected": len(clients), "connect_s": round(connect_s, 2),
    "total": len(lat), "wall_s": round(wall, 3),
    "errors": errs[:5],
}
if lat:
    out["p50_ms"] = round(lat[len(lat) // 2] * 1000, 3)
    out["p99_ms"] = round(lat[int(len(lat) * 0.99)] * 1000, 3)
print(json.dumps(out), flush=True)
for cli in clients:
    try:
        cli.sock.close()
    except OSError:
        pass
"""


def write_leg(record, t_start) -> None:
    """Write path (ROADMAP item 4): the three-legged differential vs
    the seed configuration (fsync-per-commit inside the WAL mutex,
    GTS grant per commit, plan-pipeline row inserts) on the SAME
    binary — ``enable_group_commit=off`` + ``enable_bulk_insert_rewrite
    =off`` reproduces the seed behavior byte-for-byte.

    Three measurements, all at ``BENCH_WRITE_SESSIONS`` concurrent
    sessions / single-statement commits, synchronous_commit=local:

    - ``write_tps``: TPC-B-style 1:1 mixed prepared UPDATE accounts /
      INSERT history autocommit statements;
    - ``write_burst_tps``: the PREPAREd-insert burst (the tentpole's
      named workload — every statement one durable commit);
    - ``ingest_rows_per_sec``: bulk multi-row INSERT ... VALUES
      (BENCH_INGEST_BATCH rows/statement) through the INSERT->COPY
      rewrite, vs the seed shape for the same rows: row-at-a-time
      single-row INSERT statements (the "dozens of times" v2.5.0
      claim's own baseline)."""
    import shutil
    import tempfile

    secs = float(os.environ.get("BENCH_WRITE_SECS", 4))
    sessions = int(os.environ.get("BENCH_WRITE_SESSIONS", 8))
    batch_rows = int(os.environ.get("BENCH_INGEST_BATCH", 2000))
    ingest_total = int(os.environ.get("BENCH_INGEST_ROWS", 20000))
    rowwise_n = int(os.environ.get("BENCH_INGEST_ROWWISE", 400))

    def make_cluster(optimized, d):
        c = Cluster(num_datanodes=NUM_DN, shard_groups=64, data_dir=d)
        c.conf_gucs["enable_fused_execution"] = False
        c.conf_gucs["synchronous_commit"] = "local"
        if not optimized:
            c.conf_gucs["enable_group_commit"] = False
            c.conf_gucs["enable_bulk_insert_rewrite"] = False
        s = c.session()
        s.execute(
            "create table accounts (aid bigint, bal bigint) "
            "distribute by shard(aid)"
        )
        s.execute(
            "create table history (hid bigint, aid bigint, delta bigint)"
            " distribute by shard(hid)"
        )
        s.execute(
            "insert into accounts values "
            + ",".join(f"({i},1000)" for i in range(256))
        )
        return c

    def drive(c, mixed) -> float:
        stop_at = time.monotonic() + secs
        counts = [0] * sessions
        errs: list[str] = []

        def worker(w):
            try:
                x = c.session()
                x.execute(
                    "prepare hins as insert into history values "
                    "($1, $2, $3)"
                )
                x.execute(
                    "prepare aupd as update accounts set bal = bal + $1"
                    " where aid = $2"
                )
                i = 0
                while time.monotonic() < stop_at:
                    i += 1
                    try:
                        if mixed and i % 2 == 0:
                            x.execute(
                                f"execute aupd({i % 13 - 6}, "
                                f"{(w * 37 + i) % 256})"
                            )
                        else:
                            x.execute(
                                f"execute hins({w * 10_000_000 + i}, "
                                f"{i % 256}, 1)"
                            )
                        counts[w] += 1
                    except Exception as e:
                        # write-write conflicts on a hot account are
                        # the workload's own serialization failures,
                        # not harness errors — retry the next txn
                        if "serialize" not in str(e):
                            raise
            except Exception as e:
                errs.append(f"{e!r:.200}")

        ths = [
            threading.Thread(target=worker, args=(w,))
            for w in range(sessions)
        ]
        for th in ths:
            th.start()
        for th in ths:
            th.join()
        if errs:
            raise RuntimeError(f"write driver errors: {errs}")
        return sum(counts) / secs

    def ingest_bulk(c) -> float:
        s = c.session()
        t0 = time.perf_counter()
        done = 0
        while done < ingest_total:
            n = min(batch_rows, ingest_total - done)
            vals = ",".join(
                f"({5_000_000 + done + i}, {i % 256}, 1)"
                for i in range(n)
            )
            s.execute(f"insert into history values {vals}")
            done += n
        return ingest_total / (time.perf_counter() - t0)

    def ingest_rowwise(c) -> float:
        s = c.session()
        t0 = time.perf_counter()
        for i in range(rowwise_n):
            s.execute(
                f"insert into history values ({8_000_000 + i}, "
                f"{i % 256}, 1)"
            )
        return rowwise_n / (time.perf_counter() - t0)

    work = tempfile.mkdtemp(prefix="otb_write_bench_")
    try:
        base = make_cluster(False, f"{work}/base")
        try:
            base_tps = drive(base, mixed=True)
            base_burst = drive(base, mixed=False)
            base_ingest = ingest_rowwise(base)
        finally:
            base.close()
        _phase(
            f"write baseline: {base_tps:.0f} mixed tps, "
            f"{base_burst:.0f} burst tps, "
            f"{base_ingest:.0f} row-at-a-time rows/s",
            t_start,
        )
        opt = make_cluster(True, f"{work}/opt")
        try:
            tps = drive(opt, mixed=True)
            burst = drive(opt, mixed=False)
            ingest = ingest_bulk(opt)
            s = opt.session()
            wal_stats = dict(
                s.query("select stat, value from pg_stat_wal")
            )
        finally:
            opt.close()
    finally:
        shutil.rmtree(work, ignore_errors=True)
    record["write_sessions"] = sessions
    record["write_tps"] = round(tps, 1)
    record["write_tps_baseline"] = round(base_tps, 1)
    record["write_speedup"] = round(tps / max(base_tps, 1e-9), 2)
    record["write_burst_tps"] = round(burst, 1)
    record["write_burst_baseline"] = round(base_burst, 1)
    record["write_burst_speedup"] = round(
        burst / max(base_burst, 1e-9), 2
    )
    record["ingest_rows_per_sec"] = round(ingest)
    record["ingest_baseline_rows_per_sec"] = round(base_ingest)
    record["ingest_speedup"] = round(ingest / max(base_ingest, 1e-9), 1)
    record["ingest_batch_rows"] = batch_rows
    record["group_commit_fsyncs_saved"] = wal_stats.get(
        "fsyncs_saved", 0
    )
    record["insert_rewrites"] = wal_stats.get("insert_rewrites", 0)
    _phase(
        f"write leg: {tps:.0f} mixed tps ({record['write_speedup']}x), "
        f"{burst:.0f} burst tps ({record['write_burst_speedup']}x), "
        f"ingest {ingest:.0f} rows/s "
        f"({record['ingest_speedup']}x row-at-a-time)",
        t_start,
    )
    print(json.dumps(record), flush=True)


def htap_leg(record, t_start) -> None:
    """HTAP read-after-write (ISSUE-15): interleaved ingest + point
    UPDATEs + top-k scans on ONE growing table, the scannable delta
    plane vs the fold-on-read baseline (``enable_delta_scan=off``
    reproduces the legacy read path — host scans fold, the device
    cache compacts before refresh and keeps the flat >8-entry MVCC
    full-plane cutoff) on the SAME binary.

    Per iteration: one multi-row INSERT (fresh rows park as delta
    batches), a burst of point UPDATEs (commit stamps on both old and
    delta-resident rows — more log entries than the legacy cutoff
    tolerates), then a top-k scan that must see every write. The
    baseline pays a host fold + a full MVCC-plane rebuild per scan;
    the delta plane serves the same scan with a tail upload + one
    coalesced scatter sized by rows touched.

    - ``htap_rows_per_sec``: rows written (ingest + update) per second
      of the mixed loop, scans included in the wall clock;
    - ``htap_fold_avoided``: fold-on-read events the optimized run
      avoided (pg_stat_fused counter — proof the fold is GONE);
    - ``htap_speedup``: optimized / baseline mixed throughput."""
    secs = float(os.environ.get("BENCH_HTAP_SECS", 4))
    preload = int(os.environ.get("BENCH_HTAP_PRELOAD", 100_000))
    ins_rows = int(os.environ.get("BENCH_HTAP_INS_ROWS", 500))
    upd_stmts = int(os.environ.get("BENCH_HTAP_UPDATES", 8))

    def run_side(delta_scan: bool):
        # no data_dir: WAL/fsync cost is identical on both sides and
        # not what this leg measures — the read-after-write refresh is
        c = Cluster(num_datanodes=NUM_DN, shard_groups=64)
        if not delta_scan:
            c.conf_gucs["enable_delta_scan"] = False
        s = c.session()
        s.execute(
            "create table ht (k bigint, g bigint, v bigint) "
            "distribute by shard(k)"
        )
        done = 0
        while done < preload:
            n = min(8000, preload - done)
            s.execute("insert into ht values " + ",".join(
                f"({done + i}, {(done + i) % 64}, {(done + i) % 9973})"
                for i in range(n)
            ))
            done += n
        c.compact_deltas()
        # top-k leaderboard over live groups: the fresh rows written
        # the iteration BEFORE this scan must already count
        topk = (
            "select g, count(*), sum(v) from ht "
            "group by g order by 3 desc, g limit 5"
        )
        warm = s.query(topk)  # compile the fused program once
        assert len(warm) == 5
        fu0 = dict(s.query("select event, detail from pg_stat_fused"))
        abs0 = dict(
            s.query("select stat, value from pg_stat_wal")
        )["deltas_absorbed"]
        rng = random.Random(11)
        stop_at = time.monotonic() + secs
        written = 0
        scans = 0
        k_next = preload
        t0 = time.perf_counter()
        while time.monotonic() < stop_at:
            s.execute("insert into ht values " + ",".join(
                f"({k_next + i}, {(k_next + i) % 64}, "
                f"{(k_next + i) % 9973})"
                for i in range(ins_rows)
            ))
            k_next += ins_rows
            written += ins_rows
            for _ in range(upd_stmts):
                lo = rng.randrange(0, k_next - 10)
                s.execute(
                    f"update ht set v = v + 1 "
                    f"where k >= {lo} and k < {lo + 10}"
                )
                written += 10
            rows = s.query(topk)
            assert len(rows) == 5
            scans += 1
        elapsed = time.perf_counter() - t0
        fu1 = dict(s.query("select event, detail from pg_stat_fused"))
        wal = dict(s.query("select stat, value from pg_stat_wal"))
        stats = {
            "rows_per_sec": written / elapsed,
            "scans": scans,
            "fold_avoided": (
                int(fu1.get("fold_on_read_avoided", 0))
                - int(fu0.get("fold_on_read_avoided", 0))
            ),
            "deltas_absorbed": int(wal["deltas_absorbed"]) - abs0,
            "pending_delta_rows": int(wal.get("pending_delta_rows", 0)),
        }
        c.close()
        return stats

    base = run_side(False)
    _phase(
        f"htap baseline (fold-on-read): "
        f"{base['rows_per_sec']:.0f} rows/s, "
        f"{base['scans']} scans, "
        f"{base['deltas_absorbed']} folds",
        t_start,
    )
    opt = run_side(True)
    record["htap_rows_per_sec"] = round(opt["rows_per_sec"], 1)
    record["htap_baseline_rows_per_sec"] = round(
        base["rows_per_sec"], 1
    )
    record["htap_speedup"] = round(
        opt["rows_per_sec"] / max(base["rows_per_sec"], 1e-9), 2
    )
    record["htap_scans"] = opt["scans"]
    record["htap_fold_avoided"] = opt["fold_avoided"]
    record["htap_deltas_absorbed"] = opt["deltas_absorbed"]
    record["htap_platform"] = _leg_platform()
    _phase(
        f"htap leg: {opt['rows_per_sec']:.0f} rows/s "
        f"({record['htap_speedup']}x fold-on-read), "
        f"{opt['scans']} scans, {opt['fold_avoided']} folds avoided, "
        f"{opt['deltas_absorbed']} absorbed",
        t_start,
    )
    print(json.dumps(record), flush=True)


def serving_leg(record, t_start) -> None:
    """Serving plane (ROADMAP open item 2): statements/sec and p50/p99
    for a hot read-only query mix under 10k+ simulated pgwire clients
    multiplexed by the session concentrator with the cross-session
    plan cache + versioned result cache on, against the uncached /
    unconcentrated baseline (fresh planning per statement, in-process
    session). The client fleet runs in a subprocess with its own fd
    budget and GIL."""
    import resource

    from opentenbase_tpu.net.concentrator import PgConcentrator

    try:
        _soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
        resource.setrlimit(resource.RLIMIT_NOFILE, (hard, hard))
    except (ValueError, OSError):
        pass
    n = int(os.environ.get("BENCH_SERVING_ROWS", 200_000))
    want = int(os.environ.get("BENCH_SERVING_CLIENTS", 10_000))
    duration = float(os.environ.get("BENCH_SERVING_SECS", 20))
    rng = np.random.default_rng(23)
    data = {
        "k": np.arange(n, dtype=np.int64),
        "g": rng.integers(0, 1000, n).astype(np.int64),
        "v": rng.integers(0, 10_000, n).astype(np.int64),
    }
    c = Cluster(num_datanodes=NUM_DN, shard_groups=64)
    # front-end measurement: the fused/device path is off so both
    # sides pay the same (host) execution cost on a miss, and the win
    # measured is parse/plan/execute elision — not device speed. Set
    # at the CONF level so the concentrator's backend sessions
    # (created below with default GUCs) inherit it too.
    c.conf_gucs["enable_fused_execution"] = False
    s = c.session()
    s.execute(
        "create table serv (k bigint, g bigint, v bigint) "
        "distribute by shard(k)"
    )
    _bulk_append(c, "serv", data)
    s.execute("analyze")
    # hot top-k aggregates: a miss pays a real plan (agg + sort +
    # limit) and a grouped scan; a hit pays ~nothing; the ≤5-row
    # results keep the wire cost out of the measurement
    queries = [
        f"select g, count(*), sum(v * 2 + g) from serv "
        f"where g < {100 * (i + 1)} group by g order by 3 desc limit 5"
        for i in range(8)
    ]
    # baseline: no caches, no concentrator — every statement pays the
    # full parse -> analyze -> distribute -> cost -> execute trip
    s.execute("set enable_plan_cache = off")
    s.execute("set enable_result_cache = off")
    for q in queries:
        s.query(q)  # warm stores/JIT so the baseline isn't cold-start
    base_n = 16
    t0 = time.perf_counter()
    for i in range(base_n):
        s.query(queries[i % len(queries)])
    base_sps = base_n / (time.perf_counter() - t0)
    _phase(f"serving baseline {base_sps:.1f} st/s", t_start)
    # serving plane on
    s.execute("set enable_plan_cache = on")
    s.execute("set enable_result_cache = on")
    conc = PgConcentrator(
        c, backends=4, queue_depth=4096, queue_timeout_s=120,
    ).start()
    driver = None
    try:
        driver = subprocess.Popen(
            [
                sys.executable, "-c", _SERVING_DRIVER,
                conc.host, str(conc.port), str(want), str(duration),
                json.dumps(queries),
            ],
            stdout=subprocess.PIPE, text=True,
        )
        out, _ = driver.communicate(timeout=duration + 600)
        res = json.loads(out.strip().splitlines()[-1])
        if res.get("errors"):
            raise RuntimeError(
                f"serving driver errors: {res['errors']}"
            )
        sps = res["total"] / res["wall_s"] if res["wall_s"] else 0.0
        record["serving_clients"] = res["connected"]
        record["serving_backends"] = conc.backends
        record["serving_connect_s"] = res["connect_s"]
        record["serving_stmts"] = res["total"]
        record["serving_stmts_per_sec"] = round(sps, 1)
        record["serving_p50_ms"] = res.get("p50_ms")
        record["serving_p99_ms"] = res.get("p99_ms")
        record["serving_baseline_stmts_per_sec"] = round(base_sps, 2)
        record["serving_speedup"] = round(sps / max(base_sps, 1e-9), 1)
        record["serving_plan_cache_hits"] = dict(
            s.query("select stat, value from pg_stat_plan_cache")
        )["hits"]
        record["serving_result_cache_hits"] = dict(
            s.query("select stat, value from pg_stat_result_cache")
        )["hits"]
        record["serving_sheds"] = dict(conc.stat_rows())["sheds"]
    finally:
        # a wedged/failed driver must not leak the concentrator's
        # threads, 4 backend sessions, the cluster, or a still-running
        # 10k-socket child into the device legs' measurements
        if driver is not None and driver.poll() is None:
            driver.kill()
        conc.stop()
        c.close()
    _phase(
        f"serving leg: {res['connected']} clients, {sps:.0f} st/s "
        f"({record['serving_speedup']}x baseline), "
        f"p50={res.get('p50_ms')}ms p99={res.get('p99_ms')}ms",
        t_start,
    )
    print(json.dumps(record), flush=True)



def _leg_platform() -> str:
    """The backend the NEXT query actually dispatches to — recorded per
    leg so every BENCH record says where each formulation ran (r04/r05
    ran whole rounds on cpu with only one buried field saying so)."""
    import jax

    return str(jax.devices()[0].platform)


def _gate(record) -> int:
    """Perf-regression gate (opentenbase_tpu/bench_gate.py): evaluate
    the final record against BENCH_FLOORS.json + demotion checks, print
    the verdict as one JSON line, and return the process exit code.
    BENCH_GATE=0 keeps the verdict line but always returns 0."""
    from opentenbase_tpu import bench_gate

    if record is None:
        return 0
    # process-lifetime total — per-executor counters die when a leg
    # frees device residency via cluster._fused = None
    try:
        from opentenbase_tpu.executor.fused import PALLAS_DEMOTIONS_TOTAL

        record["pallas_demotions"] = int(PALLAS_DEMOTIONS_TOTAL[0])
    except Exception:
        record["pallas_demotions"] = 0
    try:
        doc = bench_gate.load_floors()
        violations = bench_gate.check_record(record, doc)
    except Exception as e:  # a broken floors file is itself a failure
        violations = [f"floors file unusable: {e!r:.200}"]
    print(
        json.dumps({
            "metric": "bench_gate",
            "pass": not violations,
            "enforced": bench_gate.gate_enabled(),
            "violations": violations,
        }),
        flush=True,
    )
    if violations and bench_gate.gate_enabled():
        return bench_gate.GATE_EXIT_CODE
    return 0


def dnproc_leg(record, t_start) -> None:
    """Q6 through a REAL process topology: 1 coordinator + 2 datanode
    server processes executing fragments over pooled channels (VERDICT
    r3 weak-7: the perf numbers must include a leg where the
    distributed-systems stack is on the measured path). Fused device
    execution is OFF — this measures the process fabric: WAL-streamed
    data, serialized plans, remote fragment fan-out, response
    combining. A multi-node write also runs through, exercising the
    shipped-DML 2PC path on the measured topology."""
    import shutil
    import tempfile

    from opentenbase_tpu.storage.replication import WalSender

    n = int(os.environ.get("BENCH_DN_ROWS", 4_000_000))
    arrays = make_lineitem(n, seed=77)
    tmp = tempfile.mkdtemp(prefix="otb_dnproc_")
    procs = []
    sender = None
    c = None
    try:
        c = Cluster(
            num_datanodes=2, shard_groups=64,
            data_dir=os.path.join(tmp, "cn"),
        )
        s = c.session()
        s.execute(
            "create table lineitem (l_orderkey bigint, l_quantity "
            "numeric(10,2), l_extendedprice numeric(12,2), l_discount "
            "numeric(4,2), l_shipdate date, l_returnflag int, "
            "l_linestatus int) distribute by roundrobin"
        )
        _bulk_append(c, "lineitem", arrays)
        # the bulk loader bypasses the WAL; log the load as ONE commit
        # frame so the DN standbys replicate it
        meta = c.catalog.get("lineitem")
        c.persistence.log_commit_group(
            [
                (node, "lineitem",
                 [(0, c.stores[node]["lineitem"].nrows)], [])
                for node in meta.node_indices
            ],
            c.stores,
            c.gts.get_gts(),
        )
        sender = WalSender(c.persistence)
        # DN procs are host-side roles: one process owns the chip
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        for node in (0, 1):
            p = subprocess.Popen(
                [
                    sys.executable, "-m", "opentenbase_tpu.dn.server",
                    "--data-dir", os.path.join(tmp, f"dn{node}"),
                    "--wal-host", sender.host,
                    "--wal-port", str(sender.port),
                    "--num-datanodes", "2",
                    "--shard-groups", "64",
                ],
                stdout=subprocess.PIPE, text=True, env=env,
            )
            procs.append(p)  # before READY: a failed start must not leak
            line = p.stdout.readline().strip()
            assert line.startswith("READY "), line
            c.attach_datanode(
                node, "127.0.0.1", int(line.split()[1]),
                pool_size=2, rpc_timeout=600,
            )
        _phase("dnproc topology up", t_start)
        s.execute("set enable_fused_execution = off")
        s.query(Q6)  # warm (waits for WAL catch-up on the DNs)
        # within-fragment workers (execParallel.c analog): K=1 vs K=4
        # on the same topology — VERDICT r4 ask #8's measurement
        s.execute("set dn_parallel_workers = 1")
        best1 = float("inf")
        for _ in range(2):
            t0 = time.perf_counter()
            s.query(Q6)
            best1 = min(best1, time.perf_counter() - t0)
        s.execute("set dn_parallel_workers = 4")
        s.query(Q6)  # warm the parallel path
        best = float("inf")
        for _ in range(2):
            t0 = time.perf_counter()
            s.query(Q6)
            best = min(best, time.perf_counter() - t0)
        _cpu_res, cpu_t = cpu_baseline(arrays)
        record["dnproc_rows"] = n
        record["dnproc_q6_rows_per_sec"] = round(n / best)
        record["dnproc_vs_baseline"] = round(cpu_t / best, 3)
        # interpret against host_cores: block workers can't beat the
        # serial path on a 1-core driver box (os.cpu_count() there)
        record["dnproc_par_speedup"] = round(best1 / best, 2)
        record["host_cores"] = os.cpu_count()
        # shipped-DML write across both DNs on the same topology
        s.execute(
            "insert into lineitem values "
            + ",".join(
                f"({i}, 1, 2, 0.05, date '1994-06-01', 0, 0)"
                for i in range(1000)
            )
        )
        got = s.query("select count(*) from lineitem")[0][0]
        assert got == n + 1000, (got, n)
        record["dnproc_write_ok"] = True
        _phase("dnproc measured", t_start)
        print(json.dumps(record), flush=True)
    finally:
        try:
            for node in (0, 1):
                c.detach_datanode(node)
        except Exception:
            pass
        for p in procs:
            try:
                p.terminate()
            except Exception:
                pass
        if sender is not None:
            sender.stop()
        try:
            if c is not None:
                c.close()
        except Exception:
            pass
        shutil.rmtree(tmp, ignore_errors=True)


class _ExtStore:
    """Planner/version stub for a device-resident external table (no
    host rows — DeviceCache.register_external holds the data)."""

    def __init__(self, nrows: int):
        self.nrows = nrows
        self.version = 1
        self.structure_version = 0
        self.mvcc_seq = 0


def sf100_legs(record, t_start) -> None:
    """TPC-H SF100-scale Q3 + Q6 ON DEVICE (BASELINE config 3 at its
    written scale): 604M lineitem rows generated on-chip with threefry
    (deterministic across backends — the CPU baseline regenerates bit-
    identical data locally, so ~12GB never crosses the host),
    registered as device-resident external tables. Q3 runs the
    windowed gagg path (build sides hoisted + folded, probe streamed in
    HBM-budget windows); Q6 the fused scan path."""
    import jax
    import jax.numpy as jnp

    avail_kb = 0
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemAvailable"):
                    avail_kb = int(line.split()[1])
                    break
    except OSError:
        pass
    N = int(os.environ.get(
        "BENCH_SF_ROWS",
        # default 2^26 * 9: window-halvable, ~SF100.6
        603_979_776,
    ))
    # The host baseline regenerates bit-identical data locally and
    # peaks around ~40 bytes/row live at once (5 int32 columns + the
    # int64 product/bincount temporaries). Cap N to what the driver
    # box can verify — shrinking the WHOLE leg (device and host alike)
    # instead of skipping it, so the leg still emits a correctness-
    # checked ratio at its true, labeled scale (VERDICT r4 weak-10).
    if avail_kb:
        n_cap = (avail_kb * 1024 // 2) // 40
        if N > n_cap:
            if n_cap < 8_000_000:
                _phase(
                    f"sf100 skipped: {avail_kb}kB host RAM can't "
                    "verify even 8M rows", t_start,
                )
                return
            N = int(n_cap)
            _phase(f"sf100 shrunk to {N}: {avail_kb}kB host RAM",
                   t_start)
    NO, NC = N // 4, N // 40
    cpu0 = jax.devices("cpu")[0]

    def gen(seed, shape, lo, hi, device):
        k = jax.random.PRNGKey(seed)
        with jax.default_device(device):
            return jax.random.randint(k, shape, lo, hi, dtype=jnp.int32)

    specs_li = {
        "l_orderkey": (11, 1, NO + 1),
        "l_quantity": (12, 100, 5100),
        "l_extendedprice": (13, 900, 105001),
        "l_discount": (14, 0, 11),
        "l_shipdate": (15, 8036, 8036 + 2556),
    }
    specs_ord = {
        "o_custkey": (21, 1, NC + 1),
        "o_orderdate": (22, 8036, 8036 + 2405),
        "o_shippriority": (23, 0, 3),
    }

    from opentenbase_tpu.engine import Cluster as _Cluster

    c3 = _Cluster(num_datanodes=1, shard_groups=16)
    s4 = c3.session()
    s4.execute(
        "create table lineitem (l_orderkey int, l_quantity int, "
        "l_extendedprice int, l_discount int, l_shipdate int) "
        "distribute by roundrobin"
    )
    s4.execute(
        "create table orders (o_orderkey int, o_custkey int, "
        "o_orderdate int, o_shippriority int) distribute by roundrobin"
    )
    s4.execute(
        "create table customer (c_custkey int, c_mktsegment int) "
        "distribute by roundrobin"
    )
    node_li = c3.catalog.get("lineitem").node_indices[0]
    c3.stores[node_li]["lineitem"] = _ExtStore(N)
    c3.stores[node_li]["orders"] = _ExtStore(NO)
    c3.stores[node_li]["customer"] = _ExtStore(NC)
    # optimizer stats the ANALYZE pass would have produced
    c3.catalog.get("lineitem").stats = {
        "rows": N, "ndv": {"l_orderkey": NO, "l_shipdate": 2556},
    }
    c3.catalog.get("orders").stats = {
        "rows": NO, "ndv": {"o_orderkey": NO, "o_custkey": NC},
    }
    c3.catalog.get("customer").stats = {
        "rows": NC, "ndv": {"c_custkey": NC, "c_mktsegment": 5},
    }
    fx = c3.fused_executor()

    def register(table, nrows, cols):
        meta = c3.catalog.get(table)
        fx.cache.register_external(
            table, meta, (node_li,), cols, [nrows]
        )

    # device-side generation (TPU threefry): orders/customer up front
    ord_cols = {
        "o_orderkey": jnp.arange(
            1, NO + 1, dtype=jnp.int32
        ).reshape(1, NO),
    }
    for name, (seed, lo, hi) in specs_ord.items():
        ord_cols[name] = gen(seed, (1, NO), lo, hi, jax.devices()[0])
    register("orders", NO, ord_cols)
    del ord_cols
    cust_cols = {
        "c_custkey": jnp.arange(
            1, NC + 1, dtype=jnp.int32
        ).reshape(1, NC),
        "c_mktsegment": gen(31, (1, NC), 0, 5, jax.devices()[0]),
    }
    register("customer", NC, cust_cols)
    del cust_cols

    # determinism spot-check: device threefry must equal host threefry
    probe_dev = np.asarray(
        gen(13, (1, 64), 900, 105001, jax.devices()[0])
    )
    probe_cpu = np.asarray(gen(13, (1, 64), 900, 105001, cpu0))
    if not np.array_equal(probe_dev, probe_cpu):
        _phase("sf100 skipped: threefry backend mismatch", t_start)
        return

    # ---- Q6 at SF100: resident scan columns qty/price/disc/ship ----
    li_cols = {
        name: gen(sd, (1, N), lo, hi, jax.devices()[0])
        for name, (sd, lo, hi) in specs_li.items()
        if name != "l_orderkey"
    }
    register("lineitem", N, li_cols)
    del li_cols
    Q6_SF = (
        "select sum(l_extendedprice * l_discount) from lineitem "
        "where l_shipdate >= 8766 and l_shipdate < 9131 "
        "and l_discount between 5 and 7 and l_quantity < 2400"
    )
    got6 = s4.query(Q6_SF)[0][0]
    _phase("sf100 q6 compiled", t_start)
    q6_best = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        s4.query(Q6_SF)
        q6_best = min(q6_best, time.perf_counter() - t0)
    # CPU baseline on bit-identical host-generated data
    qty = np.asarray(gen(12, (1, N), 100, 5100, cpu0)).ravel()
    price = np.asarray(gen(13, (1, N), 900, 105001, cpu0)).ravel()
    disc = np.asarray(gen(14, (1, N), 0, 11, cpu0)).ravel()
    ship = np.asarray(
        gen(15, (1, N), 8036, 8036 + 2556, cpu0)
    ).ravel()
    t0 = time.perf_counter()
    keep = (
        (ship >= 8766) & (ship < 9131) & (disc >= 5) & (disc <= 7)
        & (qty < 2400)
    )
    want6 = int(
        np.sum(np.where(keep, price.astype(np.int64) * disc, 0))
    )
    q6_cpu = time.perf_counter() - t0
    assert got6 == want6, (got6, want6)
    del qty
    record["sf100_rows"] = N
    record["sf100_platform"] = _leg_platform()
    record["q6_sf100_rows_per_sec"] = round(N / q6_best)
    record["q6_sf100_vs_baseline"] = round(q6_cpu / q6_best, 3)
    _phase("sf100 q6 measured", t_start)
    print(json.dumps(record), flush=True)

    # ---- Q3 at SF100: swap qty column for the orderkey ----
    dt = fx.cache._tables[("lineitem", (node_li,))]
    del dt.columns["l_quantity"]
    dt.columns["l_orderkey"] = jax.device_put(
        gen(11, (1, N), 1, NO + 1, jax.devices()[0])
    )
    dt.validity["l_orderkey"] = None
    dt.col_range["l_orderkey"] = (1, NO)
    dt.col_maxabs["l_orderkey"] = float(NO)
    Q3_SF = (
        "select l_orderkey, sum(l_extendedprice * (10 - l_discount)), "
        "o_orderdate, o_shippriority "
        "from customer, orders, lineitem "
        "where c_mktsegment = 0 and c_custkey = o_custkey "
        "and l_orderkey = o_orderkey and o_orderdate < 9204 "
        "and l_shipdate > 9204 "
        "group by l_orderkey, o_orderdate, o_shippriority "
        "order by 2 desc, o_orderdate limit 10"
    )
    q3sf_c0 = _dag_completed(c3)
    got3 = s4.query(Q3_SF)
    _phase(
        f"sf100 q3 compiled (mode={_q3_modes(c3, q3sf_c0)[0]})", t_start
    )
    q3_best = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        s4.query(Q3_SF)
        q3_best = min(q3_best, time.perf_counter() - t0)
    okey = np.asarray(gen(11, (1, N), 1, NO + 1, cpu0)).ravel()
    ocust = np.asarray(gen(21, (1, NO), 1, NC + 1, cpu0)).ravel()
    odate = np.asarray(
        gen(22, (1, NO), 8036, 8036 + 2405, cpu0)
    ).ravel()
    seg = np.asarray(gen(31, (1, NC), 0, 5, cpu0)).ravel()
    t0 = time.perf_counter()
    building = np.zeros(NC + 1, dtype=bool)
    building[np.arange(1, NC + 1)[seg == 0]] = True
    okeep = (odate < 9204) & building[ocust]
    okmask = np.zeros(NO + 1, dtype=bool)
    okmask[np.arange(1, NO + 1)[okeep]] = True
    keep = (ship > 9204) & okmask[okey]
    rev = np.bincount(
        okey[keep],
        weights=(
            price[keep].astype(np.int64) * (10 - disc[keep])
        ),
        minlength=NO + 1,
    )
    top = np.argpartition(rev, -10)[-10:]
    top = top[np.argsort(-rev[top])]
    q3_cpu = time.perf_counter() - t0
    assert got3 and got3[0][0] == int(top[0]) and (
        got3[0][1] == int(rev[top[0]])
    ), (got3[:2], top[:2], rev[top[0]])
    record["q3_sf100_rows_per_sec"] = round(N / q3_best)
    record["q3_sf100_vs_baseline"] = round(q3_cpu / q3_best, 3)
    record["q3_sf100_mode"], record["q3_sf100_join_modes"] = _q3_modes(
        c3, q3sf_c0
    )
    _phase("sf100 q3 measured", t_start)
    print(json.dumps(record), flush=True)


if __name__ == "__main__":
    sys.exit(_gate(main()))
