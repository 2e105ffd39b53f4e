#!/usr/bin/env bash
# Tier-1 gate wrapper (ROADMAP.md "Tier-1 verify"):
#
#   1. python -m compileall  — syntax breakage fails in seconds, before
#      the 1500 s pytest budget is spent;
#   2. static analysis: otb_lint --check against tools/lint_baseline.json
#      (the ratchet — NEW invariant violations fail here in seconds);
#   3. race analysis: otb_race --check against tools/race_baseline.json
#      (the lockset ratchet — a NEW guarded/unguarded mix, check-then-
#      act, or finally-less release fails here in seconds), then the
#      racewatch chaos smoke: one fixed-seed chaos schedule under
#      OTB_RACEWATCH=1 with every @shared_state class instrumented —
#      any non-baselined disjoint-lockset race fails;
#   4. lockwatch smoke: a wire-driven concurrent workload under
#      OTB_LOCKWATCH=1 — any non-allowlisted lock-order cycle fails;
#   5. the fast WLM smoke subset (tests/test_wlm.py, ~15 s) — the
#      admission-control layer sits in front of every statement, so a
#      regression there poisons everything downstream;
#   6. an observability smoke (obs/): EXPLAIN (ANALYZE, VERBOSE) of a
#      2-DN sharded join must print per-node rows, and a traced query
#      must export parseable Chrome-trace JSON;
#   7. matview / chaos / HA-chaos-schedule / telemetry /
#      join-mode+perf-gate / delta-plane-HTAP / serving /
#      multi-CN-serving smokes;
#   8. the full ROADMAP tier-1 pytest command, verbatim (1500 s cap).
#
# Usage: tools/tier1.sh   (from anywhere; cd's to the repo root)

set -o pipefail
cd "$(dirname "$0")/.." || exit 1
export JAX_PLATFORMS=cpu

echo "== tier1: compileall =="
python -m compileall -q opentenbase_tpu || exit 1

echo "== tier1: static analysis (otb_lint ratchet) =="
# fails ONLY on findings absent from tools/lint_baseline.json — new
# debt. Pre-existing entries are burned down PR by PR; a reviewed
# addition regenerates the baseline with --update-baseline. Runs
# before the 1500 s pytest budget so an invariant break (unread GUC,
# removed jax API, shutdown-less close, FAULTless boundary, int32
# cumsum, unhandled wire op, bogus SQLSTATE) surfaces in seconds.
timeout -k 10 120 python -m opentenbase_tpu.cli.otb_lint --check || exit 1

echo "== tier1: race analysis (otb_race lockset ratchet) =="
# the static half of otb_race: lockset inference over every class in
# the tree — a NEW attribute accessed both with and without its
# inferred guard (or a check-then-act read, or an acquire whose
# release isn't in a try/finally) fails here in seconds, against
# tools/race_baseline.json (same ratchet semantics as otb_lint)
timeout -k 10 120 python -m opentenbase_tpu.cli.otb_race --check || exit 1

echo "== tier1: racewatch chaos smoke (TSan-lite sanitizer) =="
timeout -k 10 420 env OTB_RACEWATCH=1 python - <<'PY' || exit 1
# The dynamic half: one fixed-seed chaos schedule (the PR 12 harness —
# deterministic concurrency stress with a promotion, fencing, resync)
# run with every @shared_state class instrumented. Two threads touching
# the same instance field with disjoint locksets and at least one write
# is a race; any race whose race-dynamic:: key is not in
# tools/race_baseline.json fails the stage (blessing one requires
# otb_race --bless-dynamic KEY --reason WHY). The schedule itself must
# also stay green: a sanitizer run that breaks the invariants it
# watches under proves nothing.
# Replay any failure: OTB_RACEWATCH=1 python -m opentenbase_tpu.cli.otb_chaos --seed 1107 --schedules 1
import json, sys, tempfile
from opentenbase_tpu.analysis import baseline as bl
from opentenbase_tpu.analysis import racewatch
from opentenbase_tpu.fault.schedule import ChaosSchedule, run_schedule

sched = ChaosSchedule.generate(1107, duration_s=4.0, num_datanodes=2)
v = run_schedule(sched, tempfile.mkdtemp(prefix="otbracewatch_"),
                 detect_ms=1100, beats=3)
doc = bl.load("tools/race_baseline.json")
new, baselined = racewatch.check_baseline(doc)
ok = (
    v["chaos_gate"] == "ok"
    and v.get("acked_writes", 0) > 0
    and not new
)
print(json.dumps({
    "racewatch_gate": "ok" if ok else "fail",
    "seed": v["seed"],
    "chaos_gate": v["chaos_gate"],
    "acked_writes": v.get("acked_writes"),
    "races_new": [f.key for f in new],
    "races_baselined": [f.key for f in baselined],
    "violations": v.get("violations"),
}))
if not ok:
    racewatch.report()
    sys.exit(1)
PY

echo "== tier1: lockwatch smoke (lock-order watchdog) =="
timeout -k 10 180 env OTB_LOCKWATCH=1 python - <<'PY' || exit 1
# Drive the statement lock through every class it has — shared reads,
# table-granular writers on overlapping and disjoint table sets, DDL
# (exclusive), and a 2PC-committing write — with the lock-order
# watchdog recording every acquisition. Any non-allowlisted cycle in
# the per-thread acquisition graph (a potential deadlock, caught from
# the ORDERS alone without needing the fatal interleaving) fails the
# stage. Prints a one-line JSON verdict like bench_gate.
import json, sys, threading
from opentenbase_tpu.analysis import lockwatch
from opentenbase_tpu.engine import Cluster
from opentenbase_tpu.net.client import connect_tcp
from opentenbase_tpu.net.server import ClusterServer

# Statements must flow over the WIRE: the shared lock classes
# (read() / write_tables() / exclusive, and the lmgr park paths) are
# taken by the net server's backend threads, not by in-process
# sessions — a lockwatch smoke that bypasses them watches nothing.
c = Cluster(num_datanodes=2, shard_groups=16)
srv = ClusterServer(c).start()
boot = connect_tcp(srv.host, srv.port)
boot.execute("set enable_fused_execution = off")
boot.execute("create table lwa (k bigint, v bigint) distribute by shard(k)")
boot.execute("create table lwb (k bigint, v bigint) distribute by shard(k)")
boot.execute("insert into lwa values " + ",".join(
    f"({i},{i})" for i in range(50)))

def reader():
    with connect_tcp(srv.host, srv.port) as x:
        for _ in range(8):
            x.query("select count(*), sum(v) from lwa")

def writer(tbl, base):
    with connect_tcp(srv.host, srv.port) as x:
        for j in range(8):
            x.execute(f"insert into {tbl} values ({base+j}, 1)")

def multi_table():
    # two-table write set: the sorted table-mutex path (the allowlisted
    # same-site hierarchy) actually runs
    with connect_tcp(srv.host, srv.port) as x:
        for j in range(4):
            x.execute(f"insert into lwb select k+{1000+j*100}, v "
                      f"from lwa where k < 5")

def ddl():
    with connect_tcp(srv.host, srv.port) as x:
        x.execute("create table lwc (k bigint) distribute by roundrobin")
        x.execute("drop table lwc")

errs = []
def run(fn, *a):
    # a dead driver thread must FAIL the stage — with the workers
    # crashed at iteration 0 the watchdog watches nothing and a green
    # verdict would be vacuous
    def wrapped():
        try:
            fn(*a)
        except BaseException as e:
            errs.append(f"{fn.__name__}: {e!r}")
    return threading.Thread(target=wrapped)

ths = [run(reader) for _ in range(3)]
ths += [run(writer, "lwa", 100), run(writer, "lwb", 200),
        run(multi_table), run(ddl)]
for t in ths: t.start()
for t in ths: t.join()
boot.close()
srv.stop()
c.close()
cycles = lockwatch.find_cycles()
n_edges = len(lockwatch.edges())
# the concurrent drive reliably orders >= 15 lock pairs (32 observed
# on landing); far fewer means the workload didn't actually run
ok = not cycles and not errs and n_edges >= 15
print(json.dumps({
    "lockwatch_gate": "ok" if ok else "fail",
    "ordered_pairs": n_edges, "cycles": len(cycles),
    "driver_errors": errs,
}))
if not ok:
    lockwatch.report()
    sys.exit(1)
PY

echo "== tier1: WLM smoke subset =="
timeout -k 10 120 python -m pytest tests/test_wlm.py -q -m 'not slow' \
    -p no:cacheprovider -p no:xdist -p no:randomly || exit 1

echo "== tier1: observability smoke =="
timeout -k 10 180 python - <<'PY' || exit 1
import json, tempfile, os
from opentenbase_tpu.engine import Cluster
from opentenbase_tpu.obs.export import export_chrome_trace

s = Cluster(num_datanodes=2, shard_groups=16).session()
s.execute("create table st (k bigint, v text) distribute by shard(k)")
s.execute("create table su (k bigint, w bigint) distribute by shard(k)")
s.execute("insert into st values (1,'a'),(2,'b'),(3,'c'),(4,'d')")
s.execute("insert into su values (1,10),(2,20),(3,30),(4,40)")
s.execute("set enable_fused_execution = off")
s.execute("set trace_queries = on")
lines = [r[0] for r in s.query(
    "explain (analyze, verbose) select st.v, sum(su.w) "
    "from st join su on st.k = su.k group by st.v"
)]
text = "\n".join(lines)
assert "on dn0:" in text and "on dn1:" in text, text  # per-node rows
assert any("rows=" in ln and "loops=2" in ln for ln in lines), text
assert any("motion rows=" in ln for ln in lines), text
out = os.path.join(tempfile.mkdtemp(prefix="otbtrace_"), "trace.json")
export_chrome_trace(s.cluster, out)
with open(out) as f:
    doc = json.load(f)  # must be parseable JSON
assert doc["traceEvents"], "empty trace export"
print(f"observability smoke OK: {len(doc['traceEvents'])} trace events")
PY

echo "== tier1: workload observatory smoke =="
timeout -k 10 180 python - <<'PY' || exit 1
# Workload observatory (obs/statements.py): a mixed workload must land
# ONE fingerprint-keyed pg_stat_statements row per statement shape
# (literals collapsed to $n), the device columns must move on fused
# runs (host columns on a host-only platform), the slow-query line
# must be parseable JSON carrying the full resource ledger + trace_id,
# and the exporter must render queryid-labeled per-statement series.
import json
from opentenbase_tpu.engine import Cluster
from opentenbase_tpu.obs.exporter import render_cluster_metrics

c = Cluster(num_datanodes=2, shard_groups=16)
s = c.session()
s.execute("create table ws (k bigint, v bigint) distribute by shard(k)")
s.execute("insert into ws values "
          + ",".join(f"({i},{i*2})" for i in range(50)))
s.execute("set trace_queries = on")
s.execute("set log_min_duration_statement = 0")
for i in range(1, 6):                      # 5 literals, ONE shape
    s.query(f"select v from ws where k = {i}")
for _ in range(3):                         # fused-eligible aggregate
    s.query("select sum(v) from ws")
s.execute("set log_min_duration_statement = -1")
ent = {r[1]: r for r in s.query(
    "select queryid, query, calls, device_ms, compile_ms, host_ms, "
    "h2d_bytes, platform from pg_stat_statements")}
point = ent["select v from ws where (k = $1)"]
assert point[2] == 5, point                # literals collapsed
agg = ent["select sum(v) from ws"]
assert agg[2] == 3, agg
plat = agg[7]
if plat and plat != "host":                # fused ran: device columns move
    assert agg[3] + agg[4] > 0 and agg[6] > 0, agg
else:                                      # platform-any: host columns move
    assert agg[5] > 0, agg
slow = [r for r in s.query("select pg_cluster_logs('log')")
        if r[3] == "slow_query" and "sum(v) from ws" in r[4]]
assert slow, "no slow-query line emitted"
ctx = json.loads(slow[-1][5])              # structured, parseable
assert ctx["queryid"] == agg[0] and ctx["trace_id"], ctx
for f in ("exec_ms", "device_ms", "host_ms", "wal_bytes", "wait_ms"):
    assert f in ctx["ledger"], (f, ctx["ledger"])
body = render_cluster_metrics(c)
for series in ("otb_stmt_calls", "otb_stmt_total_ms",
               "otb_stmt_device_ms", "otb_stmt_transfer_bytes"):
    assert f'{series}{{queryid="{agg[0]}"}}' in body, series
c.close()
print(f"workload observatory smoke OK: {len(ent)} fingerprints, "
      f"platform={plat or 'host'}")
PY

echo "== tier1: matview smoke =="
timeout -k 10 180 python - <<'PY' || exit 1
import tempfile
from opentenbase_tpu.engine import Cluster

d = tempfile.mkdtemp(prefix="otbmv_")
c = Cluster(num_datanodes=2, shard_groups=16, data_dir=d)
s = c.session()
s.execute("create table f (k bigint, g text, v bigint) "
          "distribute by shard(k)")
s.execute("insert into f values (1,'a',10),(2,'b',20),(3,'a',30)")
Q = "select g, count(*) as n, sum(v) as s from f group by g"
s.execute(f"create materialized view mv as {Q}")
s.execute("insert into f values (4,'b',40),(5,'c',50)")
s.execute("delete from f where k = 1")
s.execute("refresh materialized view mv")
st = s.query("select incremental_refreshes, full_refreshes, last_mode "
             "from pg_stat_matview")
assert st == [(1, 0, "incremental")], st  # the delta path ran
lines = [r[0] for r in s.query(f"explain {Q}")]
assert any("Matview rewrite" in ln for ln in lines), lines
s.execute("set enable_matview_rewrite = off")
want = sorted(s.query(Q))
assert sorted(s.query("select * from mv")) == want
c.close()  # crash
c2 = Cluster.recover(d, num_datanodes=2, shard_groups=16)
s2 = c2.session()
assert s2.query("select matviewname from pg_matviews") == [("mv",)]
s2.execute("insert into f values (6,'a',60)")
s2.execute("refresh materialized view mv")
st = s2.query("select incremental_refreshes, last_mode "
              "from pg_stat_matview")
assert st == [(2, "incremental")], st  # incremental across recovery
s2.execute("set enable_matview_rewrite = off")
assert sorted(s2.query("select * from mv")) == sorted(s2.query(Q))
c2.close()
print("matview smoke OK: incremental refresh + rewrite + recovery")
PY

echo "== tier1: chaos smoke =="
timeout -k 10 180 python - <<'PY' || exit 1
# Arm a DN-crash failpoint, run a distributed query, assert the read
# healed itself (retry + failover) and the pg_stat_faults / pg_stat_2pc
# counters moved, clear the faults, rerun clean (fault/ subsystem).
import tempfile
from opentenbase_tpu import fault
from opentenbase_tpu.dn.server import DNServer
from opentenbase_tpu.engine import Cluster
from opentenbase_tpu.storage.replication import WalSender

d = tempfile.mkdtemp(prefix="otbchaos_")
c = Cluster(num_datanodes=2, shard_groups=16, data_dir=f"{d}/cn")
s = c.session()
s.execute("set enable_fused_execution = off")
s.execute("create table t (k bigint, v bigint) distribute by shard(k)")
s.execute("insert into t values " + ",".join(
    f"({i},{i*3})" for i in range(200)))
sender = WalSender(c.persistence)
dns = [DNServer(f"{d}/dn{n}", sender.host, sender.port, 2, 16).start()
       for n in (0, 1)]
for n, dn in enumerate(dns):
    c.attach_datanode(n, "127.0.0.1", dn.port, pool_size=2,
                      rpc_timeout=60)
want = s.query("select count(*), sum(v) from t")
s.execute("set fault_injection = on")
s.execute("set fragment_retries = 1")
s.execute("set fragment_retry_backoff_ms = 5")
s.execute("select pg_fault_inject('dn/exec_fragment', 'crash_node',"
          " 'node=1, once')")
assert s.query("select count(*), sum(v) from t") == want  # self-healed
act = {r[0]: r for r in s.query(
    "select session_id, frag_retries, frag_failovers "
    "from pg_stat_cluster_activity")}[s.session_id]
assert act[1] >= 1 and act[2] >= 1, act
fired = dict((tuple(r[:2]), r[2]) for r in s.query(
    "select node, site, fired from pg_stat_faults"))
assert fired.get(("cn", "dn/exec_fragment"), 0) >= 1, fired
st = dict(s.query("select stat, value from pg_stat_2pc"))
assert s.query("select pg_resolve_indoubt()") == []  # nothing in doubt
st2 = dict(s.query("select stat, value from pg_stat_2pc"))
assert st2["resolver_runs"] == st.get("resolver_runs", 0) + 1, st2
s.execute("select pg_fault_clear()")
dns[1]._revive()
assert s.query("select count(*), sum(v) from t") == want  # clean rerun
assert fault.armed() == {}
for n in (0, 1):
    c.detach_datanode(n)
for dn in dns:
    dn.stop()
sender.stop()
c.close()
print("chaos smoke OK: crash_node -> retry+failover, counters moved, "
      "clean rerun")
PY

echo "== tier1: self-healing HA chaos-schedule smoke =="
timeout -k 10 240 python - <<'PY' || exit 1
# One fixed-seed chaos schedule end to end (fault/schedule.py + ha.py):
# background drop_conn / delay / wal_torn faults armed, a DN crashed
# and revived, a kill inside the promotion window, then the primary
# crashed under live read-write traffic -> the HA monitor must declare
# it dead within the detection budget and auto-promote the most
# caught-up standby; afterwards the invariant checker must be green:
# zero lost committed writes, zero stale-generation reads or accepted
# writes (the revived ex-primary refuses with SQLSTATE 72000), every
# in-doubt gid resolved to its WAL decision, and the ex-primary
# rewound + resynced as the new standby serving identical rows.
# Replay any failure: python -m opentenbase_tpu.cli.otb_chaos
#   --seed 1107 --schedules 1
import json, sys, tempfile
from opentenbase_tpu.fault.schedule import ChaosSchedule, run_schedule

sched = ChaosSchedule.generate(1107, duration_s=5.0, num_datanodes=2)
v = run_schedule(sched, tempfile.mkdtemp(prefix="otbha_"),
                 detect_ms=1100, beats=3)
ok = (
    v["chaos_gate"] == "ok"
    and v.get("promotions") == 1
    and v.get("acked_writes", 0) > 0
    and v.get("fenced_probe") == "refused"
    and v.get("resync", {}).get("rows") == v.get("final_rows")
)
print(json.dumps({
    "ha_chaos_gate": "ok" if ok else "fail",
    "seed": v["seed"],
    "acked_writes": v.get("acked_writes"),
    "detect_latency_ms": v.get("detect_latency_ms"),
    "promotions": v.get("promotions"),
    "generation": v.get("generation"),
    "violations": v.get("violations"),
}))
if not ok:
    sys.exit(1)
PY

echo "== tier1: partition chaos smoke (connectivity matrix + lease) =="
timeout -k 10 240 python - <<'PY' || exit 1
# One fixed-seed asymmetric-partition schedule (fault/partition.py +
# fault/schedule.py): the connectivity matrix cuts monitor->cn0 and
# cn0->every-DN while CLIENTS still reach cn0, under live traffic.
# The serving lease must make the reachable-but-partitioned primary
# self-demote BEFORE serving any statement: the invariant checker is
# green only if zero acked writes were lost, zero reads were stale,
# the deposed primary refused its own warmed result-cache probe with
# SQLSTATE 72000 after the heal, and the ex-primary rejoined as a
# standby serving identical rows.
# Replay any failure: python -m opentenbase_tpu.cli.otb_chaos
#   --schedule partition --seed 1201 --schedules 1 --scenarios asymmetric
import json, sys, tempfile
from opentenbase_tpu.fault.schedule import run_partition_schedule

v = run_partition_schedule(
    1201, tempfile.mkdtemp(prefix="otbpart_"),
    scenario="asymmetric", duration_s=4.0,
)
ok = (
    v["chaos_gate"] == "ok"
    and v.get("promotions") == 1
    and v.get("acked_writes", 0) > 0
    and v.get("probe_cache_hit_warm") is True
    and v.get("fenced_probe") == "refused"
    and v.get("lease", {}).get("self_demotions", 0) >= 1
    and v.get("lost_acked_writes") == 0
    and v.get("stale_reads") == 0
)
print(json.dumps({
    "partition_chaos_gate": "ok" if ok else "fail",
    "seed": v["seed"],
    "scenario": v["scenario"],
    "acked_writes": v.get("acked_writes"),
    "detect_latency_ms": v.get("detect_latency_ms"),
    "lease": v.get("lease"),
    "violations": v.get("violations"),
}))
if not ok:
    sys.exit(1)
PY

echo "== tier1: telemetry smoke =="
timeout -k 10 180 python - <<'PY' || exit 1
# Telemetry plane (obs/log.py + exporter + health): start a cluster with
# the metrics_port GUC, scrape twice and assert a known counter moved,
# then arm crash_node on a DN and reconstruct the whole incident from
# telemetry alone — fault firing, retries, failover in pg_cluster_logs;
# the DN down then revived in pg_cluster_health.
import socket, tempfile
from opentenbase_tpu import fault
from opentenbase_tpu.dn.server import DNServer
from opentenbase_tpu.engine import Cluster
from opentenbase_tpu.obs.exporter import scrape
from opentenbase_tpu.storage.replication import WalSender

probe = socket.socket(); probe.bind(("127.0.0.1", 0))
mport = probe.getsockname()[1]; probe.close()
d = tempfile.mkdtemp(prefix="otbtelsmoke_")
import os; os.makedirs(f"{d}/cn")
with open(f"{d}/cn/opentenbase.conf", "w") as f:
    f.write(f"metrics_port = {mport}\n")
c = Cluster(num_datanodes=2, shard_groups=16, data_dir=f"{d}/cn")
s = c.session()
s.execute("set enable_fused_execution = off")
s.execute("create table t (k bigint, v bigint) distribute by shard(k)")
s.execute("insert into t values " + ",".join(f"({i},{i*2})" for i in range(120)))
b1 = scrape("127.0.0.1", mport)
s.execute("select count(*), sum(v) from t")
b2 = scrape("127.0.0.1", mport)
def execs(b):
    for ln in b.splitlines():
        if ln.startswith('otb_phase_duration_ms_count{phase="execute"}'):
            return float(ln.rpartition(" ")[2])
    return 0.0
assert execs(b2) > execs(b1), "execute-phase counter did not move"
sender = WalSender(c.persistence)
dns = [DNServer(f"{d}/dn{n}", sender.host, sender.port, 2, 16).start()
       for n in (0, 1)]
for n, dn in enumerate(dns):
    c.attach_datanode(n, "127.0.0.1", dn.port, pool_size=2, rpc_timeout=60)
want = s.query("select count(*), sum(v) from t")
s.execute("set fault_injection = on")
s.execute("set fragment_retries = 1")
s.execute("set fragment_retry_backoff_ms = 5")
s.execute("select pg_fault_inject('dn/exec_fragment', 'crash_node',"
          " 'node=1, once')")
assert s.query("select count(*), sum(v) from t") == want  # self-healed
h = {r[0]: r[2] for r in s.query("select * from pg_cluster_health")}
assert h["dn1"] is False and h["dn0"] is True, h          # DN down
s.execute("select pg_fault_clear()")
dns[1]._revive()
h = {r[0]: r[2] for r in s.query("select * from pg_cluster_health")}
assert h["dn1"] is True, h                                # DN revived
logs = s.query("select pg_cluster_logs()")
msgs = {(r[2], r[3]): [] for r in logs}
for r in logs: msgs[(r[2], r[3])].append(r[4])
assert any("fault fired" in m for m in msgs.get(("dn1", "fault"), [])), msgs
assert any("retrying" in m for m in msgs.get(("cn0", "executor"), [])), msgs
assert any("failed over" in m for m in msgs.get(("cn0", "executor"), [])), msgs
assert [r[0] for r in logs] == sorted(r[0] for r in logs)  # time-ordered
b3 = scrape("127.0.0.1", mport)
assert "otb_fault_hits_total" in b3                       # fault counters render
assert "otb_dn_up" in b3 and "otb_replication_lag_bytes" in b3

# cross-node trace stitch: ONE traced statement must export spans from
# >= 3 distinct nodes (CN + DN server processes + GTM) under one
# trace_id, with the per-node process_name tracks in place
import json as _json
s.execute("set trace_queries = on")
s.query("select count(*), sum(v) from t")
s.execute("set trace_queries = off")
doc = _json.loads(s.query("select pg_export_traces(5)")[0][0])
meta = {e["args"]["name"]: e["pid"]
        for e in doc["traceEvents"] if e.get("ph") == "M"}
assert "cn0" in meta and "gtm0" in meta and "dn0" in meta, meta
by_trace = {}
for e in doc["traceEvents"]:
    if e.get("ph") != "X": continue
    tid = (e.get("args") or {}).get("trace_id")
    if tid: by_trace.setdefault(tid, set()).add(e["pid"])
assert any(len(pids) >= 3 for pids in by_trace.values()), \
    {t: len(p) for t, p in by_trace.items()}

# device-platform watchdog: a forced demotion (expect TPU, run on this
# CPU box) is observable within one statement — counter on a scrape,
# platform in pg_cluster_health, elog(warning) in pg_cluster_logs
s.execute("set enable_fused_execution = on")
s.execute("set expected_device_platform = tpu")
s.query("select count(*) from t")
h = {r[0]: r for r in s.query("select * from pg_cluster_health")}
assert h["cn0"][7] == "cpu", h["cn0"]
b4 = scrape("127.0.0.1", mport)
demo = [ln for ln in b4.splitlines()
        if ln.startswith("otb_platform_demotions_total")]
assert demo and float(demo[0].rpartition(" ")[2]) >= 1, demo
wlogs = s.query("select pg_cluster_logs('warning')")
assert any(r[3] == "device" and "demoted" in r[4] for r in wlogs), wlogs

for n in (0, 1): c.detach_datanode(n)
for dn in dns: dn.stop()
sender.stop(); c.close(); fault.reset_stats()
print("telemetry smoke OK: scrape moved, chaos run reconstructed "
      "from logs + health, cross-node trace stitched, platform "
      "watchdog fired")
PY

echo "== tier1: join-mode + perf-gate smoke =="
timeout -k 10 180 python - <<'PY' || exit 1
# Join-mode smoke (ops/join.py radix path + executor mode selection) and
# the perf-regression gate: a tiny join must answer identically under
# BOTH formulations on BOTH executors, EXPLAIN must say which mode ran
# (a mode-selection regression fails HERE, not in the next TPU bench),
# the checked-in BENCH_FLOORS.json must validate against its schema, and
# the gate must fail a synthetic floor violation and a forced demotion.
import os
from opentenbase_tpu import bench_gate
from opentenbase_tpu.engine import Cluster

s = Cluster(num_datanodes=2, shard_groups=16).session()
s.execute("create table jd (k bigint, g int) distribute by roundrobin")
s.execute("create table jf (k bigint, v bigint) distribute by roundrobin")
s.execute("insert into jd values "
          + ",".join(f"({i*5+2}, {i})" for i in range(30)))
s.execute("insert into jf values "
          + ",".join(f"({(i%40)*5+2}, {i})" for i in range(900)))
s.execute("analyze")
Q = "select g, sum(v) from jf, jd where jf.k = jd.k group by g order by g"
res = {}
for mode in ("radix", "sortmerge"):
    s.execute(f"set join_mode = {mode}")
    res[mode] = s.query(Q)
assert res["radix"] == res["sortmerge"], "fused join-mode parity broke"
s.execute("set join_mode = radix")
lines = [r[0] for r in s.query(f"explain analyze {Q}")]
assert any("Fused join modes:" in ln and "radix" in ln for ln in lines), lines
s.execute("set enable_fused_execution = off")
os.environ["OTB_JOIN_MODE"] = "radix"
hostrows = s.query(Q)
lines = [r[0] for r in s.query(f"explain analyze {Q}")]
del os.environ["OTB_JOIN_MODE"]
assert hostrows == res["radix"], "host radix parity broke"
assert any(ln.strip().startswith("Join") and "(radix)" in ln
           for ln in lines), lines
doc = bench_gate.load_floors()  # raises on schema errors
green = {"platform": "tpu"}
for m, spec in doc["floors"].items():
    green[m] = spec["floor"] * 2
assert bench_gate.check_record(green, doc) == []
bad = dict(green); bad["q3_rows_per_sec"] = 1
assert any("q3_rows_per_sec" in v
           for v in bench_gate.check_record(bad, doc))
dem = dict(green); dem["platform"] = "cpu"
assert any("demotion" in v for v in bench_gate.check_record(dem, doc))
print("join smoke OK: radix == sortmerge (fused+host), EXPLAIN shows "
      "mode, floors validate, gate fails violation+demotion")
PY

echo "== tier1: delta-plane HTAP smoke =="
timeout -k 10 180 python - <<'PY' || exit 1
# Scannable delta plane (ISSUE-15): an ingest burst followed by an
# immediate SELECT must complete WITHOUT folding (pg_stat_wal
# deltas_absorbed unchanged), without a device-cache rebuild
# (full_uploads flat), with the appended rows tail-uploaded straight
# from delta batches (pg_stat_fused delta_tail_uploads moved), EXPLAIN
# ANALYZE must show the delta-resident rows on the host path, and the
# checked-in HTAP floors must schema-validate with platform any.
from opentenbase_tpu import bench_gate
from opentenbase_tpu.engine import Cluster

c = Cluster(num_datanodes=2, shard_groups=16)
s = c.session()
s.execute("create table dp (k bigint, v bigint) distribute by shard(k)")
s.execute("insert into dp values " + ",".join(
    f"({i},{i * 2})" for i in range(1100)))
assert s.query("select count(*) from dp") == [(1100,)]  # warm the cache
wal0 = dict(s.query("select stat, value from pg_stat_wal"))
dc0 = dict(s.query("select stat, value from pg_stat_device_cache"))
# the burst -> immediate scan (read-after-write)
s.execute("insert into dp values " + ",".join(
    f"({2000 + i},{i})" for i in range(400)))
assert s.query("select count(*), sum(v) from dp") == [
    (1500, 2 * sum(range(1100)) + sum(range(400)))
]
wal = dict(s.query("select stat, value from pg_stat_wal"))
dc = dict(s.query("select stat, value from pg_stat_device_cache"))
fu = dict(s.query("select event, detail from pg_stat_fused"))
assert wal["deltas_absorbed"] == wal0["deltas_absorbed"], \
    (wal["deltas_absorbed"], wal0["deltas_absorbed"])  # fold is GONE
assert wal["pending_delta_rows"] > 0, wal
assert dc["full_uploads"] == dc0["full_uploads"], (dc0, dc)
assert int(fu["delta_tail_uploads"]) >= 1, fu
assert int(fu["fold_on_read_avoided"]) >= 1, fu
# EXPLAIN ANALYZE scan rows show the delta-resident count (host path)
s.execute("set enable_fused_execution = off")
lines = [r[0] for r in s.query(
    "explain analyze select count(*) from dp where v >= 0")]
assert any("delta-resident:" in ln for ln in lines), lines[:6]
# UPDATE/DELETE target delta rows without folding; fused == host
s.execute("set enable_fused_execution = on")
s.execute("update dp set v = v + 1 where k >= 2000 and k < 2010")
s.execute("delete from dp where k = 2399")
fused = sorted(s.query("select k, v from dp where k >= 2000"))
s.execute("set enable_fused_execution = off")
host = sorted(s.query("select k, v from dp where k >= 2000"))
assert fused == host and len(fused) == 399
wal2 = dict(s.query("select stat, value from pg_stat_wal"))
assert wal2["deltas_absorbed"] == wal0["deltas_absorbed"], wal2
# HTAP floors: present, platform any, schema-valid (load_floors raises)
doc = bench_gate.load_floors()
for m in ("htap_rows_per_sec", "htap_fold_avoided", "htap_speedup"):
    assert m in doc["floors"], m
    assert doc["floors"][m]["platform"] == "any", m
c.close()
print("delta-plane smoke OK: burst -> scan with zero folds, tail "
      f"uploads={fu['delta_tail_uploads']}, EXPLAIN shows "
      "delta-resident rows, htap floors validate")
PY

echo "== tier1: serving-plane smoke =="
timeout -k 10 180 python - <<'PY' || exit 1
# Serving plane (serving/ + net/concentrator.py): prepared and ad-hoc
# executions of the same query must answer identically THROUGH the
# shared plan cache (hit counters prove the path), a result-cache hit
# must invalidate on the next committed write, a concentrator with
# more clients than backends must round-trip them all with session
# pinning intact, and the checked-in serving floors must validate.
import json, struct, socket, sys
from opentenbase_tpu import bench_gate
from opentenbase_tpu.engine import Cluster
from opentenbase_tpu.net.concentrator import PgConcentrator

c = Cluster(num_datanodes=2, shard_groups=16)
s = c.session()
s.execute("set enable_fused_execution = off")
s.execute("create table sv (k bigint, g bigint, v bigint) "
          "distribute by shard(k)")
s.execute("insert into sv values " + ",".join(
    f"({i},{i%5},{i*3})" for i in range(200)))
Q = "select g, count(*), sum(v) from sv where g < 4 group by g order by g"
adhoc = s.query(Q)
s2 = c.session()
s2.execute("set enable_fused_execution = off")
s2.execute("prepare p as select g, count(*), sum(v) from sv "
           "where g < $1 group by g order by g")
pc0 = dict(s2.query("select stat, value from pg_stat_plan_cache"))
prepared = s2.query("execute p(4)")
pc1 = dict(s2.query("select stat, value from pg_stat_plan_cache"))
assert prepared == adhoc, (prepared, adhoc)          # parity
assert pc1["hits"] == pc0["hits"] + 1, (pc0, pc1)    # shared-cache hit
lines = [r[0] for r in s.query(f"explain analyze {Q}")]
assert any("plan_cache=hit" in ln for ln in lines), lines[:3]
s.execute("set enable_result_cache = on")
a = s.query(Q); b = s.query(Q)
rc = dict(s.query("select stat, value from pg_stat_result_cache"))
assert a == b and rc["hits"] >= 1, rc
s2.execute("insert into sv values (999, 1, 5)")
a2 = s.query(Q)
assert a2 != a, "result cache served stale rows after a committed write"
rc2 = dict(s.query("select stat, value from pg_stat_result_cache"))
assert rc2["invalidations"] >= 1, rc2

# concentrator: 6 clients over 2 backends, all round-trip; SET pins
conc = PgConcentrator(c, backends=2, queue_depth=64).start()
class Cli:
    def __init__(self):
        self.sock = socket.create_connection((conc.host, conc.port), timeout=30)
        body = struct.pack("!I", 196608) + b"user\0smoke\0\0"
        self.sock.sendall(struct.pack("!I", len(body) + 4) + body)
        self.drain()
    def rd(self, k):
        buf = b""
        while len(buf) < k:
            ch = self.sock.recv(k - len(buf)); assert ch; buf += ch
        return buf
    def drain(self):
        rows = []; err = None
        while True:
            tag = self.rd(1); (ln,) = struct.unpack("!I", self.rd(4))
            body = self.rd(ln - 4)
            if tag == b"D":
                (ncol,) = struct.unpack("!H", body[:2]); off = 2; row = []
                for _ in range(ncol):
                    (l2,) = struct.unpack_from("!i", body, off); off += 4
                    row.append(None if l2 == -1 else body[off:off+l2].decode())
                    off += max(l2, 0)
                rows.append(tuple(row))
            elif tag == b"E": err = body
            elif tag == b"Z":
                if err: raise RuntimeError(err.decode(errors="replace"))
                return rows
    def q(self, sql):
        b = sql.encode() + b"\0"
        self.sock.sendall(b"Q" + struct.pack("!I", len(b) + 4) + b)
        return self.drain()

clis = [Cli() for _ in range(6)]
want = [tuple(str(x) for x in r) for r in s.query(Q)]
for cl in clis:
    assert cl.q(Q) == want
clis[0].q("set application_name = smoketest")
assert clis[0].q("show application_name") == [("smoketest",)]
assert clis[1].q("show application_name") != [("smoketest",)]
st = dict(conc.stat_rows())
assert st["clients"] == 6 and st["backends"] == 2 and st["pinned"] == 1, st
for cl in clis: cl.sock.close()
conc.stop()
c.close()
doc = bench_gate.load_floors()  # raises on schema errors
for m in ("serving_stmts_per_sec", "serving_speedup"):
    assert m in doc["floors"], f"missing serving floor {m}"
    assert doc["floors"][m]["platform"] == "any", m
print(json.dumps({"serving_gate": "ok",
                  "plan_cache_hits": pc1["hits"],
                  "result_invalidations": rc2["invalidations"]}))
PY

echo "== tier1: multi-CN serving smoke =="
timeout -k 10 240 python - <<'PY' || exit 1
# Multi-coordinator serving plane (coord/): boot 2 CNs + 1 hot standby.
# DDL on CN-A must force CN-B to RE-PLAN (the streamed D-record bumps
# the peer's catalog epoch -> plan-cache miss, then hit again), a write
# forwarded from CN-B must be readable by its own next local read, a
# replica read must route under max_staleness with the staleness proof
# in-bound, and one seeded chaos schedule (primary CN killed
# mid-DDL-stream) must end green: zero lost acked writes, zero stale
# cache hits.
import json, tempfile
from opentenbase_tpu.coord.peer import PeerCoordinator
from opentenbase_tpu.coord.replica import StandbyTarget
from opentenbase_tpu.engine import Cluster
from opentenbase_tpu.fault.schedule import run_multicn_schedule
from opentenbase_tpu.net.server import ClusterServer
from opentenbase_tpu.storage.replication import StandbyCluster, WalSender

d = tempfile.mkdtemp(prefix="otbmcn_")
c = Cluster(num_datanodes=2, shard_groups=16, data_dir=f"{d}/cn0")
s = c.session()
s.execute("create table mt (k bigint, v bigint) distribute by shard(k)")
s.execute("insert into mt values " + ",".join(
    f"({i},{i*3})" for i in range(100)))
sender = WalSender(c.persistence, poll_s=0.005)
server = ClusterServer(c).start()
peer = PeerCoordinator(f"{d}/cn1", num_datanodes=2, shard_groups=16,
                       name="cn1").follow(sender.host, sender.port,
                                          "127.0.0.1", server.port)
sb = StandbyCluster(f"{d}/sb", 2, 16).start_replication(
    sender.host, sender.port)
assert peer.wait_applied(c.persistence.wal.position, 10.0)
assert sb.wait_caught_up(c.persistence, 10.0)
c.replica_targets.append(StandbyTarget("sb0", sb))
# DDL on CN-A -> CN-B re-plans (miss), then caches again (hit)
ps = peer.cluster.session()
ps.execute("set enable_plan_cache = on")
Q = "select v from mt where k = 7"
assert ps.query(Q) == [(21,)] and ps.query(Q) == [(21,)]
assert ps._last_plan_cache == "hit"
s.execute("alter table mt add column w bigint")
assert peer.wait_applied(c.persistence.wal.position, 10.0)
assert ps.query(Q) == [(21,)]
assert ps._last_plan_cache == "miss", "stale plan survived remote DDL"
assert ps.query(Q) == [(21,)]
assert ps._last_plan_cache == "hit"
pc = dict(ps.query("select stat, value from pg_stat_plan_cache"))
assert pc["last_invalidation_epoch"] >= 0 and pc["invalidations"] >= 1
# a write forwarded from CN-B is readable by its own next local read
ps.execute("insert into mt (k, v) values (555, 777)")
assert ps.query("select v from mt where k = 555") == [(777,)]
# replica read under max_staleness, staleness proof in-bound
assert sb.wait_caught_up(c.persistence, 10.0)
s.execute("set read_routing = replica")
s.execute("set max_staleness = '30s'")
assert s.query("select count(*) from mt") == [(101,)]
assert s._last_plan_cache == "routed", "read did not route to standby"
st = s.query("select pg_replica_status()")
assert st[0][0] == "sb0" and 0 <= st[0][3] < 30.0, st
server.stop(); sender.stop()
for closer in (sb.stop, peer.stop, c.close):
    try: closer()
    except Exception: pass
# seeded chaos: the primary CN killed mid-DDL-stream
v = run_multicn_schedule(1111, f"{d}/chaos", duration_s=2.5)
assert v["chaos_gate"] == "ok", v["violations"]
assert v["lost_acked_writes"] == 0 and v["ddl_acked"] >= 1
print(json.dumps({
    "multicn_gate": "ok",
    "peer_invalidations": pc["invalidations"],
    "chaos_acked_writes": v["acked_writes"],
    "chaos_ddl_acked": v["ddl_acked"],
    "chaos_lost_acked": v["lost_acked_writes"],
}))
PY

echo "== tier1: elastic rebalance smoke =="
timeout -k 10 240 python - <<'PY' || exit 1
# Elastic cluster (rebalance/): load a sharded table, ADD NODE under
# live writer traffic — zero failed statements, the shard map must
# cover the newcomer within 10% of byte-even (balance_verdict), and
# pg_stat_rebalance must show every wave done; then REMOVE NODE must
# drain the victim to zero owned shard groups with every row intact;
# finally one seeded crash schedule (coordinator killed mid-COPYING)
# must recover with zero lost acked writes.
import json, tempfile, threading, time
from opentenbase_tpu.engine import Cluster
from opentenbase_tpu.fault.schedule import run_rebalance_schedule

d = tempfile.mkdtemp(prefix="otbrb_")
c = Cluster(num_datanodes=2, shard_groups=32, data_dir=f"{d}/cn")
s = c.session()
s.execute("create table t (k bigint, v bigint) distribute by shard(k)")
s.execute("insert into t values " + ",".join(
    f"({i},{i*3})" for i in range(2000)))
stop = threading.Event(); acked = []; failures = []
def writer():
    ws = c.session(); i = 0
    while not stop.is_set():
        i += 1
        try:
            ws.execute(f"insert into t values ({10_000+i},{i})")
            acked.append(i)
        except Exception as e:
            failures.append(repr(e))
        time.sleep(0.002)
th = threading.Thread(target=writer, daemon=True); th.start()
time.sleep(0.1)
s.execute("alter cluster add node dn2 wait")
stop.set(); th.join(timeout=30)
assert failures == [], failures[:5]
verdict, spread = c.rebalance.balance_verdict()
assert verdict == "balanced" and spread <= 10.0, (verdict, spread)
assert s.query("select count(*) from t") == [(2000 + len(acked),)]
hist = s.query("select phase, rows_copied from pg_stat_rebalance")
assert hist and all(p == "done" for p, _r in hist), hist
s.execute("alter cluster remove node dn1 wait")
assert not bool((c.shardmap.map == 1).any())
assert s.query("select count(*) from t") == [(2000 + len(acked),)]
c.close()
v = run_rebalance_schedule(1109, f"{d}/chaos", "copying")
assert v["chaos_gate"] == "ok" and v["crashed_mid_move"], v
print(json.dumps({
    "rebalance_gate": "ok", "spread_pct": round(spread, 2),
    "writes_during_move": len(acked),
    "chaos_lost_acked": v["lost_acked_writes"],
}))
PY

echo "== tier1: full suite =="
rm -f /tmp/_t1.log
# 870s was calibrated against a 786s run of 664 tests; the suite is now
# 728 tests (join-device differential suite included) and a loaded
# shared runner measured 1257s — 1500s keeps the cap meaningful (a hang
# still trips it) without cutting a slow but healthy run short
timeout -k 10 1500 python -m pytest tests/ -q -m 'not slow' \
    --continue-on-collection-errors -p no:cacheprovider -p no:xdist \
    -p no:randomly 2>&1 | tee /tmp/_t1.log
rc=${PIPESTATUS[0]}
echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)
exit $rc
