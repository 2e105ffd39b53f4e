"""Seeded chaos schedules: randomized fault timelines over live
read-write traffic, with an invariant checker — fully replayable from
one seed (the Jepsen-nemesis shape, bolted onto the failpoint
registry and the self-healing HA plane).

A schedule is GENERATED deterministically from its seed: every event
time, target, action flavor, and probability is drawn at generate()
time from ``random.Random(seed)``, and while the run is active the
fault plane's own randomness — ALL ``prob(p)`` fault draws (including
faults armed with their own explicit seed: one schedule seed governs
the whole run, by design), connect backoff jitter, wal_torn tear
positions — routes through per-name child streams of the same seed
(``fault.set_chaos_seed``). Re-running the seed re-runs the same
chaos.

Every schedule mixes the whole menagerie (the acceptance contract):

- background **drop_conn** / **delay** probability faults on the
  coordinator→DN RPC plane,
- a **wal_torn** probability fault tearing the replication stream at
  byte-arbitrary positions,
- a **crash_node** on one datanode (with a later revive),
- a **crash_primary** (kill the coordinator under traffic) that the
  HAMonitor must detect and heal by auto-promotion,
- a **promotion-window kill**: a one-shot fault armed at the
  ``dn/promote`` site, so the monitor's first candidate dies (or
  errors) MID-PROMOTE and the failover must converge on the next one.

Invariants checked after the run (the verdict):

1. **zero lost committed writes** — every client-ACKED (client, seq)
   row is present exactly once after failover + resync;
2. **zero phantom/duplicate rows** — nothing appears that was never
   attempted, nothing appears twice;
3. **zero stale-generation reads or accepted writes** — reads must
   never regress below the client's acked watermark, and the revived
   ex-primary must refuse both a read and a write with SQLSTATE 72000;
4. **auto-promotion within the detection budget** —
   declared-dead latency <= failover_detect_ms + one beat + probe
   timeout;
5. **every in-doubt gid resolved to its WAL decision** — after the
   resolver runs, no DN holds a vote journal;
6. **the ex-primary resyncs** — rejoins as a standby, catches up to
   the promoted WAL position, and serves the same rows read-only.
"""

from __future__ import annotations

import os
import random
import threading
import time
from dataclasses import dataclass, field
from typing import Optional

from opentenbase_tpu import fault as _fault


@dataclass
class ChaosEvent:
    at_s: float          # offset from run start
    kind: str            # arm_fault | crash_node | revive_node |
    #                      crash_primary
    spec: dict = field(default_factory=dict)

    def describe(self) -> str:
        items = ", ".join(f"{k}={v}" for k, v in sorted(self.spec.items()))
        return f"t+{self.at_s:.2f}s {self.kind}({items})"


@dataclass
class ChaosSchedule:
    seed: int
    duration_s: float
    num_datanodes: int
    events: list = field(default_factory=list)
    writers: int = 3
    readers: int = 2

    @classmethod
    def generate(
        cls,
        seed: int,
        duration_s: float = 6.0,
        num_datanodes: int = 2,
    ) -> "ChaosSchedule":
        """Deterministic schedule for ``seed``: same seed, same events,
        same times, same targets — the replay contract."""
        rng = random.Random(seed)
        ev: list[ChaosEvent] = []
        # background probability faults, armed early. prob() draws are
        # themselves routed through the schedule's per-site streams at
        # runtime (fault.set_chaos_seed), so the SPECS don't need seeds.
        ev.append(ChaosEvent(0.1, "arm_fault", {
            "site": "net/pool/rpc_send", "action": "drop_conn",
            "spec": f"prob({rng.uniform(0.004, 0.02):.4f})",
        }))
        # delay rides dn/dispatch, NOT dn/exec_fragment: the registry
        # holds one fault per site and the crash_node event below must
        # not replace the delay (nor the revive's clear disarm it)
        ev.append(ChaosEvent(0.1, "arm_fault", {
            "site": "dn/dispatch",
            "action": f"delay({rng.randint(5, 40)})",
            "spec": f"prob({rng.uniform(0.01, 0.05):.4f})",
        }))
        ev.append(ChaosEvent(0.15, "arm_fault", {
            "site": "repl/wal_stream", "action": "wal_torn",
            "spec": f"prob({rng.uniform(0.2, 0.6):.3f})",
        }))
        # one DN crash + revive, somewhere in the first half
        victim = rng.randrange(num_datanodes)
        t_dn = rng.uniform(0.4, duration_s * 0.35)
        ev.append(ChaosEvent(t_dn, "crash_node", {"node": victim}))
        ev.append(ChaosEvent(
            t_dn + rng.uniform(0.8, 1.6), "revive_node", {"node": victim},
        ))
        # the promotion-window kill: armed BEFORE the primary crash so
        # the monitor's FIRST promote attempt dies inside the window.
        # 'error' fails the promote RPC and leaves the candidate as a
        # healthy standby; 'crash_node' takes the whole candidate down
        # (it revives with the final cleanup). Either way the failover
        # loop must converge on another candidate.
        kill_action = rng.choice(["error", "crash_node"])
        t_crash = rng.uniform(duration_s * 0.45, duration_s * 0.65)
        ev.append(ChaosEvent(t_crash - 0.05, "arm_fault", {
            "site": "dn/promote", "action": kill_action, "spec": "once",
        }))
        ev.append(ChaosEvent(t_crash, "crash_primary", {}))
        ev.sort(key=lambda e: e.at_s)
        return cls(
            seed=seed, duration_s=duration_s,
            num_datanodes=num_datanodes, events=ev,
        )


class _Traffic:
    """Live randomized read-write traffic through RoutingClients.
    Writers insert unique (client, seq) rows and record every ACK;
    readers verify acked-watermark monotonicity on every read."""

    def __init__(self, topo, schedule: ChaosSchedule):
        self.topo = topo
        self.schedule = schedule
        self.stop_evt = threading.Event()
        self.acked: dict[int, int] = {}      # client -> max acked seq
        self.acked_set: set = set()          # (client, seq)
        self.indeterminate: set = set()      # errored attempts
        self.stale_reads: list = []
        self.reads_ok = 0
        self._mu = threading.Lock()
        self.threads: list[threading.Thread] = []
        # set once a write was acked AND a read answered
        self.flowing = threading.Event()

    def start(self) -> None:
        for w in range(self.schedule.writers):
            t = threading.Thread(
                target=self._writer, args=(w,), daemon=True
            )
            t.start()
            self.threads.append(t)
        for r in range(self.schedule.readers):
            t = threading.Thread(
                target=self._reader, args=(r,), daemon=True
            )
            t.start()
            self.threads.append(t)

    def stop(self) -> None:
        self.stop_evt.set()
        for t in self.threads:
            t.join(timeout=30)

    def _note_progress(self) -> None:
        """Caller holds ``_mu``."""
        if self.acked_set and self.reads_ok:
            self.flowing.set()

    def _writer(self, cid: int) -> None:
        from opentenbase_tpu.ha import RoutingClient

        rng = _fault.chaos_rng(f"traffic/writer{cid}") or random.Random(
            cid
        )
        rc = RoutingClient(self.topo)
        seq = 0
        while not self.stop_evt.is_set():
            seq += 1
            # occasionally a two-row batch spanning shards (a
            # multi-node txn exercising the implicit-2PC ship path);
            # usually a single-node write riding sync-commit
            batch = [seq]
            if rng.random() < 0.3:
                seq += 1
                batch.append(seq)
            vals = ",".join(
                f"({cid}, {s}, {cid * 1000000 + s})" for s in batch
            )
            try:
                rc.execute(f"insert into chaos_t values {vals}")
                with self._mu:
                    for s in batch:
                        self.acked_set.add((cid, s))
                    self.acked[cid] = max(
                        self.acked.get(cid, 0), batch[-1]
                    )
                    self._note_progress()
            except Exception:
                with self._mu:
                    for s in batch:
                        self.indeterminate.add((cid, s))
                self.stop_evt.wait(0.05)
            self.stop_evt.wait(0.01 + rng.random() * 0.02)
        rc.close()

    def _reader(self, rid: int) -> None:
        from opentenbase_tpu.ha import RoutingClient

        rng = _fault.chaos_rng(f"traffic/reader{rid}") or random.Random(
            1000 + rid
        )
        rc = RoutingClient(self.topo)
        while not self.stop_evt.is_set():
            cid = rng.randrange(self.schedule.writers)
            with self._mu:
                floor = self.acked.get(cid, 0)
            try:
                rows = rc.query(
                    "select max(seq) from chaos_t "
                    f"where client = {cid}"
                )
                got = rows[0][0] or 0
                # an acked write is on every reachable standby
                # (synchronous_commit=on), so NO read — before or
                # after a failover — may show less than the acked
                # watermark captured before the read started
                if got < floor:
                    with self._mu:
                        self.stale_reads.append(
                            {"client": cid, "saw": int(got),
                             "acked_floor": int(floor)}
                        )
                else:
                    with self._mu:
                        self.reads_ok += 1
                        self._note_progress()
            except Exception:
                self.stop_evt.wait(0.05)
            self.stop_evt.wait(0.01 + rng.random() * 0.03)
        rc.close()


def run_schedule(
    schedule: ChaosSchedule,
    workdir: str,
    detect_ms: int = 1200,
    beats: int = 3,
    keep: bool = False,
    sync_mode: str = "on",
) -> dict:
    """Execute one seeded schedule end to end and return the verdict
    dict (chaos_gate ok/fail + every invariant's evidence).

    ``sync_mode`` is the cluster-wide ``synchronous_commit`` rung the
    run proves (ROADMAP item 4b — every mode must keep exactly what it
    promises, under the same crash schedule):

    - ``on`` / ``remote_write``: ZERO lost acked writes after the
      failover (remote-apply on every standby / quorum-acked receipt),
      and reads never regress below a client's acked watermark;
    - ``local`` / ``off``: the acked TAIL may be lost to the failover
      (replication is asynchronous), but the per-client lost run must
      be CONTIGUOUS — a survivor inside it is a replay hole, i.e.
      reordering, and fails; duplicates and phantoms fail in every
      mode."""
    from opentenbase_tpu.ha import HAMonitor, HATopology

    os.makedirs(workdir, exist_ok=True)
    verdict: dict = {
        "seed": schedule.seed,
        "sync_mode": sync_mode,
        "events": [e.describe() for e in schedule.events],
        "violations": [],
    }
    _fault.set_chaos_seed(schedule.seed)
    topo = None
    mon = None
    traffic = None
    try:
        topo = HATopology(
            workdir, schedule.num_datanodes, 32, conf_gucs={
                "enable_fused_execution": "off",
                "synchronous_commit": sync_mode,
                "failover_detect_ms": detect_ms,
                "failover_beats": beats,
                "fragment_retries": 1,
                "fragment_retry_backoff_ms": 5,
                # bound every statement: a straggler standby's WAL
                # wait must cut at the deadline and self-heal, not
                # park a traffic thread for the DN's full 90s budget
                "statement_timeout": 5000,
            },
        )
        boot = topo.active_cluster.session()
        boot.execute(
            "create table chaos_t (client bigint, seq bigint, v bigint)"
            " distribute by shard(seq)"
        )
        mon = HAMonitor(topo, detect_ms=detect_ms, beats=beats).start()
        traffic = _Traffic(topo, schedule)
        traffic.start()
        # the schedule's clock starts once traffic flows: on a loaded
        # host the first statements alone can outlast the first faults,
        # and the run would judge a cluster that never served (a
        # cluster that cannot serve at all still fails liveness below)
        traffic.flowing.wait(30)
        t0 = time.monotonic()
        crash_wall: Optional[float] = None
        for ev in schedule.events:
            delay = t0 + ev.at_s - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            if ev.kind == "arm_fault":
                _fault.inject(
                    ev.spec["site"], ev.spec["action"],
                    ev.spec.get("spec", ""),
                )
            elif ev.kind == "crash_node":
                _fault.inject(
                    "dn/exec_fragment", "crash_node",
                    f"node={ev.spec['node']}, once",
                )
            elif ev.kind == "revive_node":
                _fault.clear("dn/exec_fragment")
                topo.dns[ev.spec["node"]]._revive()
            elif ev.kind == "crash_primary":
                crash_wall = time.time()
                topo.crash_primary()
        # let the run play out, then quiesce
        left = t0 + schedule.duration_s - time.monotonic()
        if left > 0:
            time.sleep(left)
        # give the monitor room to finish healing before the checks
        deadline = time.time() + max(detect_ms / 1000.0 * 4, 8.0)
        while time.time() < deadline and topo.promoted_index is None:
            time.sleep(0.1)
        traffic.stop()
        mon.stop()
        # disarm every background fault; revive any still-crashed DN so
        # the invariant sweep can reach all vote journals, and make
        # sure every survivor follows the promoted timeline (a DN that
        # was crashed DURING the failover missed its repoint)
        _fault.clear()
        for dn in topo.dns:
            if dn._crashed:
                dn._revive()
        if topo.promoted_index is not None:
            host, wport = topo.active_wal_address()
            for j in range(len(topo.dns)):
                if j == topo.promoted_index:
                    continue
                try:
                    topo._dn_rpc(j, {
                        "op": "repl_repoint", "wal_host": host,
                        "wal_port": wport, "hgen": topo.generation,
                    })
                except Exception:
                    pass  # already on the new timeline, or truly gone
        _verify(schedule, topo, mon, traffic, crash_wall,
                detect_ms, beats, verdict, sync_mode)
    except Exception as e:  # harness failure IS a failed run
        verdict["violations"].append(
            {"invariant": "harness", "error": f"{type(e).__name__}: {e}"}
        )
    finally:
        _fault.clear()
        _fault.reset_stats()
        _fault.set_chaos_seed(None)
        if traffic is not None and not traffic.stop_evt.is_set():
            traffic.stop()
        if mon is not None:
            mon.stop()
        if topo is not None:
            topo.stop()
        if not keep:
            import shutil

            shutil.rmtree(workdir, ignore_errors=True)
    verdict["chaos_gate"] = "ok" if not verdict["violations"] else "fail"
    return verdict


def _verify(schedule, topo, mon, traffic, crash_wall,
            detect_ms, beats, verdict, sync_mode="on") -> None:
    from opentenbase_tpu.net.client import WireError, connect_tcp

    bad = verdict["violations"]
    # quiesce the data plane before judging it: the repointed
    # survivors may still be replaying the promoted timeline, and a
    # verify scan racing that catch-up would stall on the WAL wait
    # (a latency artifact, not an invariant violation — slow machines
    # made it flaky). Bounded: a DN that never catches up still gets
    # judged below, via the scan's own failover path.
    active0 = topo.active_cluster
    deadline = time.time() + 20
    while time.time() < deadline:
        pos = active0.persistence.wal.position
        pings = [topo.dn_ping(i) for i in range(len(topo.dns))]
        if all(
            p is not None and (
                p.get("promoted") or int(p.get("applied") or 0) >= pos
            )
            for p in pings
        ):
            break
        time.sleep(0.1)
    mon_stats = mon.stats()  # guarded snapshot of the beat counters
    verdict["acked_writes"] = len(traffic.acked_set)
    verdict["indeterminate_writes"] = len(traffic.indeterminate)
    verdict["reads_ok"] = traffic.reads_ok
    verdict["promotions"] = mon_stats["promotions"]
    verdict["generation"] = topo.generation

    # -- invariant 4: auto-promotion within the detection budget ------
    if crash_wall is not None:
        if topo.promoted_index is None:
            bad.append({"invariant": "auto_promotion",
                        "error": "primary crashed but nothing promoted"})
        elif mon_stats["declared_dead_at"] is not None:
            latency_ms = (mon_stats["declared_dead_at"] - crash_wall) * 1000.0
            budget_ms = detect_ms + detect_ms / beats + 600
            verdict["detect_latency_ms"] = round(latency_ms, 1)
            verdict["detect_budget_ms"] = round(budget_ms, 1)
            if latency_ms > budget_ms:
                bad.append({
                    "invariant": "detection_budget",
                    "latency_ms": round(latency_ms, 1),
                    "budget_ms": round(budget_ms, 1),
                })

    # -- invariant 3b: the revived ex-primary is FENCED ----------------
    if crash_wall is not None and topo.promoted_index is not None:
        srv = topo.revive_ex_primary()
        stale = connect_tcp(srv.host, srv.port)
        probe_outcome = "refused"
        try:
            for sql, what in (
                ("select max(seq) from chaos_t where client = 0",
                 "read"),
                ("insert into chaos_t values (999, 1, 1)", "write"),
            ):
                try:
                    stale.execute(sql)
                    probe_outcome = f"accepted_{what}"
                    bad.append({
                        "invariant": "stale_generation",
                        "error": f"ex-primary ACCEPTED a {what}",
                    })
                except WireError as e:
                    if getattr(e, "sqlstate", None) != "72000":
                        probe_outcome = "wrong_sqlstate"
                        bad.append({
                            "invariant": "stale_generation",
                            "error": f"{what} refused without the "
                            f"fenced SQLSTATE: {e.sqlstate} {e}",
                        })
        finally:
            stale.close()
        # the verdict must agree with the violations list — a probe
        # that got through is recorded as what actually happened
        verdict["fenced_probe"] = probe_outcome

    # -- invariant 5: every in-doubt gid resolved ----------------------
    active = topo.active_cluster
    try:
        resolved = active.resolve_indoubt()
        verdict["indoubt_resolved"] = [list(r) for r in resolved]
    except Exception as e:
        bad.append({"invariant": "indoubt",
                    "error": f"resolver failed: {e}"})
    leftover = []
    for i, dn in enumerate(topo.dns):
        for e in dn._twophase_list():
            leftover.append((i, e["gid"]))
    if leftover:
        bad.append({"invariant": "indoubt",
                    "error": f"unresolved vote journals: {leftover}"})

    # -- invariants 1+2: lost / phantom / duplicate rows ---------------
    s = active.session()
    # the verify scans must never be cut by the traffic-plane
    # statement budget: a straggler fragment fails over to the
    # coordinator's own caught-up copy instead
    s.execute("set statement_timeout = 0")
    rows = s.query("select client, seq from chaos_t")
    seen: dict = {}
    for cid, seq in rows:
        seen[(cid, seq)] = seen.get((cid, seq), 0) + 1
    dups = [k for k, n in seen.items() if n > 1]
    if dups:
        bad.append({"invariant": "no_duplicates",
                    "rows": dups[:10], "count": len(dups)})
    lost = [k for k in traffic.acked_set if k not in seen]
    verdict["lost_acked_writes"] = len(lost)
    if sync_mode in ("on", "remote_write"):
        # the remote rungs promise ZERO lost acked writes across the
        # failover (remote-apply / quorum-acked receipt)
        if lost:
            bad.append({"invariant": "zero_lost_committed_writes",
                        "rows": sorted(lost)[:10], "count": len(lost)})
    elif lost:
        # off/local: replication is asynchronous, so the acked TAIL
        # may die with the primary — ONE contiguous per-client run of
        # acked seqs ending at the failover cut (the writer keeps
        # writing on the promoted timeline afterwards, so LATER acked
        # survivors are expected and fine). What must never happen is
        # a SCATTERED loss — lost 41, survived 45, lost 47 — because
        # the WAL is ordered and promotion takes a standby's applied
        # prefix: a hole inside the lost run means a frame was
        # replayed out of order or dropped mid-stream.
        lost_by_client: dict = {}
        for cid, s in lost:
            lost_by_client.setdefault(cid, []).append(s)
        holes = []
        for cid, lseqs in lost_by_client.items():
            lo, hi = min(lseqs), max(lseqs)
            acked_in_run = [
                s for (c2, s) in traffic.acked_set
                if c2 == cid and lo <= s <= hi
            ]
            if len(lseqs) != len(acked_in_run):
                holes.append({
                    "client": cid, "lost": sorted(lseqs)[:10],
                    "acked_in_run": len(acked_in_run),
                })
        if holes:
            bad.append({"invariant": "lost_tail_contiguous",
                        "holes": holes[:10], "count": len(holes)})
    attempted = traffic.acked_set | traffic.indeterminate
    phantom = [k for k in seen if k not in attempted and k[0] != 999]
    if phantom:
        bad.append({"invariant": "no_phantom_rows",
                    "rows": sorted(phantom)[:10],
                    "count": len(phantom)})
    verdict["final_rows"] = len(rows)

    # -- invariant 3a: monotone / non-stale reads ----------------------
    verdict["stale_reads"] = len(traffic.stale_reads)
    if traffic.stale_reads and sync_mode in ("on", "remote_write"):
        # under off/local an acked write may legitimately be invisible
        # on the promoted standby, so the acked-watermark floor only
        # binds on the remote rungs (recorded above either way)
        bad.append({"invariant": "zero_stale_reads",
                    "cases": traffic.stale_reads[:10],
                    "count": len(traffic.stale_reads)})
    if traffic.reads_ok == 0:
        bad.append({"invariant": "liveness",
                    "error": "no read ever succeeded"})
    if not traffic.acked_set:
        bad.append({"invariant": "liveness",
                    "error": "no write was ever acknowledged"})

    # -- invariant 6: the ex-primary resyncs ---------------------------
    if crash_wall is not None and topo.promoted_index is not None:
        sb = topo.rejoin_ex_primary()
        if not sb.wait_caught_up(active.persistence, timeout_s=15):
            bad.append({
                "invariant": "resync",
                "error": "rejoined ex-primary never caught up",
                "applied": sb.applied,
                "primary_wal": active.persistence.wal.position,
            })
        else:
            sb_rows = sb.session().query(
                "select client, seq from chaos_t"
            )
            if sorted(sb_rows) != sorted(rows):
                p_set = {tuple(r) for r in rows}
                s_set = {tuple(r) for r in sb_rows}
                bad.append({
                    "invariant": "resync",
                    "error": "rejoined standby diverges from primary",
                    "standby_rows": len(sb_rows),
                    "primary_rows": len(rows),
                    "missing_on_standby": sorted(p_set - s_set)[:10],
                    "extra_on_standby": sorted(s_set - p_set)[:10],
                })
            verdict["resync"] = {
                "applied": sb.applied, "rows": len(sb_rows),
            }


# ---------------------------------------------------------------------------
# Elastic-rebalance chaos (rebalance/): kill the coordinator mid-move
# ---------------------------------------------------------------------------

def _moving_snapshot(cluster) -> set:
    """Lock-free copy of the barrier's in-move shard set; retried
    because the mover can mutate the set mid-iteration."""
    for _ in range(8):
        try:
            return set(cluster.shard_barrier._active)
        except RuntimeError:
            continue
    return set(cluster.shard_barrier._active)


class _RebalanceTraffic:
    """Embedded-session read/write traffic against one coordinator while
    a rebalance runs. Every write is a unique (client, seq) row; every
    failure is recorded WITH the barrier state and the statement's shard
    id at failure time, so the verdict can tell an excused wait-timeout
    on a moving shard from a forbidden failure on a non-moving one."""

    def __init__(self, cluster, seed: int, writers: int = 2,
                 readers: int = 1):
        self.cluster = cluster
        self.seed = seed
        self.writers = writers
        self.readers = readers
        self.stop_evt = threading.Event()
        self.acked: set = set()            # (client, seq)
        self.failures: list = []           # {client, seq, shard, moving,
        #                                     error}
        self.reads_ok = 0
        self._mu = threading.Lock()
        self.threads: list[threading.Thread] = []

    def start(self) -> None:
        for w in range(self.writers):
            t = threading.Thread(
                target=self._writer, args=(w,), daemon=True
            )
            t.start()
            self.threads.append(t)
        for r in range(self.readers):
            t = threading.Thread(
                target=self._reader, args=(r,), daemon=True
            )
            t.start()
            self.threads.append(t)

    def stop(self) -> None:
        self.stop_evt.set()
        for t in self.threads:
            t.join(timeout=30)

    def _shard_of(self, k: int):
        try:
            loc = self.cluster.catalog.get("rb_t").locator
            return loc.shard_id_by_key_equal({"k": k})
        except Exception:
            return None

    def _writer(self, cid: int) -> None:
        rng = random.Random(self.seed * 1000 + cid)
        s = self.cluster.session()
        seq = 0
        while not self.stop_evt.is_set():
            seq += 1
            k = cid * 1_000_000 + seq
            moving = _moving_snapshot(self.cluster)
            try:
                s.execute(
                    f"insert into rb_t values ({k}, {cid}, {seq})"
                )
                with self._mu:
                    self.acked.add((cid, seq))
            except Exception as e:
                # union of the barrier set before and after the
                # statement: a barrier-induced failure is excusable
                # whenever the barrier was up at either edge
                moving |= _moving_snapshot(self.cluster)
                with self._mu:
                    self.failures.append({
                        "client": cid, "seq": seq,
                        "shard": self._shard_of(k),
                        "moving": sorted(moving),
                        "error": f"{type(e).__name__}: {e}",
                    })
            self.stop_evt.wait(0.002 + rng.random() * 0.004)

    def _reader(self, rid: int) -> None:
        rng = random.Random(self.seed * 2000 + rid)
        s = self.cluster.session()
        while not self.stop_evt.is_set():
            cid = rng.randrange(self.writers)
            moving = _moving_snapshot(self.cluster)
            try:
                s.query(
                    f"select max(seq) from rb_t where client = {cid}"
                )
                with self._mu:
                    self.reads_ok += 1
            except Exception as e:
                moving |= _moving_snapshot(self.cluster)
                with self._mu:
                    self.failures.append({
                        "client": -1, "seq": -1, "shard": None,
                        "moving": sorted(moving),
                        "error": f"{type(e).__name__}: {e}",
                    })
            self.stop_evt.wait(0.005 + rng.random() * 0.01)


def run_rebalance_schedule(
    seed: int,
    workdir: str,
    kill_phase: str = "copying",
    keep: bool = False,
) -> dict:
    """One seeded elastic-rebalance crash schedule: seeded traffic over
    a 2-node cluster, ``ALTER CLUSTER ADD NODE`` in the background, the
    coordinator "killed" mid-move (``kill_phase``: ``copying`` arms
    ``rebalance/copy``, ``flip`` arms ``rebalance/flip``, ``journal``
    arms ``rebalance/journal`` — each FaultError leaves the journal
    exactly as a dead coordinator would), then ``Cluster.recover`` +
    resume. Invariants:

    1. zero lost acked writes across the crash + resume;
    2. zero duplicate rows (a re-copied chunk must not double-land);
    3. zero failed statements on NON-moving shards (a failure is
       excused only if the barrier was up and the statement's shard was
       in — or unprovably outside — the moving set);
    4. the resumed map completes the journaled plan exactly
       (``map[sid] == dst`` for every journaled move);
    5. fused == host result parity after resume.
    """
    from opentenbase_tpu.engine import Cluster

    os.makedirs(workdir, exist_ok=True)
    site = {
        "copying": "rebalance/copy",
        "flip": "rebalance/flip",
        "journal": "rebalance/journal",
    }[kill_phase]
    verdict: dict = {
        "seed": seed, "kill_phase": kill_phase, "violations": [],
    }
    bad = verdict["violations"]
    rng = random.Random(seed)
    traffic = None
    try:
        c = Cluster(num_datanodes=2, shard_groups=32, data_dir=workdir)
        boot = c.session()
        boot.execute(
            "create table rb_t (k bigint, client bigint, seq bigint)"
            " distribute by shard(k)"
        )
        # seed data so the planner has bytes to move
        vals = ",".join(
            f"({9_000_000 + i}, 99, {i})" for i in range(2000)
        )
        boot.execute(f"insert into rb_t values {vals}")
        pre_seed = {(99, i) for i in range(2000)}
        traffic = _RebalanceTraffic(c, seed)
        traffic.start()
        time.sleep(0.3)  # let traffic establish before the move
        # the kill: fires on the n-th copy chunk (copying/journal) or
        # the first flip; the service treats FaultError as a simulated
        # coordinator crash — no cleanup, journal left mid-move. Chunk
        # count per run is small (each wave's initial copy is one
        # sub-CHUNK_ROWS chunk), so n is capped at 1: both waves'
        # initial copies are guaranteed hits, deeper skips may starve.
        spec = (
            "once" if kill_phase == "flip"
            else f"after({rng.randint(0, 1)})"
        )
        _fault.inject(site, "error", spec)
        boot.execute("alter cluster add node dn_new")
        if not c.rebalance.wait(60):
            bad.append({"invariant": "harness",
                        "error": "rebalance never stopped"})
        _fault.clear(site)
        crashed = any(
            st.phase == "crashed" for st in c.rebalance.status_rows()
        )
        verdict["crashed_mid_move"] = crashed
        if not crashed:
            bad.append({
                "invariant": "harness",
                "error": f"fault at {site} never fired "
                "(move completed uninterrupted)",
            })
        time.sleep(0.2)  # post-crash traffic against the dead move
        traffic.stop()
        journaled = {
            rbid: dict(rec)
            for rbid, rec in c.rebalance._journaled.items()
        }
        # abandon `c` (the simulated dead coordinator) and recover
        r = Cluster.recover(workdir, num_datanodes=2, shard_groups=32)
        rs = r.session()
        state = rs.query("select pg_rebalance_wait()")[0][0]
        verdict["resume_state"] = state
        if state != "idle":
            bad.append({"invariant": "resume",
                        "error": f"resume finished {state!r}"})
        # 1+2: every acked write present exactly once
        rows = rs.query("select client, seq from rb_t")
        seen: dict = {}
        for cid, sq in rows:
            seen[(cid, sq)] = seen.get((cid, sq), 0) + 1
        expected = traffic.acked | pre_seed
        lost = [key for key in expected if key not in seen]
        dups = [key for key, n in seen.items() if n > 1]
        verdict["acked_writes"] = len(traffic.acked)
        verdict["lost_acked_writes"] = len(lost)
        if lost:
            bad.append({"invariant": "zero_lost_acked_writes",
                        "rows": sorted(lost)[:10], "count": len(lost)})
        if dups:
            bad.append({"invariant": "no_duplicates",
                        "rows": sorted(dups)[:10], "count": len(dups)})
        # 3: failures only excusable on moving shards under the barrier
        unexcused = [
            f for f in traffic.failures
            if not (f["moving"] and (
                f["shard"] is None or f["shard"] in f["moving"]
            ))
        ]
        verdict["failed_statements"] = len(traffic.failures)
        if unexcused:
            bad.append({
                "invariant": "zero_failed_on_nonmoving_shards",
                "cases": unexcused[:10], "count": len(unexcused),
            })
        if traffic.reads_ok == 0 or not traffic.acked:
            bad.append({"invariant": "liveness",
                        "error": "traffic never made progress"})
        # 4: the journaled plan completed exactly
        for rbid, rec in journaled.items():
            for sid, (_src, dst) in rec["moves"].items():
                if int(r.shardmap.map[int(sid)]) != int(dst):
                    bad.append({
                        "invariant": "plan_completed",
                        "rbid": rbid, "shard": int(sid),
                        "owner": int(r.shardmap.map[int(sid)]),
                        "planned_dst": int(dst),
                    })
        # 5: fused == host parity on the resumed cluster
        q = ("select client, count(*), sum(seq), max(seq) from rb_t "
             "group by client order by client")
        rs.execute("set enable_fused_execution = off")
        host_rows = rs.query(q)
        rs.execute("set enable_fused_execution = on")
        fused_rows = rs.query(q)
        if host_rows != fused_rows:
            bad.append({"invariant": "fused_host_parity",
                        "host": host_rows[:5], "fused": fused_rows[:5]})
        verdict["final_rows"] = len(rows)
    except Exception as e:  # harness failure IS a failed run
        bad.append({
            "invariant": "harness",
            "error": f"{type(e).__name__}: {e}",
        })
    finally:
        _fault.clear()
        if traffic is not None and not traffic.stop_evt.is_set():
            traffic.stop()
        if not keep:
            import shutil

            shutil.rmtree(workdir, ignore_errors=True)
    verdict["chaos_gate"] = "ok" if not verdict["violations"] else "fail"
    return verdict


# ---------------------------------------------------------------------------
# Multi-coordinator chaos (coord/): kill the primary CN mid-DDL-stream
# ---------------------------------------------------------------------------

class _MultiCNTraffic:
    """Seeded traffic against a two-coordinator cluster: one writer on
    the primary (over the wire, so the kill severs it like a real
    client), one writer on the peer CN (exercising write forwarding +
    read-your-writes), and a reader on the peer probing the one
    invariant a streamed catalog must keep under a DDL storm — the
    column shape of a CACHED statement never regresses. A stale plan
    served after the peer replayed an ``ADD COLUMN`` would show fewer
    columns than an earlier read already proved exist."""

    def __init__(self, primary_addr, peer, seed: int):
        self.primary_addr = primary_addr
        self.peer = peer
        self.seed = seed
        self.stop_evt = threading.Event()
        self.killed_evt = threading.Event()  # failures after this: excused
        self.acked: set = set()              # (client, seq)
        self.failures: list = []
        self.ryw_violations: list = []
        self.shape_violations: list = []
        self.reads_ok = 0
        self._max_cols = 0
        self._mu = threading.Lock()
        self.threads: list[threading.Thread] = []

    def start(self) -> None:
        for target, cid in (
            (self._primary_writer, 0), (self._peer_writer, 1),
        ):
            t = threading.Thread(target=target, args=(cid,), daemon=True)
            t.start()
            self.threads.append(t)
        t = threading.Thread(target=self._peer_reader, daemon=True)
        t.start()
        self.threads.append(t)

    def stop(self) -> None:
        self.stop_evt.set()
        for t in self.threads:
            t.join(timeout=30)

    def _note_failure(self, cid: int, seq: int, e: Exception) -> None:
        if self.killed_evt.is_set():
            return  # the primary is dead — failing is the correct outcome
        with self._mu:
            self.failures.append({
                "client": cid, "seq": seq,
                "error": f"{type(e).__name__}: {e}",
            })

    def _primary_writer(self, cid: int) -> None:
        from opentenbase_tpu.net.client import connect_tcp

        rng = random.Random(self.seed * 1000 + cid)
        cl = None
        seq = 0
        while not self.stop_evt.is_set():
            seq += 1
            k = cid * 1_000_000 + seq
            try:
                if cl is None:
                    cl = connect_tcp(host=self.primary_addr[0],
                                     port=self.primary_addr[1])
                cl.execute(
                    f"insert into mc_t (k, client, seq)"
                    f" values ({k}, {cid}, {seq})"
                )
                with self._mu:
                    self.acked.add((cid, seq))
            except Exception as e:
                cl = None
                self._note_failure(cid, seq, e)
                if self.killed_evt.is_set():
                    return
            self.stop_evt.wait(0.002 + rng.random() * 0.006)

    def _peer_writer(self, cid: int) -> None:
        rng = random.Random(self.seed * 1000 + cid)
        s = self.peer.cluster.session()
        seq = 0
        while not self.stop_evt.is_set():
            seq += 1
            k = cid * 1_000_000 + seq
            try:
                # forwards to the primary through the session service;
                # the reply's wal_pos becomes the session's
                # read-your-writes floor
                s.execute(
                    f"insert into mc_t (k, client, seq)"
                    f" values ({k}, {cid}, {seq})"
                )
                with self._mu:
                    self.acked.add((cid, seq))
                if seq % 8 == 0:
                    # read-your-writes: the row this session just got
                    # acked must be visible to its own LOCAL read
                    got = s.query(
                        f"select client, seq from mc_t where k = {k}"
                    )
                    if got != [(cid, seq)]:
                        with self._mu:
                            self.ryw_violations.append({
                                "client": cid, "seq": seq, "got": got,
                            })
            except Exception as e:
                self._note_failure(cid, seq, e)
                if self.killed_evt.is_set():
                    return
            self.stop_evt.wait(0.002 + rng.random() * 0.006)

    def _peer_reader(self) -> None:
        rng = random.Random(self.seed * 2000)
        s = self.peer.cluster.session()
        # both strings are CONSTANT so the peer's plan cache can hit:
        # a hit served across a replayed DDL is exactly the staleness
        # this schedule exists to rule out
        q_shape = "select * from mc_t where k = -1"
        q_agg = "select max(seq) from mc_t where client = 0"
        while not self.stop_evt.is_set():
            try:
                res = s.execute(q_shape)
                ncols = len(res.columns)
                with self._mu:
                    if ncols < self._max_cols:
                        self.shape_violations.append({
                            "cols": ncols, "seen_max": self._max_cols,
                        })
                    self._max_cols = max(self._max_cols, ncols)
                    self.reads_ok += 1
                if rng.random() < 0.5:
                    s.query(q_agg)
            except Exception as e:
                self._note_failure(-1, -1, e)
            self.stop_evt.wait(0.004 + rng.random() * 0.008)


def run_multicn_schedule(
    seed: int,
    workdir: str,
    duration_s: float = 4.0,
    keep: bool = False,
) -> dict:
    """One seeded multi-coordinator crash schedule: a primary CN
    serving wire clients, a peer CN (coord/) streaming its WHOLE WAL
    and forwarding writes, seeded traffic on both, a DDL storm adding
    columns on the primary, the replication stream TORN at seeded
    positions early in the run, and the primary killed mid-DDL-stream
    at a seeded time. The peer then promotes and the verdict checks:

    1. **zero lost acked writes** — ``synchronous_commit =
       remote_write`` with the peer as the sole walsender standby makes
       every ack wait for the peer's applied position, so every
       client-acked (client, seq) row must exist on the promoted peer
       exactly once (torn-window acks are covered by a post-tear
       barrier write the harness waits on);
    2. **zero stale cache hits** — the peer reader's column shape never
       regresses (a cached plan surviving a replayed ADD COLUMN would
       show fewer columns than an earlier read proved), AND the peer's
       plan cache records a real epoch invalidation;
    3. **zero lost acked DDL** — the promoted catalog shows at least
       3 + acked-DDL columns on mc_t;
    4. **read-your-writes** — a peer session's own forwarded commit is
       always visible to its next local read;
    5. **liveness** — both writers, the reader, and the storm made
       progress before the kill.
    """
    from opentenbase_tpu.coord.peer import PeerCoordinator
    from opentenbase_tpu.engine import Cluster
    from opentenbase_tpu.net.client import connect_tcp
    from opentenbase_tpu.net.server import ClusterServer
    from opentenbase_tpu.storage.replication import WalSender

    os.makedirs(workdir, exist_ok=True)
    verdict: dict = {"seed": seed, "violations": []}
    bad = verdict["violations"]
    rng = random.Random(seed)
    traffic = None
    sender = server = peer = promoted = None
    ddl_acked = [0]
    try:
        _fault.set_chaos_seed(seed)
        c = Cluster(
            num_datanodes=2, shard_groups=32,
            data_dir=os.path.join(workdir, "cn0"),
        )
        boot = c.session()
        boot.execute(
            "create table mc_t (k bigint, client bigint, seq bigint)"
            " distribute by shard(k)"
        )
        vals = ",".join(f"({9_000_000 + i}, 99, {i})" for i in range(500))
        boot.execute(f"insert into mc_t values {vals}")
        pre_seed = {(99, i) for i in range(500)}
        sender = WalSender(c.persistence, poll_s=0.005)
        server = ClusterServer(c).start()
        peer = PeerCoordinator(
            os.path.join(workdir, "cn1"), num_datanodes=2,
            shard_groups=32, name="cn1",
        ).follow(sender.host, sender.port, "127.0.0.1", server.port)
        if not peer.wait_applied(c.persistence.wal.position, 15.0):
            bad.append({"invariant": "harness",
                        "error": "peer never caught up at boot"})
            raise RuntimeError("boot catch-up failed")
        # from here every ack waits on the peer's applied position
        c.conf_gucs["synchronous_commit"] = "remote_write"
        # chaos: seeded ack-path delays for the whole run, plus a torn
        # replication stream during the early window
        _fault.inject("repl/ack_recv", "delay(40)", "prob(0.05)")
        _fault.inject("repl/wal_stream", "wal_torn", "prob(0.03)")
        traffic = _MultiCNTraffic(
            ("127.0.0.1", server.port), peer, seed
        )
        traffic.start()
        # DDL storm on the primary over the wire (dies with the kill)
        storm_stop = threading.Event()

        def _storm():
            srng = random.Random(seed * 3000)
            cl = None
            i = 0
            while not storm_stop.is_set():
                i += 1
                try:
                    if cl is None:
                        cl = connect_tcp(host="127.0.0.1",
                                         port=server.port)
                    cl.execute(f"alter table mc_t add column c{i} bigint")
                    ddl_acked[0] += 1
                except Exception as e:
                    cl = None
                    if traffic.killed_evt.is_set():
                        return
                    bad.append({"invariant": "harness",
                                "error": f"DDL storm failed pre-kill: "
                                f"{type(e).__name__}: {e}"})
                    return
                storm_stop.wait(0.05 + srng.random() * 0.05)

        storm = threading.Thread(target=_storm, daemon=True)
        storm.start()
        # torn window ends at 35%: clear the tear, then a barrier write
        # whose applied-wait proves the stream reconnected and caught
        # up — every ack before this point is covered by the barrier,
        # every ack after it by the remote_write quorum wait
        time.sleep(max(duration_s * 0.35, 0.3))
        _fault.clear("repl/wal_stream")
        mk = connect_tcp(host="127.0.0.1", port=server.port)
        wr = mk.execute("insert into mc_t (k, client, seq)"
                        " values (-777, 98, 1)")
        mk.close()
        if not peer.wait_applied(wr.wal_pos, 15.0):
            bad.append({"invariant": "harness",
                        "error": "post-tear barrier never applied"})
            raise RuntimeError("barrier failed")
        verdict["barrier_wal"] = wr.wal_pos
        # run on, then kill the primary mid-DDL-stream at a seeded time
        time.sleep(max(duration_s * (0.2 + rng.random() * 0.25), 0.2))
        verdict["killed_at_wal"] = c.persistence.wal.position
        traffic.killed_evt.set()
        server.stop()
        sender.stop()
        storm_stop.set()
        time.sleep(0.2)  # post-kill traffic against the dead primary
        traffic.stop()
        storm.join(timeout=10)
        verdict["ddl_acked"] = ddl_acked[0]
        verdict["acked_writes"] = len(traffic.acked)
        # positive cache-coherence witness BEFORE promote flips roles:
        # the peer's plan cache must have recorded a replayed-DDL epoch
        # invalidation (otherwise the shape check proved nothing)
        inval_epoch = int(
            peer.cluster.serving.plan_cache.last_invalidation_epoch
        )
        verdict["peer_invalidation_epoch"] = inval_epoch
        # the peer takes over; streamed WAL carried every acked write,
        # every DDL, and every gid decision the primary made durable
        c2 = peer.promote()
        promoted = c2
        s2 = c2.session()
        rows = s2.query("select client, seq from mc_t")
        seen: dict = {}
        for cid, sq in rows:
            seen[(cid, sq)] = seen.get((cid, sq), 0) + 1
        expected = traffic.acked | pre_seed | {(98, 1)}
        lost = [key for key in expected if key not in seen]
        dups = [key for key, n in seen.items() if n > 1]
        verdict["lost_acked_writes"] = len(lost)
        if lost:
            bad.append({"invariant": "zero_lost_acked_writes",
                        "rows": sorted(lost)[:10], "count": len(lost)})
        if dups:
            bad.append({"invariant": "no_duplicates",
                        "rows": sorted(dups)[:10], "count": len(dups)})
        ncols = len(s2.execute("select * from mc_t where k = -1").columns)
        verdict["final_columns"] = ncols
        if ncols < 3 + ddl_acked[0]:
            bad.append({
                "invariant": "zero_lost_acked_ddl",
                "columns": ncols, "acked_ddl": ddl_acked[0],
            })
        if traffic.shape_violations:
            bad.append({
                "invariant": "zero_stale_cache_hits",
                "cases": traffic.shape_violations[:10],
                "count": len(traffic.shape_violations),
            })
        if ddl_acked[0] > 0 and traffic.reads_ok > 10 and inval_epoch < 0:
            bad.append({
                "invariant": "zero_stale_cache_hits",
                "error": "peer plan cache never recorded a streamed-DDL "
                "invalidation — the shape probe proved nothing",
            })
        if traffic.ryw_violations:
            bad.append({
                "invariant": "read_your_writes",
                "cases": traffic.ryw_violations[:10],
                "count": len(traffic.ryw_violations),
            })
        if traffic.failures:
            bad.append({
                "invariant": "zero_failed_pre_kill",
                "cases": traffic.failures[:10],
                "count": len(traffic.failures),
            })
        acked_by = {cid for cid, _ in traffic.acked}
        if (
            acked_by != {0, 1} or traffic.reads_ok == 0
            or ddl_acked[0] < 1
        ):
            bad.append({
                "invariant": "liveness",
                "error": "a writer, the reader, or the DDL storm never "
                "made progress",
                "acked_by": sorted(acked_by),
                "reads_ok": traffic.reads_ok,
                "ddl_acked": ddl_acked[0],
            })
        verdict["reads_ok"] = traffic.reads_ok
    except Exception as e:  # harness failure IS a failed run
        bad.append({
            "invariant": "harness",
            "error": f"{type(e).__name__}: {e}",
        })
    finally:
        _fault.clear()
        _fault.set_chaos_seed(None)
        if traffic is not None and not traffic.stop_evt.is_set():
            traffic.killed_evt.set()
            traffic.stop()
        for closer in (
            (server.stop if server is not None else None),
            (sender.stop if sender is not None else None),
            (promoted.close if promoted is not None else None),
            (peer.stop if peer is not None and promoted is None else None),
        ):
            if closer is None:
                continue
            try:
                closer()
            except Exception:
                pass
        if not keep:
            import shutil

            shutil.rmtree(workdir, ignore_errors=True)
    verdict["chaos_gate"] = "ok" if not verdict["violations"] else "fail"
    return verdict


# ---------------------------------------------------------------------------
# Partition chaos (fault/partition.py): asymmetric + gray failures
# ---------------------------------------------------------------------------

PARTITION_SCENARIOS = ("asymmetric", "full", "gray_slow", "flapping")

# the cached probe: a constant SELECT over a table NO traffic writes,
# warmed into the primary's result cache before the partition — the one
# read a fenced CN could serve with zero datanode RPCs, i.e. the exact
# staleness hole the serving lease exists to close
_PART_PROBE_SQL = "select v from lease_probe_t"


def _until(pred, timeout_s: float, step_s: float = 0.05) -> bool:
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(step_s)
    return bool(pred())


def run_partition_schedule(
    seed: int,
    workdir: str,
    scenario: str = "asymmetric",
    duration_s: float = 6.0,
    num_datanodes: int = 2,
    detect_ms: int = 900,
    beats: int = 3,
    lease_ttl_ms: int = 600,
    lease_skew_ms: int = 100,
    keep: bool = False,
) -> dict:
    """One seeded network-partition schedule over live traffic: the
    connectivity matrix (fault/partition.py) severs or degrades
    specific DIRECTED legs of a live HA topology while the serving
    lease, the flap hysteresis, and the failover backoff must keep the
    cluster linearizable. Scenarios:

    - ``asymmetric`` — the monitor cannot see cn0 and cn0 cannot reach
      any datanode, but CLIENTS still reach cn0. Without the lease,
      cn0 would keep serving result-cache hits and replica reads with
      no staleness bound while a promoted peer accepts writes; with it,
      cn0 self-demotes (72000) before serving ANY statement once its
      DN-quorum renewals stop landing.
    - ``full`` — cn0 cut off in both directions (the classic dead
      primary, reached via the matrix rather than a process kill).
    - ``gray_slow`` — the monitor→cn0 leg is SLOW (every probe times
      out) while every other leg is healthy: the monitor promotes a
      standby out from under a perfectly live primary. The promote's
      generation bump fences cn0's lease renewals (a stale-generation
      grant is refused below the DN hgen gate), its sync-commit waits
      stop confirming (a promoted standby never counts), and the
      lease wait-out keeps the new primary from serving until every
      grant the old generation could still hold has run out.
    - ``flapping`` — seeded cut/heal cycles of the probe leg: the
      first dip (with the monitor also cut from the DNs) drives
      declared-dead into FAILED failovers that must back off
      exponentially; the heal arms the cooldown; the second dip's
      failover must be SUPPRESSED by that cooldown. Bounded verdict:
      zero promotions, >=2 failed-failover retries, >=2 heals, >=1
      cooldown suppression, traffic never stops.

    Invariants on every scenario: zero lost acked writes, zero
    duplicate/phantom rows, zero stale reads (the acked-watermark
    floor), and — after the matrix heals — the deposed primary still
    REFUSES the warmed result-cache probe and a write with SQLSTATE
    72000 (lease fenced), then rejoins as a standby and serves the
    same rows. Fully replayable: one seed drives the matrix, the
    backoff jitter, and the traffic mix."""
    from opentenbase_tpu.ha import HAMonitor, HATopology
    from opentenbase_tpu.net.client import WireError, connect_tcp

    if scenario not in PARTITION_SCENARIOS:
        raise ValueError(
            f"unknown scenario {scenario!r}; one of {PARTITION_SCENARIOS}"
        )
    os.makedirs(workdir, exist_ok=True)
    verdict: dict = {
        "seed": seed, "scenario": scenario, "violations": [],
        "timeline": [],
    }
    bad = verdict["violations"]
    tl = verdict["timeline"]
    _fault.set_chaos_seed(seed)
    matrix = _fault.NetMatrix()
    prev_matrix = _fault.install_matrix(matrix)
    topo = mon = traffic = None
    try:
        topo = HATopology(
            workdir, num_datanodes, 32, conf_gucs={
                "enable_fused_execution": "off",
                "synchronous_commit": "on",
                "failover_detect_ms": detect_ms,
                "failover_beats": beats,
                "lease_ttl_ms": lease_ttl_ms,
                "lease_skew_ms": lease_skew_ms,
                "failover_retry_max_ms": 2000,
                "failover_cooldown_ms": 1500,
                "enable_result_cache": "on",
                "fragment_retries": 1,
                "fragment_retry_backoff_ms": 5,
                "statement_timeout": 5000,
            },
        )
        matrix.register_endpoint(
            "cn0", topo.server.port, topo.sender.port,
        )
        for i, dn in enumerate(topo.dns):
            matrix.register_endpoint(f"dn{i}", dn.port)
        # boot + warm the cache probe OVER THE WIRE (the same path the
        # fenced probe takes later); the second execute must be a real
        # result-cache hit or the fenced probe proves nothing
        boot = connect_tcp(*topo.active_address())
        boot.execute(
            "create table chaos_t (client bigint, seq bigint, v bigint)"
            " distribute by shard(seq)"
        )
        boot.execute(
            "create table lease_probe_t (v bigint) distribute by shard(v)"
        )
        boot.execute("insert into lease_probe_t values (72)")
        rc_stats = topo.primary.serving.result_cache.stats
        boot.execute(_PART_PROBE_SQL)
        hits0 = rc_stats["hits"]
        warm = boot.execute(_PART_PROBE_SQL).rows
        boot.close()
        verdict["probe_cache_hit_warm"] = rc_stats["hits"] > hits0
        if warm != [(72,)] or not verdict["probe_cache_hit_warm"]:
            bad.append({
                "invariant": "harness",
                "error": "cache probe never warmed into the result "
                f"cache (rows={warm}, hit={verdict['probe_cache_hit_warm']})",
            })
        mon = HAMonitor(topo).start()  # detect/beats from conf_gucs
        sched = ChaosSchedule(
            seed=seed, duration_s=duration_s,
            num_datanodes=num_datanodes, events=[],
        )
        traffic = _Traffic(topo, sched)
        traffic.start()
        time.sleep(0.8)  # healthy baseline under traffic
        cut_wall = time.time()
        if scenario == "flapping":
            _run_flap_phase(topo, mon, matrix, num_datanodes, verdict)
        else:
            if scenario == "asymmetric":
                matrix.cut("monitor", "cn0")
                matrix.cut("cn0", "*")
            elif scenario == "full":
                matrix.cut("*", "cn0")
                matrix.cut("cn0", "*")
            else:  # gray_slow: probes time out, every other leg is fine
                matrix.slow_link("monitor", "cn0", detect_ms)
            tl.append(f"cut[{scenario}] {sorted(matrix.describe()['cuts'])}"
                      f" slow={matrix.describe()['slow']}")
            if not _until(
                lambda: topo.promoted_index is not None,
                max(duration_s, 12.0), step_s=0.05,
            ):
                bad.append({
                    "invariant": "auto_promotion",
                    "error": f"{scenario}: primary partitioned but "
                    "nothing promoted",
                })
            tl.append(f"promoted={topo.promoted_index}")
            time.sleep(1.2)  # traffic window on the promoted primary
        healed = matrix.heal_all()
        tl.append(f"heal_all removed {healed} rules")
        verdict["matrix"] = matrix.describe()["stats"]
        # post-heal settle: the deposed CN's lease thread must get one
        # renewal attempt THROUGH the healed matrix so the hgen gate can
        # permanently fence it (<= ttl/3 between attempts)
        time.sleep(max(lease_ttl_ms / 1000.0, 0.3))
        if scenario != "flapping":
            _part_fenced_probe(topo, verdict, bad)
        traffic.stop()
        mon.stop()
        _fault.clear()
        lease_stats = dict(topo.primary.ha_stats)
        verdict["lease"] = {
            k: lease_stats.get(k, 0)
            for k in ("lease_expirations", "self_demotions",
                      "fenced_refusals", "failover_retries",
                      "partition_heals")
        }
        if scenario == "flapping":
            _verify_flap(topo, mon, traffic, verdict, bad)
        else:
            if lease_stats.get("self_demotions", 0) < 1:
                bad.append({
                    "invariant": "lease_self_demotion",
                    "error": "partitioned primary never self-demoted",
                    "lease": verdict["lease"],
                })
            # converge to the crash shape: retire the deposed CN
            # "process" (operator demotion), then the shared verifier
            # re-probes the revived process and rejoins it as a standby
            topo.crash_primary()
            if topo.promoted_index is not None:
                host, wport = topo.active_wal_address()
                for j in range(len(topo.dns)):
                    if j == topo.promoted_index:
                        continue
                    try:
                        topo._dn_rpc(j, {
                            "op": "repl_repoint", "wal_host": host,
                            "wal_port": wport, "hgen": topo.generation,
                        })
                    except Exception:
                        pass
            # gray_slow: every missed probe burns interval + the FULL
            # probe timeout (the link is slow, not dead), so the
            # declare-latency budget carries that tax explicitly
            eff_detect_ms = detect_ms + (
                beats * 300 if scenario == "gray_slow" else 0
            )
            _verify(sched, topo, mon, traffic, cut_wall,
                    eff_detect_ms, beats, verdict, "on")
    except Exception as e:  # harness failure IS a failed run
        bad.append({
            "invariant": "harness",
            "error": f"{type(e).__name__}: {e}",
        })
    finally:
        try:
            matrix.heal_all()
        except Exception:
            pass
        _fault.install_matrix(prev_matrix)
        _fault.clear()
        _fault.reset_stats()
        _fault.set_chaos_seed(None)
        if traffic is not None and not traffic.stop_evt.is_set():
            traffic.stop()
        if mon is not None:
            mon.stop()
        if topo is not None:
            topo.stop()
        if not keep:
            import shutil

            shutil.rmtree(workdir, ignore_errors=True)
    verdict["chaos_gate"] = "ok" if not verdict["violations"] else "fail"
    return verdict


def _run_flap_phase(topo, mon, matrix, num_datanodes, verdict) -> None:
    """The deterministic two-dip flap: dip 1 proves the failed-failover
    backoff (monitor cut from cn0 AND every DN, so no candidate can be
    pinged), the heal arms the cooldown, dip 2 proves the cooldown
    suppresses the next promotion attempt. Both dips also keep the
    monitor cut from the DNs so a timing slip can never promote — the
    bounded-promotions verdict stays deterministic."""
    tl = verdict["timeline"]

    def _dip():
        matrix.cut("monitor", "cn0")
        for i in range(num_datanodes):
            matrix.cut("monitor", f"dn{i}")

    _dip()
    tl.append("flap dip 1 (monitor cut from cn0 + all DNs)")
    if not _until(
        lambda: mon.stats()["declared_dead_at"] is not None, 8.0,
    ):
        verdict["violations"].append({
            "invariant": "flap",
            "error": "dip 1 never reached declared-dead",
        })
    if not _until(lambda: mon.stats()["failover_retries"] >= 1, 8.0):
        verdict["violations"].append({
            "invariant": "failover_backoff",
            "error": "failed failover never retried/backed off",
        })
    retries_after_dip1 = mon.stats()["failover_retries"]
    matrix.heal_all()
    tl.append("flap heal 1")
    if not _until(
        lambda: any(
            e["kind"] == "primary_healed" for e in topo.events
        ), 8.0,
    ):
        verdict["violations"].append({
            "invariant": "flap",
            "error": "heal 1 never noted (cooldown never armed)",
        })
    _dip()
    tl.append("flap dip 2 (inside the cooldown window)")
    _until(
        lambda: any(
            e["kind"] == "failover_suppressed" for e in topo.events
        ) or mon.stats()["failover_retries"] > retries_after_dip1,
        8.0,
    )
    matrix.heal_all()
    tl.append("flap heal 2")
    _until(
        lambda: sum(
            1 for e in topo.events if e["kind"] == "primary_healed"
        ) >= 2, 8.0,
    )
    time.sleep(1.0)  # traffic window after the flap settles


def _part_fenced_probe(topo, verdict, bad) -> None:
    """The ISSUE's stale-read witness, sharpened: the matrix has
    HEALED, the deposed primary is running and reachable, its result
    cache still holds the warmed probe row — and it must refuse both
    the cached read and a write with SQLSTATE 72000, because its lease
    is permanently fenced (renewals carry the old generation)."""
    from opentenbase_tpu.net.client import WireError, connect_tcp

    probe_outcome = "refused"
    try:
        stale = connect_tcp(topo.server.host, topo.server.port)
    except OSError as e:
        verdict["fenced_probe"] = "unreachable"
        bad.append({
            "invariant": "lease_fencing",
            "error": "deposed primary unreachable after heal "
            f"(the probe must SEE the refusal): {e}",
        })
        return
    try:
        for sql, what in (
            (_PART_PROBE_SQL, "cached_read"),
            ("insert into chaos_t values (999, 1, 1)", "write"),
        ):
            try:
                res = stale.execute(sql)
                probe_outcome = f"accepted_{what}"
                bad.append({
                    "invariant": "lease_fencing",
                    "error": f"healed-but-deposed primary ACCEPTED a "
                    f"{what} (rows={getattr(res, 'rows', None)})",
                })
            except WireError as e:
                if getattr(e, "sqlstate", None) != "72000":
                    probe_outcome = "wrong_sqlstate"
                    bad.append({
                        "invariant": "lease_fencing",
                        "error": f"{what} refused without the fenced "
                        f"SQLSTATE: {e.sqlstate} {e}",
                    })
    finally:
        stale.close()
    verdict["fenced_probe"] = probe_outcome


def _verify_flap(topo, mon, traffic, verdict, bad) -> None:
    """Flap verdict: the primary survived, promotions are bounded at
    ZERO, the backoff and the cooldown both fired, and the row-level
    invariants hold on the never-deposed primary."""
    st = mon.stats()
    verdict["promotions"] = st["promotions"]
    verdict["failover_retries"] = st["failover_retries"]
    heals = sum(
        1 for e in topo.events if e["kind"] == "primary_healed"
    )
    suppressed = sum(
        1 for e in topo.events if e["kind"] == "failover_suppressed"
    )
    verdict["partition_heals"] = heals
    verdict["cooldown_suppressed"] = suppressed
    if st["promotions"] != 0 or topo.promoted_index is not None:
        bad.append({
            "invariant": "bounded_promotions",
            "error": "a flap deposed a healthy primary",
            "promotions": st["promotions"],
        })
    if st["failover_retries"] < 2:
        bad.append({
            "invariant": "failover_backoff",
            "retries": st["failover_retries"],
            "error": "expected >=2 failed-failover retries across dips",
        })
    if heals < 2:
        bad.append({"invariant": "flap_heals", "heals": heals})
    if suppressed < 1:
        bad.append({
            "invariant": "cooldown_hysteresis",
            "error": "dip 2's failover was never suppressed by the "
            "heal cooldown",
        })
    # row invariants on the surviving primary
    s = topo.active_cluster.session()
    s.execute("set statement_timeout = 0")
    rows = s.query("select client, seq from chaos_t")
    seen: dict = {}
    for cid, sq in rows:
        seen[(cid, sq)] = seen.get((cid, sq), 0) + 1
    lost = [k for k in traffic.acked_set if k not in seen]
    dups = [k for k, n in seen.items() if n > 1]
    verdict["acked_writes"] = len(traffic.acked_set)
    verdict["lost_acked_writes"] = len(lost)
    verdict["final_rows"] = len(rows)
    verdict["reads_ok"] = traffic.reads_ok
    verdict["stale_reads"] = len(traffic.stale_reads)
    if lost:
        bad.append({"invariant": "zero_lost_committed_writes",
                    "rows": sorted(lost)[:10], "count": len(lost)})
    if dups:
        bad.append({"invariant": "no_duplicates",
                    "rows": dups[:10], "count": len(dups)})
    if traffic.stale_reads:
        bad.append({"invariant": "zero_stale_reads",
                    "cases": traffic.stale_reads[:10],
                    "count": len(traffic.stale_reads)})
    attempted = traffic.acked_set | traffic.indeterminate
    phantom = [k for k in seen if k not in attempted and k[0] != 999]
    if phantom:
        bad.append({"invariant": "no_phantom_rows",
                    "rows": sorted(phantom)[:10],
                    "count": len(phantom)})
    if traffic.reads_ok == 0 or not traffic.acked_set:
        bad.append({"invariant": "liveness",
                    "error": "traffic never made progress under flap"})
    # the lease must still be VALID: a flap of the PROBE leg must not
    # cost the primary its serving lease (cn0->DN legs stayed up)
    lease = getattr(topo.active_cluster, "serving_lease", None)
    if lease is not None and not lease.valid():
        bad.append({
            "invariant": "lease_liveness",
            "error": "probe-leg flap invalidated the primary's lease",
        })


def run_partition_schedules(
    base_seed: int,
    count: int,
    workdir: str,
    scenarios=PARTITION_SCENARIOS,
    duration_s: float = 6.0,
    num_datanodes: int = 2,
    keep: bool = False,
) -> list[dict]:
    """``count`` seeds x every scenario (the acceptance matrix); one
    verdict per (seed, scenario) run."""
    out = []
    for k in range(count):
        seed = base_seed + k
        for scenario in scenarios:
            out.append(run_partition_schedule(
                seed, os.path.join(workdir, f"s{seed}_{scenario}"),
                scenario=scenario, duration_s=duration_s,
                num_datanodes=num_datanodes, keep=keep,
            ))
    return out


def run_schedules(
    base_seed: int,
    count: int,
    workdir: str,
    duration_s: float = 6.0,
    num_datanodes: int = 2,
    detect_ms: int = 1200,
    beats: int = 3,
    keep: bool = False,
    sync_mode: str = "on",
) -> list[dict]:
    """Run ``count`` distinct seeded schedules (seeds base..base+n-1);
    one verdict per schedule."""
    out = []
    for k in range(count):
        seed = base_seed + k
        sched = ChaosSchedule.generate(
            seed, duration_s=duration_s, num_datanodes=num_datanodes,
        )
        out.append(run_schedule(
            sched, os.path.join(workdir, f"seed{seed}"),
            detect_ms=detect_ms, beats=beats, keep=keep,
            sync_mode=sync_mode,
        ))
    return out
