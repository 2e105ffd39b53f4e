"""Seeded chaos-schedule runner — the self-healing HA acceptance gate.

    python -m opentenbase_tpu.cli.otb_chaos [--schedule crash|partition]
        [--seed N] [--schedules K] [--duration S] [--datanodes D]
        [--detect-ms MS] [--beats B] [--keep] [--workdir DIR]

``--schedule crash`` (default): each schedule (seeds N .. N+K-1)
builds a fresh topology (coordinator + WAL-streaming datanode standbys
+ HAMonitor), runs a randomized fault timeline — drop_conn, delays,
wal_torn stream tears, a datanode crash/revive, a primary crash, and a
kill inside the promotion window — under live read-write traffic, then
checks the invariants (fault/schedule.py docstring).

``--schedule partition``: each seed runs the four network-partition
scenarios (``--scenarios`` to narrow) through the connectivity matrix
— asymmetric (clients reach cn0, cn0 cannot reach the DNs), full
isolation, gray-slow probe leg, and a flapping link — and the verdict
additionally proves the serving lease: the partitioned primary
self-demotes BEFORE serving any statement, a healed-but-deposed
primary refuses its own warmed result-cache hit with SQLSTATE 72000,
promotions stay bounded under flap, and the ex-primary rejoins.

One JSON verdict line per run plus a final ``chaos_gate`` summary
line; exit code 4 on any violated invariant.

A failing run replays from its printed seed alone: the schedule, the
prob-fault draws, the matrix flap timings, the reconnect jitter, and
the wal_torn tear positions all derive from it.
"""

from __future__ import annotations

import argparse
import json
import sys
import tempfile


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--schedule", default="crash",
                    choices=("crash", "partition"),
                    help="crash: randomized fault timeline with a "
                    "primary kill; partition: connectivity-matrix "
                    "scenarios with lease fencing invariants")
    ap.add_argument("--seed", type=int, default=1107,
                    help="base seed (schedules use seed..seed+K-1)")
    ap.add_argument("--schedules", type=int, default=5)
    ap.add_argument("--duration", type=float, default=6.0,
                    help="seconds of live traffic per schedule")
    ap.add_argument("--datanodes", type=int, default=2)
    ap.add_argument("--detect-ms", type=int, default=1200,
                    help="failover_detect_ms for the HA monitor")
    ap.add_argument("--beats", type=int, default=3,
                    help="consecutive missed beats before promotion")
    ap.add_argument("--scenarios", default=None,
                    help="partition only: comma-separated subset of "
                    "asymmetric,full,gray_slow,flapping")
    ap.add_argument("--keep", action="store_true",
                    help="keep each schedule's data dirs")
    ap.add_argument("--workdir", default=None)
    ap.add_argument("--sync-mode", default="on",
                    choices=("off", "local", "remote_write", "on"),
                    help="crash only: synchronous_commit rung to "
                    "prove — the invariants adapt to what the mode "
                    "promises (remote rungs: zero lost acked writes; "
                    "off/local: contiguous-tail loss only)")
    args = ap.parse_args(argv)

    workdir = args.workdir or tempfile.mkdtemp(prefix="otb_chaos_")
    verdicts = []
    if args.schedule == "partition":
        from opentenbase_tpu.fault.schedule import (
            PARTITION_SCENARIOS,
            run_partition_schedule,
        )

        scenarios = tuple(
            s.strip() for s in args.scenarios.split(",") if s.strip()
        ) if args.scenarios else PARTITION_SCENARIOS
        unknown = [s for s in scenarios if s not in PARTITION_SCENARIOS]
        if unknown:
            ap.error(f"unknown scenarios {unknown}; "
                     f"choose from {PARTITION_SCENARIOS}")
        for k in range(args.schedules):
            seed = args.seed + k
            for scenario in scenarios:
                v = run_partition_schedule(
                    seed, f"{workdir}/s{seed}_{scenario}",
                    scenario=scenario, duration_s=args.duration,
                    num_datanodes=args.datanodes,
                    detect_ms=args.detect_ms, beats=args.beats,
                    keep=args.keep,
                )
                verdicts.append(v)
                print(json.dumps(v, default=str), flush=True)
        failed = [
            (v["seed"], v["scenario"]) for v in verdicts
            if v["chaos_gate"] != "ok"
        ]
        summary = {
            "chaos_gate": "ok" if not failed else "fail",
            "schedule": "partition",
            "runs": len(verdicts),
            "failed": [f"{s}/{sc}" for s, sc in failed],
            "acked_writes": sum(
                v.get("acked_writes", 0) for v in verdicts
            ),
            "promotions": sum(v.get("promotions", 0) for v in verdicts),
            "replay_hint": (
                f"python -m opentenbase_tpu.cli.otb_chaos "
                f"--schedule partition --seed {failed[0][0]} "
                f"--schedules 1 --scenarios {failed[0][1]}"
                if failed else ""
            ),
        }
        print(json.dumps(summary, default=str), flush=True)
        return 4 if failed else 0

    from opentenbase_tpu.fault.schedule import (
        ChaosSchedule,
        run_schedule,
    )

    for k in range(args.schedules):
        seed = args.seed + k
        sched = ChaosSchedule.generate(
            seed, duration_s=args.duration,
            num_datanodes=args.datanodes,
        )
        v = run_schedule(
            sched, f"{workdir}/seed{seed}",
            detect_ms=args.detect_ms, beats=args.beats,
            keep=args.keep, sync_mode=args.sync_mode,
        )
        verdicts.append(v)
        print(json.dumps(v, default=str), flush=True)
    failed = [v["seed"] for v in verdicts if v["chaos_gate"] != "ok"]
    summary = {
        "chaos_gate": "ok" if not failed else "fail",
        "schedules": len(verdicts),
        "failed_seeds": failed,
        "acked_writes": sum(
            v.get("acked_writes", 0) for v in verdicts
        ),
        "promotions": sum(v.get("promotions", 0) for v in verdicts),
        "replay_hint": (
            f"python -m opentenbase_tpu.cli.otb_chaos --seed "
            f"{failed[0]} --schedules 1" if failed else ""
        ),
    }
    print(json.dumps(summary, default=str), flush=True)
    return 4 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
