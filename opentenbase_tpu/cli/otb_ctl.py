"""Cluster control plane — the pgxc_ctl analog (contrib/pgxc_ctl).

Drives a whole topology (coordinator + walsender + hot standbys, each a
real OS process) from one JSON config:

    python -m opentenbase_tpu.cli.otb_ctl init CONFIG.json   # scaffold
    python -m opentenbase_tpu.cli.otb_ctl start CONFIG.json
    python -m opentenbase_tpu.cli.otb_ctl status CONFIG.json
    python -m opentenbase_tpu.cli.otb_ctl promote CONFIG.json sb1
    python -m opentenbase_tpu.cli.otb_ctl add-coordinator CONFIG.json cn1
    python -m opentenbase_tpu.cli.otb_ctl list-coordinators CONFIG.json
    python -m opentenbase_tpu.cli.otb_ctl replica-status CONFIG.json
    python -m opentenbase_tpu.cli.otb_ctl stop CONFIG.json

Config shape:

    {"coordinator": {"port": 5433, "wal_port": 5444,
                     "data_dir": "data/pri", "datanodes": 2,
                     "gts": "python"},
     "coordinators": [{"name": "cn1", "data_dir": "data/cn1",
                       "serve_port": 5534, "control_port": 5634}],
     "standbys": [{"name": "sb1", "data_dir": "data/sb1",
                   "serve_port": 5533, "control_port": 5633}]}

``coordinators`` are PEER CNs (otb_peer processes): each streams the
primary's catalog+WAL, serves reads locally, forwards writes to the
primary, and is registered there with pg_add_coordinator so the
multi-CN health rows appear in pg_cluster_health.

PID files live beside each data_dir (postmaster.pid convention).
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import socket
import subprocess
import sys
import time

TEMPLATE = {
    "coordinator": {
        "port": 5433, "wal_port": 5444, "data_dir": "data/pri",
        "datanodes": 2, "shard_groups": 256, "gts": "python",
    },
    "coordinators": [
        {"name": "cn1", "data_dir": "data/cn1",
         "serve_port": 5534, "control_port": 5634}
    ],
    "standbys": [
        {"name": "sb1", "data_dir": "data/sb1",
         "serve_port": 5533, "control_port": 5633}
    ],
}


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _pid_path(data_dir: str) -> str:
    return os.path.join(data_dir, "postmaster.pid")


def _alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
        return True
    except OSError:
        return False


def _read_pid(data_dir: str):
    try:
        with open(_pid_path(data_dir)) as f:
            pid = int(f.read().strip())
        return pid if _alive(pid) else None
    except (OSError, ValueError):
        return None


def _host_role_env() -> dict:
    """Environment for standbys and peer coordinators: host-side roles
    on the CPU backend whatever the caller's environment says — the
    coordinator process owns the chip (one process per chip)."""
    return dict(os.environ, JAX_PLATFORMS="cpu")


def _spawn(
    cmd: list[str], data_dir: str, ready_marker: str, env=None
) -> int:
    os.makedirs(data_dir, exist_ok=True)
    log = open(os.path.join(data_dir, "server.log"), "ab")
    proc = subprocess.Popen(cmd, stdout=log, stderr=log, env=env)
    with open(_pid_path(data_dir), "w") as f:
        f.write(str(proc.pid))
    # wait for the ready banner in the log (pg_ctl -w behavior)
    path = os.path.join(data_dir, "server.log")
    for _ in range(600):
        if proc.poll() is not None:
            raise SystemExit(
                f"process died during startup; see {path}"
            )
        try:
            with open(path, "rb") as f:
                if ready_marker.encode() in f.read():
                    return proc.pid
        except OSError:
            pass
        time.sleep(0.1)
    raise SystemExit(f"startup timed out; see {path}")


def cmd_init(cfg_path: str) -> None:
    if os.path.exists(cfg_path):
        raise SystemExit(f"{cfg_path} already exists")
    with open(cfg_path, "w") as f:
        json.dump(TEMPLATE, f, indent=2)
    print(f"wrote {cfg_path}; edit it and run: otb_ctl start {cfg_path}")


def _validate(cfg: dict) -> None:
    co = cfg.get("coordinator")
    if not co or "port" not in co or "data_dir" not in co:
        raise SystemExit("config needs coordinator.port and .data_dir")
    if cfg.get("standbys"):
        if not co.get("wal_port"):
            raise SystemExit(
                "standbys need coordinator.wal_port (the WAL stream source)"
            )
        for sb in cfg["standbys"]:
            for field in ("name", "data_dir", "serve_port", "control_port"):
                if not sb.get(field):
                    raise SystemExit(
                        f"standby config needs explicit {field!r} "
                        "(status/promote dial these ports later)"
                    )


def cmd_start(cfg: dict) -> None:
    _validate(cfg)
    co = cfg["coordinator"]
    if _read_pid(co["data_dir"]):
        print("coordinator: already running")
    else:
        recover = os.path.exists(os.path.join(co["data_dir"], "wal.log"))
        cmd = [
            sys.executable, "-m", "opentenbase_tpu.cli.otb_server",
            "--port", str(co["port"]), "--data-dir", co["data_dir"],
            "--datanodes", str(co.get("datanodes", 2)),
            "--shard-groups", str(co.get("shard_groups", 256)),
            "--gts", co.get("gts", "python"),
        ]
        if co.get("wal_port"):
            cmd += ["--wal-port", str(co["wal_port"])]
        if recover:
            cmd += ["--recover"]
        pid = _spawn(cmd, co["data_dir"], "listening on")
        print(f"coordinator: started (pid {pid}, port {co['port']})")
    for sb in cfg.get("standbys", []):
        if _read_pid(sb["data_dir"]):
            print(f"{sb['name']}: already running")
            continue
        cmd = [
            sys.executable, "-m", "opentenbase_tpu.cli.otb_standby",
            "--primary-port", str(co["wal_port"]),
            "--data-dir", sb["data_dir"],
            "--datanodes", str(co.get("datanodes", 2)),
            "--shard-groups", str(co.get("shard_groups", 256)),
            "--serve-port", str(sb.get("serve_port", 0)),
            "--control-port", str(sb.get("control_port", 0)),
        ]
        pid = _spawn(
            cmd, sb["data_dir"], "standby ready", env=_host_role_env()
        )
        print(f"{sb['name']}: started (pid {pid}, sql port {sb.get('serve_port')})")


def _control(sb: dict, command: str) -> dict:
    with socket.create_connection(
        ("127.0.0.1", sb["control_port"]), timeout=10
    ) as s:
        f = s.makefile("rw")
        f.write(command + "\n")
        f.flush()
        return json.loads(f.readline())


def cmd_status(cfg: dict) -> None:
    co = cfg["coordinator"]
    pid = _read_pid(co["data_dir"])
    print(f"coordinator: {'up (pid %d)' % pid if pid else 'down'}")
    for cn in cfg.get("coordinators", []):
        pid = _read_pid(cn["data_dir"])
        if not pid:
            print(f"{cn['name']}: down")
            continue
        try:
            st = _control(cn, "status")
            print(
                f"{cn['name']}: up (pid {pid}) role={st['role']}"
                f" applied={st['applied']}"
                f" catalog_epoch={st['catalog_epoch']}"
            )
        except (OSError, ValueError, KeyError):
            print(f"{cn['name']}: up (pid {pid}) control unreachable")
    for sb in cfg.get("standbys", []):
        pid = _read_pid(sb["data_dir"])
        if not pid:
            print(f"{sb['name']}: down")
            continue
        try:
            st = _control(sb, "status")
            print(
                f"{sb['name']}: up (pid {pid}) role={st['role']}"
                f" applied={st['applied']}"
            )
        except (OSError, ValueError, KeyError):
            # connection refused/reset, empty reply mid-shutdown, or a
            # config missing the control port
            print(f"{sb['name']}: up (pid {pid}) control unreachable")


def cmd_promote(cfg: dict, name: str) -> None:
    for sb in cfg.get("standbys", []):
        if sb["name"] == name:
            out = _control(sb, "promote")
            print(f"{name}: {out}")
            return
    raise SystemExit(f"no standby named {name!r} in config")


def _sql(cfg: dict):
    """SQL session to the running coordinator (elastic-cluster verbs
    are online DDL, so they go through the front door, not the pid)."""
    from opentenbase_tpu.net.client import connect_tcp

    co = cfg["coordinator"]
    return connect_tcp(port=int(co["port"]))


def _peer_cfg(cfg: dict, name: str) -> dict:
    for cn in cfg.get("coordinators", []):
        if cn.get("name") == name:
            for field in ("data_dir", "serve_port", "control_port"):
                if not cn.get(field):
                    raise SystemExit(
                        f"coordinator config for {name!r} needs "
                        f"explicit {field!r}"
                    )
            return cn
    raise SystemExit(f"no coordinator named {name!r} in config")


def cmd_add_coordinator(cfg: dict, name: str) -> None:
    """Spawn a peer CN process and register it on the primary — the
    pgxc_ctl add-coordinator two-step (spawn, then CREATE NODE)."""
    co = cfg["coordinator"]
    if not co.get("wal_port"):
        raise SystemExit(
            "peer coordinators need coordinator.wal_port "
            "(the catalog/WAL stream source)"
        )
    cn = _peer_cfg(cfg, name)
    if _read_pid(cn["data_dir"]):
        print(f"{name}: already running")
    else:
        cmd = [
            sys.executable, "-m", "opentenbase_tpu.cli.otb_peer",
            "--name", name,
            "--primary-wal-port", str(co["wal_port"]),
            "--primary-sql-port", str(co["port"]),
            "--data-dir", cn["data_dir"],
            "--datanodes", str(co.get("datanodes", 2)),
            "--shard-groups", str(co.get("shard_groups", 256)),
            "--serve-port", str(cn["serve_port"]),
            "--control-port", str(cn["control_port"]),
        ]
        pid = _spawn(
            cmd, cn["data_dir"], "peer ready", env=_host_role_env()
        )
        print(f"{name}: started (pid {pid}, sql port {cn['serve_port']})")
    with _sql(cfg) as s:
        s.query(
            f"SELECT pg_add_coordinator('{name}', '127.0.0.1', "
            f"{int(cn['serve_port'])})"
        )
    print(f"{name}: registered on primary coordinator")


def cmd_list_coordinators(cfg: dict) -> None:
    with _sql(cfg) as s:
        rows = s.query("SELECT pg_coordinators()")
    for name, host, port, role, up, epoch, lag in rows:
        state = "up" if up else "DOWN"
        line = (
            f"{name} {role} {host}:{port} {state} "
            f"catalog_epoch={epoch}"
        )
        if int(lag) >= 0:
            line += f" stream_lag={lag}B"
        print(line)


def cmd_replica_status(cfg: dict) -> None:
    with _sql(cfg) as s:
        rows = s.query("SELECT pg_replica_status()")
    for name, addr, acked, stale, reads, refused in rows:
        if name == "-":
            print("no replica targets registered")
            continue
        stale_s = (
            f"{float(stale) * 1000:.1f}ms" if float(stale) >= 0
            else "unknown"
        )
        print(
            f"{name} {addr or '?'} acked={acked} staleness={stale_s} "
            f"reads={reads} refused={refused}"
        )


def cmd_add_node(cfg: dict, name: str) -> None:
    with _sql(cfg) as s:
        s.execute(f"ALTER CLUSTER ADD NODE {name} WAIT")
        state, moves, rows = s.query("SELECT pg_rebalance_wait()")[0]
        print(
            f"{name}: joined ({state}; {moves} moves, "
            f"{rows} rows rebalanced)"
        )


def cmd_remove_node(cfg: dict, name: str) -> None:
    with _sql(cfg) as s:
        s.execute(f"ALTER CLUSTER REMOVE NODE {name} WAIT")
        state, moves, rows = s.query("SELECT pg_rebalance_wait()")[0]
        print(
            f"{name}: drained and detached ({state}; {moves} moves, "
            f"{rows} rows rebalanced)"
        )


def cmd_rebalance_status(cfg: dict) -> None:
    with _sql(cfg) as s:
        rows = s.query(
            "SELECT rbid, kind, src, dst, phase, rows_copied, "
            "bytes_per_sec, barrier_wait_ms, error "
            "FROM pg_stat_rebalance"
        )
        if not rows:
            print("no rebalance activity")
            return
        for r in rows:
            rbid, kind, src, dst, phase, nrows, bps, bar, err = r
            line = (
                f"{rbid} {kind} dn{src}->dn{dst} {phase}: "
                f"{nrows} rows, {float(bps):.0f} B/s, "
                f"barrier {float(bar):.1f} ms"
            )
            if err:
                line += f" ERROR: {err}"
            print(line)


def cmd_stop(cfg: dict) -> None:
    targets = [("coordinator", cfg["coordinator"])] + [
        (cn["name"], cn) for cn in cfg.get("coordinators", [])
    ] + [
        (sb["name"], sb) for sb in cfg.get("standbys", [])
    ]
    for label, node in targets:
        pid = _read_pid(node["data_dir"])
        if not pid:
            print(f"{label}: not running")
            continue
        os.kill(pid, signal.SIGTERM)
        for _ in range(100):
            if not _alive(pid):
                break
            time.sleep(0.1)
        else:
            os.kill(pid, signal.SIGKILL)
        try:
            os.remove(_pid_path(node["data_dir"]))
        except OSError:
            pass
        print(f"{label}: stopped")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("verb", choices=[
        "init", "start", "stop", "status", "promote",
        "add-node", "remove-node", "rebalance-status",
        "add-coordinator", "list-coordinators", "replica-status",
    ])
    ap.add_argument("config")
    ap.add_argument("target", nargs="?")
    args = ap.parse_args(argv)
    if args.verb == "init":
        cmd_init(args.config)
        return 0
    cfg = _load(args.config)
    if args.verb == "start":
        cmd_start(cfg)
    elif args.verb == "status":
        cmd_status(cfg)
    elif args.verb == "promote":
        if not args.target:
            ap.error("promote needs a standby name")
        cmd_promote(cfg, args.target)
    elif args.verb == "add-node":
        if not args.target:
            ap.error("add-node needs a node name")
        cmd_add_node(cfg, args.target)
    elif args.verb == "remove-node":
        if not args.target:
            ap.error("remove-node needs a node name")
        cmd_remove_node(cfg, args.target)
    elif args.verb == "rebalance-status":
        cmd_rebalance_status(cfg)
    elif args.verb == "add-coordinator":
        if not args.target:
            ap.error("add-coordinator needs a coordinator name")
        cmd_add_coordinator(cfg, args.target)
    elif args.verb == "list-coordinators":
        cmd_list_coordinators(cfg)
    elif args.verb == "replica-status":
        cmd_replica_status(cfg)
    elif args.verb == "stop":
        cmd_stop(cfg)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
