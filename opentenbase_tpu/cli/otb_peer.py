"""Peer-coordinator runner process — a second CN as its own OS process.

    python -m opentenbase_tpu.cli.otb_peer --name cn1 \
        --primary-host H --primary-wal-port W --primary-sql-port S \
        --data-dir DIR [--serve-port N] [--control-port N]

The peer streams the primary CN's WAL (catalog D-records and committed
writes ride the same stream), serves reads locally, and forwards
writes/DDL to the primary's SQL port (coord/peer.py). Clients connect
to --serve-port exactly as they would to the primary; the control port
accepts the same line commands as otb_standby:

    status   -> JSON {role, applied, catalog_epoch, read_only}
    promote  -> takes over as primary CN (stops forwarding writes)
    stop     -> clean shutdown

(pgxc_ctl's add-coordinator spawns this process, then registers it on
the primary with pg_add_coordinator so health views can see it.)
"""

from __future__ import annotations

import argparse
import json
import socket
import threading


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--name", default="cn1")
    ap.add_argument("--primary-host", default="127.0.0.1")
    ap.add_argument("--primary-wal-port", type=int, required=True)
    ap.add_argument("--primary-sql-port", type=int, required=True)
    ap.add_argument("--data-dir", required=True)
    ap.add_argument("--datanodes", type=int, default=2)
    ap.add_argument("--shard-groups", type=int, default=256)
    ap.add_argument("--serve-port", type=int, default=0)
    ap.add_argument("--control-port", type=int, default=0)
    args = ap.parse_args(argv)

    from opentenbase_tpu import host_side_role

    host_side_role()
    from opentenbase_tpu.coord.peer import PeerCoordinator
    from opentenbase_tpu.net.server import ClusterServer

    peer = PeerCoordinator(
        args.data_dir, args.datanodes, args.shard_groups, name=args.name
    )
    peer.follow(
        args.primary_host, args.primary_wal_port,
        args.primary_host, args.primary_sql_port,
    )
    server = ClusterServer(peer.cluster, port=args.serve_port).start()

    ctl = socket.socket()
    ctl.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    ctl.bind(("127.0.0.1", args.control_port))
    ctl.listen(4)
    # periodic accept timeout so done.set() can actually end the loop
    # (the otb_standby socket-blocking-loop finding, not repeated here)
    ctl.settimeout(0.5)
    print(
        f"peer ready sql=127.0.0.1:{server.port} "
        f"control=127.0.0.1:{ctl.getsockname()[1]}",
        flush=True,
    )

    done = threading.Event()
    import signal

    signal.signal(signal.SIGTERM, lambda *a: done.set())
    signal.signal(signal.SIGINT, lambda *a: done.set())

    def handle(conn: socket.socket) -> None:
        try:
            f = conn.makefile("rw")
            for line in f:
                cmd = line.strip()
                if cmd == "status":
                    c = peer.cluster
                    f.write(json.dumps({
                        "role": c.catalog_service.role(),
                        "applied": peer.applied,
                        "catalog_epoch": int(c.catalog_epoch),
                        "read_only": c.read_only,
                    }) + "\n")
                    f.flush()
                elif cmd == "promote":
                    if not peer.promoted:
                        peer.promote()
                    f.write(json.dumps({"promoted": True}) + "\n")
                    f.flush()
                elif cmd == "stop":
                    f.write(json.dumps({"stopping": True}) + "\n")
                    f.flush()
                    done.set()
                    return
        except OSError:
            pass
        finally:
            try:
                conn.close()
            except OSError:
                pass

    def accept_loop() -> None:
        while not done.is_set():
            try:
                conn, _ = ctl.accept()
            except socket.timeout:
                continue
            except OSError:
                return
            threading.Thread(target=handle, args=(conn,), daemon=True).start()

    threading.Thread(target=accept_loop, daemon=True).start()
    done.wait()
    server.stop()
    peer.stop()
    peer.cluster.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
