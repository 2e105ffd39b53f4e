"""Hot-standby runner process — the standby side of streaming replication
as its own OS process, with a control port for status/promote.

    python -m opentenbase_tpu.cli.otb_standby --primary-host H \
        --primary-port P --data-dir DIR [--serve-port N] [--control-port N]

While standing by it applies the primary's WAL stream and serves
read-only SQL on --serve-port. The control port accepts line commands:

    status   -> JSON {role, applied, read_only}
    promote  -> finishes recovery, flips read-write, keeps serving SQL
    stop     -> clean shutdown

(`pg_ctl promote` talks to the postmaster via signal+trigger file; a
control socket is the same contract made explicit.)
"""

from __future__ import annotations

import argparse
import json
import socket
import threading


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--primary-host", default="127.0.0.1")
    ap.add_argument("--primary-port", type=int, required=True)
    ap.add_argument("--data-dir", required=True)
    ap.add_argument("--datanodes", type=int, default=2)
    ap.add_argument("--shard-groups", type=int, default=256)
    ap.add_argument("--serve-port", type=int, default=0)
    ap.add_argument("--control-port", type=int, default=0)
    args = ap.parse_args(argv)

    from opentenbase_tpu import host_side_role

    host_side_role()
    from opentenbase_tpu.net.server import ClusterServer
    from opentenbase_tpu.storage.replication import StandbyCluster

    sb = StandbyCluster(args.data_dir, args.datanodes, args.shard_groups)
    sb.start_replication(args.primary_host, args.primary_port)
    server = ClusterServer(
        sb.cluster, port=args.serve_port
    ).start()  # read-only SQL while standing by

    ctl = socket.socket()
    ctl.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    ctl.bind(("127.0.0.1", args.control_port))
    ctl.listen(4)
    print(
        f"standby ready sql=127.0.0.1:{server.port} "
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
                    f.write(json.dumps({
                        "role": "primary" if sb.promoted else "standby",
                        "applied": sb.applied,
                        "read_only": sb.cluster.read_only,
                    }) + "\n")
                    f.flush()
                elif cmd == "promote":
                    if not sb.promoted:
                        sb.promote()
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
            except OSError:
                return
            threading.Thread(target=handle, args=(conn,), daemon=True).start()

    threading.Thread(target=accept_loop, daemon=True).start()
    done.wait()
    server.stop()
    sb.stop()
    sb.cluster.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
