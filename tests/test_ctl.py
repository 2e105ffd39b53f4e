"""otb_ctl topology tests: real multi-process cluster bring-up, standby
replication across processes, remote promote — the pgxc_ctl flow
(contrib/pgxc_ctl 'init all' / 'start' / failover)."""

import json
import os
import subprocess
import sys
import time

import pytest

from opentenbase_tpu.net.client import WireError, connect_tcp


def _free_ports(n):
    import socket

    socks, ports = [], []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    return ports


def _ctl(cfg_path, verb, *rest):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "-m", "opentenbase_tpu.cli.otb_ctl",
         verb, cfg_path, *rest],
        capture_output=True, text=True, env=env, cwd="/root/repo", timeout=180,
    )


@pytest.mark.slow
def test_topology_lifecycle(tmp_path):
    co_port, wal_port, sb_port, ctl_port = _free_ports(4)
    cfg = {
        "coordinator": {
            "port": co_port, "wal_port": wal_port,
            "data_dir": str(tmp_path / "pri"), "datanodes": 2,
            "shard_groups": 32, "gts": "python",
        },
        "standbys": [{
            "name": "sb1", "data_dir": str(tmp_path / "sb1"),
            "serve_port": sb_port, "control_port": ctl_port,
        }],
    }
    cfg_path = str(tmp_path / "topo.json")
    with open(cfg_path, "w") as f:
        json.dump(cfg, f)

    out = _ctl(cfg_path, "start")
    assert "coordinator: started" in out.stdout, out.stdout + out.stderr
    assert "sb1: started" in out.stdout
    try:
        with connect_tcp("127.0.0.1", co_port, timeout=60) as s:
            s.execute(
                "create table t (k bigint, v text) distribute by shard(k)"
            )
            s.execute("insert into t values (1,'a'),(2,'b')")

        # the standby serves the replicated rows read-only
        for _ in range(100):
            try:
                with connect_tcp("127.0.0.1", sb_port, timeout=30) as rs:
                    if rs.query("select count(*) from t") == [(2,)]:
                        break
            except (WireError, OSError):
                pass
            time.sleep(0.1)
        with connect_tcp("127.0.0.1", sb_port, timeout=30) as rs:
            assert rs.query("select v from t order by k") == [("a",), ("b",)]
            with pytest.raises(WireError, match="read-only"):
                rs.execute("insert into t values (9,'x')")

        st = _ctl(cfg_path, "status")
        assert "coordinator: up" in st.stdout and "role=standby" in st.stdout

        # failover: promote sb1, then write THROUGH ITS SQL PORT
        pr = _ctl(cfg_path, "promote", "sb1")
        assert "'promoted': True" in pr.stdout or '"promoted": true' in pr.stdout
        with connect_tcp("127.0.0.1", sb_port, timeout=30) as ns:
            ns.execute("insert into t values (3,'c')")
            assert ns.query("select count(*) from t") == [(3,)]
        st = _ctl(cfg_path, "status")
        assert "role=primary" in st.stdout
    finally:
        out = _ctl(cfg_path, "stop")
    assert "coordinator: stopped" in out.stdout
    assert not subprocess.run(
        ["pgrep", "-x", "gts_server"], capture_output=True
    ).stdout
