"""Build the deployment a configuration file describes and load it.

The cluster is built as ``cli/otb_server.py`` builds it (durable
``Cluster`` behind a ``ClusterServer``); tables are created by DDL sent
over the wire; rows are routed with the table's own ``Locator`` and
appended to the shard stores (COPY is a row-at-a-time loop, minutes at
this size). Only this module and ``run.py`` import the engine.
"""

from __future__ import annotations

import importlib
import json
import os
import shutil
import tempfile
import time
import traceback
from concurrent.futures import ThreadPoolExecutor

import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GEN_THREADS = 6


def read_config(name: str) -> dict:
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        return json.load(f)


def dataset_module(cfg: dict):
    return importlib.import_module(f"datasets.{cfg['dataset']}")


def ddl(table: str, spec: dict) -> str:
    cols = ", ".join(
        f"{name} {sqltype}" for name, sqltype, loaded in spec["columns"]
        if loaded
    )
    return f"create table {table} ({cols}) distribute by {spec['distribute']}"


class Data:
    """What the generator made: kept for the reference, untouched by the
    engine (the stores copy what they are given)."""

    def __init__(self, module, blocks: list, glob: dict):
        self.module = module
        self.blocks = blocks
        self.glob = glob

    def rows(self, table: str) -> int:
        if table in self.glob:
            return len(next(iter(self.glob[table].values())))
        return sum(len(next(iter(b[table].values()))) for b in self.blocks)


def generate(cfg: dict, seed: int, scale: float) -> Data:
    mod = dataset_module(cfg)
    with ThreadPoolExecutor(GEN_THREADS) as pool:
        blocks = list(pool.map(
            lambda b: mod.make_block(seed, scale, b),
            range(mod.n_blocks(scale)),
        ))
    return Data(mod, blocks, mod.make_global(seed, scale))


class Deployment:
    def __init__(self, cfg: dict):
        from opentenbase_tpu.engine import Cluster
        from opentenbase_tpu.net.client import connect_tcp
        from opentenbase_tpu.net.server import ClusterServer

        self.cfg = cfg
        self.data_dir = tempfile.mkdtemp(prefix="otb_bench_")
        self.cluster = Cluster(
            cfg["datanodes"], cfg["shard_groups"],
            os.path.join(self.data_dir, "cn"), gts_backend="python",
        )
        self.server = ClusterServer(self.cluster, "127.0.0.1", 0).start()
        self.client = connect_tcp(
            self.server.host, self.server.port, timeout=1100.0
        )

    def sql(self, text: str):
        return self.client.execute(text)

    def create_tables(self) -> None:
        for table, spec in self.cfg["tables"].items():
            if spec.get("loaded", True):
                self.sql(ddl(table, spec))

    def _columns(self, meta, table: str, arrays: dict, dicts: dict) -> dict:
        from opentenbase_tpu.storage.column import Column

        cols = {}
        for name, ty in meta.schema.items():
            data = arrays[name]
            if name in dicts.get(table, {}):
                # generator codes -> the table dictionary's codes
                d = meta.dictionaries[name]
                data = d.encode(dicts[table][name])[data]
            cols[name] = Column(ty, data, None, meta.dictionaries.get(name))
        return cols

    def append(self, table: str, arrays: dict, dicts: dict) -> None:
        """Route one batch of rows and append it to the shard stores."""
        from opentenbase_tpu.storage.table import ColumnBatch

        c = self.cluster
        meta = c.catalog.get(table)
        n = len(next(iter(arrays.values())))
        cols = self._columns(meta, table, arrays, dicts)
        ts = c.gts.get_gts()
        if meta.dist.is_replicated:
            for node in meta.node_indices:
                c.stores[node][table].append_batch(ColumnBatch(cols, n), ts)
            return
        keys = {k: cols[k] for k in meta.dist.key_columns}
        dest = meta.locator.route_insert(keys, n)
        for node in meta.node_indices:
            idx = np.nonzero(dest == node)[0]
            part = {k: col.take(idx) for k, col in cols.items()}
            c.stores[node][table].append_batch(
                ColumnBatch(part, len(idx)), ts
            )

    def load(self, data: Data) -> None:
        dicts = data.module.DICTIONARIES
        for table, arrays in data.glob.items():
            self.append(table, arrays, dicts)
        for block in data.blocks:
            for table, arrays in block.items():
                self.append(table, arrays, dicts)
        self.sql("analyze")

    def shard_rows(self) -> dict:
        return {
            t: [self.cluster.stores[n][t].nrows for n in
                self.cluster.catalog.get(t).node_indices]
            for t, spec in self.cfg["tables"].items()
            if spec.get("loaded", True)
        }

    def close(self) -> None:
        for step in (
            lambda: self.client.close(),
            lambda: self.server.stop(),
            lambda: self.cluster.close(),
            lambda: shutil.rmtree(self.data_dir, ignore_errors=True),
        ):
            try:
                step()
            except Exception:  # teardown must reach every step
                traceback.print_exc()


class Phases:
    """Named set-up phases on the host's clock, for the split line."""

    def __init__(self):
        self.t = {}
        self._last = time.perf_counter()

    def mark(self, name: str) -> None:
        now = time.perf_counter()
        self.t[name] = self.t.get(name, 0.0) + now - self._last
        self._last = now
