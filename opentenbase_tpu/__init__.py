"""opentenbase-tpu: a TPU-native distributed SQL engine.

A ground-up rebuild of the capabilities of OpenTenBase (Tencent's distributed
PostgreSQL fork in the Postgres-XC/XL lineage) designed TPU-first:

- Plan fragments compile to jitted JAX functions over sharded columnar batches
  (instead of the Volcano iterator in the reference's src/backend/executor).
- Shards map to TPU devices via ``jax.sharding``/``shard_map``; inter-datanode
  tuple redistribution is ``lax.all_to_all``/``psum`` over ICI (instead of the
  squeue/DataPump socket fabric in src/backend/pgxc/squeue/squeue.c).
- MVCC visibility is a vectorized commit-timestamp comparison on device
  (instead of HeapTupleSatisfiesMVCC in src/backend/utils/time/tqual.c).
- The control plane — catalog, locator/shard map, GTS service, 2PC
  coordinator, session management — runs host-side.

Top-level layout (mirrors SURVEY.md section 2's component inventory):

- ``types``     — SQL type system (decimal-as-int64, dict-encoded text).
- ``storage``   — columnar tables, MVCC version columns, shard partitions.
- ``catalog``   — table/distribution metadata (pgxc_class, pgxc_shard_map).
- ``sql``       — lexer, recursive-descent parser, AST.
- ``plan``      — analyzer, logical/physical plans, Distribution property,
                  FQS fast path, distributed planner.
- ``exec``      — expression compiler + jitted device kernels + fragment
                  executor (scan/filter/project/agg/sort/join).
- ``parallel``  — device mesh, shard_map fragments, collective redistribution.
- ``gts``       — global timestamp service (GTM equivalent).
- ``txn``       — snapshots, MVCC filters, implicit two-phase commit.
- ``server``    — coordinator/datanode session layer.
"""

__version__ = "0.1.0"


def host_side_role() -> None:
    """One process per chip. The process that owns the device mesh is
    the coordinator (``otb_server``, or whatever embeds ``Cluster``); a
    chip belongs to one process at a time. DN servers, hot standbys and
    peer coordinators are host-side roles (they run the host executor),
    so their entry points call this before JAX initialises: it selects
    the CPU backend unless ``JAX_PLATFORMS`` in their environment
    already says otherwise."""
    import os

    os.environ.setdefault("JAX_PLATFORMS", "cpu")


def __getattr__(name):
    # Lazy: importing the package must not pull in jax/the server stack.
    if name == "connect":
        from opentenbase_tpu.net.client import connect_tcp

        return connect_tcp
    raise AttributeError(name)
