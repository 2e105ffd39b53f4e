"""Device-join differential suite + demotion observability.

Covers the PR-6 join stack end to end, all tier-1 safe on
JAX_PLATFORMS=cpu:

- ops-level byte-parity: the bucket-padded radix hash join
  (ops/join.py radix_* + emit_pairs) against the encode+sort-merge
  formulation over duplicate keys, NULL keys, skewed build sides, and
  empty inputs — identical PAIR SEQUENCES, not just identical sets;
- SQL-level parity: inner/left/semi/anti joins through the host
  executor under OTB_JOIN_MODE=radix vs =sortmerge, and through the
  fused DAG under the join_mode GUC — every path must agree with every
  other, and EXPLAIN must say which formulation answered;
- the Pallas MXU bucket-probe kernel (ops/pallas_join.py) in
  interpreter mode against the XLA probe;
- the spill-aware batch planner's sizing and multi-pass splitting
  (plan/batchplan.py + fused_dag._lookup_radix);
- the emit_pairs int32->int64 offset overflow fix;
- demotion observability: a pallas->XLA demotion emits a warning into
  pg_cluster_logs and moves the otb_pallas_demotions_total exporter
  counter; otb_device_platform renders on every scrape.
"""

from __future__ import annotations

import os

import numpy as np
import pytest

import opentenbase_tpu.ops  # noqa: F401  (x64)
import jax.numpy as jnp

from opentenbase_tpu.engine import Cluster
from opentenbase_tpu.ops import filter as filt_ops
from opentenbase_tpu.ops import join as join_ops
from opentenbase_tpu.plan import batchplan


# ---------------------------------------------------------------------------
# ops-level byte parity
# ---------------------------------------------------------------------------


def _sort_path(bk, breal, pk, preal):
    bids, pids = join_ops.encode_keys(
        [(jnp.asarray(bk), jnp.asarray(breal))],
        [(jnp.asarray(pk), jnp.asarray(preal))],
        None, None,
    )
    return join_ops.match_counts(bids, pids)


def _radix_path(bk, breal, pk, preal):
    plan = batchplan.plan_radix_join(
        len(bk), len(pk), batchplan.DEFAULT_EXCHANGE_BUDGET
    )
    # the planner declines an empty build (production falls back to the
    # sort path there); the table itself handles nb=0 — probe it anyway
    partitions, bucket = (
        (plan.partitions, plan.bucket) if plan is not None else (1, 8)
    )
    for _ in range(3):
        bo, lo, cnt, tot, ovf = join_ops.radix_match_counts(
            jnp.asarray(bk), jnp.asarray(breal),
            jnp.asarray(pk), jnp.asarray(preal),
            partitions, bucket,
        )
        if not bool(ovf):
            return bo, lo, cnt, tot
        bucket *= 4
    raise AssertionError("radix table overflowed at 16x quantum")


def _pairs(build_order, lo, counts, total, outer=False):
    out = filt_ops.bucket_size(max(int(total) + len(np.asarray(counts)), 1))
    pi, bi, m, v = join_ops.emit_pairs(
        build_order, lo, counts, out, outer
    )
    keep = np.asarray(v)
    return list(zip(
        np.asarray(pi)[keep].tolist(),
        np.asarray(bi)[keep].tolist(),
        np.asarray(m)[keep].tolist(),
    ))


SCENARIOS = {
    "duplicates": lambda r: (
        np.repeat(r.integers(-50, 50, 60), 3).astype(np.int64),
        np.ones(180, bool),
        r.integers(-60, 60, 700).astype(np.int64),
        np.ones(700, bool),
    ),
    "null_keys": lambda r: (
        r.integers(0, 40, 120).astype(np.int64),
        r.random(120) > 0.3,
        r.integers(0, 40, 500).astype(np.int64),
        r.random(500) > 0.3,
    ),
    "skewed_build": lambda r: (
        np.concatenate([
            np.zeros(150, np.int64),  # one hot key
            r.integers(10**9, 10**12, 50),
        ]).astype(np.int64),
        np.ones(200, bool),
        np.concatenate([
            np.zeros(400, np.int64),
            r.integers(10**9, 10**12, 200),
        ]).astype(np.int64),
        np.ones(600, bool),
    ),
    "empty_build": lambda r: (
        np.zeros(0, np.int64), np.zeros(0, bool),
        r.integers(0, 10, 100).astype(np.int64), np.ones(100, bool),
    ),
    "empty_probe": lambda r: (
        r.integers(0, 10, 100).astype(np.int64), np.ones(100, bool),
        np.zeros(0, np.int64), np.zeros(0, bool),
    ),
    "all_dead": lambda r: (
        r.integers(0, 10, 50).astype(np.int64), np.zeros(50, bool),
        r.integers(0, 10, 50).astype(np.int64), np.zeros(50, bool),
    ),
    "wide_values": lambda r: (
        r.integers(-2**62, 2**62, 300).astype(np.int64),
        np.ones(300, bool),
        r.integers(-2**62, 2**62, 300).astype(np.int64),
        np.ones(300, bool),
    ),
}


@pytest.mark.parametrize("name", sorted(SCENARIOS))
@pytest.mark.parametrize("outer", [False, True])
def test_radix_byte_equals_sort_path(name, outer):
    rng = np.random.default_rng(hash(name) % 2**31)
    bk, breal, pk, preal = SCENARIOS[name](rng)
    # overlap half the probe keys with build keys so matches exist
    if len(pk) and len(bk):
        take = rng.integers(0, len(bk), len(pk) // 2)
        pk = pk.copy()
        pk[: len(take)] = bk[take]
    ref = _sort_path(bk, breal, pk, preal)
    got = _radix_path(bk, breal, pk, preal)
    assert int(ref[3]) == int(got[3])
    assert _pairs(*ref, outer=outer) == _pairs(*got, outer=outer)
    # semi/anti derive from counts alone: dead probe rows never match
    ref_has = (np.asarray(ref[2]) > 0) & preal
    got_has = (np.asarray(got[2]) > 0) & preal
    assert np.array_equal(ref_has, got_has)


def test_emit_pairs_int64_offsets():
    # three probe rows each claiming 2^30 matches: int32 cumsum wraps
    # negative at the third prefix (3*2^30 > 2^31), scrambling every
    # lane's probe_idx; int64 offsets keep the mapping exact
    counts = jnp.asarray(np.full(3, 2**30, np.int32))
    lo = jnp.zeros(3, jnp.int32)
    build_order = jnp.zeros(8, jnp.int32)
    pi, bi, m, v = join_ops.emit_pairs(build_order, lo, counts, 16)
    assert np.asarray(pi).tolist() == [0] * 16  # all lanes in row 0's run
    assert bool(np.asarray(m).all()) and bool(np.asarray(v).all())


# ---------------------------------------------------------------------------
# Pallas MXU bucket probe (interpreter mode)
# ---------------------------------------------------------------------------


def test_pallas_probe_matches_xla_probe():
    from opentenbase_tpu.ops import pallas_join as pj

    rng = np.random.default_rng(7)
    nb, npr = 1500, 4000
    bk = (rng.permutation(np.arange(5000))[:nb] * 9 - 10**10).astype(
        np.int64
    )
    breal = rng.random(nb) > 0.1
    pk = np.concatenate([
        bk[rng.integers(0, nb, npr - 300)],
        rng.integers(-(10**14), 10**14, 300),
    ]).astype(np.int64)
    preal = rng.random(npr) > 0.1
    plan = batchplan.plan_radix_join(
        nb, npr, batchplan.DEFAULT_EXCHANGE_BUDGET
    )
    assert pj.eligible(nb, plan.partitions, plan.bucket)
    tk, tv, ti, dup, ovf = join_ops.build_radix_table(
        jnp.asarray(bk), jnp.asarray(breal), plan.partitions, plan.bucket
    )
    assert not bool(dup) and not bool(ovf)
    m_x, b_x = join_ops.probe_radix_first(
        tk, tv, ti, jnp.asarray(pk), jnp.asarray(preal),
        plan.partitions, plan.bucket,
    )
    m_p, b_p = pj.probe_radix_pallas(
        tk, tv, ti, jnp.asarray(pk), jnp.asarray(preal),
        plan.partitions, plan.bucket, interpret=True,
    )
    m_x = np.asarray(m_x)
    assert m_x.any(), "probe must actually hit"
    assert np.array_equal(m_x, np.asarray(m_p))
    assert np.array_equal(np.asarray(b_x)[m_x], np.asarray(b_p)[m_x])


# ---------------------------------------------------------------------------
# spill-aware batch planner
# ---------------------------------------------------------------------------


def test_batchplan_sizing_and_passes():
    p = batchplan.plan_radix_join(1_000_000, 10_000_000, 4_000_000_000)
    assert p.passes == 1 and p.partitions & (p.partitions - 1) == 0
    assert p.bucket % batchplan.RADIX_BUCKET_QUANTUM == 0
    # tighter budget: the SAME build side splits into multi-pass probes
    tight = batchplan.plan_radix_join(10_000_000, 50_000_000, 500_000_000)
    assert tight is not None and tight.passes > 1
    assert tight.table_bytes <= 500_000_000 // batchplan.RADIX_TABLE_FRACTION
    # hopeless budget: no plan — caller keeps sort-merge
    assert batchplan.plan_radix_join(10**9, 10**9, 1_000_000) is None
    assert batchplan.plan_radix_join(0, 100, 10**9) is None


def test_resolve_budget_precedence(monkeypatch):
    """The device_memory_limit GUC over the op's constant, and nothing
    else: a budget in the process environment changes no answer."""
    assert batchplan.resolve_budget(0, 42) == 42
    assert batchplan.resolve_budget(99, 42) == 99
    for name in ("EXCHANGE_HBM", "SCAN_HBM", "RADIX_HBM", "DAG_WINDOW"):
        monkeypatch.setenv(f"OTB_{name}_BUDGET", "77")
    assert batchplan.resolve_budget(0, 42) == 42
    assert batchplan.resolve_budget(99, 42) == 99


def test_multipass_lookup_radix_matches_single_table():
    from opentenbase_tpu.executor.fused_dag import _lookup, _lookup_radix

    rng = np.random.default_rng(3)
    nb, npr = 4000, 9000
    bk = (rng.permutation(np.arange(20000))[:nb]).astype(np.int64)
    pk = np.concatenate([
        bk[rng.integers(0, nb, npr - 500)],
        rng.integers(30000, 60000, 500),
    ]).astype(np.int64)
    bmask = jnp.asarray(rng.random(nb) > 0.2)
    pmask = jnp.asarray(rng.random(npr) > 0.2)
    bkp = (jnp.asarray(bk), None)
    pkp = (jnp.asarray(pk), None)
    want = _lookup(pkp, pmask, bkp, bmask, check_dup=True)
    # budget tiny enough to force several build chunks, big enough to
    # admit a plan
    plan = None
    budget = 37_500
    while plan is None:
        budget *= 2
        plan = batchplan.plan_radix_join(nb, npr, budget)
    assert plan.passes > 1, plan
    got = _lookup_radix(pkp, pmask, bkp, bmask, budget, _lookup)
    assert not bool(got[2]) and not bool(want[2])
    assert np.array_equal(np.asarray(want[0]), np.asarray(got[0]))
    m = np.asarray(want[0])
    assert np.array_equal(
        np.asarray(want[1])[m], np.asarray(got[1])[m]
    )


# ---------------------------------------------------------------------------
# SQL-level parity: host executor + fused DAG, all four join types
# ---------------------------------------------------------------------------


QUERIES = [
    # inner with duplicates on the probe side + NULL keys
    "select d.name, sum(f.v) from f, d where f.k = d.k "
    "group by d.name order by d.name",
    # left outer with NULL-extended rows
    "select d.k, f.v from d left join f on d.k = f.k "
    "order by d.k, f.v",
    # semi
    "select count(*) from f where f.k in (select k from d)",
    # anti
    "select count(*) from f where not exists "
    "(select 1 from d where d.k = f.k)",
]


@pytest.fixture(scope="module")
def join_cluster():
    c = Cluster(num_datanodes=2, shard_groups=16)
    s = c.session()
    s.execute(
        "create table d (k bigint, name int) distribute by roundrobin"
    )
    s.execute(
        "create table f (k bigint, v bigint) distribute by roundrobin"
    )
    rng = np.random.default_rng(11)
    dvals = []
    for i in range(60):
        k = "null" if i % 13 == 0 else i * 7 + 3  # sparse, some NULLs
        dvals.append(f"({k}, {i})")
    s.execute("insert into d values " + ",".join(dvals))
    fvals = []
    for i in range(2500):
        k = "null" if i % 17 == 0 else int(rng.integers(0, 75)) * 7 + 3
        fvals.append(f"({k}, {i})")
    s.execute("insert into f values " + ",".join(fvals))
    s.execute("analyze")
    yield c
    for sess in list(c.sessions):
        sess.close()


@pytest.mark.parametrize("qi", range(len(QUERIES)))
def test_sql_parity_host_and_fused(join_cluster, qi, monkeypatch):
    q = QUERIES[qi]
    s = join_cluster.session()
    results = {}
    # host executor, both formulations forced via the env knob
    s.execute("set enable_fused_execution = off")
    for mode in ("radix", "sortmerge"):
        monkeypatch.setenv("OTB_JOIN_MODE", mode)
        results[f"host:{mode}"] = s.query(q)
    monkeypatch.delenv("OTB_JOIN_MODE", raising=False)
    # fused DAG, both formulations forced via the GUC
    s.execute("set enable_fused_execution = on")
    for mode in ("radix", "sortmerge"):
        s.execute(f"set join_mode = {mode}")
        results[f"fused:{mode}"] = s.query(q)
    want = results["host:sortmerge"]
    for label, got in results.items():
        assert got == want, (label, got[:5], want[:5])
    s.close()


def test_explain_shows_join_mode(join_cluster):
    s = join_cluster.session()
    q = QUERIES[0]
    s.execute("set join_mode = radix")
    s.execute("set enable_fused_execution = on")
    s.query(q)  # ensure compiled
    lines = [r[0] for r in s.query(f"explain analyze {q}")]
    fused = [ln for ln in lines if "Fused join modes:" in ln]
    if fused:  # device DAG answered
        assert "radix" in fused[0], lines
    s.execute("set enable_fused_execution = off")
    os.environ["OTB_JOIN_MODE"] = "radix"
    try:
        lines = [r[0] for r in s.query(f"explain analyze {q}")]
    finally:
        os.environ.pop("OTB_JOIN_MODE", None)
    joins = [
        ln for ln in lines
        if ln.strip().startswith("Join") and "rows=" in ln
    ]
    assert joins and any("(radix)" in ln for ln in joins), lines
    s.close()


def test_fused_radix_flag_degrades_to_sortmerge(join_cluster):
    """Duplicate build keys under forced radix: the flag machinery must
    disable the radix table for that join and re-answer via sort-merge
    (then flip orientation if needed) — never a wrong result."""
    c = join_cluster
    s = c.session()
    s.execute(
        "create table dupd (k bigint, g int) distribute by roundrobin"
    )
    s.execute("insert into dupd values " + ",".join(
        f"({i % 8}, {i})" for i in range(64)  # every key duplicated
    ))
    s.execute("analyze")
    q = ("select count(*) from f, dupd where f.k = dupd.k")
    s.execute("set enable_fused_execution = off")
    want = s.query(q)
    s.execute("set enable_fused_execution = on")
    s.execute("set join_mode = radix")
    assert s.query(q) == want
    s.close()


# ---------------------------------------------------------------------------
# demotion observability (logs + exporter)
# ---------------------------------------------------------------------------


def test_pallas_demotion_is_loud(tmp_path):
    import socket

    from opentenbase_tpu.obs.exporter import scrape

    probe = socket.socket()
    probe.bind(("127.0.0.1", 0))
    mport = probe.getsockname()[1]
    probe.close()
    d = tmp_path / "cn"
    d.mkdir()
    # the exporter listener opens from the conf file at cluster start
    (d / "opentenbase.conf").write_text(f"metrics_port = {mport}\n")
    c = Cluster(num_datanodes=1, shard_groups=16, data_dir=str(d))
    s = c.session()
    fx = c.fused_executor()
    assert fx is not None

    def counter(body, name):
        for ln in body.splitlines():
            if ln.startswith(name) and not ln.startswith("#"):
                return float(ln.rpartition(" ")[2])
        return None

    b1 = scrape("127.0.0.1", mport)
    assert "otb_device_platform" in b1
    c1 = counter(b1, "otb_pallas_demotions_total")
    assert c1 is not None
    try:
        raise RuntimeError("synthetic mosaic lowering failure")
    except RuntimeError:
        fx._note_pallas_failure(("pallas", "test-kernel"))
    b2 = scrape("127.0.0.1", mport)
    assert counter(b2, "otb_pallas_demotions_total") == c1 + 1
    logs = s.query("select pg_cluster_logs('warning')")
    msgs = [r[4] for r in logs if r[3] == "device"]
    assert any("demoted to XLA" in m for m in msgs), logs[-5:]
    s.close()
